"""Model FLOP/s utilization of a stack of single-branch layers -- scalar-decay
state-space mixers, attention layers without rotation, ungated sparse-expert
layers: the operations forward and backward REQUIRE per token
(benchmark/ssd_costs.py: the mixers' projections and the RECURRENCE's own work
whatever form implements it, the attention's projections and kept pairs, the
router over every expert, the shared expert, the HELD experts at a level
load, the sliced head; no recompute) x tokens/s, over chips x the chip's bf16
peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import ssd_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = ssd_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = ssd_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
