"""Model FLOP/s utilization of a GQA / sparse-expert decoder whose layer kinds
differ in head count, behind a leading dense layer: the operations forward
and backward REQUIRE per token (benchmark/laguna_costs.py: the projections and
the KEPT pairs of each kind at its own head count, the dense SwiGLU, the
router over every expert, the shared expert, the HELD experts at a level
load, the sliced head; no recompute) x tokens/s, over chips x the chip's bf16
peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import laguna_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = laguna_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = laguna_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
