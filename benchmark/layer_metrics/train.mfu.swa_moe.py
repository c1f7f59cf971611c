"""Model FLOP/s utilization of a GQA / sparse-expert decoder under a pattern
of layer kinds: the operations forward and backward REQUIRE per token
(benchmark/swa_costs.py: the projections, each kind's core at the mean keys
its own window leaves a query, the router over every expert, the HELD experts
at a level load, the sliced head; no recompute) x tokens/s, over chips x the
chip's bf16 peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import swa_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = swa_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = swa_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
