"""Model FLOP/s utilization of a stack of vector-decay delta-rule layers and
latent-attention layers over a dense SwiGLU and sparse-expert layers: the
operations forward and backward REQUIRE per token (benchmark/kda_costs.py: the
mixers' projections and the RECURRENCE's own ``7 dk dv`` a head whatever form
implements it, the latent attention's projections and kept pairs, the dense
SwiGLU, the router over every expert, the shared expert, the HELD experts at
a level load, the sliced head; no recompute) x tokens/s, over chips x the
chip's bf16 peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import kda_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = kda_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = kda_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
