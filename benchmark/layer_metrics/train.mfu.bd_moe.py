"""Model FLOP/s utilization of a block-diffusion training step over a GQA /
sparse-expert stack: the operations forward and backward REQUIRE per TRAINED
token (benchmark/sdar_costs.py: both halves' projections, router and held
experts at a level load, the attention core at the pairs the rule KEEPS, the
sliced head once; no recompute) x trained tokens/s, over chips x the chip's
bf16 peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import sdar_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = sdar_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = sdar_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
