"""Model FLOP/s utilization of a decoder of gated delta-rule and gated
full-attention layers over a sparse-expert layer: the operations forward and
backward REQUIRE per token (benchmark/gdn_costs.py: the mixers' projections,
the rule's products as the recurrence needs them, whatever chunk the program
runs it in, the full layers' core at the causal mean of keys,
the router over every expert, the shared expert, the HELD experts at a level
load, the sliced head; no recompute) x tokens/s, over chips x the chip's bf16
peak (benchmark/peaks.json): the share of the whole step."""

from benchmark import gdn_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = gdn_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = gdn_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
