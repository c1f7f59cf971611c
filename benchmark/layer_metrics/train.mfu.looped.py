"""Model FLOP/s utilization of a looped decoder: the operations forward and
backward REQUIRE per token (benchmark/ouro_costs.py: ``total_ut_steps`` times
the layers' products and attention cores at the causal mean of keys, as many
times the head and the one-column exit gate; no recompute) x tokens/s, over
chips x the chip's bf16 peak (benchmark/peaks.json): the share of the whole
step."""

from benchmark import ouro_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    found = ouro_costs.cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    sizes, mix = found
    per_token = ouro_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
