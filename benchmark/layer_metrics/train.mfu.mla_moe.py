"""Model FLOP/s utilization of a latent-attention, shared-expert
configuration at this chip's share: the operations forward and backward
REQUIRE per token (benchmark/mla_costs.py: latent projections, two head
widths, the router over all experts, the held experts at a level load, the
shared experts, the sliced head; no recompute) x tokens/s, over chips x the
chip's bf16 peak (benchmark/peaks.json)."""

from benchmark import kernel_costs, mla_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    if o["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    files = kernel_costs.cell_files(run)
    if not files or not mla_costs.is_mla(files[0]):
        return None
    sizes, _, mix = files
    per_token = mla_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
