"""Model FLOP/s utilization of a compressed-attention, skip-expert
configuration at this chip's share: the operations forward and backward
REQUIRE per token (benchmark/cca_costs.py: the five projections, the two
convolutions, the core at ``head_dim_override`` columns, the router's MLP
over all its columns, the held experts at a level load, the sliced tied
head; no recompute) x tokens/s, over chips x the chip's bf16 peak
(benchmark/peaks.json)."""

from benchmark import cca_costs, kernel_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    if o["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    files = kernel_costs.cell_files(run)
    if not files or not cca_costs.is_cca(files[0]):
        return None
    sizes, _, mix = files
    per_token = cca_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
