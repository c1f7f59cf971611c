"""Model FLOP/s utilization of a learned-sparse-attention, held-share
configuration: the operations forward and backward REQUIRE per token
(benchmark/sa_costs.py: the projections, the indexer's projections and its
scores over every causal pair, the core over the SELECTED pairs, the router
over all experts, the held experts at a level load, the sliced head; no
recompute, no counting passes) x tokens/s, over chips x the chip's bf16 peak
(benchmark/peaks.json)."""

from benchmark import kernel_costs, sa_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    if o["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    files = kernel_costs.cell_files(run)
    if not files or not sa_costs.is_sa(files[0]):
        return None
    sizes, _, mix = files
    per_token = sa_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
