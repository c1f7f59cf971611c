"""Model FLOP/s utilization of a decoder-hybrid-decoder with state-space
layers: the operations forward and backward REQUIRE per token
(benchmark/ssm_costs.py: every layer's SwiGLU, the Mamba projections,
convolution and scan, the differential attention's projections and its core
at 64-wide keys and 128-wide values, the gated memory units, the sliced tied
head; no recompute) x tokens/s, over chips x the chip's bf16 peak
(benchmark/peaks.json): the share of the whole step."""

from benchmark import kernel_costs, ssm_costs
from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    if o["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    files = kernel_costs.cell_files(run)
    if not files or not ssm_costs.is_ssm_hybrid(files[0]):
        return None
    sizes, _, mix = files
    per_token = ssm_costs.train_flops_per_token(sizes, mix["seq_len"])
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * per_token * o["tokens_per_s"] / peak
