"""Model FLOP/s utilization: the operations forward and backward REQUIRE per
token (benchmark/flops.py: no recompute, only routed experts) x tokens/s,
over chips x the chip's bf16 peak (benchmark/peaks.json)."""

from benchmark.common import peak_flops


def read(run):
    o = run["observed"]
    if o["kind"] != "train" or run["device"]["platform"] != "tpu":
        return None         # a CPU rehearsal has no peak to be a share of
    peak = peak_flops(run["device"]["kind"]) * o["chips"]
    return 100.0 * o["flops_per_token"] * o["tokens_per_s"] / peak
