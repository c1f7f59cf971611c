"""Share of device busy time under ``ds.ssm_scan`` (the selective scan of the
Mamba layers and nothing else: the two kernels, or the chunked XLA scan),
forward, backward and recomputed together (benchmark/scope_reduce). None for a
program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.ssm_scan")
