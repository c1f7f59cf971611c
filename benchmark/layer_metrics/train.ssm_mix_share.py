"""Share of device busy time under ``ds.ssm_mix`` (what a Mamba layer runs
beside its scan: the in and out projections, the causal convolution, W_x,
W_dt, softplus, the gate), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.ssm_mix")
