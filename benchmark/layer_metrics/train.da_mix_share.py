"""Share of device busy time under ``ds.da_mix`` (what differential attention
adds behind the kernels: lambda, the subtraction of the two streams, the
pair's RMSNorm and rescale), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.da_mix")
