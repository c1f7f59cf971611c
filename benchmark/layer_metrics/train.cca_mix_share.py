"""Share of device busy time under ``ds.cca_mix`` (what compressed
convolutional attention adds ahead of the kernels: the two causal
convolutions, the query-key mean, the unit-length norm and temperature, the
value's one-token shift), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.cca_mix")
