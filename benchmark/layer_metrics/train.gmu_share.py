"""Share of device busy time under ``ds.gmu`` (the gated memory units of the
cross-decoder: two projections about the memory layer's scan output times a
SiLU gate), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.gmu")
