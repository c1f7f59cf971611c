"""Share of device busy time under ``ds.kda_mix`` (what stands around the KDA
rule: the three depthwise convolutions and their SiLU, unit length of q and
k, beta's sigmoid, the decay's softplus, the gated norm of the output),
forward, backward and recomputed together (benchmark/scope_reduce). None for
a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.kda_mix")
