"""Share of device busy time under ``ds.moe_shared`` (the shared experts'
one SwiGLU over every token, beside the routed experts), forward, backward
and recomputed together (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.moe_shared")
