"""Share of device busy time under ``ds.moe_experts`` (the expert SwiGLU and
the weighted combine), forward, backward and recomputed together
(benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.moe_experts")
