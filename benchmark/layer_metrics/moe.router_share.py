"""Share of device busy time under ``ds.moe_router`` (where the router is an
MLP: the down-projection, the state carried from the layer before, its norm,
three layers, the softmax, the choice and the balancing rule's load count),
forward, backward and recomputed together (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.moe_router")
