"""Share of device busy time of the leading DENSE layers: every operation
whose path holds the outer scope ``ds.layer_dense`` (the whole block: full
attention and the dense SwiGLU), forward, backward and recomputed together
(benchmark/swa_costs.py ``path_share``). None for a program without that
scope."""

from benchmark import swa_costs


def read(run):
    return swa_costs.path_share(run, "ds.layer_dense")
