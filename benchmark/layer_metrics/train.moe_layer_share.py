"""Share of device busy time of the EXPERT layers of a stack of
single-branch layers: every operation whose path holds the outer scope
``ds.layer_moe`` (the whole block: its norm, router, held experts, shared
expert, the residual), forward, backward and recomputed together
(benchmark/swa_costs.py ``path_share``). None for a program without that
scope."""

from benchmark import swa_costs


def read(run):
    return swa_costs.path_share(run, "ds.layer_moe")
