"""Share of device busy time of the STATE-SPACE layers of a stack of
single-branch layers: every operation whose path holds the outer scope
``ds.layer_mamba`` (the whole block: its norm, ``ds.ssm_mix``, ``ds.ssm_scan``,
the residual), forward, backward and recomputed together
(benchmark/swa_costs.py ``path_share``). None for a program without that
scope."""

from benchmark import swa_costs


def read(run):
    return swa_costs.path_share(run, "ds.layer_mamba")
