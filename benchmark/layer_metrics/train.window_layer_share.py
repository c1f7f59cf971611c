"""Share of device busy time of the SLIDING-window layers: every operation
whose path holds the outer scope ``ds.layer_window`` (the whole block, its
core under the window's tile table), forward, backward and recomputed
together (benchmark/swa_costs.py ``path_share``). None for a program without
that scope."""

from benchmark import swa_costs


def read(run):
    return swa_costs.path_share(run, "ds.layer_window")
