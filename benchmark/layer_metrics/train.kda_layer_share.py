"""Share of device busy time of the KDA (vector-decay delta-rule) layers:
every operation whose path holds the outer scope ``ds.layer_kda`` (the whole
block: mixer, norms, the dense SwiGLU or router, shared and held experts),
forward, backward and recomputed together (benchmark/swa_costs.py
``path_share``). None for a program without that scope."""

from benchmark import swa_costs


def read(run):
    return swa_costs.path_share(run, "ds.layer_kda")
