"""Share of device busy time spent recomputing inside the backward pass:
what ``jax.checkpoint`` replays (``rematted_computation`` in the path) and
the operations the compiler cloned to save memory (``<name>.remat[N]``)
(benchmark/scope_reduce.phase_of)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "recompute", table="by_phase")
