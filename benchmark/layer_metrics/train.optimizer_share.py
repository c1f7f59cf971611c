"""Share of device busy time under ``ds.optimizer`` (unscale, global norm,
overflow, the optimizer's update, apply, keep) (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.optimizer")
