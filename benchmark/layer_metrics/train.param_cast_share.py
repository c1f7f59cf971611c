"""Share of device busy time under ``ds.param_cast``: the engine's cast of the
float32 master weights to the compute dtype and, in the backward pass, of the
gradients back (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.param_cast")
