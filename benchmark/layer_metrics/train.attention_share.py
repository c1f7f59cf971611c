"""Share of device busy time under the ``ds.attention`` scope (score, softmax,
value: the flash kernels or their XLA counterpart), forward, backward and
recomputed together (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.attention")
