"""Share of device busy time whose innermost scope is ``ds.layer_stack``: the
loop over the layers less everything a layer names itself — under ``nn.scan``
each layer's weights sliced out of the stacked tree forward and again
backward, its gradients and kept values written back
(benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.layer_stack")
