"""Share of device busy time under ``ds.loop_stack`` and no scope inside it:
what the loop over the PASSES costs beyond what the passes' own scopes name
-- the passes' readings stacked, the sums of the shared weights' gradients
over the passes that used them -- forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.loop_stack")
