"""Share of device busy time under ``ds.exit_gate``: every pass's one-column
gate, the exit distribution over the passes, the mixing of the passes' token
losses and the entropy, forward and backward (benchmark/scope_reduce). None
for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.exit_gate")
