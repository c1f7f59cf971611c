"""Share of device busy time under ``ds.lm_head_loss`` (final norm, head,
cross-entropy), forward and backward (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.lm_head_loss")
