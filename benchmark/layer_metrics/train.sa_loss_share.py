"""Share of device busy time under ``ds.sa_loss`` (the indexer's loss: the
head-mean attention probabilities, ``ds_sa_probs`` on the flash path, and the
KL term against the softmax of the index scores over the selection), forward,
backward and recomputed together (benchmark/scope_reduce). None for a program
without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.sa_loss")
