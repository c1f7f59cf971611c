"""Share of device busy time under ``ds.sa_select`` (the exact selection of a
learned sparse attention: the counting passes that find each row's k-th
largest score and its ties, the mask, its bit-packed copy and the kept-tile
count), forward and replay together (benchmark/scope_reduce). None for a
program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.sa_select")
