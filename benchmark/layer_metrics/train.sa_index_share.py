"""Share of device busy time under ``ds.sa_index`` (the indexer of a learned
sparse attention: its three projections, the key's LayerNorm, rotary, and the
index scores of every causal pair), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.sa_index")
