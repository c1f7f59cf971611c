"""Share of device busy time under ``ds.kda_rule`` (the chunked delta rule
under a decay a channel and nothing else: the running decays, the pair tables
a block, the triangular solve, the scan over chunk boundaries), forward,
backward and recomputed together (benchmark/scope_reduce). None for a program
without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.kda_rule")
