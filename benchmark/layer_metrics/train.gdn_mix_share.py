"""Share of device busy time under ``ds.gdn_mix`` (what a delta-rule mixer
does around its rule and its projections: the causal convolution and SiLU, the
unit-length queries and keys, beta, the decay, the gated output norm), forward,
backward and recomputed together (benchmark/scope_reduce). None for a program
without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.gdn_mix")
