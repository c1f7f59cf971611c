"""Share of device busy time under ``ds.gdn_rule`` (the chunked gated delta
rule and nothing else: the chunks' products, the triangular solve, the scan
over chunk boundaries), forward, backward and recomputed together
(benchmark/scope_reduce). None for a program without that scope."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.gdn_rule")
