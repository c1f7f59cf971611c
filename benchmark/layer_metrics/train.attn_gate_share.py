"""Share of device busy time under ``ds.attn_gate`` (the product of each
head's attention output with its learned gate, ahead of ``o_proj``; the
gate's projection stands under ``ds.attn_proj``), forward, backward and
recomputed together (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.attn_gate")
