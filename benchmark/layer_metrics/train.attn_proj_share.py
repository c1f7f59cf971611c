"""Share of device busy time under ``ds.attn_proj`` (the projections around
the attention core; for latent attention the low-rank key/value path, its
norm, RoPE on the rotary columns and the keys' assembly), forward, backward
and recomputed together (benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "train", "ds.attn_proj")
