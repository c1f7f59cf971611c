"""Ring attention: KV rotation over the ``seq`` mesh axis with online softmax.

Capability upgrade over the 2022 reference (see ``ulysses.py`` docstring).
Unlike Ulysses (which bounds sequence length by total head count), ring
attention scales T with the number of devices: each shard keeps its query
block resident and the K/V blocks travel the ring via ``lax.ppermute`` —
ICI-neighbor traffic — while a numerically-stable streaming softmax
(max/denominator/numerator carry, flash-attention style) accumulates the
output block by block. Memory per device is O(T/sp · T/sp) logits instead of
O(T²).

Backward: reverse-mode AD through the scan regenerates the KV rotation
(ppermute transposes to the reverse ring) — matching the recomputation
strategy of the ring-attention paper without bespoke backward plumbing.
"""

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..parallel.topology import get_mesh


def _ring_local(q, k, v, *, n_shards: int, causal: bool, axis: str = "seq"):
    """Per-shard ring loop. q/k/v local blocks ``[B, Tl, H, D]``."""
    B, Tl, H, D = q.shape
    me = jax.lax.axis_index(axis)
    scale = 1.0 / np.sqrt(D)
    qs = q.astype(jnp.float32) * scale
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    o0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    m0 = jnp.full((B, H, Tl), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)

    def body(carry, r):
        o, m, l, k_blk, v_blk = carry
        # the block we hold at round r originated at rank (me - r) mod s
        src = jax.lax.rem(me - r + n_shards, n_shards)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qs, k_blk.astype(jnp.float32))
        if causal:
            q_pos = me * Tl + jnp.arange(Tl)
            k_pos = src * Tl + jnp.arange(Tl)
            keep = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(keep[None, None], logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # fully-masked rows keep m=-inf; guard the exp shift
        shift = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(logits - shift[..., None])
        if causal:
            p = jnp.where(keep[None, None], p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - shift))
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        m = m_new
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = jax.lax.scan(body, (o0, m0, l0, k, v),
                                      jnp.arange(n_shards))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(q, k, v, causal: bool = True, mesh=None, axis: str = "seq"):
    """Logical ``[B, T, H, D]`` ring attention, token dim sharded over
    ``axis``. Falls back to plain attention when the axis is absent/size 1."""
    mesh = mesh or get_mesh()
    shape = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}
    sp = shape.get(axis, 1)
    if sp <= 1:
        from ..models.layers import dot_product_attention

        return dot_product_attention(q, k, v, causal=causal, attention_impl="xla")
    if q.shape[1] % sp != 0:
        raise ValueError(f"sequence length {q.shape[1]} not divisible by "
                         f"{axis} axis size {sp}")

    # manual only over the ring axis; batch/head dims stay auto-partitioned
    # (specs may only name manual axes)
    spec = P(None, axis)
    fn = jax.shard_map(
        lambda a, b, c: _ring_local(a, b, c, n_shards=sp, causal=causal, axis=axis),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis}), check_vma=False)
    if not any(isinstance(x, jax.core.Tracer) for x in (q, k, v)):
        # partially-manual shard_map only traces under jit (eager calls — e.g.
        # flax module.init — reject specs on auto axes)
        return jax.jit(fn)(q, k, v)
    return fn(q, k, v)
