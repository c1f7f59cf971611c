"""Quantized TP collectives (EQuARX-style, arxiv 2506.17615).

The all-reduce behind tensor parallelism's row-parallel projections
(attention ``o_proj``, MLP ``down_proj``) is THE per-token wire cost of
multi-chip serving: every decode step moves ``hidden * batch`` floats per
layer over ICI. EQuARX shows a quantized all-reduce inside XLA recovers
most of that bandwidth with negligible quality loss. This module is that
collective, built from the verbs in :mod:`.comm` so the payload mix rides
the existing ``comm_op_s{op, dtype, bytes_bucket}`` histograms — the
before/after dtype shift (f32/bf16 → int8 buckets) is directly observable.

Mechanics (the standard two-phase reduce-scatter + all-gather all-reduce,
with both wire phases quantized):

1. each shard views its local partial as ``[rows, features]`` (rows =
   packed tokens for the serving projections), splits the ROWS into
   ``n`` peer chunks and **blockwise absmax-quantizes** them — int8
   codes + one fp32 scale per ``block`` contiguous values WITHIN each
   row (the scale payload is ``~4/block`` of the int8 payload, and no
   scale block ever spans two tokens — see the determinism contract on
   :func:`quantized_psum`);
2. ``all_to_all`` routes row-chunk ``j`` of every shard to peer ``j``
   (int8 on the wire), which **dequant-reduces locally in fp32** — the
   reduction itself is never quantized, only the transport;
3. the reduced rows re-quantize and ``all_gather`` broadcasts them (int8
   on the wire again); every shard dequantizes the full tensor.

Wire bytes vs a plain fp32 psum: ``~(1/4 + 1/block)`` of the payload —
about 0.25x at ``block=256`` (both schemes pay the same two
reduce-scatter + all-gather phases, so the per-phase ratio IS the total
ratio; matches the bench's ``wire_bytes_ratio_computed`` and the docs).
Error: two int8 roundings of blockwise-scaled
values; on logit-scale activations the end-to-end greedy-token effect is
pinned by ``tests/unit/serving/test_quantized.py`` the same way
``test_tp_numerics`` pins TP reduction-order noise.

Must be called INSIDE ``shard_map`` (it is a per-shard SPMD collective,
like every verb in :mod:`.comm`); world size 1 degrades to the plain psum.
"""

from typing import Tuple

import jax.numpy as jnp
from jax import lax

from .comm import AxisName, all_gather, all_to_all_single

#: default quantization block (values per absmax scale). 256 keeps the
#: fp32 scale side-channel under 2% of the int8 payload while bounding
#: the dynamic range one outlier can flatten.
DEFAULT_BLOCK = 256


def blockwise_absmax_quantize(x: jnp.ndarray,
                              block: int = DEFAULT_BLOCK
                              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize the last axis in contiguous blocks of ``block`` values:
    ``[..., M]`` (``M % block == 0``) -> int8 codes ``[..., M]`` + fp32
    absmax/127 scales ``[..., M // block]``. An all-zero block gets the
    epsilon scale (codes 0, dequantizes to exact zeros)."""
    g = x.astype(jnp.float32).reshape(
        x.shape[:-1] + (x.shape[-1] // block, block))
    amax = jnp.max(jnp.abs(g), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.round(g / scale[..., None]).astype(jnp.int8)
    return q.reshape(x.shape), scale


def blockwise_dequantize(q: jnp.ndarray, scale: jnp.ndarray, block: int,
                         dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`blockwise_absmax_quantize`."""
    g = q.reshape(q.shape[:-1] + (q.shape[-1] // block, block))
    return (g.astype(jnp.float32) * scale[..., None]).reshape(
        q.shape).astype(dtype)


def quantized_psum(x: jnp.ndarray, axis: AxisName = "model",
                   block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    """All-reduce ``x`` over mesh axis ``axis`` with int8 wire payloads.

    Call inside ``shard_map`` exactly like ``lax.psum``. Returns the
    (approximately) reduced tensor in ``x.dtype`` on every shard. The
    reduction accumulates in fp32 — quantization touches only the two
    wire phases. World size 1 short-circuits to the exact psum (which
    XLA folds to a no-op), so a single-chip engine pays nothing.

    DETERMINISM CONTRACT (why blocks live inside the LAST axis): scale
    blocks never cross a row of ``x.reshape(-1, x.shape[-1])``, and the
    reduce-scatter chunking splits whole ROWS across peers. For the
    serving projections (rows = packed tokens, last axis = features)
    every token therefore quantizes against only its own values — a
    token's result is independent of what else is packed in the batch,
    so the serving engine's mixed step stays token-identical to the
    offline ``generate`` path and to itself under any traffic mix. A
    flat-chunked layout (blocks spanning token boundaries) would make
    logits depend on batch composition. The cost: the row count pads to
    a multiple of the world size (zero rows on the wire — negligible
    for serving's packed batches, up to ``n``x for a single-token
    offline decode, which is not the path this collective serves).
    """
    n = lax.axis_size(axis)
    if n == 1:
        return lax.psum(x, axis)
    shape, dtype = x.shape, x.dtype
    feat = shape[-1]
    rows = 1
    for d in shape[:-1]:
        rows *= d
    loc = x.astype(jnp.float32).reshape(rows, feat)
    bl = min(block, feat)
    pad_f = (-feat) % bl
    pad_r = (-rows) % n
    if pad_f:
        loc = jnp.concatenate(
            [loc, jnp.zeros((rows, pad_f), jnp.float32)], axis=1)
    if pad_r:
        loc = jnp.concatenate(
            [loc, jnp.zeros((pad_r, feat + pad_f), jnp.float32)], axis=0)
    R, F = loc.shape  # R % n == 0, F % bl == 0

    # phase 1 (reduce-scatter, quantized transport): peer j receives
    # every shard's row-chunk j as int8 + per-(row, block) scales and
    # dequant-reduces in fp32
    q, s = blockwise_absmax_quantize(loc, bl)
    q = all_to_all_single(q, axis, split_axis=0, concat_axis=0, tiled=True)
    s = all_to_all_single(s, axis, split_axis=0, concat_axis=0, tiled=True)
    part = blockwise_dequantize(q.reshape(n, R // n, F),
                                s.reshape(n, R // n, F // bl),
                                bl).sum(axis=0)

    # phase 2 (all-gather, quantized transport): the reduced row-chunks
    # go back out as int8 + scales; every shard rebuilds the full tensor
    q2, s2 = blockwise_absmax_quantize(part, bl)
    q2 = all_gather(q2, axis, axis=0, tiled=True)
    s2 = all_gather(s2, axis, axis=0, tiled=True)
    out = blockwise_dequantize(q2, s2, bl)
    return out[:rows, :feat].reshape(shape).astype(dtype)
