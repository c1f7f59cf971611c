"""Quantized-weight matmul (Pallas): ``y = x @ dequant(Wq)`` with int8/int4
HBM reads and in-VMEM dequantization.

This is the PROJECTION half of the quantized serving path (the KV half —
int8 VMEM dequant per cache block — already lives in
``decode_attention.py``/``ragged_attention.py``): serving-time matmuls are
weight-bandwidth-bound, so streaming int8 (or packed int4) weight codes
from HBM and dequantizing per K-block in VMEM halves (quarters) the bytes
the way the reference's ``dequantize.cu`` + ``vector_matmul_int8`` GEMMs
do. ``int8_matmul.py`` keeps the per-column fast path (the scale factors
out of the contraction entirely); this kernel is the GROUPED generalization
both modes share:

- **int8**: codes ``[K, N]``, scales ``[G, N]`` (``G = K / group``; per
  output column when ``G == 1``);
- **int4**: codes packed two-per-byte along K — byte ``r`` of ``[K//2, N]``
  holds K-rows ``2r`` (low nibble) and ``2r+1`` (high nibble), symmetric
  range [-7, 7] — with grouped scales ``[G, N]``. Groups must span an even
  number of K rows so nibble pairs never straddle a scale boundary.

The kernel accumulates ``x_blk @ (codes * scale)`` in fp32 VMEM scratch
across K blocks; HBM never sees a dequantized copy of the weights. Scale
groups align with K blocks (``block_k`` is clamped to a multiple of the
group), so each grid step reads exactly its ``[bk/g, bn]`` scale tile.

Off-TPU the public entry falls back to dequantize+matmul — bit-identical
math to the grouped-dequant XLA reference path in ``models/layers.py``,
which is what keeps CPU tier-1 token-exact-testable; interpret mode is
used for kernel parity tests.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import QUANT_MATMUL

#: weight-quantization modes; int4 packs two codes per byte along K
MODES = ("int8", "int4")


def _check_mode(mode: str) -> None:
    if mode not in MODES:
        raise ValueError(f"quantize mode must be one of {MODES}, got {mode!r}")


def pack_int4(vals: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 codes (int, range [-8, 7]) ``[K, N]`` -> uint8
    ``[K//2, N]``: byte ``r`` = K-row ``2r`` in the low nibble, ``2r+1``
    in the high nibble. K must be even."""
    K = vals.shape[0]
    if K % 2:
        raise ValueError(f"int4 packing needs an even K, got {K}")
    v = vals.astype(jnp.int32) & 0xF
    lo, hi = v[0::2], v[1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: uint8 ``[K//2, N]`` -> int8 ``[K, N]``
    (sign-extended nibbles)."""
    w = packed.astype(jnp.int32)
    lo = ((w & 0xF) ^ 8) - 8
    hi = ((w >> 4) ^ 8) - 8
    K2, N = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * K2, N).astype(jnp.int8)


#: int4 per-output-column scales are measurably lossy (~7% max weight
#: error on gaussian kernels vs ~2.5% grouped at 64); int8 per-column is
#: already at its rounding floor, so grouping defaults off there.
DEFAULT_INT4_GROUP = 64


def effective_group_size(k: int, mode: str, group_size: int,
                         shards: int = 1) -> int:
    """The group length the serving stack actually uses for a ``[K, N]``
    kernel: the configured ``group_size`` (0 = per-column, except int4
    which defaults to :data:`DEFAULT_INT4_GROUP`), resolved against the
    per-shard K so scale groups tile TP shards exactly. The ONE
    derivation shared by ``inference/quant.py`` (which writes the scales)
    and ``models/layers.py QuantDense`` (whose param shapes must agree)."""
    if group_size <= 0:
        group_size = DEFAULT_INT4_GROUP if mode == "int4" else 0
    align = k // shards if shards > 1 and k % shards == 0 else k
    return resolve_group_size(align, mode, group_size)


def resolve_group_size(k: int, mode: str, group_size: int) -> int:
    """Effective scale-group length along K: the requested ``group_size``
    shrunk to the largest divisor of ``k`` at most that big (0 = one group
    spanning all of K, i.e. per-output-column scales). int4 groups must be
    even (nibble pairs must not straddle a scale boundary)."""
    if mode == "int4" and k % 2:
        # fail here with the named precondition, not a ZeroDivisionError
        # from the even-divisor walk below
        raise ValueError(f"int4 quantization needs an even K, got {k}")
    g = k if group_size <= 0 else min(group_size, k)
    while k % g:
        g -= 1
    if mode == "int4" and g % 2:
        # K is even (checked above), so an even divisor >= 2 always exists
        g = 2 if g == 1 else g - 1
        while k % g or g % 2:
            g -= 1
    return g


def quantize_linear_weight(w: jnp.ndarray, mode: str = "int8",
                           group_size: int = 0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Absmax-quantize a linear kernel ``[K, N]`` (K = input features).

    Returns ``(codes, scale)``: int8 codes ``[K, N]`` (int8) or packed
    uint8 ``[K//2, N]`` (int4), and fp32 scales ``[G, N]`` with one scale
    per ``group`` contiguous K rows per output column (``group_size <= 0``
    = one group = per-column). Symmetric ranges: ±127 (int8), ±7 (int4).
    """
    _check_mode(mode)
    k, n = w.shape
    if mode == "int4" and k % 2:
        raise ValueError(f"int4 quantization needs an even K, got {k}")
    g = resolve_group_size(k, mode, group_size)
    qmax = 127.0 if mode == "int8" else 7.0
    wg = w.astype(jnp.float32).reshape(k // g, g, n)
    amax = jnp.max(jnp.abs(wg), axis=1)
    scale = jnp.maximum(amax / qmax, 1e-12)              # [G, N]
    q = jnp.clip(jnp.round(wg / scale[:, None, :]), -qmax, qmax)
    q = q.reshape(k, n)
    if mode == "int4":
        return pack_int4(q), scale
    return q.astype(jnp.int8), scale


def dequantize_linear_weight(q: jnp.ndarray, scale: jnp.ndarray, mode: str,
                             dtype=jnp.float32) -> jnp.ndarray:
    """Rebuild the dense ``[K, N]`` kernel from codes + grouped scales —
    the XLA reference dequant (one fused multiply per element; XLA folds
    it into the consumer matmul's operand read on the reference path)."""
    _check_mode(mode)
    codes = unpack_int4(q) if mode == "int4" else q
    k, n = codes.shape
    gcount = scale.shape[0]
    wg = codes.astype(jnp.float32).reshape(gcount, k // gcount, n)
    return (wg * scale[:, None, :].astype(jnp.float32)).reshape(
        k, n).astype(dtype)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc, *, nk: int, mode: str,
            g_rows: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...]
    if mode == "int4":
        # the module-level unpack helper (pure jnp) runs on the VMEM
        # block, so kernel and XLA reference share ONE decode definition
        codes = unpack_int4(w_ref[...])
    else:
        codes = w_ref[...].astype(jnp.int32)
    # grouped dequant IN VMEM: broadcast each scale row over its g_rows
    # K rows, multiply, cast to the activation dtype for the MXU
    s = jnp.repeat(s_ref[...], g_rows, axis=0)           # [bk, bn]
    w = (codes.astype(jnp.float32) * s).astype(x.dtype)
    acc[:] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = acc[:].astype(o_ref.dtype)


def quant_matmul(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                 mode: str = "int8", block_k: int = 512, block_n: int = 512,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x``: [B, K] activations (bf16/f32); ``wq``/``scale`` from
    :func:`quantize_linear_weight`. Returns ``[B, N]`` in ``x.dtype``.

    ``interpret=None`` auto-selects: real kernel on TPU, dequant+matmul
    fallback elsewhere (identical math to the layers.py reference path).
    """
    _check_mode(mode)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return x @ dequantize_linear_weight(wq, scale, mode, x.dtype)
        interpret = False
    b, k = x.shape
    kq, n = wq.shape
    if (2 * kq if mode == "int4" else kq) != k:
        raise ValueError(f"wq K dim {kq} inconsistent with x K {k} ({mode})")
    gcount = scale.shape[0]
    g = k // gcount
    # K blocks must hold whole scale groups (and whole nibble pairs)
    bk = max(g, (min(block_k, k) // g) * g)
    bn = min(block_n, n)
    pad_k = (-k) % bk
    pad_n = (-n) % bn
    if pad_k:
        # zero-padding is exact: padded x columns are 0, padded weight
        # bytes decode to 0 (both nibbles of 0x00 sign-extend to 0)
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        wq = jnp.pad(wq, ((0, pad_k // (2 if mode == "int4" else 1)),
                          (0, 0)))
        scale = jnp.pad(scale, ((0, pad_k // g), (0, 0)))
    if pad_n:
        wq = jnp.pad(wq, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale, ((0, 0), (0, pad_n)))
    nk = (k + pad_k) // bk
    nn = (n + pad_n) // bn
    wrows = bk // 2 if mode == "int4" else bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, mode=mode, g_rows=g),
        grid=(nn, nk),
        in_specs=[
            pl.BlockSpec((b, bk), lambda jn, ik: (0, ik)),
            pl.BlockSpec((wrows, bn), lambda jn, ik: (ik, jn)),
            pl.BlockSpec((bk // g, bn), lambda jn, ik: (ik, jn)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda jn, ik: (0, jn)),
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, n + pad_n), x.dtype),
        interpret=interpret,
        name=QUANT_MATMUL,
    )(x, wq, scale)
    return out[:, :n]
