"""Weight-int8 matmul (Pallas): y = x @ dequant(Wq) with int8 HBM reads.

Counterpart of the reference's int8 inference GEMMs
(``csrc/transformer/inference/csrc/dequantize.cu``, the
``vector_matmul_int8``/``qkv_gemm_int8`` ops in ``pt_binding.cpp``): the
decode-time matmul is weight-bandwidth-bound, so reading int8 weights
halves the bytes.

TPU-native design: per-OUTPUT-COLUMN absmax scales mean the dequant factors
out of the contraction — the kernel accumulates ``x @ Wq`` (int8 weights
cast to the activation dtype in VMEM, fp32 accumulation on the MXU) across
K blocks in VMEM scratch and applies the column scales ONCE at the end.
HBM never sees a dequantized copy of the weights.

Off-TPU the public entry falls back to dequantize+matmul (same math);
interpret mode is used for kernel parity tests.

The GROUPED generalization (grouped scales, packed int4) used by the
quantized serving path lives in ``quant_matmul.py``; this kernel keeps
the per-column factor-out fast path.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import INT8_MATMUL


def quantize_weight_per_col(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K, N] float -> (int8 [K, N], fp32 scale [N]) with absmax/127 per
    output column (the granularity that factors out of the K contraction)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.round(w.astype(jnp.float32) / scale[None, :]).astype(jnp.int8)
    return q, scale


def _kernel(x_ref, w_ref, s_ref, o_ref, acc, *, nk: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)  # int8 -> activation dtype, in VMEM
    acc[:] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = (acc[:] * s_ref[...][None, :]).astype(o_ref.dtype)


def int8_matmul(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                block_k: int = 512, block_n: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """``x``: [B, K] activations (bf16/f32), ``wq``: [K, N] int8,
    ``scale``: [N] fp32 per-column. Returns [B, N] in ``x.dtype``.

    ``interpret=None`` auto-selects: real kernel on TPU, dequant+matmul
    fallback elsewhere.
    """
    if interpret is None:
        if jax.default_backend() != "tpu":
            w = (wq.astype(jnp.float32) * scale[None, :]).astype(x.dtype)
            return x @ w
        interpret = False
    b, k = x.shape
    k2, n = wq.shape
    assert k == k2 and scale.shape == (n,)
    bk = min(block_k, k)
    bn = min(block_n, n)
    pad_k = (-k) % bk
    pad_n = (-n) % bn
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        wq = jnp.pad(wq, ((0, pad_k), (0, 0)))
    if pad_n:
        wq = jnp.pad(wq, ((0, 0), (0, pad_n)))
        scale = jnp.pad(scale, (0, pad_n))
    nk = (k + pad_k) // bk
    nn = (n + pad_n) // bn
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(nn, nk),
        in_specs=[
            pl.BlockSpec((b, bk), lambda jn, ik: (0, ik)),
            pl.BlockSpec((bk, bn), lambda jn, ik: (ik, jn)),
            pl.BlockSpec((bn,), lambda jn, ik: (jn,)),
        ],
        out_specs=pl.BlockSpec((b, bn), lambda jn, ik: (0, jn)),
        scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((b, n + pad_n), x.dtype),
        interpret=interpret,
        name=INT8_MATMUL,
    )(x, wq, scale)
    return out[:, :n]
