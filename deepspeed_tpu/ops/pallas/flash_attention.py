"""Flash attention (Pallas TPU kernel), forward + backward.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, strided-batch attention GEMMs in
``csrc/transformer/ds_transformer_cuda.cpp``): an online-softmax tiled
attention that never materializes the [T, T] score matrix in HBM.

Layout: inputs are [B, T, H, D] (model convention); kernels operate on
[B, H, T, D]. The kv-block grid dimension is innermost, so the per-q-block
running max / sum / accumulator live in VMEM scratch across sequential grid
steps (standard TPU flash pattern). Backward uses the saved logsumexp and
recomputes P per tile: one kernel for dQ (loop over kv), one for dK/dV
(loop over q).

On non-TPU backends the public entry falls back to reference einsum math so
the same model code runs everywhere (tests use the fallback + interpret
mode for kernel parity).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD

NEG_INF = -1e30


def _ceil_div(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                tq: int, tk: int, window, has_mask: bool = False):
    if has_mask:
        kmask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip fully-masked kv blocks (top-right triangle). Causality is
    # bottom-right aligned (offset = tk - tq), matching the decode convention
    # and the einsum fallback's tril(k=Tk-Tq).
    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1 + (tk - tq)
    if window is not None:
        # kv block wholly below the sliding window of every q row: skip
        run = run & (ik * block_k + block_k - 1 + window >
                     iq * block_q + (tk - tq))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + iq * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ik * block_k
        # ragged tails: padded kv columns/q rows contribute nothing
        valid = (cols < tk) & (rows < tq)
        if causal:
            valid = valid & (rows + (tk - tq) >= cols)
        if window is not None:
            valid = valid & (rows + (tk - tq) - cols < window)
        if has_mask:  # [B, Tk] key-padding mask (left-padded prompts)
            valid = valid & (kmask_ref[0] > 0)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[:]                       # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                  # [bq, bk]
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # compact residual, one fp32 per q row (not lane-broadcast to 128
        # columns). It is stored as a [1, bq] ROW of [B, H, 1, Tq]: a
        # (1, bq) block of a bare [B, H, Tq] array breaks Mosaic's
        # block-shape rule
        lse_ref[0, 0, 0] = (m_scr[:] + jnp.log(l_safe))[:, 0]


def _pad_seq(x, block):
    t = x.shape[2]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               window=None, key_mask=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    Hkv = k.shape[1]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    rep = H // Hkv  # GQA: q head h reads kv head h // rep — no
    # repeat_kv materialization (the index map does the mapping)
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    # pad to block multiples; kernels mask with the ORIGINAL lengths
    q, k, v = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    grid = (B, H, Tq_p // bq, Tk_p // bk)

    mask_args = []
    mask_specs = []
    if key_mask is not None:
        km = jnp.pad(key_mask.astype(jnp.int32),
                     ((0, 0), (0, Tk_p - key_mask.shape[1])))
        mask_args = [km[:, None]]
        mask_specs = [pl.BlockSpec((1, 1, bk),
                                   lambda b, h, iq, ik: (b, 0, ik))]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, tq=Tq, tk=Tk,
                          window=window, has_mask=key_mask is not None),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // rep, ik, 0)),
        ] + mask_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Tq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(q, k, v, *mask_args)
    return out[:, :, :Tq], lse[:, :, 0, :Tq]  # lse: compact [B,H,Tq] fp32


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   sm_scale: float, causal: bool, block_q: int, block_k: int,
                   tq: int, tk: int, window):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1 + (tk - tq)
    if window is not None:
        run = run & (ik * block_k + block_k - 1 + window >
                     iq * block_q + (tk - tq))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]            # compact [bq] residual
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + iq * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ik * block_k
        valid = (cols < tk) & (rows < tq)
        if causal:
            valid = valid & (rows + (tk - tq) >= cols)
        if window is not None:
            valid = valid & (rows + (tk - tq) - cols < window)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] += sm_scale * jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr, *, sm_scale: float, causal: bool, block_q: int,
                    block_k: int, tq: int, tk: int, window):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        # q block fully above the diagonal contributes nothing to this kv block
        run = iq * block_q + block_q - 1 + (tk - tq) >= ik * block_k
    if window is not None:
        # q block whose window lies wholly past this kv block: skip
        run = run & (ik * block_k + block_k - 1 + window >
                     iq * block_q + (tk - tq))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]            # compact [bq] residual
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + iq * block_q
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + ik * block_k
        # ragged tails: padded q rows AND padded kv cols must contribute zero
        valid = (cols < tk) & (rows < tq)
        if causal:
            valid = valid & (rows + (tk - tq) >= cols)
        if window is not None:
            valid = valid & (rows + (tk - tq) - cols < window)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)                    # [bq, bk]
        p = jnp.where(rows < tq, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                   # [bq, bk]
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
               window=None):
    q, k, v, out, lse = res
    do = g
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq, bk = min(block_q, Tq), min(block_k, Tk)

    # compact [B,H,Tq] residuals (see _fwd_kernel finalize note)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    # pad to block multiples (kernels mask with the original lengths)
    q, do = _pad_seq(q, bq), _pad_seq(do, bq)
    k, v = _pad_seq(k, bk), _pad_seq(v, bk)
    pad_q = q.shape[2] - Tq
    if pad_q:
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    lse, delta = lse[:, :, None], delta[:, :, None]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, tq=Tq, tk=Tk,
                          window=window),
        grid=(B, H, Tq_p // bq, Tk_p // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik: (b, h, 0, iq)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name=FLASH_BWD_DQ,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, tq=Tq, tk=Tk,
                          window=window),
        grid=(B, H, Tk_p // bk, Tq_p // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, ik, iq: (b, h, 0, iq)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, ik, iq: (b, h, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV,
    )(q, k, v, do, lse, delta)
    return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_bhtd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                          window=None):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        window)
    return out


def _vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
             window=None):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret, window)
    return out, (q, k, v, out, lse)


def _vjp_bwd(sm_scale, causal, block_q, block_k, interpret, window, res, g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
                      window)


_flash_attention_bhtd.defvjp(_vjp_fwd, _vjp_bwd)


def _reference_attention(q, k, v, causal, sm_scale, window=None,
                         key_mask=None):
    """[B,T,H,D] einsum reference (used on non-TPU backends)."""
    if k.shape[2] != q.shape[2]:
        # GQA (masked fwd-only path accepts un-repeated kv heads): expand
        # consecutively, matching the kernel's h // rep index map
        rep = q.shape[2] // k.shape[2]
        b, t, hk, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None], (b, t, hk, rep, d)).reshape(
            b, t, hk * rep, d)
        v = jnp.broadcast_to(v[:, :, :, None], (b, t, hk, rep, d)).reshape(
            b, t, hk * rep, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    Tq, Tk = q.shape[1], k.shape[1]
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if window is not None:
        i = jnp.arange(Tq)[:, None]
        j = jnp.arange(Tk)[None, :]
        wmask = (i + (Tk - Tq) - j) < window
        logits = jnp.where(wmask[None, None], logits, NEG_INF)
    if key_mask is not None:
        logits = jnp.where((key_mask > 0)[:, None, None, :], logits,
                           NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None, force_pallas: bool = False,
                    window: Optional[int] = None, key_mask=None):
    """Flash attention over [B, T, H, D] tensors.

    ``interpret=None`` auto-selects: real kernel on TPU, reference math
    elsewhere (interpret mode is available for kernel-parity tests).

    ``key_mask`` ``[B, Tk]`` (1 = real key) masks padded keys in-kernel
    (left-padded prefill). FORWARD-ONLY: the masked path skips the
    custom-vjp wrapper (serving prefill never differentiates); taking a
    gradient through it falls to JAX's default AD over the kernel,
    which pallas_call does not support — use the unmasked path (drop
    padding via the loss mask) for training.
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            return _reference_attention(q, k, v, causal, sm_scale,
                                        window=window, key_mask=key_mask)
        interpret = not on_tpu

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if key_mask is not None:
        # fwd-only masked path; GQA rides the kv-head index map (no
        # repeat_kv materialization)
        out, _ = _flash_fwd(qt, kt, vt, sm_scale, causal, block_q,
                            block_k, interpret, window, key_mask)
    else:
        if k.shape[2] != q.shape[2]:
            raise ValueError(
                "flash_attention training path needs pre-repeated kv "
                "heads (repeat_kv) — the dK/dV grid accumulates per "
                "head; GQA-native reads are forward-only (key_mask "
                "path)")
        out = _flash_attention_bhtd(qt, kt, vt, sm_scale, causal,
                                    block_q, block_k, interpret, window)
    return jnp.transpose(out, (0, 2, 1, 3))
