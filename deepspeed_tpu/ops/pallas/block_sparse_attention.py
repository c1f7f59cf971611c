"""Block-sparse flash attention (Pallas TPU kernel, fwd + bwd).

Counterpart of the reference's Triton block-sparse attention
(``deepspeed/ops/sparse_attention/matmul.py`` SDD/DSD, ``softmax.py``) driven
by the layouts in ``ops/sparse_attention/sparsity_config.py``. Instead of
composing three block-sparse matmul kernels, this is a splash-style design:
ONE flash-attention kernel whose kv-block sequence per (head, q-block) comes
from scalar-prefetched index arrays — the grid only visits ACTIVE blocks
(padded to the max row degree), so compute and DMA scale with layout density,
not with T^2.

Index layout: ``kv_idx[h, iq, a]`` = a'th active kv block of q-block iq
(padded by repeating the last entry), ``kv_cnt[h, iq]`` = active count; the
backward dk/dv pass uses the transposed mapping ``q_idx``/``q_cnt``.

Cost note: the grid's inner extent is the MAX row degree, so one global row
(a block attending to everything, as in BigBird/Longformer global tokens)
raises every row's padded extent to nb — padded slots skip compute via
``pl.when`` but still occupy grid steps. Layouts dominated by windows/random
blocks get the full density win; heavy global patterns approach dense grid
cost in the q direction (the reference's SDD kernels share the property that
global rows cost O(nb)).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import BLOCK_SPARSE_BWD_DKV, BLOCK_SPARSE_BWD_DQ, BLOCK_SPARSE_FWD

NEG_INF = -1e30


def layout_indices(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """[H, R, C] 0/1 layout → (idx [H, R, A], cnt [H, R]) active-column lists
    padded (by repetition) to the max row degree A."""
    H, R, C = layout.shape
    cnt = layout.sum(-1).astype(np.int32)
    if (cnt == 0).any():
        raise ValueError("sparsity layout has an empty row: every q block "
                         "must attend to at least one kv block")
    A = int(cnt.max())
    idx = np.zeros((H, R, A), np.int32)
    for h in range(H):
        for r in range(R):
            active = np.nonzero(layout[h, r])[0]
            idx[h, r, :len(active)] = active
            idx[h, r, len(active):] = active[-1]
    return idx, cnt


def _fwd_kernel(kv_idx, kv_cnt, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, bq, bk):
    h, iq, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    na = pl.num_programs(3)

    @pl.when(a == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ki = kv_idx[h, iq, a]
    active = a < kv_cnt[h, iq]
    if causal:
        active = active & (ki * bk <= iq * bq + bq - 1)

    @pl.when(active)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(a == na - 1)
    def _fin():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # compact [bq] residual (same HBM-traffic fix as flash_attention:
        # the old 128-lane fp32 broadcast cost multiples of the q-block
        # bytes per backward inner step)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[:] + jnp.log(l_safe))
        lse_ref[0, 0] = lse[:, 0]


def _bwd_dq_kernel(kv_idx, kv_cnt, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, sm_scale, causal, bq, bk):
    h, iq, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    na = pl.num_programs(3)

    @pl.when(a == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    ki = kv_idx[h, iq, a]
    active = a < kv_cnt[h, iq]
    if causal:
        active = active & (ki * bk <= iq * bq + bq - 1)

    @pl.when(active)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] += sm_scale * jax.lax.dot(ds, k,
                                            preferred_element_type=jnp.float32)

    @pl.when(a == na - 1)
    def _fin():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_idx, q_cnt, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, bq, bk):
    h, ik, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    na = pl.num_programs(3)

    @pl.when(a == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qi = q_idx[h, ik, a]
    active = a < q_cnt[h, ik]
    if causal:
        active = active & (qi * bq + bq - 1 >= ik * bk)

    @pl.when(active)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(a == na - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _spec_q(bq, D):
    return pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, a, *_: (b, h, iq, 0))


def _spec_kv(bk, D):
    def index_map(b, h, iq, a, kv_idx, kv_cnt):
        return (b, h, kv_idx[h, iq, a], 0)

    return pl.BlockSpec((1, 1, bk, D), index_map)


def _fwd(q, k, v, kv_idx, kv_cnt, sm_scale, causal, bq, bk, interpret):
    B, H, T, D = q.shape
    nq = T // bq
    A = kv_idx.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, A),
        in_specs=[
            _spec_q(bq, D),
            _spec_kv(bk, D),
            _spec_kv(bk, D),
        ],
        out_specs=[
            _spec_q(bq, D),
            pl.BlockSpec((1, 1, bq), lambda b, h, iq, a, *_: (b, h, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bk=bk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T), jnp.float32),
        ],
        interpret=interpret,
        name=BLOCK_SPARSE_FWD,
    )(kv_idx, kv_cnt, q, k, v)
    return out, lse


def _bwd(res, g, kv_idx, kv_cnt, q_idx, q_cnt, sm_scale, causal, bq, bk,
         interpret):
    q, k, v, out, lse = res
    do = g
    B, H, T, D = q.shape
    nq, nk = T // bq, k.shape[2] // bk
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    A = kv_idx.shape[-1]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nq, A),
            in_specs=[
                _spec_q(bq, D),
                _spec_kv(bk, D),
                _spec_kv(bk, D),
                _spec_q(bq, D),
                pl.BlockSpec((1, 1, bq), lambda b, h, iq, a, *_: (b, h, iq)),
                pl.BlockSpec((1, 1, bq), lambda b, h, iq, a, *_: (b, h, iq)),
            ],
            out_specs=_spec_q(bq, D),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        name=BLOCK_SPARSE_BWD_DQ,
    )(kv_idx, kv_cnt, q, k, v, do, lse, delta)

    Aq = q_idx.shape[-1]

    def qmap(b, h, ik, a, q_idx_ref, q_cnt_ref):
        return (b, h, q_idx_ref[h, ik, a], 0)

    def qmap_1d(b, h, ik, a, q_idx_ref, q_cnt_ref):
        return (b, h, q_idx_ref[h, ik, a])

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, nk, Aq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), qmap),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, a, *_: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, a, *_: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bq, D), qmap),
                pl.BlockSpec((1, 1, bq), qmap_1d),
                pl.BlockSpec((1, 1, bq), qmap_1d),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, a, *_: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, a, *_: (b, h, ik, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, D), jnp.float32),
                pltpu.VMEM((bk, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, T, D), v.dtype),
        ],
        interpret=interpret,
        name=BLOCK_SPARSE_BWD_DKV,
    )(q_idx, q_cnt, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _sparse_attn_bhtd(q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, sm_scale, causal,
                      bq, bk, interpret):
    out, _ = _fwd(q, k, v, kv_idx, kv_cnt, sm_scale, causal, bq, bk, interpret)
    return out


def _vjp_fwd(q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, sm_scale, causal, bq, bk,
             interpret):
    out, lse = _fwd(q, k, v, kv_idx, kv_cnt, sm_scale, causal, bq, bk, interpret)
    return out, (q, k, v, out, lse, kv_idx, kv_cnt, q_idx, q_cnt)


def _vjp_bwd(sm_scale, causal, bq, bk, interpret, res, g):
    *res5, kv_idx, kv_cnt, q_idx, q_cnt = res
    dq, dk, dv = _bwd(tuple(res5), g, kv_idx, kv_cnt, q_idx, q_cnt, sm_scale,
                      causal, bq, bk, interpret)
    # index operands are integer: their cotangent type is float0
    f0 = lambda x: np.zeros(x.shape, jax.dtypes.float0)
    return dq, dk, dv, f0(kv_idx), f0(kv_cnt), f0(q_idx), f0(q_cnt)


_sparse_attn_bhtd.defvjp(_vjp_fwd, _vjp_bwd)


def _reference_sparse(q, k, v, layout, block, causal, sm_scale):
    """Dense einsum with the block layout as a mask (tests / non-TPU)."""
    H = q.shape[2]
    T, S = q.shape[1], k.shape[1]
    mask = np.kron(layout, np.ones((block, block)))[:, :T, :S].astype(bool)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    m = jnp.asarray(mask)[None]
    if causal:
        m = m & jnp.tril(jnp.ones((T, S), bool))[None, None]
    logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (possible only with degenerate layouts) → zeros
    probs = jnp.where(m.any(-1, keepdims=True), probs, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def sparse_attention(q, k, v, sparsity_config=None, layout: Optional[np.ndarray] = None,
                     causal: bool = True, sm_scale: Optional[float] = None,
                     interpret: Optional[bool] = None,
                     force_pallas: bool = False):
    """Block-sparse attention over ``[B, T, H, D]`` tensors.

    Provide either a ``SparsityConfig`` (``ops/sparse_attention``) or a
    precomputed ``layout [H, nb, nb]``. Non-TPU backends use the dense
    masked reference unless ``force_pallas`` (interpret mode, for tests).
    """
    B, T, H, D = q.shape
    if layout is None:
        if sparsity_config is None:
            raise ValueError("need sparsity_config or layout")
        layout = sparsity_config.make_layout(T)
    nb = layout.shape[1]
    if T % nb or layout.shape[1] != layout.shape[2]:
        raise ValueError(f"layout [{layout.shape}] must be square and tile "
                         f"seq_len {T} exactly")
    block = T // nb
    if layout.shape[0] != H:
        raise ValueError(f"layout heads {layout.shape[0]} != {H}")
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    if causal:
        nb = layout.shape[1]
        layout = np.asarray(layout) * np.tril(np.ones((nb, nb), np.int64))
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            return _reference_sparse(q, k, v, layout, block, causal, sm_scale)
        interpret = not on_tpu

    kv_idx, kv_cnt = layout_indices(layout)
    q_idx, q_cnt = layout_indices(np.swapaxes(layout, 1, 2))

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = _sparse_attn_bhtd(qt, kt, vt, jnp.asarray(kv_idx),
                            jnp.asarray(kv_cnt), jnp.asarray(q_idx),
                            jnp.asarray(q_cnt), sm_scale, causal, block,
                            block, interpret)
    return jnp.transpose(out, (0, 2, 1, 3))
