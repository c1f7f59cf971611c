"""Pallas decode attention over a partially-filled KV cache.

Counterpart of the reference's ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/pt_binding.cpp:1286``,
``softmax_kernels.cu``): single-position attention against the persistent KV
cache with triangular/padding masking — the hot op of every decode step.

TPU-native design: one Pallas program per (batch row, kv head) streams the
cache in ``block_k`` chunks with an online softmax; the grouped-query heads
of a kv head ride the same pass (GQA never materializes repeated K/V — the
XLA fallback's ``repeat_kv`` copies the cache ``H/Hkv`` times per step). KV
blocks wholly beyond the filled prefix (``cache_index``) are skipped under
``pl.when`` — as the cache fills, work grows with the REAL sequence length
while the XLA path always pays for the full padded cache.

Parity is tested against the engine's XLA decode path in interpret mode
(CPU) and the kernel is opt-in via ``decode_attention_impl="pallas"`` on the
model config.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import DECODE_ATTENTION, PAGED_DECODE_ATTENTION, PAGED_PREFILL_ATTENTION

NEG_INF = float("-inf")


def _ceil_div(a, b):
    return (a + b - 1) // b


def _decode_kernel(cidx_ref, q_ref, k_ref, v_ref, *rest,
                   sm_scale: float, block_k: int, s_total: int, window,
                   int8: bool):
    if int8:
        ks_ref, vs_ref, mask_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        mask_ref, o_ref, m_scr, l_scr, acc_scr = rest
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    cidx = cidx_ref[0]
    # skip blocks entirely beyond the filled prefix AND (with a sliding
    # window) blocks entirely below it: compute grows with
    # min(real length, window)
    run = ik * block_k <= cidx
    if window is not None:
        run = run & ((ik + 1) * block_k > cidx - window)

    @pl.when(run)
    def _body():
        # refs index the caches' HEAD-MAJOR [B, Hkv, S, D] layout (see
        # models/layers.py init_kv_cache): blocks are (1, 1, bk, D) —
        # well-tiled minor dims AND zero host-side cache transforms
        q = q_ref[0, 0].astype(jnp.float32)     # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)     # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)     # [bk, D]
        # the trailing partial block (S % bk) arrives with UNSPECIFIED
        # edge-padding bytes on hardware; scores are masked below (p == 0
        # there) but 0 * NaN would still poison dot(p, v) — zero V's tail
        # rows explicitly (K needs no guard: its garbage flows into s,
        # which the where() below overwrites)
        rows = jax.lax.broadcasted_iota(jnp.int32, (v.shape[0], 1), 0) \
            + ik * block_k
        v = jnp.where(rows < s_total, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if int8:
            # int8 cache: HBM->VMEM moved half the bytes. The per-(kv
            # head, position) absmax scales ride as [1, bk] ROWS, and
            # scaling the score / probability columns equals dequantizing
            # K / V first
            s = s * ks_ref[0, 0]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ik * block_k
        valid = (cols <= cidx) & (cols < s_total)
        if window is not None:  # Mistral sliding window: cidx - j < window
            valid = valid & (cidx - cols < window)
        valid = valid & (mask_ref[0] > 0)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[:]                        # [G, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # all-masked blocks keep m at -inf; exp(-inf - -inf) guards below
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if int8:  # the scale row's edge padding is unspecified too
            p = jnp.where(cols < s_total, p * vs_ref[0, 0], 0.0)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _reference_decode(q, k_cache, v_cache, cache_index, key_mask, sm_scale,
                      window=None):
    from ...models.layers import (cache_attention_bias,
                                  dot_product_attention, repeat_kv)

    H, Hkv = q.shape[1], k_cache.shape[2]
    k = repeat_kv(k_cache.astype(q.dtype), H // Hkv)
    v = repeat_kv(v_cache.astype(q.dtype), H // Hkv)
    bias = cache_attention_bias(1, k.shape[1], cache_index, key_mask=key_mask,
                                window=window)
    return dot_product_attention(q[:, None], k, v, bias=bias, causal=False,
                                 scale=sm_scale)[:, 0]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, cache_index,
                     key_mask: Optional[jnp.ndarray] = None,
                     sm_scale: Optional[float] = None, block_k: int = 256,
                     interpret: Optional[bool] = None,
                     force_pallas: bool = False,
                     window: Optional[int] = None,
                     k_scale: Optional[jnp.ndarray] = None,
                     v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Single-position cached attention.

    q: ``[B, H, D]`` (the one new token's query heads), k_cache/v_cache:
    head-major ``[B, Hkv, S, D]`` (the ``init_kv_cache`` layout),
    ``cache_index``: scalar count of already-cached tokens (the new token
    sits at that position), ``key_mask``: ``[B, S]`` 1 = real token.
    Returns ``[B, H, D]``.

    An int8 cache passes ``k_scale``/``v_scale`` ``[B, Hkv, S]`` (see
    ``models/layers.py init_kv_cache``): the kernel reads int8 from HBM —
    half the decode bandwidth — and dequantizes per block in VMEM. The
    reference's int8 inference kernels dequantize in shared memory the same
    way (``csrc/transformer/inference``, SURVEY row 46).

    ``interpret=None`` auto-selects: real kernel on TPU, the XLA reference
    math elsewhere (interpret mode available for kernel-parity tests).
    """
    int8 = k_scale is not None
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            if sm_scale is None:
                sm_scale = 1.0 / (q.shape[-1] ** 0.5)
            if int8:
                from ...models.layers import dequantize_kv
                k_cache = dequantize_kv(k_cache, k_scale, q.dtype)
                v_cache = dequantize_kv(v_cache, v_scale, q.dtype)
            return _reference_decode(
                q, jnp.swapaxes(k_cache, 1, 2),
                jnp.swapaxes(v_cache, 1, 2), cache_index, key_mask,
                sm_scale, window=window)
        interpret = not on_tpu
    B, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    if H % Hkv:
        raise ValueError(f"query heads {H} must divide into kv heads {Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    bk = min(block_k, S)

    # q regrouped per kv head (tiny: [B, H, D]); K/V/scales arrive in the
    # HEAD-MAJOR [B, Hkv, S, D] cache layout (models/layers.py
    # init_kv_cache), so blocks are (1, 1, bk, D) — well-tiled minor dims
    # — and the host side does NO cache-sized transform at all (earlier
    # versions swapaxes+padded the whole cache EVERY step, an O(S) copy
    # that dwarfed the kernel's own bandwidth savings)
    qg = q.reshape(B, Hkv, G, D)
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    # mask [B, S] and scales [B, Hkv, S] gain a unit second-minor axis: a
    # (1, bk) block of the bare array breaks Mosaic's block-shape rule, the
    # same data as a row of [.., 1, S] does not
    key_mask = key_mask.astype(jnp.int32)[:, None]
    cidx = jnp.asarray(cache_index, jnp.int32).reshape(1)
    scales = []
    if int8:
        scales = [k_scale.astype(jnp.float32)[:, :, None],
                  v_scale.astype(jnp.float32)[:, :, None]]

    nk = _ceil_div(S, bk)

    # Clamp the K/V/mask block index to the filled prefix: grid steps beyond
    # cache_index revisit the SAME already-resident block, so Pallas skips
    # the HBM->VMEM copy — decode bandwidth (the bottleneck) grows with the
    # REAL sequence length, not the padded cache. Compute for those steps is
    # skipped by the pl.when in the kernel body. The trailing partial block
    # (S % bk) is handled by Pallas' edge padding; compute masks it via
    # ``cols < s_total``.
    def kv_idx(b, h, ik, cidx_ref):
        return (b, h, jnp.minimum(ik, cidx_ref[0] // bk), 0)

    def mask_idx(b, h, ik, cidx_ref):
        return (b, 0, jnp.minimum(ik, cidx_ref[0] // bk))

    def scale_idx(b, h, ik, cidx_ref):
        return (b, h, 0, jnp.minimum(ik, cidx_ref[0] // bk))

    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bk, D), kv_idx),
        pl.BlockSpec((1, 1, bk, D), kv_idx),
    ]
    if int8:
        in_specs += [pl.BlockSpec((1, 1, 1, bk), scale_idx)] * 2
    in_specs.append(pl.BlockSpec((1, 1, bk), mask_idx))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, block_k=bk,
                          s_total=S, window=window, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name=DECODE_ATTENTION,
    )(cidx, qg, k_cache, v_cache, *scales, key_mask)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Paged (block-table) decode attention — the LEGACY serving engine's kernel
#
# Same online-softmax pass as the dense kernel above, but the KV operand is
# the SHARED block pool ``[N, Hkv, bs, D]`` (models/layers.py
# init_paged_kv_cache) and each grid step ``ik`` DMAs the page named by the
# sequence's block table instead of a contiguous cache stripe. This is the
# TPU-native shape of "Ragged Paged Attention" (arxiv 2604.15464): one
# fixed-shape program serves every mix of sequence lengths — ragged-ness
# lives entirely in the prefetched block tables / context lengths, never in
# the compiled shape.
#
# The default serving engine now runs the UNIFIED kernel
# (ops/pallas/ragged_attention.py): decode rows and prefill chunks on one
# packed grid. The split decode/prefill kernels below remain as the legacy
# (ServingConfig.mixed_step=False) path and as the per-row ground truth the
# unified kernel's parity tests are pinned against.
# ---------------------------------------------------------------------------


def _paged_decode_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, *rest,
                         sm_scale: float, block_size: int, window,
                         int8: bool):
    if int8:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    clen = cl_ref[b]
    # pages wholly beyond the context are skipped (their index map revisits
    # the last real page, so the DMA is also elided); with a sliding window
    # pages wholly below it are skipped too
    run = ik * block_size < clen
    if window is not None:
        run = run & ((ik + 1) * block_size > clen - 1 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)      # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)      # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)      # [bs, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if int8:  # [1, bs] scale rows, as in _decode_kernel
            s = s * ks_ref[0, 0]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + ik * block_size
        valid = cols < clen
        if window is not None:  # query position is clen - 1
            valid = valid & (clen - 1 - cols < window)
        s = jnp.where(valid, s, NEG_INF)
        # freed/unwritten page tails hold stale-but-finite values (pools are
        # zero-initialized and only ever hold real appends), so masked p==0
        # rows cannot poison dot(p, v) the way hardware edge padding can
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if int8:
            p = p * vs_ref[0, 0]
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           force_pallas: bool = False,
                           window: Optional[int] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Single-position attention over a paged KV pool via block tables.

    ``q``: ``[B, H, D]``; ``k_pages``/``v_pages``: ``[N, Hkv, bs, D]`` (the
    ``init_paged_kv_cache`` pool, new token ALREADY appended);
    ``block_tables``: int32 ``[B, nb_max]`` page ids (``N`` = unallocated
    sentinel); ``context_lens``: int32 ``[B]`` valid tokens per sequence
    including the new one. Returns ``[B, H, D]``.

    An int8 pool passes ``k_scale``/``v_scale`` ``[N, Hkv, bs]``; pages are
    dequantized per block in VMEM (HBM reads stay int8). ``interpret=None``
    auto-selects: real kernel on TPU, the gather-based XLA reference
    (``models/layers.py paged_attention_reference``) elsewhere.
    """
    int8 = k_scale is not None
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            from ...models.layers import paged_attention_reference

            cache = {"k": k_pages, "v": v_pages}
            if int8:
                cache["k_scale"], cache["v_scale"] = k_scale, v_scale
            return paged_attention_reference(q, cache, block_tables,
                                             context_lens, window=window,
                                             scale=sm_scale)
        interpret = not on_tpu
    B, H, D = q.shape
    N, Hkv, bs, _ = k_pages.shape
    if H % Hkv:
        raise ValueError(f"query heads {H} must divide into kv heads {Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    nb = block_tables.shape[1]

    qg = q.reshape(B, Hkv, G, D)
    bt = jnp.asarray(block_tables, jnp.int32)
    clen = jnp.asarray(context_lens, jnp.int32)

    # Grid steps beyond a sequence's context revisit its LAST real page (the
    # DMA is skipped — Pallas elides copies of an already-resident block);
    # sentinel table entries clamp to a real page whose contents the
    # in-kernel context mask hides. Per-sequence work therefore grows with
    # the REAL context, not nb_max * bs.
    def kv_idx(b, h, ik, bt_ref, cl_ref):
        last = jnp.maximum(cl_ref[b] - 1, 0) // bs
        pid = bt_ref[b, jnp.minimum(ik, last)]
        return (jnp.minimum(pid, N - 1), h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
    ]
    if int8:
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_idx)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    scales = []
    if int8:
        scales = [k_scale.astype(jnp.float32)[:, :, None],
                  v_scale.astype(jnp.float32)[:, :, None]]
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                          block_size=bs, window=window, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
        name=PAGED_DECODE_ATTENTION,
    )(bt, clen, qg, k_pages, v_pages, *scales)
    return out.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Paged CHUNKED-PREFILL attention — the serving layer's mixed-step kernel
#
# Same per-(sequence, kv head) page walk as the decode kernel, but the query
# operand is a whole prefill CHUNK: [T] tokens whose absolute positions start
# at a per-sequence offset that rides in the scalar prefetch (chunk_start),
# never in the compiled shape. Row t of the chunk sits at position
# chunk_start + t and sees kv positions <= that — causality across chunk
# boundaries AND over any prefix-cache hit, with zero recompiles as chunks
# advance or hit lengths vary. This is the prefill half of "Ragged Paged
# Attention": prefill raggedness is data over the same paged pool the decode
# kernel reads.
# ---------------------------------------------------------------------------


def _paged_prefill_kernel(bt_ref, cs_ref, cl_ref, q_ref, k_ref, v_ref, *rest,
                          sm_scale: float, block_size: int, group: int,
                          window, int8: bool):
    if int8:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = cs_ref[b]
    clen = cl_ref[b]
    # pages wholly beyond the context are skipped (their index map revisits
    # the last real page, so the DMA is also elided); with a sliding window
    # pages wholly below the FIRST chunk row's window are skipped too
    run = ik * block_size < clen
    if window is not None:
        run = run & ((ik + 1) * block_size > start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)      # [T*G, D]
        k = k_ref[0, 0].astype(jnp.float32)      # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)      # [bs, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if int8:  # [1, bs] scale rows, as in _decode_kernel
            s = s * ks_ref[0, 0]
        # row r is the (r // group)-th chunk token at absolute position
        # start + r // group; chunk-padding rows (position >= clen) end up
        # all-masked — their l stays 0 and _finalize writes zeros
        q_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // group
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + ik * block_size
        valid = (cols <= q_pos) & (cols < clen) & (q_pos < clen)
        if window is not None:
            valid = valid & (q_pos - cols < window)
        s = jnp.where(valid, s, NEG_INF)
        # pool pages are always materialized full (bs x D block == page), so
        # no hardware edge padding can poison dot(p, v) — same argument as
        # the paged decode kernel
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(s == NEG_INF, 0.0, jnp.exp(s - m_new))
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if int8:
            p = p * vs_ref[0, 0]
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                            chunk_start: jnp.ndarray,
                            context_lens: jnp.ndarray,
                            sm_scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            force_pallas: bool = False,
                            window: Optional[int] = None,
                            k_scale: Optional[jnp.ndarray] = None,
                            v_scale: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """Chunked-prefill attention over a paged KV pool via block tables.

    ``q``: ``[B, T, H, D]`` (one prefill chunk per sequence, KV ALREADY
    appended to the pool); ``chunk_start``: int32 ``[B]`` absolute position
    of each chunk's first token (tokens before it — prefix-cache hits and
    earlier chunks — are read from the pool); ``context_lens``: int32
    ``[B]`` valid tokens after this append, so a chunk shorter than ``T``
    pads at the tail (rows past ``context_lens`` return zeros). Causality
    is per row: chunk token t sees kv positions ``<= chunk_start + t``.

    Both the chunk offset and the cached-prefix length are scalar-prefetch
    DATA — every chunk position and every hit length reuses ONE compiled
    program. ``interpret=None`` auto-selects: real kernel on TPU, the
    gather-based XLA reference elsewhere.
    """
    int8 = k_scale is not None
    B, T, H, D = q.shape
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            from ...models.layers import paged_prefill_attention_reference

            cache = {"k": k_pages, "v": v_pages}
            if int8:
                cache["k_scale"], cache["v_scale"] = k_scale, v_scale
            pos = jnp.asarray(chunk_start, jnp.int32)[:, None] \
                + jnp.arange(T)[None, :]
            pos = jnp.where(
                pos < jnp.asarray(context_lens, jnp.int32)[:, None], pos, -1)
            return paged_prefill_attention_reference(
                q, cache, block_tables, pos, context_lens, window=window,
                scale=sm_scale)
        interpret = not on_tpu
    N, Hkv, bs, _ = k_pages.shape
    if H % Hkv:
        raise ValueError(f"query heads {H} must divide into kv heads {Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    nb = block_tables.shape[1]

    # rows grouped [T, G] per kv head: row r = chunk token r // G, query
    # head r % G — the same [B, Hkv, rows, D] layout as the decode kernel,
    # just with T*G rows instead of G
    qg = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Hkv, T * G, D)
    bt = jnp.asarray(block_tables, jnp.int32)
    cs = jnp.asarray(chunk_start, jnp.int32)
    clen = jnp.asarray(context_lens, jnp.int32)

    def kv_idx(b, h, ik, bt_ref, cs_ref, cl_ref):
        last = jnp.maximum(cl_ref[b] - 1, 0) // bs
        pid = bt_ref[b, jnp.minimum(ik, last)]
        return (jnp.minimum(pid, N - 1), h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, T * G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
    ]
    if int8:
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_idx)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, T * G, D),
                               lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, 1), jnp.float32),
            pltpu.VMEM((T * G, D), jnp.float32),
        ],
    )
    scales = []
    if int8:
        scales = [k_scale.astype(jnp.float32)[:, :, None],
                  v_scale.astype(jnp.float32)[:, :, None]]
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, sm_scale=sm_scale,
                          block_size=bs, group=G, window=window, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, T * G, D), q.dtype),
        interpret=interpret,
        name=PAGED_PREFILL_ATTENTION,
    )(bt, cs, clen, qg, k_pages, v_pages, *scales)
    return out.reshape(B, Hkv, T, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, T, H, D)
