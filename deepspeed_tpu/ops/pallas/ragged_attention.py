"""Unified ragged paged attention — ONE kernel for the whole serving step.

The serving engine used to keep TWO resident programs per step: the ragged
decode over ``max_batch_size`` slots (``decode_attention.py
paged_decode_attention``) plus a ``[1, chunk]`` chunked prefill
(``paged_prefill_attention``), with mid-prefill slots burning sentinel
decode rows. Following "Ragged Paged Attention" (arxiv 2604.15464), this
kernel serves BOTH on the same grid: the query operand is a flat PACKED
token batch — decode rows (1 token) and prefill chunks (n tokens) laid out
as contiguous per-sequence segments — and every per-row fact rides a
scalar-prefetched DESCRIPTOR array, never the compiled shape:

- ``query_start[r]`` / ``query_len[r]``: the row's segment in the packed
  token axis (0-length rows are inert — no sentinel work);
- ``chunk_start[r]``: absolute position of the row's first query token
  (decode rows: ``context_len - 1``; chunks mid-prompt: the chunk offset);
- ``context_lens[r]`` + ``block_tables[r]``: the same page-walk state the
  split kernels used.

The grid is ``(Hkv, R, nt, nb)``: per kv head, per row, per q-tile of the
row's segment, per KV page. The machinery is inherited from the split
kernels in ``decode_attention.py``:

- **page-walk DMA elision**: grid steps beyond a row's context (or beyond
  its query segment) revisit an already-resident page, so the copy is
  skipped — per-row work grows with the REAL context;
- **int8 VMEM dequant**: an int8 pool streams int8 from HBM and
  dequantizes per page in VMEM with the absmax scales;
- **per-row causality at ``chunk_start``**: query token t of row r sits at
  absolute position ``chunk_start[r] + t`` and sees kv positions <= that —
  decode (one token at ``clen - 1``) and chunk causality are the SAME rule.

Packed-segment mechanics: segments need no tile alignment and decode rows
cost ONE q-tile, not a padded chunk. Mosaic slices the sublane axis
dynamically only at tile-aligned starts, so a q-tile loads the aligned span
of packed rows that covers its ``q_tile * G`` rows (G = query heads per kv
head) and masks the rows outside them. Tiles wholly beyond ``query_len``
are skipped (compute AND copy). Stores are masked per row, so a span never
clobbers a neighbouring segment. The packed axis is padded by one span so
tail tiles never slice out of bounds.

Parity: ``query_len = [1] * B`` with ``chunk_start = context - 1``
reproduces ``paged_decode_attention`` exactly; one segment per sequence
reproduces ``paged_prefill_attention`` — both pinned in interpret mode by
``tests/unit/ops/test_ragged_attention.py``. ``interpret=None``
auto-selects: real kernel on TPU, the XLA reference
(``models/layers.py ragged_mixed_attention_reference``) elsewhere.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import RAGGED_PAGED_ATTENTION

NEG_INF = float("-inf")


def _ceil_div(a, b):
    return (a + b - 1) // b


def _ragged_kernel(bt_ref, qs_ref, ql_ref, cs_ref, cl_ref, q_ref, k_ref,
                   v_ref, *rest, sm_scale: float, block_size: int,
                   q_tile: int, group: int, align: int, span: int, window,
                   int8: bool):
    if int8:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    r = pl.program_id(1)
    it = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when((r == 0) & (it == 0) & (ik == 0))
    def _zero_out():
        # first program of this kv head's pass: blank the packed output
        # block once, so packed padding (and 0-length rows) read as zeros
        o_ref[:] = jnp.zeros_like(o_ref)

    qs = qs_ref[r]
    ql = ql_ref[r]
    cs = cs_ref[r]
    clen = cl_ref[r]
    rows0 = (qs + it * q_tile) * group        # tile's packed-row offset
    # Mosaic loads and stores a dynamic sublane slice only at a start it
    # can prove tile-aligned, and segments are tightly packed — so the
    # tile works on the ALIGNED span [a0, a0 + span) that covers its rows
    # [rows0, rows0 + q_tile * group) and masks the few rows outside them
    a0 = pl.multiple_of((rows0 // align) * align, align)
    # a tile wholly beyond the row's segment is inert; within it, pages
    # wholly beyond the context are skipped (their index map revisits the
    # last real page, so the DMA is also elided); with a sliding window
    # pages wholly below the tile's FIRST row's window are skipped too
    tile_live = (it * q_tile < ql) & (clen > 0)
    run = tile_live & (ik * block_size < clen)
    if window is not None:
        run = run & ((ik + 1) * block_size > cs + it * q_tile - window)

    def span_rows(width):
        # packed row j of the span -> (is one of this tile's real tokens,
        # that token's index in the row's segment)
        row = a0 + jax.lax.broadcasted_iota(jnp.int32, (span, width), 0)
        tok = row // group - qs
        mine = (row >= rows0) & (row < rows0 + q_tile * group) & (tok < ql)
        return mine, tok

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(run)
    def _body():
        q = q_ref[0, pl.ds(a0, span), :].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)   # [bs, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if int8:
            # per-position absmax scales ride as [1, bs] ROWS: scaling the
            # score / probability columns equals dequantizing K / V first
            s = s * ks_ref[0, 0]
        # a token at segment index tok sits at absolute position
        # chunk_start + tok; rows past query_len (or outside the tile) end
        # up all-masked (l stays 0, the store is masked anyway)
        mine, tok = span_rows(block_size)
        q_pos = cs + tok
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + ik * block_size
        valid = mine & (cols <= q_pos) & (cols < clen)
        if window is not None:
            valid = valid & (q_pos - cols < window)
        s = jnp.where(valid, s, NEG_INF)
        # pool pages are always materialized full (bs x D block == page),
        # so no hardware edge padding can poison dot(p, v) — same argument
        # as the paged decode kernel
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

    @pl.when((ik == nk - 1) & tile_live)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # masked store: the span reaches into the neighbouring rows'
        # packed segments — only this tile's real tokens may land
        mine, _ = span_rows(1)
        cur = o_ref[0, pl.ds(a0, span), :]
        o_ref[0, pl.ds(a0, span), :] = jnp.where(mine, out, cur)


def _reference_ragged(q, k_pages, v_pages, block_tables, query_start,
                      query_len, chunk_start, context_lens, sm_scale,
                      window, k_scale, v_scale):
    from ...models.layers import ragged_mixed_attention_reference

    T = q.shape[0]
    qs = jnp.asarray(query_start, jnp.int32)
    ql = jnp.asarray(query_len, jnp.int32)
    cs = jnp.asarray(chunk_start, jnp.int32)
    t = jnp.arange(T, dtype=jnp.int32)
    in_row = (t[None, :] >= qs[:, None]) & (t[None, :] < (qs + ql)[:, None])
    covered = in_row.any(axis=0)
    row = jnp.argmax(in_row, axis=0)
    pos = jnp.where(covered, cs[row] + t - qs[row], -1)
    row = jnp.where(covered, row, -1)
    cache = {"k": k_pages, "v": v_pages}
    if k_scale is not None:
        cache["k_scale"], cache["v_scale"] = k_scale, v_scale
    idx = {"block_tables": jnp.asarray(block_tables, jnp.int32),
           "append_pos": pos[None], "token_rows": row[None],
           "context_len": jnp.asarray(context_lens, jnp.int32),
           "chunk_start": cs, "query_start": qs, "query_len": ql}
    return ragged_mixed_attention_reference(q[None], cache, idx,
                                            window=window,
                                            scale=sm_scale)[0]


def ragged_paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           query_start: jnp.ndarray, query_len: jnp.ndarray,
                           chunk_start: jnp.ndarray,
                           context_lens: jnp.ndarray,
                           sm_scale: Optional[float] = None,
                           q_tile: int = 8,
                           interpret: Optional[bool] = None,
                           force_pallas: bool = False,
                           window: Optional[int] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Unified ragged mixed-batch attention over a paged KV pool.

    ``q``: ``[T, H, D]`` — the PACKED mixed token batch (contiguous
    per-row segments, KV ALREADY appended to the pool);
    ``k_pages``/``v_pages``: ``[N, Hkv, bs, D]`` (``init_paged_kv_cache``);
    ``block_tables``: int32 ``[R, nb_max]``; ``query_start``/``query_len``:
    int32 ``[R]`` each row's packed segment (len 0 = inactive row);
    ``chunk_start``: int32 ``[R]`` absolute position of the row's first
    query token; ``context_lens``: int32 ``[R]`` valid pool tokens after
    this step's append. Returns ``[T, H, D]``; packed positions no row
    claims return zeros.

    Segments must be disjoint in the packed axis (the serving engine packs
    them slot-ascending and contiguous). An int8 pool passes
    ``k_scale``/``v_scale`` ``[N, Hkv, bs]``. ``interpret=None``
    auto-selects: real kernel on TPU, the XLA reference elsewhere.
    """
    int8 = k_scale is not None
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            return _reference_ragged(q, k_pages, v_pages, block_tables,
                                     query_start, query_len, chunk_start,
                                     context_lens, sm_scale, window,
                                     k_scale, v_scale)
        interpret = not on_tpu
    T, H, D = q.shape
    N, Hkv, bs, _ = k_pages.shape
    if H % Hkv:
        raise ValueError(f"query heads {H} must divide into kv heads {Hkv}")
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    R, nb = block_tables.shape
    q_tile = max(1, min(q_tile, T))
    nt = _ceil_div(T, q_tile)
    # sublane tile of the packed q/out blocks (8 rows of 32 bits) and the
    # aligned span a q-tile works on (see _ragged_kernel); the packed axis
    # is padded by one span so the last tile never slices out of bounds
    # (a clamped, shifted dynamic slice would hand the masked compute
    # WRONG rows)
    align = 32 // q.dtype.itemsize
    span = _ceil_div(q_tile * G + align - math.gcd(G, align), align) * align
    rows = _ceil_div(T * G + span, align) * align

    qg = q.reshape(T, Hkv, G, D).transpose(1, 0, 2, 3).reshape(Hkv, T * G, D)
    qg = jnp.pad(qg, ((0, 0), (0, rows - T * G), (0, 0)))
    bt = jnp.asarray(block_tables, jnp.int32)
    qs = jnp.asarray(query_start, jnp.int32)
    ql = jnp.asarray(query_len, jnp.int32)
    cs = jnp.asarray(chunk_start, jnp.int32)
    cl = jnp.asarray(context_lens, jnp.int32)

    # Pages beyond a row's context revisit its LAST real page and tiles
    # beyond its segment park on page 0 — consecutive grid steps then name
    # the same block, so Pallas elides the HBM->VMEM copy (the split
    # kernels' trick, applied per tile). Sentinel table entries clamp to a
    # real page whose contents the in-kernel masks hide.
    def kv_idx(h, r, it, ik, bt_ref, qs_ref, ql_ref, cs_ref, cl_ref):
        last = jnp.maximum(cl_ref[r] - 1, 0) // bs
        ikc = jnp.where(it * q_tile < ql_ref[r], jnp.minimum(ik, last), 0)
        pid = bt_ref[r, ikc]
        return (jnp.minimum(pid, N - 1), h, 0, 0)

    in_specs = [
        # the whole packed q for this kv head stays VMEM-resident across
        # its (r, it, ik) subgrid — the index map moves only with h
        pl.BlockSpec((1, rows, D), lambda h, r, it, ik, *_: (h, 0, 0)),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
        pl.BlockSpec((1, 1, bs, D), kv_idx),
    ]
    scales = []
    if int8:
        # [N, Hkv, bs] -> [N, Hkv, 1, bs]: a (1, bs) block of the 3-D
        # array breaks Mosaic's block-shape rule, the same data as a row
        # of a 4-D array does not
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_idx)] * 2
        scales = [k_scale.astype(jnp.float32)[:, :, None],
                  v_scale.astype(jnp.float32)[:, :, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(Hkv, R, nt, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, D),
                               lambda h, r, it, ik, *_: (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((span, 1), jnp.float32),
            pltpu.VMEM((span, 1), jnp.float32),
            pltpu.VMEM((span, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, sm_scale=sm_scale, block_size=bs,
                          q_tile=q_tile, group=G, align=align, span=span,
                          window=window, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, rows, D), q.dtype),
        interpret=interpret,
        name=RAGGED_PAGED_ATTENTION,
    )(bt, qs, ql, cs, cl, qg, k_pages, v_pages, *scales)
    return out[:, :T * G].reshape(Hkv, T, G, D).transpose(1, 0, 2, 3) \
        .reshape(T, H, D)
