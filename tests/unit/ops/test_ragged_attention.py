"""Unified ragged paged-attention kernel (``ops/pallas/ragged_attention``):
interpret-mode parity against the split kernels it replaces.

The contract of the serving engine's ONE resident mixed step: a packed
token batch whose rows are decode steps (1 query at ``context - 1``) and
prefill chunks (n queries from ``chunk_start``) must equal
``paged_decode_attention`` / ``paged_prefill_attention`` row for row —
including int8 pools, sliding windows, ``chunk_start`` causality edges and
inactive (0-length) rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.layers import (init_paged_kv_cache,
                                         paged_cache_index,
                                         update_paged_kv_cache)
from deepspeed_tpu.ops.pallas.decode_attention import (
    paged_decode_attention, paged_prefill_attention)
from deepspeed_tpu.ops.pallas.ragged_attention import ragged_paged_attention

pytestmark = pytest.mark.serving


def _mixed_setup(rs, rows, Hkv=2, H=8, D=16, bs=8, n_pool=64, nb=6,
                 int8=False):
    """Build a pool + packed mixed batch from per-row specs.

    ``rows``: list of ``(kind, start, qlen)`` — ``kind`` in
    {"decode", "chunk", "idle"}; decode rows get qlen 1 at position
    ``start`` (context ``start + 1``), chunks span
    ``[start, start + qlen)``, idle rows contribute nothing. The packed
    batch appends every row's query KV through the packed
    ``update_paged_kv_cache`` path (token_rows), exactly like the engine.
    """
    R = len(rows)
    pool = init_paged_kv_cache(n_pool, bs, Hkv, D,
                               dtype=jnp.int8 if int8 else jnp.float32)
    bt = np.full((R, nb), n_pool, np.int32)
    free = iter(range(1, n_pool))
    qs = np.zeros((R,), np.int32)
    ql = np.zeros((R,), np.int32)
    cs = np.zeros((R,), np.int32)
    cl = np.zeros((R,), np.int32)
    segs = []
    cursor = 0
    for r, (kind, start, qlen) in enumerate(rows):
        if kind == "idle":
            continue
        n = 1 if kind == "decode" else qlen
        clen = start + n
        need = -(-clen // bs)
        bt[r, :need] = [next(free) for _ in range(need)]
        # cached prefix (everything before the packed queries)
        if start:
            pk = rs.randn(1, start, Hkv, D).astype(np.float32)
            pv = rs.randn(1, start, Hkv, D).astype(np.float32)
            idx = paged_cache_index(bt[r:r + 1], np.arange(start)[None],
                                    np.asarray([start]))
            pool = update_paged_kv_cache(pool, jnp.asarray(pk),
                                         jnp.asarray(pv), idx)
        qs[r], ql[r], cs[r], cl[r] = cursor, n, start, clen
        segs.append((r, cursor, n))
        cursor += n
    T = cursor + 2  # packed tail padding no row claims
    q = rs.randn(T, H, D).astype(np.float32)
    k = rs.randn(1, T, Hkv, D).astype(np.float32)
    v = rs.randn(1, T, Hkv, D).astype(np.float32)
    pos = np.full((1, T), -1, np.int32)
    trow = np.full((1, T), -1, np.int32)
    for r, c, n in segs:
        pos[0, c:c + n] = cs[r] + np.arange(n)
        trow[0, c:c + n] = r
    idx = paged_cache_index(bt, pos, cl, chunk_start=cs, token_rows=trow,
                            query_start=qs, query_len=ql)
    pool = update_paged_kv_cache(pool, jnp.asarray(k), jnp.asarray(v), idx)
    return (pool, jnp.asarray(q), jnp.asarray(bt), jnp.asarray(qs),
            jnp.asarray(ql), jnp.asarray(cs), jnp.asarray(cl), segs)


ROWS = [("decode", 13, 1), ("chunk", 8, 5), ("idle", 0, 0),
        ("chunk", 0, 7), ("decode", 0, 1), ("chunk", 19, 3)]


def _split_kernel_rows(pool, q, bt, qs, ql, cs, cl, segs, window=None,
                       scales=None):
    """Per-row outputs of the SPLIT kernels (decode at qlen 1, prefill
    otherwise) — the ground truth the unified kernel must reproduce."""
    kw = dict(interpret=True, force_pallas=True, window=window)
    if scales:
        kw.update(scales)
    outs = {}
    for r, c, n in segs:
        if int(ql[r]) == 1 and int(cs[r]) == int(cl[r]) - 1:
            out = paged_decode_attention(q[c:c + 1], pool["k"], pool["v"],
                                         bt[r:r + 1], cl[r:r + 1], **kw)
        else:
            out = paged_prefill_attention(q[None, c:c + n], pool["k"],
                                          pool["v"], bt[r:r + 1],
                                          cs[r:r + 1], cl[r:r + 1], **kw)[0]
        outs[r] = np.asarray(out).reshape(n, *q.shape[1:])
    return outs


@pytest.mark.parametrize("window", [
    None,
    pytest.param(6, marks=pytest.mark.slow)])  # windowless is the fast
def test_unified_kernel_parity_vs_split_kernels(window):       # CI rep
    """THE tentpole invariant: decode rows and prefill chunks on the one
    packed grid equal the split decode/prefill kernels row for row, and
    packed positions no row claims come back zero."""
    setup = _mixed_setup(np.random.RandomState(11), ROWS)
    pool, q, bt, qs, ql, cs, cl, segs = setup
    got = np.asarray(ragged_paged_attention(
        q, pool["k"], pool["v"], bt, qs, ql, cs, cl,
        interpret=True, force_pallas=True, window=window))
    refs = _split_kernel_rows(pool, q, bt, qs, ql, cs, cl, segs,
                              window=window)
    claimed = np.zeros(q.shape[0], bool)
    for r, c, n in segs:
        np.testing.assert_allclose(got[c:c + n], refs[r], rtol=2e-5,
                                   atol=2e-5, err_msg=f"row {r}")
        claimed[c:c + n] = True
    assert not np.any(got[~claimed]), "unclaimed packed rows must be zero"


def test_unified_kernel_int8_parity():
    """int8 pool: the unified kernel's per-page VMEM dequant matches the
    split kernels on the SAME quantized pages exactly."""
    setup = _mixed_setup(np.random.RandomState(13), ROWS, int8=True)
    pool, q, bt, qs, ql, cs, cl, segs = setup
    scales = {"k_scale": pool["k_scale"], "v_scale": pool["v_scale"]}
    got = np.asarray(ragged_paged_attention(
        q, pool["k"], pool["v"], bt, qs, ql, cs, cl,
        interpret=True, force_pallas=True, **scales))
    refs = _split_kernel_rows(pool, q, bt, qs, ql, cs, cl, segs,
                              scales=scales)
    for r, c, n in segs:
        np.testing.assert_allclose(got[c:c + n], refs[r], rtol=2e-5,
                                   atol=2e-5, err_msg=f"row {r}")


def test_chunk_len_one_equals_decode_row():
    """``chunk_start`` causality edge: a 1-token chunk at position
    ``context - 1`` IS a decode row — the unified kernel must agree with
    BOTH split phrasings (decode kernel and prefill kernel at T=1) on the
    same pool."""
    setup = _mixed_setup(np.random.RandomState(17), [("decode", 12, 1)])
    pool, q, bt, qs, ql, cs, cl, _ = setup
    got = np.asarray(ragged_paged_attention(
        q, pool["k"], pool["v"], bt, qs, ql, cs, cl,
        interpret=True, force_pallas=True))
    dec = paged_decode_attention(q[0:1], pool["k"], pool["v"], bt, cl,
                                 interpret=True, force_pallas=True)
    pre = paged_prefill_attention(q[None, 0:1], pool["k"], pool["v"], bt,
                                  cs, cl, interpret=True, force_pallas=True)
    np.testing.assert_allclose(got[0], np.asarray(dec)[0], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(got[0], np.asarray(pre)[0, 0], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.slow
def test_q_tile_independence():
    """The q-tile size is a pure performance knob: any tiling returns the
    same packed output (tiles skip beyond query_len, stores are masked)."""
    setup = _mixed_setup(np.random.RandomState(19), ROWS)
    pool, q, bt, qs, ql, cs, cl, _ = setup
    outs = [np.asarray(ragged_paged_attention(
        q, pool["k"], pool["v"], bt, qs, ql, cs, cl, q_tile=t,
        interpret=True, force_pallas=True)) for t in (1, 4, 8, 32)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)


def test_cpu_fallback_auto_routes_to_reference():
    """interpret=None off-TPU returns the packed XLA reference (so the
    model wiring works everywhere the kernel does not)."""
    setup = _mixed_setup(np.random.RandomState(23), ROWS)
    pool, q, bt, qs, ql, cs, cl, segs = setup
    auto = np.asarray(ragged_paged_attention(q, pool["k"], pool["v"], bt,
                                             qs, ql, cs, cl))
    kern = np.asarray(ragged_paged_attention(q, pool["k"], pool["v"], bt,
                                             qs, ql, cs, cl,
                                             interpret=True,
                                             force_pallas=True))
    claimed = np.zeros(q.shape[0], bool)
    for _, c, n in segs:
        claimed[c:c + n] = True
    np.testing.assert_allclose(auto[claimed], kern[claimed], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("heads,dtype,q_tile", [
    (2, jnp.float32, 8),     # G=1: the span is mostly alignment slack
    (8, jnp.bfloat16, 8),    # G=4, 16-row bf16 tiles (the Mistral shape)
    (6, jnp.float32, 4),     # G=3: rows0 shares no factor with the tile
    (16, jnp.bfloat16, 8),   # G=8
])
def test_aligned_span_covers_any_group_and_dtype(heads, dtype, q_tile):
    """A q-tile works on the ALIGNED span of packed rows around its own
    (Mosaic slices sublanes dynamically only at tile-aligned starts): for
    every heads-per-kv-head count and tile height the span's extra rows
    must neither leak into a neighbouring segment nor drop a real token."""
    setup = _mixed_setup(np.random.RandomState(29), ROWS, H=heads)
    pool, q, bt, qs, ql, cs, cl, segs = setup
    q = q.astype(dtype)
    args = (q, pool["k"], pool["v"], bt, qs, ql, cs, cl)
    kern = np.asarray(ragged_paged_attention(
        *args, q_tile=q_tile, window=6, interpret=True,
        force_pallas=True).astype(jnp.float32))
    ref = np.asarray(ragged_paged_attention(*args, window=6)
                     .astype(jnp.float32))
    claimed = np.zeros(q.shape[0], bool)
    for _, c, n in segs:
        claimed[c:c + n] = True
    tol = 2e-5 if dtype == jnp.float32 else 2e-2  # bf16 output rounding
    np.testing.assert_allclose(kern[claimed], ref[claimed], rtol=tol,
                               atol=tol)
    assert not np.any(kern[~claimed]), "unclaimed packed rows must be zero"
