"""Flash attention kernel parity tests (reference model:
``tests/unit/test_cuda_forward.py`` / ``test_cuda_backward.py`` — fwd/bwd
allclose across a shape grid, here Pallas-interpret vs einsum reference)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.layers import resolve_remat_policy
from deepspeed_tpu.ops.pallas.flash_attention import (
    BlockDiffusion,
    _reference_attention,
    flash_attention,
)


def _qkv(b, t, h, d, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("t,causal", [(128, True), (128, False), (256, True)])
def test_flash_forward_matches_reference(t, causal):
    q, k, v = _qkv(2, t, 2, 64)
    ref = _reference_attention(q, k, v, causal, 1.0 / 8.0)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_reference(causal):
    q, k, v = _qkv(1, 128, 2, 64, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                       interpret=True, force_pallas=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, causal, 1.0 / 8.0) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


def test_flash_uneven_blocks():
    # T not a multiple of the block size exercises ragged grid handling
    q, k, v = _qkv(1, 96, 2, 64)
    ref = _reference_attention(q, k, v, True, 1.0 / 8.0)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_cpu_fallback_is_reference():
    q, k, v = _qkv(1, 64, 2, 32)
    out = flash_attention(q, k, v, causal=True)  # auto: einsum on CPU
    ref = _reference_attention(q, k, v, True, 1.0 / np.sqrt(32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_flash_cross_length_causality():
    """Tq != Tk (decode shape): bottom-right-aligned causality must match the
    einsum fallback."""
    q, _, _ = _qkv(1, 32, 2, 64, seed=3)
    _, k, v = _qkv(1, 128, 2, 64, seed=4)
    ref = _reference_attention(q, k, v, True, 1.0 / 8.0)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=64,
                          interpret=True, force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_dispatch_falls_back_with_mask():
    """attention_impl=flash with a padding mask must not change semantics
    (falls back to the XLA path)."""
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    am = jnp.ones((2, 16), jnp.int32).at[0, 8:].set(0)
    m_x = LlamaForCausalLM(LlamaConfig.tiny(remat=False, attention_impl="xla"))
    m_f = LlamaForCausalLM(LlamaConfig.tiny(remat=False, attention_impl="flash"))
    p = m_x.init(jax.random.PRNGKey(0), ids)["params"]
    lx = m_x.apply({"params": p}, ids, labels=ids, attention_mask=am)
    lf = m_f.apply({"params": p}, ids, labels=ids, attention_mask=am)
    np.testing.assert_allclose(float(lx), float(lf), rtol=1e-5)


def test_model_attention_impl_flash():
    """Llama with attention_impl=flash on CPU falls back but stays correct."""
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    m_x = LlamaForCausalLM(LlamaConfig.tiny(remat=False, attention_impl="xla"))
    m_f = LlamaForCausalLM(LlamaConfig.tiny(remat=False, attention_impl="flash"))
    p = m_x.init(jax.random.PRNGKey(0), ids)["params"]
    lx = m_x.apply({"params": p}, ids, labels=ids)
    lf = m_f.apply({"params": p}, ids, labels=ids)
    np.testing.assert_allclose(float(lx), float(lf), rtol=1e-4)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_sliding_window_forward(window):
    """Windowed causality (Mistral): kernel masks AND block-skips by the
    window; parity vs the windowed einsum reference."""
    q, k, v = _qkv(2, 128, 2, 64, seed=3)
    ref = _reference_attention(q, k, v, True, 1.0 / 8.0, window=window)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True, force_pallas=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_sliding_window_backward():
    q, k, v = _qkv(1, 128, 2, 64, seed=4)

    def loss_pallas(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True,
                                       force_pallas=True, window=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_reference_attention(q, k, v, True, 1.0 / 8.0,
                                            window=32) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_key_mask_parity_left_padded():
    """key_mask (left-padded prefill) masks padded keys in-kernel; parity
    vs the einsum reference for REAL query rows (pad rows are degenerate
    in both paths and unused downstream)."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _reference_attention, flash_attention)

    rs = np.random.RandomState(0)
    B, T, H, D = 2, 48, 4, 16
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    mask = np.ones((B, T), np.int32)
    mask[0, :7] = 0  # row 0 left-padded by 7
    mask = jnp.asarray(mask)

    got = flash_attention(q, k, v, causal=True, key_mask=mask, block_q=16,
                          block_k=16, force_pallas=True, interpret=True)
    ref = _reference_attention(q, k, v, True, 1.0 / np.sqrt(D),
                               key_mask=mask)
    np.testing.assert_allclose(np.asarray(got[0, 7:]), np.asarray(ref[0, 7:]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref[1]),
                               rtol=2e-5, atol=2e-5)


def test_key_mask_path_gqa_native_kv_heads():
    """The masked forward accepts UN-repeated kv heads: q head h reads kv
    head h // rep via the index map (no repeat_kv materialization) —
    parity vs the expanded reference."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _reference_attention, flash_attention)

    rs = np.random.RandomState(1)
    B, T, H, Hkv, D = 2, 32, 8, 2, 16
    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, Hkv, D), jnp.float32)
    mask = jnp.ones((B, T), jnp.int32)

    got = flash_attention(q, k, v, causal=True, key_mask=mask, block_q=16,
                          block_k=16, force_pallas=True, interpret=True)
    ref = _reference_attention(q, k, v, True, 1.0 / np.sqrt(D),
                               key_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# -- the static tile table ----------------------------------------------------


def _block_diffusion_pairs(t, half, block):
    """The rule as ISSUE 58 states it, pair by pair: ``half(p) = [p >= L]``,
    ``blk(p) = (p mod L) // B``; with no noised half every position is
    clean."""
    clean = [p >= half for p in range(t)]
    blk = [(p % half if half else p) // block for p in range(t)]
    return np.array([[
        (clean[q] == clean[j] and blk[q] == blk[j])
        or (not clean[q] and clean[j] and blk[q] > blk[j])
        or (clean[q] and clean[j] and blk[q] >= blk[j])
        for j in range(t)] for q in range(t)])


def _rule_mask(tq, tk, causal, window):
    if isinstance(window, BlockDiffusion):  # it stands for causality too
        return _block_diffusion_pairs(tq, window.half, window.block)
    d = np.arange(tq)[:, None] + (tk - tq) - np.arange(tk)[None, :]
    mask = np.ones((tq, tk), bool)
    if causal:
        mask &= d >= 0
    if window is not None:
        mask &= d < window
    return mask


def _dense_tiles(tq, tk, bq, bk, causal, window):
    """[nq, nk] "any entry visible" and "every entry visible" of the dense
    mask, padded tails counting as not visible."""
    mask = _rule_mask(tq, tk, causal, window)
    nq, nk = -(-tq // bq), -(-tk // bk)
    padded = np.zeros((nq * bq, nk * bk), bool)
    padded[:tq, :tk] = mask
    tiles = padded.reshape(nq, bq, nk, bk)
    return tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))


@pytest.mark.parametrize("tq,tk,bq,bk,causal,window", [
    (256, 256, 64, 64, True, None),      # the plain triangle
    (256, 256, 64, 64, False, None),     # the full rectangle
    (256, 256, 64, 64, True, 128),       # window of two blocks
    (256, 256, 64, 64, True, 16),        # window smaller than a block
    (256, 256, 32, 64, True, 100),       # bq != bk, window off the grid
    (96, 96, 64, 64, True, None),        # ragged tails
    (100, 200, 32, 64, True, 48),        # ragged, tq < tk, window
    (32, 128, 16, 32, True, 16),         # kv rows no query sees
    (96, 32, 32, 32, True, None),        # q rows that see no key
    (128, 64, 32, 32, True, 8),          # tq > tk with a window
    (200, 100, 64, 32, False, 24),       # a window without causality
    (1, 128, 1, 64, True, None),         # the decode shape
    # block diffusion over [x_t ; x_0]: blocks inside a tile, a tile, and
    # (L 1536, B 512, tiles of 128) tiles inside a block
    *[(2 * L, 2 * L, tile, tile, True, BlockDiffusion(L, B))
      for L in (512, 1024, 1536) for B in (4, 32, 512)
      for tile in (128, 512)],
    (400, 400, 128, 128, True, BlockDiffusion(200, 8)),   # no tile divides L
    (1000, 1000, 128, 64, True, BlockDiffusion(500, 4)),  # ragged, bq != bk
    (600, 600, 128, 128, True, BlockDiffusion(0, 4)),     # block-causal alone
])
def test_tile_table_is_the_dense_mask_by_tile(tq, tk, bq, bk, causal, window):
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _CUT, _FIRST, _INSIDE, _LAST, _tile_table)

    some, every = _dense_tiles(tq, tk, bq, bk, causal, window)
    for by_kv in (False, True):
        iq, ik, flags = _tile_table(tq, tk, bq, bk, causal, window, by_kv)
        assert iq.dtype == ik.dtype == flags.dtype == np.int32
        outer, inner = (ik, iq) if by_kv else (iq, ik)
        # ordered by output row, the inner index ascending: the kernels'
        # accumulation order
        order = list(zip(outer.tolist(), inner.tolist()))
        assert order == sorted(set(order))
        # every row of output tiles is initialised and written exactly once
        n_outer = some.shape[1] if by_kv else some.shape[0]
        assert sorted(set(outer.tolist())) == list(range(n_outer))
        first = np.r_[True, outer[1:] != outer[:-1]]
        last = np.r_[first[1:], True]
        assert ((flags & _FIRST != 0) == first).all()
        assert ((flags & _LAST != 0) == last).all()
        # the entries that run are the tiles with a visible entry, and those
        # that skip the in-tile mask are the wholly visible ones
        runs = flags & (_INSIDE | _CUT) != 0
        got = np.zeros_like(some)
        got[iq[runs], ik[runs]] = True
        assert (got == some).all()
        assert ((flags & _INSIDE != 0) == every[iq, ik]).all()
        assert not (flags & _INSIDE != 0)[~runs].any()
        assert ((flags & _INSIDE != 0) & (flags & _CUT != 0)).sum() == 0
        # a placeholder only where the row has nothing to run
        rows_that_run = set(outer[runs].tolist())
        assert all(o not in rows_that_run for o in outer[~runs].tolist())


@pytest.mark.parametrize("cell,t,window,rect,kept,inside", [
    ("mistral-7b.train.8k", 8192, 4096, 256, 108, 84),
    ("olmoe-1b-7b.train.4k", 4096, None, 64, 36, 28),
    # 2 x 8,192 positions in blocks of 4: the clean-clean quadrant's 136,
    # noised-clean 136, the noised quadrant's 16 diagonal tiles, clean-noised
    # none; 48 cut. The causal rule over 16,384 walks 528
    ("sdar-30b-a3b.train.8k", 16384, BlockDiffusion(8192, 4), 1024, 288, 240),
])
def test_tile_table_counts_of_the_benchmark_cells(cell, t, window, rect, kept,
                                                  inside):
    """The grid's "hit share" is a function of the shapes alone: 108 of 256
    tiles (84 of them mask-free) at train.8k, 36 of 64 (28) at OLMoE's 4k."""
    from deepspeed_tpu.ops.pallas.flash_attention import _INSIDE, _tile_table

    assert (t // 512) ** 2 == rect
    for by_kv in (False, True):
        flags = _tile_table(t, t, 512, 512, True, window, by_kv)[2]
        assert flags.shape == (kept,)
        assert int((flags & _INSIDE != 0).sum()) == inside


@pytest.mark.parametrize("tq,tk,bq,bk,window,first_real_row", [
    (32, 128, 16, 32, 16, 0),     # window with cross length: kv tiles 0, 1
                                  # are seen by no query
    (96, 32, 32, 32, None, 64),   # tk < tq: q tiles 0, 1 see no key
])
def test_flash_placeholder_rows_forward_and_gradients(tq, tk, bq, bk, window,
                                                      first_real_row):
    """A row of output tiles with nothing to run is still written (zeros).
    Query rows that see no key are degenerate in the reference (a uniform
    softmax over masked keys), so parity covers the rows that see one."""
    q, _, _ = _qkv(1, tq, 2, 64, seed=5)
    _, k, v = _qkv(1, tk, 2, 64, seed=6)
    r = first_real_row

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                               interpret=True, force_pallas=True,
                               window=window)

    def ref(q, k, v):
        return _reference_attention(q, k, v, True, 1.0 / 8.0, window=window)

    out = flash(q, k, v)
    np.testing.assert_allclose(np.asarray(out[:, r:]),
                               np.asarray(ref(q, k, v)[:, r:]),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out[:, :r]).any()
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v)[:, r:] ** 2)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5, err_msg=f"d{name}")


# -- a value width of its own (latent attention) ------------------------------

def _qkv_two_widths(t, h, d, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, t, h, d)),
            jax.random.normal(ks[1], (1, t, h, d)),
            jax.random.normal(ks[2], (1, t, h, dv)))


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("t,d,dv,window", [
    (128, 48, 32, None),    # latent attention's ratio, 192 : 128
    (96, 24, 40, None),     # values the wider; a ragged tail
    (128, 48, 32, 40),      # under a window too
], ids=["wide_keys", "wide_values_ragged", "windowed"])
def test_flash_with_a_value_width_of_its_own(t, d, dv, window, what):
    """Queries and keys ``d`` wide, values and the output ``dv``: the
    kernels (interpret mode) against plain attention, the output and all
    three gradients, at the scale of the query's width."""
    q, k, v = _qkv_two_widths(t, 2, d, dv, seed=3)
    scale = d ** -0.5
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, sm_scale=scale, block_q=32, block_k=32,
        interpret=True, force_pallas=True, window=window)
    plain = lambda q, k, v: _reference_attention(q, k, v, True, scale,
                                                 window=window)
    if what == "out":
        got, want = flash(q, k, v), plain(q, k, v)
        assert got.shape == (1, t, 2, dv)
    else:
        i = "qkv".index(what[1])
        w = jax.random.normal(jax.random.PRNGKey(9), (1, t, 2, dv))
        got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=i)(
            q, k, v) for f in (flash, plain))
        assert got.shape == (q, k, v)[i].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4,
                               rtol=5e-4)


def _traced_calls(window, bq=32, bk=32, t=96):
    """The three ``pallas_call`` equations of the traced gradient at equal
    widths: forward, dQ, dK/dV."""
    from deepspeed_tpu.ops.pallas import (FLASH_BWD_DKV, FLASH_BWD_DQ,
                                          FLASH_FWD)

    fwd = functools.partial(flash_attention, causal=True, interpret=True,
                            window=window, block_q=bq, block_k=bk)
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    x = jnp.zeros((1, t, 2, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == [FLASH_FWD, FLASH_BWD_DQ,
                                                 FLASH_BWD_DKV]
    return calls


@pytest.mark.parametrize("window,which,digest", [
    (None, "backward", "975c02806911ef62"), (40, "backward", "6dc6cd441bad9d98"),
    (None, "forward", "f14f4c937a441b1e"), (40, "forward", "2c3219fb2deb9e1a")])
def test_equal_widths_trace_the_program_they_always_did(window, which, digest):
    """Where values are as wide as keys the calls are, parameter for
    parameter, what they were: each digest is of ``pallas_call``s of the
    traced gradient (block shapes, grids, scratch and kernel bodies) at these
    shapes. PRs 31 to 33 pinned the whole traced text (``ff8e37f2…`` /
    ``8dc581f5…``); PR 34 put two ``checkpoint_name`` equations around the
    calls and pinned the three calls by one digest (``d90a97f2…`` /
    ``5363675f…``: PR 33's file and PR 34's both gave them). PR 38 turned the
    forward's tile (keys on the sublanes, the statistics as rows), so the
    pin is split: **the two backward calls keep a digest of their own,
    computed on PR 37's file** — their bodies, grids and scratch did not
    move — and the forward has the digest of its new body."""
    import hashlib

    calls = _traced_calls(window)
    calls = calls[:1] if which == "forward" else calls[1:]
    text = "\n".join(str(sorted((k, str(v)) for k, v in e.params.items()))
                     for e in calls)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_forward_statistics_are_rows_not_columns():
    """The forward call's scratch holds no ``(bq, 1)`` column: the running
    max and sum are ``(1, bq)`` rows and the accumulator ``(Dv, bq)``, the
    tile's queries on the lanes (``bq`` 64 against ``bk`` 32 and ``Dv`` 16,
    so no two of them can be mistaken for each other)."""
    bq, bk, dv = 64, 32, 16
    call = _traced_calls(None, bq=bq, bk=bk, t=128)[0]
    n = call.params["grid_mapping"].num_scratch_operands
    scratch = [tuple(v.aval.shape) for v in call.params["jaxpr"].invars[-n:]]
    assert scratch == [(1, bq), (1, bq), (dv, bq)]


# -- the forward's orientation: output AND log-sum-exp ------------------------

#: name: tq, tk, heads, kv heads, d, dv, bq, bk, causal, window, left padding
_ORIENTATION = {
    "latent_widths": (128, 128, 2, 2, 192, 128, 64, 64, True, None, 0),
    "bq_under_bk": (128, 128, 2, 2, 32, 32, 32, 64, True, None, 0),
    "bq_over_bk": (128, 128, 2, 2, 32, 32, 64, 32, True, None, 0),
    "cross_length": (64, 160, 2, 2, 32, 32, 32, 32, True, None, 0),
    "ragged_query_tail": (100, 128, 2, 2, 32, 32, 64, 64, True, None, 0),
    "ragged_key_tail": (64, 100, 2, 2, 32, 32, 32, 64, True, None, 0),
    "windowed": (128, 128, 2, 2, 32, 32, 32, 32, True, 40, 0),
    "not_causal_two_widths": (96, 80, 2, 2, 24, 40, 32, 32, False, None, 0),
    "key_mask_gqa": (48, 48, 8, 2, 16, 16, 16, 16, True, None, 7),
    "placeholder_rows": (96, 32, 2, 2, 32, 32, 32, 32, True, None, 0),
}


def _plain_attention(q, k, v, scale, causal, window, key_mask):
    """float32 attention over [B, T, H, D] written out: the output
    [B, Tq, H, Dv], the log-sum-exp [B, H, Tq] and which query rows see a
    key at all [B, Tq] (the others are degenerate: excluded)."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    tq, tk = q.shape[1], k.shape[1]
    d = jnp.arange(tq)[:, None] + (tk - tq) - jnp.arange(tk)[None, :]
    mask = jnp.ones((tq, tk), bool)
    if causal:
        mask &= d >= 0
    if window is not None:
        mask &= d < window
    mask = jnp.broadcast_to(mask, (q.shape[0], tq, tk))
    if key_mask is not None:
        mask &= (key_mask > 0)[:, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = jnp.where(mask[:, None], logits, -jnp.inf)
    seen = mask.any(-1)
    lse = jax.nn.logsumexp(jnp.where(seen[:, None, :, None], logits, 0.0),
                           axis=-1)
    probs = jnp.exp(logits - lse[..., None]) * seen[:, None, :, None]
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v), lse, seen


@pytest.mark.parametrize("case,what", [
    (case, what) for case in sorted(_ORIENTATION)
    for what in ("out", "lse", "dq", "dk", "dv")
    # the masked path is forward-only (no custom vjp)
    if case != "key_mask_gqa" or what in ("out", "lse")])
def test_forward_orientation_output_lse_and_gradients(case, what):
    """What turning the forward's tile could break, each against plain
    attention in interpret mode: the output and the log-sum-exp the kernel
    writes from its ``[1, bq]`` rows (latent attention's two widths,
    ``bq != bk``, ``Tq != Tk``, a ragged tail on either side, a window, the
    key mask as a column over un-repeated kv heads, a row of tiles with
    nothing to run), and the three gradients the UNCHANGED backward kernels
    make of the new forward's ``out`` / ``lse``."""
    tq, tk, h, hkv, d, dv, bq, bk, causal, window, pad = _ORIENTATION[case]
    if what in ("out", "lse"):
        out, lse, want_out, want_lse, seen = _orientation_forward(case)
        bhtd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
        assert out.shape == (2, h, tq, dv) and lse.shape == (2, h, tq)
        assert lse.dtype == jnp.float32
        got, want = ((bhtd(out), want_out) if what == "out" else
                     (jnp.moveaxis(lse, 1, 2), jnp.moveaxis(want_lse, 1, 2)))
        np.testing.assert_allclose(np.asarray(got)[np.asarray(seen)],
                                   np.asarray(want)[np.asarray(seen)],
                                   atol=2e-5, rtol=2e-5)
        if case == "placeholder_rows":      # rows 0..63 see no key: zeros
            assert not np.asarray(seen)[:, :64].any()
            assert np.asarray(seen)[:, 64:].all()
            assert not np.asarray(bhtd(out))[:, :64].any()
        return
    got, want = _orientation_gradients(case)
    i = "qkv".index(what[1])
    np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[i]),
                               atol=5e-4, rtol=5e-4)


def _orientation_operands(case):
    tq, tk, h, hkv, d, dv, bq, bk, causal, window, pad = _ORIENTATION[case]
    ks = jax.random.split(jax.random.PRNGKey(38), 4)
    q = jax.random.normal(ks[0], (2, tq, h, d))
    k = jax.random.normal(ks[1], (2, tk, hkv, d))
    v = jax.random.normal(ks[2], (2, tk, hkv, dv))
    key_mask = None
    if case == "key_mask_gqa":
        key_mask = jnp.ones((2, tk), jnp.int32).at[0, :pad].set(0)
    return q, k, v, key_mask, jax.random.normal(ks[3], (2, tq, h, dv))


@functools.lru_cache(maxsize=None)
def _orientation_forward(case):
    """``(out, lse)`` of the forward kernel and ``(out, lse, seen)`` of plain
    attention: one program each, run once a process for the two cases that
    read them (numpy COPIES, as ``_unremat_results`` below says why)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _flash_fwd

    tq, tk, h, hkv, d, dv, bq, bk, causal, window, pad = _ORIENTATION[case]
    q, k, v, key_mask, _ = _orientation_operands(case)
    scale = d ** -0.5
    bhtd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out, lse = jax.jit(lambda q, k, v: _flash_fwd(
        bhtd(q), bhtd(k), bhtd(v), scale, causal, bq, bk, True, window,
        key_mask))(q, k, v)
    return tuple(map(np.array, (out, lse) + jax.jit(
        lambda q, k, v: _plain_attention(
            q, k, v, scale, causal, window, key_mask))(q, k, v)))


@functools.lru_cache(maxsize=None)
def _orientation_gradients(case):
    """``((dq, dk, dv) through the kernels, (dq, dk, dv) of plain
    attention)``: one program each, run once a process for the three cases
    that read them (numpy COPIES)."""
    tq, tk, h, hkv, d, dv, bq, bk, causal, window, pad = _ORIENTATION[case]
    q, k, v, _, w = _orientation_operands(case)
    scale = d ** -0.5
    seen = _plain_attention(q, k, v, scale, causal, window, None)[2]
    w = w * seen[:, :, None, None]
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, sm_scale=scale, block_q=bq, block_k=bk,
        interpret=True, force_pallas=True, window=window)
    plain = lambda q, k, v: _plain_attention(q, k, v, scale, causal, window,
                                             None)[0]
    return tuple(tuple(map(np.array, jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2)))(q, k, v)))
        for f in (flash, plain))


# -- what jax.checkpoint keeps of the kernel ----------------------------------

_SCAN_T, _SCAN_H = 64, 1


def _scanned_layers(policy, d, dv, window):
    """Two layers in a ``lax.scan``, each the kernel (interpret mode) on
    scaled operands, the body under ``jax.checkpoint`` with ``policy``
    (``"plain"``: no ``jax.checkpoint`` at all; None: one with no policy).
    Returns the function of (q, k, v) -> (loss, outputs) and its operands."""
    t, h = _SCAN_T, _SCAN_H
    q, k, v = _qkv_two_widths(t, h, d, dv, seed=5)
    g = jax.random.normal(jax.random.PRNGKey(6), (1, t, h, dv))
    scales = jnp.asarray([[1.0, 0.5, 1.0], [0.5, 1.0, 2.0]])

    def layer(c, s, q, k, v):
        o = flash_attention(q * s[0], k * s[1], v * s[2], causal=True,
                            block_q=32, block_k=32, force_pallas=True,
                            window=window)
        return c + o, o

    if policy != "plain":
        kw = {} if policy is None else {
            "policy": resolve_remat_policy(policy)}
        layer = jax.checkpoint(layer, prevent_cse=False, **kw)

    def f(q, k, v):
        c, outs = jax.lax.scan(lambda c, s: layer(c, s, q, k, v),
                               jnp.zeros_like(g), scales)
        return jnp.sum(c * g), outs

    return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True), (q, k, v)


@functools.lru_cache(maxsize=None)
def _unremat_results(d, dv, window):
    # numpy COPIES: a cached device array (or a zero-copy view of one) would
    # stay in jax.live_arrays() for the worker's later test files, and
    # test_engine.py counts them
    fn, args = _scanned_layers("plain", d, dv, window)
    return jax.tree_util.tree_map(np.array, jax.jit(fn)(*args))


@pytest.mark.parametrize("policy,fwd_calls", [
    ("nothing", 1), ("dots", 1), ("dots_no_batch", 1),
    ("offload_dots_no_batch", 1), (None, 2)])
def test_every_remat_policy_keeps_the_kernels_output_and_lse(policy,
                                                             fwd_calls):
    """Under each of ``resolve_remat_policy``'s four names the gradient of a
    remat'd scan of layers runs the forward kernel ONCE (in the forward
    scan, which hands the backward scan the stacked output and log-sum-exp
    as residuals) and the backward's replay not at all; a bare
    ``jax.checkpoint`` (no policy: ``gpt2.py``, ``pipe/module.py``) keeps
    neither and replays the call, as always. Output and all three gradients
    are the un-remat'd function's bit for bit, at equal widths and at latent
    attention's, with and without a window."""
    from deepspeed_tpu.ops.pallas import (FLASH_BWD_DKV, FLASH_BWD_DQ,
                                          FLASH_FWD, FLASH_LSE, FLASH_OUT)

    t, h = _SCAN_T, _SCAN_H
    for d, dv, window in [(128, 128, None), (128, 128, 40),
                          (192, 128, None), (192, 128, 40)]:
        fn, args = _scanned_layers(policy, d, dv, window)
        jaxpr = jax.make_jaxpr(fn)(*args)
        text = str(jaxpr)
        assert text.count(f"name={FLASH_FWD}") == fwd_calls
        assert text.count(f"name={FLASH_BWD_DQ}") == 1
        assert text.count(f"name={FLASH_BWD_DKV}") == 1
        assert FLASH_OUT in text and FLASH_LSE in text
        forward_scan = next(e for e in jaxpr.jaxpr.eqns
                            if e.primitive.name == "scan")
        stacked = [tuple(v.aval.shape) for v in forward_scan.outvars]
        kept = fwd_calls == 1
        assert ((2, 1, h, t) in stacked) == kept                # lse
        # the stacked outputs [2, 1, t, h, dv] are the scan's own result;
        # the residual is the kernel's layout, heads before positions
        assert ((2, 1, h, t, dv) in stacked) == kept

        (loss, outs), grads = jax.jit(fn)(*args)
        (loss0, outs0), grads0 = _unremat_results(d, dv, window)
        for name, a, b in [("out", outs, outs0), ("loss", loss, loss0),
                           *zip(("dq", "dk", "dv"), grads, grads0)]:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{name} at d={d} dv={dv} window={window}")


@pytest.mark.parametrize("policy", ["nothing", "dots", "dots_no_batch",
                                    "offload_dots_no_batch"])
def test_remat_policy_answers_as_its_name_but_for_the_two_names(policy):
    """``resolve_remat_policy(name)`` is the named JAX policy, equation for
    equation, except that a value carrying one of the kernel's two names is
    saved on the device (the offload policy's other answers stay
    ``Offloadable`` / ``Recompute``)."""
    from jax.ad_checkpoint import checkpoint_name

    from deepspeed_tpu.ops.pallas import FLASH_LSE, FLASH_OUT

    name_p = jax.make_jaxpr(lambda x: checkpoint_name(x, "n"))(
        1.0).eqns[0].primitive
    base = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "offload_dots_no_batch":
            jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host"),
    }[policy]
    got = resolve_remat_policy(policy)
    assert got(name_p, name=FLASH_OUT) is True
    assert got(name_p, name=FLASH_LSE) is True
    dot = dict(precision=None, preferred_element_type=None)
    for prim, params in [
            (name_p, dict(name="some_other_name")),
            (jax.lax.dot_general_p, dict(
                dimension_numbers=(((1,), (0,)), ((), ())), **dot)),
            (jax.lax.dot_general_p, dict(
                dimension_numbers=(((2,), (1,)), ((0,), (0,))), **dot)),
            (jax.lax.exp_p, dict(accuracy=None))]:
        assert repr(got(prim, **params)) == repr(base(prim, **params))


# -- the tile table under a mask that is data ---------------------------------


@pytest.mark.parametrize("by_kv", [False, True], ids=["by_q", "by_kv"])
@pytest.mark.parametrize("tq,tk,bq,bk,causal,window", [
    (256, 256, 64, 64, True, None),      # the plain triangle
    (256, 256, 32, 64, True, 100),       # bq != bk, a window off the grid
    (96, 96, 64, 64, True, None),        # ragged tails
    (100, 200, 32, 64, True, 48),        # ragged, tq < tk, window
    (32, 128, 16, 32, True, 16),         # kv rows no query sees
    (96, 32, 32, 32, True, None),        # q rows that see no key
    (200, 100, 64, 32, False, 24),       # a window without causality
])
def test_mask_tile_table_of_the_whole_rule_is_the_static_table(
        tq, tk, bq, bk, causal, window, by_kv):
    """A mask that holds every pair of the rule empties no tile: the table
    built on the device is ``_tile_table``'s, entry for entry, placeholders
    of the rule included."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _mask_tile_table, _tile_table, mask_tiles)

    mask = jnp.asarray(_rule_mask(tq, tk, causal, window)[None], jnp.int8)
    static = _tile_table(tq, tk, bq, bk, causal, window, by_kv,
                         dense_mask=True)
    got, count = _mask_tile_table(mask_tiles(mask, bq, bk), static, by_kv)
    assert got.dtype == jnp.int32 and int(count) == static.shape[1]
    np.testing.assert_array_equal(np.asarray(got), static)


@pytest.mark.parametrize("by_kv", [False, True], ids=["by_q", "by_kv"])
@pytest.mark.parametrize("seed,share", [(0, 0.7), (1, 0.3), (2, 0.0)])
def test_mask_tile_table_keeps_the_tiles_the_mask_holds(seed, share, by_kv):
    """Random tiles of an 8 x 8 triangle: the ``count`` live entries, which
    are the grid's steps, are the kept tiles in the static order (+ one
    body-less placeholder for each row of output tiles that kept none),
    ``_FIRST`` / ``_LAST`` once a row; no grid step reads the tail."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _CUT, _FIRST, _INSIDE, _LAST, _mask_tile_table, _tile_table)

    n = 8
    tiles = np.tril(np.random.default_rng(seed).random((n, n)) < share)
    static = _tile_table(n * 16, n * 16, 16, 16, True, None, by_kv,
                         dense_mask=True)
    table, count = _mask_tile_table(jnp.asarray(tiles), static, by_kv)
    iq, ik, flags = np.asarray(table)
    outer, inner = (ik, iq) if by_kv else (iq, ik)
    count = int(count)
    assert count >= n and flags.shape == static[2].shape
    iq, ik, flags, outer, inner = (a[:count] for a in
                                   (iq, ik, flags, outer, inner))
    order = list(zip(outer.tolist(), inner.tolist()))
    assert order == sorted(set(order))
    assert sorted(set(outer.tolist())) == list(range(n))
    first = np.r_[True, outer[1:] != outer[:-1]]
    assert ((flags & _FIRST != 0) == first).all()
    assert ((flags & _LAST != 0) == np.r_[first[1:], True]).all()
    assert not (flags & _INSIDE).any()
    body = flags & _CUT != 0
    got = np.zeros_like(tiles)
    got[iq[body], ik[body]] = True
    assert (got == tiles).all()
    # a placeholder only in a row that kept nothing, and on its first entry
    # of the static table (by kv row: the diagonal)
    kept_rows = tiles.any(axis=0 if by_kv else 1)
    assert sorted(outer[~body].tolist()) == np.flatnonzero(
        ~kept_rows).tolist()
    assert (inner[~body] == (outer[~body] if by_kv else 0)).all()


_SA_BLOCK = 32


def _sa_patterns(case):
    """``(T, [tiles of each sequence])``: which 32 x 32 tiles of the
    triangle a sequence selects in."""
    full = np.tril(np.ones((5, 5), bool))
    a = full.copy()
    if case == "first_tiles_of_a_q_row_empty":
        a[3, :2] = a[4, :3] = False
    elif case == "empty_diagonal_tile":
        a[2, 2] = a[4, 4] = False
    elif case == "kv_row_no_query_selects":
        a[:, 1] = a[:, 4] = False
    elif case == "batch_of_two_selections":
        b = full.copy()
        a[3, 1] = a[4, 1] = a[4, 2] = a[2, 0] = False
        b[4, 2] = b[3, 3] = b[2, 0] = b[4, 0] = False
        return 160, [a, b]
    elif case == "ragged_length":
        a[3, 0] = a[4, 1] = a[4, 3] = False
        return 150, [a, a]
    return 160, [a]


SA_CASES = ("first_tiles_of_a_q_row_empty", "empty_diagonal_tile",
            "kv_row_no_query_selects", "batch_of_two_selections",
            "ragged_length")


def _sa_mask(case):
    """``(mask [B, T, T] int8, the 5 x 5 tiles it selects in)`` of a case."""
    T, patterns = _sa_patterns(case)
    B, blk = len(patterns), _SA_BLOCK
    rng = np.random.default_rng(7)
    mask = np.zeros((B, T, T), bool)
    for b, tiles in enumerate(patterns):
        big = np.repeat(np.repeat(tiles, blk, 0), blk, 1)[:T, :T]
        mask[b] = np.tril(rng.random((T, T)) < 0.3) & big
        for t in np.flatnonzero(~mask[b].any(axis=1)):
            # a key for every query: the first of its row's first kept tile
            mask[b, t, np.flatnonzero(tiles[t // blk])[0] * blk] = True
    union = np.zeros((5, 5), bool)
    for b in range(B):
        padded = np.zeros((5 * blk, 5 * blk), bool)
        padded[:T, :T] = mask[b]
        union |= padded.reshape(5, blk, 5, blk).any(axis=(1, 3))
    return jnp.asarray(mask, jnp.int8), union


def _sa_case(case):
    """The kernels (interpret mode) and the einsum reference under one mask:
    forward, the three gradients and the indexer's loss with its own, as numpy
    arrays (a device array left alive fails ``test_engine.py``'s look at
    ``jax.live_arrays()`` in the same worker)."""
    from deepspeed_tpu.ops.pallas import sa_probs
    from deepspeed_tpu.ops.pallas.flash_attention import mask_tiles

    mask, union = _sa_mask(case)
    (B, T, _), H, D, blk = mask.shape, 2, 16, _SA_BLOCK
    np.testing.assert_array_equal(np.asarray(mask_tiles(mask, blk, blk)),
                                  union)
    q, k, v = _qkv(B, T, H, D, seed=11)
    weight = jax.random.normal(jax.random.PRNGKey(12), (B, T, H, D))
    scale = D ** -0.5

    def kernels(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=blk,
                               block_k=blk, mask=mask, interpret=True)

    def reference(q, k, v):
        return _reference_attention(q, k, v, True, scale, mask=mask)

    def both(fn):
        out, lse = fn(q, k, v)
        dq, dk, dv = jax.grad(lambda *a: (fn(*a)[0] * weight).sum(),
                              argnums=(0, 1, 2))(q, k, v)
        return dict(out=out, lse=lse, dq=dq, dk=dk, dv=dv), lse

    got, lse = both(kernels)
    want, _ = both(reference)
    # the indexer's loss under the same mask, from its two kernels and from
    # the XLA path's formula on the einsum probabilities; the gradient as
    # that of the rows' summed KL, so that the tolerances below mean something
    from deepspeed_tpu.models.indexed_attention import index_loss

    scores = 3.0 * jax.random.normal(jax.random.PRNGKey(13), (B, T, T))
    kl = lambda sc: B * T * sa_probs.index_kl(
        q, k, lse, sc, mask, block_q=blk, block_k=blk, interpret=True)
    kl_ref = lambda sc: B * T * index_loss(
        sa_probs._reference(q, k, lse, mask, scale), sc, mask)
    got["kl"], got["dscores"] = jax.value_and_grad(kl)(scores)
    want["kl"], want["dscores"] = jax.value_and_grad(kl_ref)(scores)
    as_numpy = lambda d: {k: np.asarray(v) for k, v in d.items()}
    return as_numpy(got), as_numpy(want), union


@pytest.fixture(scope="module")
def sa_case():
    """``sa_case(name)``: each case computed once for the module."""
    done = {}
    yield lambda case: done.get(case) or done.setdefault(case, _sa_case(case))
    done.clear()


@pytest.mark.parametrize("what", ["out", "lse", "dq", "dk", "dv", "kl",
                                  "dscores"])
@pytest.mark.parametrize("case", SA_CASES)
def test_flash_under_a_mask_that_empties_tiles(sa_case, case, what):
    """The kernels walk a table without the tiles the mask empties — a q
    row's first tiles, a diagonal tile, a whole kv row, another set for each
    sequence (the table holds their union), a ragged last tile — and give
    what the einsum reference gives under the same mask."""
    got, want, union = sa_case(case)
    assert not union[np.tril_indices(5)].all()      # the case empties tiles
    np.testing.assert_allclose(got[what], want[what], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("what", ["dk", "dv"])
def test_a_kv_row_no_query_selects_gets_zero_gradients(sa_case, what):
    """Its diagonal entry stays in the table as a placeholder: the block is
    initialised and written, zeros and not what the buffer held."""
    got, _, union = sa_case("kv_row_no_query_selects")
    for row in np.flatnonzero(~union.any(axis=0)):
        block = got[what][:, row * _SA_BLOCK:(row + 1) * _SA_BLOCK]
        assert block.size and not np.asarray(block).any()


@pytest.mark.parametrize("value", [1, -1, -128, 64])
def test_mask_tiles_counts_any_nonzero_byte(value):
    """The kernels read ``mask != 0``; so do the tiles, whatever the sign."""
    from deepspeed_tpu.ops.pallas.flash_attention import mask_tiles

    mask = np.zeros((2, 70, 70), np.int8)
    mask[1, 40, 3] = mask[0, 69, 69] = value
    want = np.zeros((3, 3), bool)
    want[1, 0] = want[2, 2] = True
    np.testing.assert_array_equal(
        np.asarray(mask_tiles(jnp.asarray(mask), 32, 32)), want)


# -- the backward as ONE kernel: a head's dQ resident in VMEM -----------------

V5E = "TPU v5 lite"

#: the cells' shapes cut small -- name: tq, tk, d, dv, bq, bk, causal, window,
#: first query row that sees a key, a mask that is data (an ``_sa_mask`` case)
_FUSED = {
    "causal": (128, 128, 32, 32, 32, 32, True, None, 0, None),
    # mellum2's three-tile walk: 1,024 over 512-row tiles
    "window_two_tiles": (160, 160, 32, 32, 32, 32, True, 64, 0, None),
    # kv tiles 0 and 1 are seen by no query: placeholders by kv row
    "window_unseen_kv_rows": (32, 128, 16, 16, 16, 32, True, 16, 0, None),
    "tk_over_tq": (64, 160, 32, 32, 32, 32, True, None, 0, None),
    # q tiles 0 and 1 see no key: no entry touches their dQ rows
    "tk_under_tq": (96, 32, 32, 32, 32, 32, True, None, 64, None),
    "ragged_tails": (100, 150, 32, 32, 32, 64, True, None, 0, None),
    "not_causal_ragged": (70, 90, 16, 16, 32, 32, False, None, 0, None),
    "latent_widths": (128, 128, 192, 128, 64, 64, True, None, 0, None),
    "narrow_keys_window": (128, 128, 64, 128, 32, 32, True, 32, 0, None),
    "mask_empty_diagonal_tile": (160, 160, 16, 16, 32, 32, True, None, 0,
                                 "empty_diagonal_tile"),
    "mask_kv_row_unselected": (160, 160, 16, 16, 32, 32, True, None, 0,
                               "kv_row_no_query_selects"),
    "mask_batch_ragged": (150, 150, 16, 16, 32, 32, True, None, 0,
                          "ragged_length"),
    # sdar 8k's rule cut small: 2 x 96 positions in blocks of 4, three
    # tiles a half; a half no tile divides; the label-free forward's
    "block_diffusion": (192, 192, 32, 32, 32, 32, True,
                        BlockDiffusion(96, 4), 0, None),
    "block_diffusion_ragged": (200, 200, 16, 16, 32, 64, True,
                               BlockDiffusion(100, 4), 0, None),
    "block_causal": (100, 100, 16, 16, 32, 32, True, BlockDiffusion(0, 4),
                     0, None),
}


def _fused_case(name):
    """dq, dk, dv of one shape three ways -- the one kernel (the rule answers
    for a v5e), the two kernels (it answers for this CPU), the einsum
    reference -- and the backward kernels each traced gradient names."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    tq, tk, d, dv, bq, bk, causal, window, r, sa = _FUSED[name]
    mask = None if sa is None else _sa_mask(sa)[0]
    B = 1 if mask is None else mask.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(21), 4)
    q = jax.random.normal(ks[0], (B, tq, 2, d))
    k = jax.random.normal(ks[1], (B, tk, 2, d))
    v = jax.random.normal(ks[2], (B, tk, 2, dv))
    w = jax.random.normal(ks[3], (B, tq, 2, dv))
    scale = d ** -0.5
    first = lambda out: out if mask is None else out[0]

    def kernels(q, k, v):
        return first(flash_attention(
            q, k, v, causal=causal, sm_scale=scale, block_q=bq, block_k=bk,
            interpret=True, force_pallas=True, window=window, mask=mask))

    def reference(q, k, v):
        return first(_reference_attention(q, k, v, causal, scale,
                                          window=window, mask=mask))

    def grads(fn):
        grad = jax.grad(lambda *a: jnp.sum((fn(*a) * w)[:, r:]),
                        argnums=(0, 1, 2))
        called = [e.params["name"] for e in
                  jax.make_jaxpr(grad)(q, k, v).jaxpr.eqns
                  if e.primitive.name == "pallas_call"]
        return dict(zip(("dq", "dk", "dv"), map(np.asarray, grad(q, k, v)))), \
            [n for n in called if "bwd" in n]

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gm, "device_kind", lambda: V5E)
        fused, fused_calls = grads(kernels)
        if mask is not None:    # the walk is shorter than the rule's table
            static = fa._tile_table(tq, tk, bq, bk, causal, window,
                                    by_kv=True, dense_mask=True)
            count = fa._mask_tile_table(fa.mask_tiles(mask, bq, bk), static,
                                        by_kv=True)[1]
            assert int(count) < static.shape[1]
    two, two_calls = grads(kernels)
    assert fused_calls == ["ds_flash_bwd"]
    assert two_calls == ["ds_flash_bwd_dq", "ds_flash_bwd_dkv"]
    return fused, two, grads(reference)[0]


@pytest.fixture(scope="module")
def fused_case():
    done = {}
    yield lambda name: done.get(name) or done.setdefault(name,
                                                          _fused_case(name))
    done.clear()


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(_FUSED))
def test_fused_backward_is_the_two_kernels_and_the_reference(fused_case, case,
                                                             what):
    """``ds_flash_bwd`` (interpret mode) gives the two kernels' gradients
    BIT FOR BIT -- a tile's ``s``, ``p``, ``dp``, ``ds`` are the same
    values, dK/dV accumulate as they did, and a query row's kv tiles still
    arrive ascending, so dQ's float32 sum has today's order -- and the
    einsum reference's to its tolerance; rows of dQ no kept tile touches
    are zeros."""
    fused, two, want = fused_case(case)
    np.testing.assert_array_equal(fused[what], two[what])
    np.testing.assert_allclose(fused[what], want[what], atol=5e-4, rtol=5e-4)
    r = _FUSED[case][8]
    if what == "dq" and r:
        assert not fused["dq"][:, :r].any()


#: (Tq padded, D) of a flash call in each of the benchmark's cells
#: (``mixtral-8x7b.train.ep4`` runs XLA attention; its shape, were it asked)
_CELL_DQ = {
    "mistral-7b.train.8k": (8192, 128), "olmoe-1b-7b.train.4k": (4096, 128),
    "kimi-vl-a3b.train.8k": (8192, 192), "zaya1-8b.train.8k": (8192, 128),
    "keye-vl2-30b-a3b.train.16k": (16384, 128),
    "phi4-mini-flash.train.8k": (8192, 64),
    "mellum2-12b-a2.5b.train.8k": (8192, 128),
    "mixtral-8x7b.train.ep4": (4096, 128),
    "the longest that fits": (32768, 128),
}


@pytest.mark.parametrize("cell", sorted(_CELL_DQ))
def test_every_cells_dq_is_resident_on_a_v5e(cell):
    """The float32 buffer and the bf16 output block twice, lanes padded to
    whole registers (phi4's 64-wide rows take 128, kimi's 192 take 256): 4
    to 16 MiB in the cells, within the quarter of the v5e's 128 the rule
    allows; 32,768 x 128 fills it."""
    from deepspeed_tpu.ops.pallas.flash_attention import fused_backward

    tq, d = _CELL_DQ[cell]
    assert fused_backward(tq, d, 2, V5E) == \
        tq * -(-d // 128) * 128 * (4 + 2 * 2)
    assert fused_backward(tq, d, 2, V5E) <= 32 << 20


@pytest.mark.parametrize("tq,d,itemsize,kind", [
    (131072, 128, 2, V5E),      # 64 MB of float32 alone
    (65536, 128, 2, V5E),       # twice the share
    (32768, 128, 4, V5E),       # float32 operands: 48 MiB
    (8192, 128, 2, "TPU v4"),   # a chip nobody timed: not in the table
    (8192, 128, 2, "cpu"),      # interpret mode, the CPU
    (8192, 128, 2, "NVIDIA H100"),
], ids=["128k", "64k", "32k_float32", "tpu_v4", "cpu", "gpu"])
def test_a_dq_that_does_not_fit_or_an_unknown_chip_keeps_two_kernels(
        tq, d, itemsize, kind):
    from deepspeed_tpu.ops.pallas.flash_attention import fused_backward

    assert fused_backward(tq, d, itemsize, kind) is None


@pytest.mark.parametrize("cell", ["kimi-vl-a3b.train.8k",
                                  "keye-vl2-30b-a3b.train.16k",
                                  "phi4-mini-flash.train.8k"])
def test_fused_call_asks_for_the_vmem_it_holds(monkeypatch, cell):
    """The traced ``ds_flash_bwd`` call at a cell's real shape: its resident
    dQ (scratch + the output block twice) is what the rule counted, and
    ``vmem_limit_bytes`` covers every block twice, the scratch and the
    tile's four float32 products -- with half as much again, inside the
    cap."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    monkeypatch.setattr(gm, "device_kind", lambda: V5E)
    tq, d = _CELL_DQ[cell]
    masked = cell.startswith("keye")
    x = jax.ShapeDtypeStruct((1, tq, 2, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, tq, 2, 128), jnp.bfloat16)
    args = (x, x, v) + masked * (jax.ShapeDtypeStruct((1, tq, tq), jnp.int8),)

    def loss(q, k, v, mask=None):
        out = flash_attention(q, k, v, causal=True, interpret=False,
                              mask=mask)
        return (out if mask is None else out[0]).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"
             and e.params["name"] == "ds_flash_bwd"]
    lanes = lambda shape: shape[:-1] + (-(-shape[-1] // 128) * 128,)
    size = lambda shape, dtype: int(np.prod(lanes(tuple(
        shape)))) * np.dtype(dtype).itemsize
    mapping = call.params["grid_mapping"]
    blocks = [size(m.transformed_block_aval.shape, m.array_aval.dtype)
              for m in mapping.block_mappings]
    scratch = [size(v.aval.shape, v.aval.dtype) for v in
               call.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    resident = fa.fused_backward(tq, d, 2, V5E)
    assert scratch[-1] + 2 * blocks[-1] == resident
    limit = call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    held = 2 * sum(blocks) + sum(scratch) + 4 * 512 * 512 * 4
    assert held <= fa._fused_vmem(resident, 512, 512, d, 128, 2, masked)
    assert held * 3 // 2 <= limit <= gm._VMEM_CAP


@pytest.mark.parametrize("cell,t,window,dense,by_q,by_kv", [
    ("mistral-7b.train.8k", 8192, 4096, False,
     "371cd7f2227cb1fa:108", "34b56f811ee487af:108"),
    ("olmoe-1b-7b.train.4k", 4096, None, False,
     "bfb269788521f3a3:36", "0898468a0af1dd09:36"),
    ("kimi-vl-a3b.train.8k", 8192, None, False,
     "a89064733947bd2d:136", "efca08a8fd38c77d:136"),
    ("keye-vl2-30b-a3b.train.16k", 16384, None, True,
     "8980f0f628858aec:528", "5c191f03a63c97f3:528"),
    ("phi4-mini-flash.train.8k", 8192, 512, False,
     "131abd77b9129cb7:31", "f2c99ea089dea4bd:31"),
    ("mellum2-12b-a2.5b.train.8k", 8192, 1024, False,
     "22f9e8d4fd9e540e:45", "b58f4fa97d2f99fc:45"),
])
def test_tables_of_the_standing_cells_are_the_parents(cell, t, window, dense,
                                                      by_q, by_kv):
    """A rule in the window's place (PR 58) moved no entry of a causal or a
    windowed table: sha256 of the bytes and the length of each standing
    cell's forward and backward tables, as the parent commit built them
    (kimi 8k's shape is zaya 8k's, ouro 8k's, qwen3-next 8k's and mellum2's
    full layer's; olmoe 4k's is ep4's)."""
    import hashlib

    from deepspeed_tpu.ops.pallas.flash_attention import _tile_table

    for want, kv in ((by_q, False), (by_kv, True)):
        table = _tile_table(t, t, 512, 512, True, window, kv, dense)
        assert hashlib.sha256(table.tobytes()).hexdigest()[:16] \
            + f":{table.shape[1]}" == want


@pytest.mark.parametrize("half,t,bq,bk", [(96, 192, 32, 32),
                                          (100, 200, 32, 64), (0, 100, 32, 32)])
def test_flash_forward_under_the_block_rule(half, t, bq, bk):
    rule = BlockDiffusion(half, 4)
    q, k, v = _qkv(2, t, 2, 32, seed=3)
    ref = _reference_attention(q, k, v, True, 32 ** -0.5, window=rule)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True, force_pallas=True, window=rule)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # the reference itself against the rule pair by pair
    seen = _block_diffusion_pairs(t, half, 4)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 32 ** -0.5
    probs = jax.nn.softmax(jnp.where(seen[None, None], logits, -jnp.inf), -1)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(jnp.einsum("bhqk,bkhd->bqhd", probs, v)),
        atol=2e-5, rtol=2e-5)
