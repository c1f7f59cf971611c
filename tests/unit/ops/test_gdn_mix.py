"""``ops/pallas/gdn_mix.py``'s four kernels in interpret mode on the CPU
against the XLA form of the same two blocks (``models/qwen3_next.py
_premix_xla``, ``_gate_xla``): values and every gradient -- ``qkvz``, the
convolution's taps, the norm's scale -- at lengths that are whole tiles,
ragged, and shorter than the taps; the first positions of every batch element
see zeros; tile sizes change nothing; bf16 operands round where the XLA form
rounds; the rule that chooses; and the model's two formulas, patched as
``tests/benchmark/qwen3_next_wrong.py`` patches them, reach the kernels."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.ops.pallas import gdn_mix

HIGHEST = jax.default_matmul_precision("highest")
F32, BF16 = jnp.float32, jnp.bfloat16
#: two key heads of 8 + 8 + 16 + 16 columns, two value heads each, four taps
HEADS = gdn_mix.Heads(2, 8, 2, 8, 4)
EPS = 1e-6


def inputs(B, T, heads=HEADS, dtype=F32, seed=0):
    """``(qkvz, taps, scale)`` and the four weights a loss takes q, k, v and
    the gated output with."""
    Hk, dk, r, dv, K = heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    qkvz = jax.random.normal(ks[0], (B, T, Hk * (2 * dk + 2 * r * dv)), F32)
    taps = 0.5 * jax.random.normal(ks[1], (K, 2 * Hk * dk + Hk * r * dv))
    scale = 1 + 0.2 * jax.random.normal(ks[2], (dv,))
    w = [jax.random.normal(k, (B, T, Hk * r * d))
         for k, d in zip(ks[3:], (dk, dk, dv, dv))]
    return (qkvz.astype(dtype), taps, scale), w


def xla_form(qkvz, taps, scale, heads=HEADS):
    """``[q, k, v, the gated output]``: a stand-in for the rule (any
    function of q, k, v) between the two blocks."""
    q, k, v, z = qn._premix_xla(qkvz, taps, heads, qn._conv_act,
                                qn._unit_length)
    return [q, k, v, qn._gate_xla(q + k - v, z, scale, EPS, heads.dv)]


def kernels(qkvz, taps, scale, heads=HEADS, rows=16):
    tiling = gdn_mix.Tiling(rows)
    q, k, v, z = gdn_mix.premix(qkvz, taps, heads, qn._conv_act,
                                qn._unit_length, tiling)
    return [q, k, v, gdn_mix.gate(q + k - v, z, qkvz, scale, EPS, heads,
                                  tiling)]


def grads(form, args, w, **kw):
    loss = lambda *a: sum(jnp.sum(x.astype(F32) * wi)
                          for x, wi in zip(form(*a, **kw), w))
    return jax.grad(loss, argnums=(0, 1, 2))(*args)


def close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < tol * max(1.0, np.abs(want).max())


NAMES = ("q", "k", "v", "gated o")
GRADS = ("qkvz", "conv1d", "norm_scale")


@pytest.mark.parametrize("B,T,rows", [
    (1, 32, 16),        # whole tiles
    (2, 64, 32),
    (2, 37, 16),        # a ragged last tile
    (1, 100, 48),
    (2, 3, 16),         # shorter than the taps
    (1, 1, 16)])
def test_kernels_are_the_xla_form(B, T, rows):
    args, w = inputs(B, T)
    with HIGHEST:
        want, got = xla_form(*args), kernels(*args, rows=rows)
        for name, a, b in zip(NAMES, got, want):
            close(a, b, 2e-6), name
        for name, a, b in zip(GRADS, grads(kernels, args, w, rows=rows),
                              grads(xla_form, args, w)):
            close(a, b, 5e-6), name


def test_first_positions_see_zeros_not_another_elements_rows():
    """A batch element's first three positions convolve with zeros: its
    values and gradients are those of the element alone, whatever the
    element before it holds in its last rows (the rows a tile's second spec
    would bring, were the tile not the sequence's first)."""
    args, w = inputs(3, 32)
    alone = lambda x: x[1:2]
    with HIGHEST:
        got = kernels(*args)
        solo = kernels(alone(args[0]), *args[1:])
        for name, a, b in zip(NAMES, got, solo):
            np.testing.assert_array_equal(np.asarray(alone(a)),
                                          np.asarray(b), name)
        dx = grads(kernels, args, w)[0]
        dx_solo = grads(kernels, (alone(args[0]),) + args[1:],
                        [alone(x) for x in w])[0]
        np.testing.assert_array_equal(np.asarray(alone(dx)),
                                      np.asarray(dx_solo))
        other = args[0].at[0].multiply(-3.0).at[2].add(1.0)
        moved = kernels(other, *args[1:])
        for name, a, b in zip(NAMES, got, moved):
            np.testing.assert_array_equal(np.asarray(alone(a)),
                                          np.asarray(alone(b)), name)


@pytest.mark.parametrize("rows", [16, 32, 48, 96])
def test_tile_sizes_change_nothing(rows):
    """Values and gradients to float32's rounding (a row's own arithmetic
    does not know its tile; the taps' and the scale's sums over time are
    taken in the tiling's order)."""
    args, w = inputs(2, 90, seed=3)
    with HIGHEST:
        want, got = kernels(*args, rows=64), kernels(*args, rows=rows)
        for name, a, b in zip(NAMES, got, want):
            close(a, b, 5e-7), name
        for name, a, b in zip(GRADS, grads(kernels, args, w, rows=rows),
                              grads(kernels, args, w, rows=64)):
            close(a, b, 2e-6), name


def _steps(got, want):
    """The largest distance in bf16 steps of ``want``'s magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return (np.abs(got - want) / step).max()


def test_bf16_operands_round_where_the_xla_form_does():
    """Operands in bf16: the kernels' arrays are bf16 where the XLA form's
    are (q, k, v, the gated output, the gradient of ``qkvz``) and float32
    where its are (the taps' and the scale's gradients). The values are the
    float32 computation ROUNDED AT THE TWO PLACES the XLA form's arrays are
    bf16 -- after the activation, after the scaled unit length -- and once
    after the gated norm (the XLA form rounds each product of the
    convolution besides, inside its fusion: the kernels are no further from
    the float32 computation than it is, values and gradients)."""
    args, w = inputs(2, 64, dtype=BF16, seed=1)
    rounded = lambda x: x.astype(BF16).astype(F32)
    exact = (args[0].astype(F32), rounded(args[1]), args[2])
    err = lambda x, t: np.linalg.norm(np.asarray(x, np.float32)
                                      - np.asarray(t, np.float32))
    tiling = gdn_mix.Tiling(32)
    with HIGHEST:
        got, want, true = kernels(*args, rows=32), xla_form(*args), \
            xla_form(*exact)
        for name, a, b, t in zip(NAMES, got, want, true):
            assert a.dtype == b.dtype == BF16, name
            assert err(a, t) <= 1.05 * err(b, t), name
        q, k, v, _ = qn._premix_xla(
            exact[0], exact[1], HEADS, lambda y: rounded(qn._conv_act(y)),
            qn._unit_length)
        for name, a, b in zip(NAMES, got, (q, k, v)):
            b = b.astype(BF16)
            assert _steps(a, b) <= 1, name
            assert (np.asarray(a) == np.asarray(b)).mean() > 0.99, name
        o = got[0] + got[1] - got[2]
        gated = gdn_mix.gate(o, jnp.zeros_like(o), args[0], args[2], EPS,
                             HEADS, tiling)
        z = qn._premix_xla(args[0], args[1], HEADS, qn._conv_act,
                           qn._unit_length)[3]
        b = qn._gate_xla(o, z, args[2], EPS, HEADS.dv)
        assert gated.dtype == b.dtype == BF16
        assert _steps(gated, b) <= 1
        assert (np.asarray(gated) == np.asarray(b)).mean() > 0.99
        g, gw = grads(kernels, args, w, rows=32), grads(xla_form, args, w)
        gt = grads(xla_form, exact, w)
        assert [x.dtype for x in g] == [x.dtype for x in gw] == [BF16, F32,
                                                                 F32]
        for name, a, b, t in zip(GRADS, g, gw, gt):
            assert err(a, t) <= 1.05 * err(b, t) + 1e-6, name
            close(a, t, 2e-2)


# -- the rule that chooses ------------------------------------------------------

CELL = gdn_mix.Heads(16, 128, 2, 128, 4)        # qwen3-next 8k


@pytest.mark.parametrize("change,why", [
    (dict(platform="cpu"), "off a TPU"),
    (dict(mesh_devices=4), "a mesh of several devices"),
    (dict(heads=CELL._replace(dk=64, dv=64)), "heads that are no whole lanes"),
    (dict(heads=CELL._replace(dv=192)), "a value head that is no whole lanes"),
    (dict(heads=CELL._replace(r=4)),
     "[q k], v and z that are not three blocks of one width"),
    (dict(heads=CELL._replace(taps=9)), "more taps than the halo holds"),
    (dict(itemsize=4), "float32 operands"),
    (dict(device_kind="TPU v9"), "a device kind that is not in the table")])
def test_chooser_leaves_the_xla_path(change, why):
    args = dict(platform="tpu", mesh_devices=1, heads=CELL, itemsize=2,
                device_kind="TPU v5 lite")
    assert gdn_mix.plan(**{**args, **change}) is None, why


def test_chooser_has_a_tiling_for_the_cell():
    tiling = gdn_mix.plan("tpu", 1, CELL)
    assert tiling == gdn_mix._TILING
    assert tiling.rows % gdn_mix._BEFORE == 0 and 8192 % tiling.rows == 0


def test_model_runs_the_xla_path_here():
    """On this CPU the model's chooser leaves the kernels, at the tiny
    widths and at the published ones."""
    assert qn._mix_tiling(HEADS, F32) is None
    assert qn._mix_tiling(CELL, BF16) is None


# -- the model's formulas reach the kernels -------------------------------------

@pytest.fixture
def wrong(monkeypatch):
    """``tests/benchmark/qwen3_next_wrong.py``'s patches, by name."""
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir, os.pardir, "benchmark"))
    import qwen3_next_wrong

    return qwen3_next_wrong.wrong


@pytest.mark.parametrize("name", ["conv_silu_left_out",
                                  "qk_unit_length_left_out"])
def test_kernel_path_computes_the_patched_formula(monkeypatch, wrong, name):
    """``GatedDeltaNet`` looks ``_conv_act`` and ``_unit_length`` up when it
    is traced and hands them to the kernels: under the benchmark's patch the
    kernel path computes the WRONG layer, as the XLA path does, and the
    cell's check goes on refusing it."""
    cfg = qn.Qwen3NextConfig.tiny()
    mixer = qn.GatedDeltaNet(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 21, cfg.hidden_size))
    params = mixer.init(jax.random.PRNGKey(1), x)
    loss = lambda p, x: jnp.sum(jnp.sin(mixer.apply(p, x)[0]))
    with HIGHEST:
        right = mixer.apply(params, x)[0]
        with wrong(name):
            want = mixer.apply(params, x)[0]
            gwant = jax.grad(loss, argnums=(0, 1))(params, x)
            monkeypatch.setattr(qn, "_mix_tiling",
                                lambda *a: gdn_mix.Tiling(16))
            got = mixer.apply(params, x)[0]
            ggot = jax.grad(loss, argnums=(0, 1))(params, x)
    assert np.abs(np.asarray(want - right)).max() > 1e-2 * float(
        jnp.abs(right).max())
    close(got, want, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ggot),
                    jax.tree_util.tree_leaves(gwant)):
        close(a, b, 2e-5)
