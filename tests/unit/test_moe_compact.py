"""The compact row buffer of a small held share (``models/mixtral.py
_compact_experts``) against the full ``N*K``-row buffer it falls back to:
the same outputs and the same five gradients at every load, under plain
``jax.grad``, under ``jax.checkpoint`` and inside a scanned, remat'd stack —
the place where a half-size buffer behind a DIFFERENTIATED ``lax.switch``
once returned zero ``dx`` rows on the chip (PR 26). Float32 on the CPU:
what differs is the order of the sums over a group's rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.models.mixtral as mx
from deepspeed_tpu.models.layers import resolve_remat_policy

N, K, E, G, H, I = 1024, 3, 32, 4, 16, 24
C = 1024       # 2 x 3072 pairs x 4/32, in whole 512-row tiles
LOADS = {"level": None, "at_capacity": C - 1, "overflow_by_one": C,
         "all_held": N * K, "none_held": 0}
NAMES = ("x", "w1", "w2", "w3", "topk_w")


def test_the_rule():
    """``C`` is the margin over the level load in 512-row tiles; no compact
    buffer over a quarter's share, nor without a router width."""
    assert mx._compact_rows(N * K, G, E) == C
    assert mx._compact_rows(8192 * 6, 8, 64) == 12288        # kimi 8k
    assert mx._compact_rows(8192, 8, 17) is None             # zaya 8k
    assert mx._compact_rows(8192 * 6, 16, 64) == 24576       # a quarter
    assert mx._compact_rows(8192 * 6, 17, 64) is None
    assert mx._compact_rows(65536, 64, None) is None         # olmoe 4k
    assert mx._sorted_experts_for(8192, 8, 17) is mx._sorted_experts
    assert mx._sorted_experts_for(65536, 64, None) is mx._sorted_experts


def _routing(held_pairs, layer=0):
    """``[N, K]`` choices of ``E`` experts, distinct within a token: random
    top-K (about ``N*K*G/E`` pairs on the held experts ``0 .. G``), or
    exactly the first ``held_pairs`` (token, choice) slots on held experts
    and every other slot past them."""
    if held_pairs is None:
        _, idx = jax.lax.top_k(jax.random.uniform(
            jax.random.PRNGKey(7 + layer), (N, E)), K)
        return idx.astype(jnp.int32)
    slot = jnp.arange(N * K).reshape(N, K)
    n, k = slot // K, slot % K
    return jnp.where(slot < held_pairs, (n + k + layer) % G,
                     G + (n * K + k + layer) % (E - G)).astype(jnp.int32)


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    return dict(
        x=jax.random.normal(ks[0], (N, H)),
        w1=jax.random.normal(ks[1], (2, G, H, I)) / H ** 0.5,
        w2=jax.random.normal(ks[2], (2, G, I, H)) / I ** 0.5,
        w3=jax.random.normal(ks[3], (2, G, H, I)) / H ** 0.5,
        topk_w=jax.random.uniform(ks[4], (N, K), minval=0.1, maxval=1.0),
        target=jax.random.normal(ks[5], (N, H)))


def _layer(experts, x, w1, w2, w3, topk_w, idx):
    """``(out, rows)`` of the layer over the held experts ``0 .. G``."""
    return mx._routed_experts(x, w1, w2, w3, topk_w, idx, 0, experts)


def _first_layer(operands):
    """The five operands of the stack's first layer."""
    return [a[0] if a.ndim == 4 else a for a in (operands[n] for n in NAMES)]


def _loss(experts, how, idx, target):
    """The loss of one layer (``plain``, ``checkpoint``) or of two scanned
    layers under the models' default remat (``scan_remat``), the compact
    buffer on (``experts`` the router's width) or off (None)."""
    if how == "scan_remat":
        def loss(x, w1, w2, w3, topk_w):
            def body(x, layer):
                w1, w2, w3, idx = layer
                return x + _layer(experts, x, w1, w2, w3, topk_w, idx)[0], None

            y, _ = jax.lax.scan(jax.checkpoint(
                body, prevent_cse=False,
                policy=resolve_remat_policy("nothing")), x, (w1, w2, w3, idx))
            return jnp.mean((y - target) ** 2)

        return loss

    def loss(x, w1, w2, w3, topk_w):
        out, _ = _layer(experts, x, w1[0], w2[0], w3[0], topk_w, idx[0])
        return jnp.mean((out - target) ** 2)

    return jax.checkpoint(loss, prevent_cse=False) if how == "checkpoint" \
        else loss


@pytest.mark.parametrize("how", ["plain", "checkpoint", "scan_remat"])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_compact_equals_full(operands, load, how):
    idx = jnp.stack([_routing(LOADS[load], layer) for layer in range(2)])
    args = [operands[n] for n in NAMES]
    held = int(_layer(None, *_first_layer(operands), idx[0])[1].sum())
    if LOADS[load] is not None:
        assert held == LOADS[load]
    assert bool(mx._fits(jnp.array([held]), C)) == \
        (load not in ("overflow_by_one", "all_held"))

    grad = lambda experts: jax.jit(jax.value_and_grad(
        _loss(experts, how, idx, operands["target"]),
        argnums=tuple(range(5))))(*args)
    (full_loss, full), (loss, compact) = grad(None), grad(E)
    np.testing.assert_allclose(loss, full_loss, rtol=1e-6)
    for name, got, want in zip(NAMES, compact, full):
        got, want = np.asarray(got), np.asarray(want)
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6 * scale,
                                   err_msg=name)
    # the hazard pinned: a token with a held pair has a gradient row, and
    # the compact path's zero rows are the full path's
    dx, dx_full = np.asarray(compact[0]), np.asarray(full[0])
    zero = lambda a: np.abs(a).max(axis=1) == 0
    if how != "scan_remat":      # there the residual stream reaches every row
        np.testing.assert_array_equal(zero(dx), zero(dx_full))
        touched = np.asarray((idx[0] < G).any(axis=1))
        assert not zero(dx)[touched].any()
    else:
        assert not zero(dx).any()
    if held:
        assert np.abs(np.asarray(compact[1])).max() > 0


@pytest.mark.parametrize("load", sorted(LOADS))
def test_outputs_are_the_full_buffers(operands, load):
    """Forward alone: every row of the output, fit or overflow."""
    args = _first_layer(operands)
    idx = _routing(LOADS[load])
    got, rows = _layer(E, *args, idx)
    want, want_rows = _layer(None, *args, idx)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("experts", [None, 9], ids=["no_share", "4_of_9"])
def test_no_cond_where_the_rule_does_not_engage(operands, experts):
    """A call without a router width (every Mixtral and OLMoE layer) and a
    share over a quarter (ZAYA's 8 of 17) build today's jaxpr: no ``cond``,
    and the same text with or without the width."""
    args = _first_layer(operands)
    idx = _routing(None)

    def text(experts):
        return str(jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(_layer(experts, *a, idx)[0] ** 2),
            argnums=tuple(range(5))))(*args))

    assert " cond[" not in text(experts)
    assert text(experts) == text(None)
    assert " cond[" in text(E)          # the check can fail
