"""``ops/pallas/gdn_rule.py``'s two kernels in interpret mode on the CPU,
reached through ``models/qwen3_next.py gated_delta_rule`` (its chooser
answered for): forward and all five gradients against the same function's
XLA path and against the token-by-token recurrence
(``benchmark/reference/qwen3_next.py``) -- lengths that are no whole chunks,
chunks of 8 and 64, decays of 20 nats a token, alike keys, bf16 operands --
that the grid's block sizes change nothing, and the rule that chooses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.ops.pallas import gdn_rule

HIGHEST = jax.default_matmul_precision("highest")


def rule_inputs(T, H=3, dk=8, dv=8, decay=1.0, seed=0, B=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) / dk ** 0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -decay * jax.random.uniform(ks[3], (B, T, H), minval=0.05, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    return jnp.stack([ref.delta_rule(*(x[b] for x in (q, k, v, g, beta)))
                      for b in range(q.shape[0])])


@pytest.fixture
def kernels(monkeypatch):
    """``rule(*inputs, chunk=, tiling=)``: ``gated_delta_rule`` with its
    chooser answering ``tiling`` (the kernels, interpreted: the backend is
    the CPU's); ``tiling=None`` is the XLA path."""
    def rule(*x, chunk, tiling=gdn_rule.Tiling(2, 2)):
        monkeypatch.setattr(qn, "_rule_tiling", lambda *a: tiling)
        return qn.gated_delta_rule(*x, chunk=chunk)
    return rule


def close(got, want, tol):
    scale = max(1.0, float(jnp.abs(want).max()))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert np.abs(np.asarray(got - want, np.float32)).max() < tol * scale


def grads(rule, x, w):
    return jax.grad(lambda *a: jnp.sum(w * rule(*a)), argnums=range(5))(*x)


@pytest.mark.parametrize("T,chunk", [
    (37, 8), (64, 64),      # a ragged tail of small chunks; one whole chunk
    # 28-35 s each cold (PR 69): whole small chunks, a sequence under one
    # chunk and a ragged tail of the published chunk ask the same of the two
    # kept above
    pytest.param(32, 8, marks=pytest.mark.slow),
    pytest.param(5, 8, marks=pytest.mark.slow),
    pytest.param(100, 64, marks=pytest.mark.slow)])
def test_kernels_are_the_xla_path_and_the_recurrence(kernels, T, chunk):
    x = rule_inputs(T, B=2)
    w = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    with HIGHEST:
        got, decay = kernels(*x, chunk=chunk)
        xla, decay_xla = kernels(*x, chunk=chunk, tiling=None)
        want = recurrence(*x)
        dgot = grads(lambda *a: kernels(*a, chunk=chunk)[0], x, w)
        dxla = grads(lambda *a: kernels(*a, chunk=chunk, tiling=None)[0], x,
                     w)
        dwant = grads(recurrence, x, w)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(decay) == float(decay_xla)
    close(got, xla, 1e-5)
    close(got, want, 1e-5)
    for a, b, c in zip(dgot, dxla, dwant):
        assert a.shape == c.shape and a.dtype == c.dtype
        close(a, b, 1e-4)
        close(a, c, 1e-4)


@pytest.mark.parametrize("T,chunk", [(64, 16), (50, 16)])
def test_twenty_nats_a_token_stay_finite_and_equal(kernels, T, chunk):
    """-20 nats a token, -320 a chunk of 16: every exponent in the kernels
    is a difference that is <= 0, forward and backward."""
    q, k, v, g, beta = rule_inputs(T, decay=20.0, seed=2)
    g = jnp.minimum(g, -15.0 * (jnp.arange(3) > 0))     # head 0 decays little
    x = (q, k, v, g, beta)
    w = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    with HIGHEST:
        got, decay = kernels(*x, chunk=chunk)
        want = recurrence(*x)
        dgot = grads(lambda *a: kernels(*a, chunk=chunk)[0], x, w)
        dwant = grads(recurrence, x, w)
    assert float(decay) > 88 * 2
    close(got, want, 1e-5)
    for a, b in zip(dgot, dwant):
        close(a, b, 1e-4)


def test_alike_keys_do_not_break_the_kernels(kernels):
    """Every key the same, ``beta`` 1, no decay: the all-ones lower
    triangle. ``o_t = v_t``, and the gradients are the recurrence's."""
    T, H, d = 64, 1, 8
    k = jnp.tile(jnp.eye(d)[0], (1, T, H, 1))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, H, d))
    x = (k, k, v, jnp.zeros((1, T, H)), jnp.ones((1, T, H)))
    with HIGHEST:
        got, _ = kernels(*x, chunk=64)
        dgot = grads(lambda *a: kernels(*a, chunk=64)[0], x, v)
        dwant = grads(recurrence, x, v)
    close(got, v, 1e-5)
    for a, b in zip(dgot, dwant):
        close(a, b, 1e-4)


def test_bf16_operands_round_once(kernels):
    """bf16 q, k, v at a head of 128 x 128 in chunks of 64: ``o`` and the
    gradients come back in the operands' types and stand as near the
    float32 recurrence as the XLA path does (whose CPU products do not
    round their float32 operands: the kernels' do, once)."""
    x32 = rule_inputs(256, H=2, dk=128, dv=128, seed=4)
    x = tuple(t.astype(jnp.bfloat16) for t in x32[:3]) + x32[3:]
    back = tuple(t.astype(jnp.float32) for t in x[:3]) + x32[3:]
    w = jax.random.normal(jax.random.PRNGKey(5), x[2].shape)
    rel = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32))
                             / jnp.linalg.norm(b))
    with HIGHEST:
        got, _ = kernels(*x, chunk=64)
        want = recurrence(*back)
        dgot = grads(lambda *a: kernels(*a, chunk=64)[0].astype(jnp.float32),
                     x, w)
        dwant = grads(recurrence, back, w)
    assert got.dtype == jnp.bfloat16
    assert rel(got, want) < 1e-2
    for a, b, like in zip(dgot, dwant, x):
        assert a.dtype == like.dtype
        assert rel(a, b) < 1e-2


@pytest.mark.parametrize("tiling", [(1, 1), (3, 1), (2, 3), (6, 2)])
def test_block_sizes_change_nothing(kernels, tiling):
    """Six chunks of three heads under grids of 6 x 3, 2 x 3, 3 x 1 and 1 x 2
    steps (a head count of 3 fits one head a step where two are asked)."""
    x = rule_inputs(48, seed=6)
    w = jax.random.normal(jax.random.PRNGKey(7), x[2].shape)
    run = lambda tiling: (
        kernels(*x, chunk=8, tiling=tiling)[0],
        grads(lambda *a: kernels(*a, chunk=8, tiling=tiling)[0], x, w))
    with HIGHEST:
        got, dgot = run(gdn_rule.Tiling(*tiling))
        want, dwant = run(gdn_rule.Tiling(2, 2))
    close(got, want, 1e-6)
    for a, b in zip(dgot, dwant):
        close(a, b, 1e-6)


V5E = ("tpu", 1, 128, 128, 64, 2, "TPU v5 lite")


@pytest.mark.parametrize("change,why", [
    ({0: "cpu"}, "a CPU"),
    ({1: 4}, "a mesh of several devices: no Pallas under a mesh"),
    ({2: 64}, "a 64-wide key head"),
    ({3: 64}, "a 64-wide value head"),
    ({4: 12}, "a chunk that is no whole sublanes"),
    ({5: 4}, "float32 operands"),
    ({6: "TPU v9"}, "a chip whose VMEM is not in the table"),
])
def test_chooser_leaves_the_xla_path(change, why):
    args = [change.get(i, a) for i, a in enumerate(V5E)]
    assert gdn_rule.plan(*args) is None, why


def test_chooser_has_a_tiling_for_the_cell():
    tiling = gdn_rule.plan(*V5E)
    assert isinstance(tiling, gdn_rule.Tiling)
    assert 128 % tiling.chunks == 0 and 32 % tiling.heads == 0


def test_model_runs_the_xla_path_here():
    """On this CPU the model's chooser says None: ``test_qwen3_next.py``'s
    tests of the rule keep testing the XLA path."""
    assert qn._rule_tiling(128, 128, 64, jnp.bfloat16) is None
