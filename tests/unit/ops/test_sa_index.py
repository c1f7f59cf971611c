"""The index-score kernels (``ops/pallas/sa_index.py``) in interpret mode on
the CPU against the XLA path they replace under ``attention_impl="flash"``
(``models/indexed_attention.py index_scores``, the blocked einsum): the
scores on and under the diagonal, the gradient with respect to each of the
three inputs, the exact selection on scores full of ties, and the indexer's
loss and parameter gradients through the model on both paths. Tiles above
the diagonal are never written, so everything is read under the causal
rule, as the model reads it."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import indexed_attention as ia
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.ops.pallas import sa_index

#: (T, J, d, tile): T a multiple of the tile, not a multiple, under a tile
SHAPES = {"tiles_x2_J16": (128, 16, 64, 64), "ragged_J2": (160, 2, 8, 64),
          "under_a_tile_J16": (48, 16, 8, 64), "tiles_x3_J2": (96, 2, 64, 32)}


def case(name, B=2):
    T, J, d, tile = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(T + J), 4)
    qi = jax.random.normal(ks[0], (B, T, J, d))
    ki = jax.random.normal(ks[1], (B, T, d))
    w = jax.random.normal(ks[2], (B, T, J)) * (J * d) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    weight = jnp.where(causal, jax.random.normal(ks[3], (B, T, T)), 0.0)
    kernels = functools.partial(sa_index.index_scores, block_q=tile,
                                block_k=tile, interpret=True)
    xla = functools.partial(ia.index_scores, block=16)
    return (qi, ki, w), causal, weight, kernels, xla


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_scores_match_the_xla_path_under_the_diagonal(name):
    args, causal, _, kernels, xla = case(name)
    want = xla(*args)
    got = jnp.where(causal, kernels(*args), 0.0)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, jnp.where(causal, want, 0.0),
                               rtol=1e-5, atol=1e-6 * scale)


@pytest.mark.parametrize("arg", [0, 1, 2], ids=["qi", "ki", "w"])
@pytest.mark.parametrize("name", sorted(SHAPES))
def test_gradients_match_the_xla_path(name, arg):
    """A seeded scalar of the causal scores: the kernels' ``dqI``, ``dkI``
    and ``dw`` against the blocked einsum's own derivative."""
    args, causal, weight, kernels, xla = case(name)
    loss = lambda fn: lambda *a: jnp.sum(
        jnp.where(causal, fn(*a), 0.0) * weight)
    want = jax.grad(loss(xla), argnums=arg)(*args)
    got = jax.grad(loss(kernels), argnums=arg)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_selection_of_the_kernels_scores_is_the_xla_paths(name):
    """Small whole numbers make every product and sum exact, so both paths
    give the same scores bit for bit whatever their order of addition, and
    ReLU makes rows full of exact zeros: the selections, ties and all, are
    equal entry for entry."""
    T, J, d, tile = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    whole = lambda key, shape, top: jax.random.randint(
        key, shape, -top, top + 1).astype(jnp.float32)
    qi, ki = whole(ks[0], (2, T, J, d), 2), whole(ks[1], (2, T, d), 2)
    w = whole(ks[2], (2, T, J), 3) / 4
    want = ia.index_scores(qi, ki, w, 16)
    got = sa_index.index_scores(qi, ki, w, tile, tile, interpret=True)
    causal = jnp.tril(jnp.ones((T, T), bool))
    zeros = float(jnp.mean((want == 0) & causal) / jnp.mean(causal))
    assert zeros > 0.02 or J > 2        # ties are no corner case here
    np.testing.assert_array_equal(np.asarray(jnp.where(causal, got, 0.0)),
                                  np.asarray(jnp.where(causal, want, 0.0)))
    for topk in (1, 12, T):
        np.testing.assert_array_equal(
            np.asarray(ia.select_mask(got, topk, 16)),
            np.asarray(ia.select_mask(want, topk, 16)))


def test_off_the_chip_the_entry_is_einsum_math():
    args, _, _, _, xla = case("ragged_J2")
    np.testing.assert_allclose(sa_index.index_scores(*args), xla(*args),
                               rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module")
def model_paths():
    """``MixtralForCausalLM.tiny(sa_config=...)`` on the XLA path and, with
    ``attention_impl="flash"``, on the kernels (interpret mode; the flash
    kernels and ``ds_sa_probs`` run their einsum math off the chip)."""
    cfg = MixtralConfig.tiny(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=16, intermediate_size=64, qk_norm_per_head=True,
        max_position_embeddings=512, flash_block_q=32, flash_block_k=32,
        sa_config=dict(indexer_head_dim=8, indexer_num_heads=4,
                       q_chunk_size=16, kv_chunk_size=16, topk=12))
    ids = jnp.asarray(np.random.RandomState(7).randint(0, 128, (2, 80)))
    model = MixtralForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(4), ids)["params"]

    def run(cfg):
        return jax.value_and_grad(
            lambda p: MixtralForCausalLM(cfg).apply(
                {"params": p}, ids, labels=ids), has_aux=True)(params)

    flash = dataclasses.replace(cfg, attention_impl="flash")
    entry, calls = sa_index.index_scores, []

    def kernels(*a, **kw):
        calls.append(kw)
        return entry(*a, **kw, interpret=True)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sa_index, "index_scores", kernels)
        got = run(flash)
    return run(cfg), got, calls


def test_model_reaches_the_kernels_with_the_flash_tiles(model_paths):
    _, _, calls = model_paths
    assert calls and all(kw == {"block_q": 32, "block_k": 32}
                         for kw in calls)


def test_indexer_loss_is_the_same_on_both_paths(model_paths):
    ((loss_x, named_x), _), ((loss_k, named_k), _), _ = model_paths
    assert float(named_x["sa_index_loss"]) > 1e-3
    np.testing.assert_allclose(named_k["sa_index_loss"],
                               named_x["sa_index_loss"], rtol=1e-5)
    np.testing.assert_allclose(loss_k, loss_x, rtol=1e-5)


@pytest.mark.parametrize("leaf", ["wq/kernel", "wk/kernel", "k_norm/scale",
                                  "k_norm/bias", "weights_proj/kernel"])
def test_indexer_parameter_gradients_are_the_same_on_both_paths(
        model_paths, leaf):
    (_, grads_x), (_, grads_k), _ = model_paths
    pick = lambda g: functools.reduce(
        lambda t, key: t[key], leaf.split("/"),
        g["model"]["layers"]["block"]["self_attn"]["indexer"])
    want, got = pick(grads_x), pick(grads_k)
    assert float(jnp.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
