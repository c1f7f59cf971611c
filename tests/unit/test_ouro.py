"""``models/ouro.py`` against the plain reference
(``benchmark/reference/ouro.py``) at tiny sizes on the CPU: the loss, each
pass's token losses, the exit distribution, the last pass's logits and every
parameter's gradient (the shared layers' as the sum over the passes that used
them); one pass alone; four passes against an UNTIED stack of four copies;
the chunked per-token loss against the plain one; bf16 compute against the
float32 reference; and the interface the engine sees."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark.reference import ouro as ref
from deepspeed_tpu.models import ouro
from deepspeed_tpu.models.layers import (chunked_cross_entropy_loss,
                                         shift_labels)
from deepspeed_tpu.models.ouro import OuroConfig, OuroForCausalLM

HIGHEST = jax.default_matmul_precision("highest")
GAUGES = {"loop_exit_step_mean", "loop_exit_entropy", "loop_loss_first",
          "loop_loss_last"}


def sizes_of(cfg):
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def seeded(model, ids, seed=0, jitter=0.2):
    """The model's own init with every vector (the norms' scales seeded at
    1, the gate's bias at 0) moved off its seed, so that a test tells a
    scale from none and a bias from none."""
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)["params"]
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + jitter * jax.random.normal(k, x.shape)
        if path[-1].key in ("scale", "bias") else x
        for (path, x), k in zip(leaves, keys)])


def build(**over):
    cfg = OuroConfig.tiny(**over)
    model = OuroForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 37)))
    return cfg, model, ids, seeded(model, ids)


@pytest.fixture(scope="module")
def case():
    return build(report_loop=True, loss_chunk=16)


def max_leaf_error(got, want):
    """The worst leaf's ``max |got - want| / max |want|``."""
    errs = jax.tree_util.tree_map(
        lambda g, w: float(jnp.abs(g - w).max() / jnp.abs(w).max()),
        got, want)
    return max(jax.tree_util.tree_leaves(errs))


# -- the model against the reference ------------------------------------------

def test_loss_gauges_and_last_logits_are_the_references(case):
    cfg, model, ids, params = case
    sizes = sizes_of(cfg)
    with HIGHEST:
        logits = model.apply({"params": params}, ids)
        loss, named = model.apply({"params": params}, ids, labels=ids)
    assert set(named) == GAUGES
    assert float(loss) == pytest.approx(float(ref.loss(params, sizes, ids)),
                                        rel=2e-6)
    ce, p = [], []
    for b in range(ids.shape[0]):
        want = ref.logits(params, ref.hidden_states(params, sizes, ids[b]))
        assert np.abs(np.asarray(logits[b] - want)).max() < 2e-5 * float(
            jnp.abs(want).max())
        ce.append(ref.step_losses(params, sizes, ids[b]))
        p.append(ref.exit_distribution(params, sizes, ids[b])[:, :-1])
    ce, p = jnp.stack(ce, 1), jnp.stack(p, 1)        # [R, B, T - 1]
    steps = jnp.arange(1, 5.0)[:, None, None]
    want = {"loop_loss_first": ce[0].mean(), "loop_loss_last": ce[-1].mean(),
            "loop_exit_step_mean": (steps * p).sum(0).mean(),
            "loop_exit_entropy": -(p * jnp.log(p)).sum(0).mean()}
    for name, value in want.items():
        assert float(named[name]) == pytest.approx(float(value), rel=2e-6)
    # four passes that differ: the loop is no repetition of one reading
    assert not np.allclose(ce[0], ce[-1], rtol=1e-3)


def test_each_passes_token_losses_and_the_exit_distribution(case):
    """What the harness cannot see: ``CE_1 .. CE_R`` and ``p`` token by
    token, as the model's own pieces give them for the reference's states."""
    cfg, model, ids, params = case
    sizes = sizes_of(cfg)
    head = params["loop"]["lm_head"]["kernel"]
    gate = params["loop"]["early_exit_gate"]
    labels = shift_labels(ids)
    with HIGHEST:
        for b in range(ids.shape[0]):
            states = jnp.stack(ref.pass_states(params, sizes, ids[b]))
            nll = jnp.stack([ouro.chunked_token_nll(
                h[None], head, labels[b:b + 1], 16)[0] for h in states])
            want = ref.step_losses(params, sizes, ids[b])
            assert np.allclose(nll[:, :-1], want, rtol=1e-5, atol=1e-6)
            assert np.all(np.asarray(nll[:, -1]) == 0)   # no label: no loss
            logit = (states @ gate["kernel"])[..., 0] + gate["bias"][0]
            p = jnp.exp(ouro.exit_log_distribution(logit))
            assert np.allclose(p, ref.exit_distribution(
                params, sizes, ids[b]), rtol=1e-5, atol=1e-7)
            assert np.allclose(p.sum(0), 1.0, atol=1e-6)


def test_every_parameters_gradient_is_the_references(case):
    """The shared layers' gradients are sums over four uses, the head's and
    the gate's over four readings: the reference's ``jax.grad`` walks its
    own unrolled loop."""
    cfg, model, ids, params = case
    with HIGHEST:
        # one program a side, not an operation at a time
        got = jax.jit(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)[0]))(params)
        want = jax.jit(lambda p: ref.grads(p, sizes_of(cfg), ids))(params)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert min(float(jnp.abs(g).max())
               for g in jax.tree_util.tree_leaves(want)) > 1e-4
    assert max_leaf_error(got, want) < 2e-4


def test_remat_and_the_plain_loss_change_nothing(case):
    cfg, model, ids, params = case
    value = lambda **over: jax.jit(jax.value_and_grad(
        lambda p: OuroForCausalLM(dataclasses.replace(cfg, **over)).apply(
            {"params": p}, ids, labels=ids)[0]))(params)
    with HIGHEST:
        loss, grads = value()
        for over in (dict(remat=True), dict(loss_chunk=0),
                     dict(remat=True, remat_policy="dots", loss_chunk=64)):
            other, other_grads = value(**over)
            assert float(other) == pytest.approx(float(loss), rel=1e-6)
            assert max_leaf_error(other_grads, grads) < 1e-4, over


# -- the loop ------------------------------------------------------------------

def test_one_pass_is_a_plain_decoder_with_p_1_and_no_entropy():
    cfg, model, ids, params = build(total_ut_steps=1, report_loop=True)
    sizes = sizes_of(cfg)
    with HIGHEST:
        loss, named = model.apply({"params": params}, ids, labels=ids)
        ce = jnp.stack([ref.step_losses(params, sizes, i) for i in ids])
    assert ce.shape == (2, 1, 36)
    assert float(loss) == pytest.approx(float(ce.mean()), rel=2e-6)
    assert float(named["loop_exit_step_mean"]) == pytest.approx(1.0)
    assert float(named["loop_exit_entropy"]) == pytest.approx(0.0, abs=1e-7)
    assert float(named["loop_loss_first"]) == float(named["loop_loss_last"])
    # the one pass's gate decides nothing: no gradient reaches it
    grads = jax.grad(lambda p: model.apply({"params": p}, ids,
                                           labels=ids)[0])(params)
    assert float(jnp.abs(grads["loop"]["early_exit_gate"]["kernel"]).max()) \
        == 0.0


class Untied(nn.Module):
    """``total_ut_steps`` passes, each with parameters of its own
    (``loop_0`` ..): a ``4 L``-layer sandwich stack read after every ``L``."""

    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, labels):
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens")(
            input_ids)
        positions = jnp.broadcast_to(jnp.arange(input_ids.shape[1])[None],
                                     input_ids.shape)
        cos, sin = ouro.rotary_embedding(positions, cfg.head_dim,
                                         cfg.rope_theta, dtype=x.dtype)
        shifted = shift_labels(labels)
        read = []
        for t in range(cfg.total_ut_steps):
            _, x, out = ouro._Pass(cfg, name=f"loop_{t}")(x, cos, sin, None,
                                                          shifted)
            read.append(out)
        nll, gate = (jnp.stack(part) for part in zip(*read))
        return ouro.expected_loss(cfg, nll, gate, shifted)[0]


def test_four_passes_are_an_untied_stack_of_four_copies(case):
    """The same loss from four copies of the weights, and the shared
    weights' gradient the SUM of the copies' (no copy's alone)."""
    cfg, model, ids, params = case
    copies = {"embed_tokens": params["embed_tokens"],
              **{f"loop_{t}": params["loop"] for t in range(4)}}
    with HIGHEST:
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)[0])(params)
        untied, each = jax.value_and_grad(lambda p: Untied(cfg).apply(
            {"params": p}, ids, ids))(copies)
    assert float(untied) == pytest.approx(float(loss), rel=1e-6)
    summed = jax.tree_util.tree_map(lambda *g: sum(g),
                                    *(each[f"loop_{t}"] for t in range(4)))
    assert max_leaf_error(grads["loop"], summed) < 1e-5
    assert max_leaf_error(grads["embed_tokens"], each["embed_tokens"]) < 1e-5
    q = lambda g: g["layers"]["block"]["self_attn"]["q_proj"]["kernel"]
    for t in range(4):
        assert max_leaf_error(q(grads["loop"]), q(each[f"loop_{t}"])) > 0.1


def test_the_state_between_passes_is_the_normed_one(case):
    """Pass 2 starts from ``h_1``: the first pass's readings of a
    four-pass model are a one-pass model's, and its second pass's are the
    one-pass model's run on ``h_1`` in place of the embeddings."""
    cfg, model, ids, params = case
    sizes = sizes_of(cfg)
    h1, h2 = ref.pass_states(params, sizes, ids[0])[:2]
    table = {**params, "embed_tokens": {"embedding": h1}}
    again = ref.pass_states(table, {**sizes, "total_ut_steps": 1},
                            jnp.arange(ids.shape[1]))[0]
    assert np.allclose(again, h2, rtol=1e-5, atol=1e-6)


# -- the per-token loss --------------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 37, 64, 2048])
def test_chunked_token_loss_is_the_plain_one(chunk):
    """Chunks that do and do not divide the 2 x 37 tokens, one longer than
    all of them; unlabelled tokens read 0; the token mean is
    ``layers.chunked_cross_entropy_loss``'s."""
    rng = np.random.RandomState(chunk)
    hidden = jnp.asarray(rng.randn(2, 37, 32), jnp.float32)
    w_out = jnp.asarray(rng.randn(32, 128) / 6, jnp.float32)
    labels = jnp.asarray(rng.randint(0, 128, (2, 37))).at[:, -1].set(-100) \
        .at[0, 5].set(-100)
    with HIGHEST:
        got = ouro.chunked_token_nll(hidden, w_out, labels, chunk)
        want = ouro.token_nll(hidden @ w_out, labels)
        mean = chunked_cross_entropy_loss(hidden, w_out, labels, chunk=chunk)
        grads = jax.grad(lambda h, w: jnp.sum(jnp.cos(ouro.chunked_token_nll(
            h, w, labels, chunk))), (0, 1))(hidden, w_out)
        plain = jax.grad(lambda h, w: jnp.sum(jnp.cos(ouro.token_nll(
            h @ w, labels))), (0, 1))(hidden, w_out)
    assert got.shape == (2, 37) and got.dtype == jnp.float32
    assert np.allclose(got, want, rtol=1e-6, atol=1e-6)
    assert float(got[0, 5]) == 0.0 and np.all(np.asarray(got[:, -1]) == 0)
    assert float(got.sum() / 71) == pytest.approx(float(mean), rel=1e-6)
    assert max_leaf_error(grads, plain) < 1e-5


def test_exit_distribution_gives_the_last_pass_the_remainder():
    gate = jnp.asarray(np.random.RandomState(3).randn(4, 5) * 3, jnp.float32)
    lam = jax.nn.sigmoid(gate)
    p = jnp.exp(ouro.exit_log_distribution(gate))
    assert np.allclose(p[0], lam[0], rtol=1e-6)
    assert np.allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), rtol=1e-5)
    assert np.allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]),
                       rtol=1e-5)
    assert np.allclose(p.sum(0), 1.0, atol=1e-6)
    # the last pass's own gate is read by nothing
    moved = jnp.exp(ouro.exit_log_distribution(gate.at[3].add(5.0)))
    assert np.array_equal(moved, p)
    # a gate shut or open to 40 nats stays finite, in value and gradient
    hard = jnp.asarray([[40.0, -40.0], [-40.0, 40.0], [0.0, 0.0]])
    entropy = lambda g: -jnp.sum(jnp.exp(ouro.exit_log_distribution(g))
                                 * ouro.exit_log_distribution(g))
    assert np.isfinite(float(entropy(hard)))
    assert np.all(np.isfinite(np.asarray(jax.grad(entropy)(hard))))


# -- bf16 ---------------------------------------------------------------------

def rel_l2(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def test_bf16_compute_stays_near_the_float32_reference():
    """The engine's discipline (bf16 copies of float32 masters, a float32
    loss) at the model's own init: the loss within 0.1% of the reference's,
    the last pass's logits within 6% of their spread, every parameter's
    gradient within 10% of the reference's by norm. Read on this CPU: 0.02%,
    3.1%, and 1.2-6.5% a leaf, of which rounding the WEIGHTS alone gives
    0.9-4.4% -- an 8-layer ``LlamaForCausalLM`` of these widths reads
    3.5-4.5%: four passes of two layers round the stream as eight layers
    do, and the shared weights' four gradients are summed in bf16, once
    each."""
    cfg = OuroConfig.tiny(loss_chunk=16)
    model = OuroForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 37)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    sizes = sizes_of(cfg)
    half = lambda p: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), p)
    loss, grads = jax.value_and_grad(lambda p: model.apply(
        {"params": half(p)}, ids, labels=ids).astype(jnp.float32))(params)
    logits = model.apply({"params": half(params)}, ids).astype(jnp.float32)
    with HIGHEST:
        want_loss = ref.loss(params, sizes, ids)
        want_grads = ref.grads(params, sizes, ids)
        want = jnp.stack([ref.logits(params, ref.hidden_states(
            params, sizes, i)) for i in ids])
    assert loss.dtype == jnp.float32
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-3)
    assert float(jnp.linalg.norm(logits - want) / jnp.linalg.norm(
        want - want.mean(-1, keepdims=True))) < 0.06
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))
    # the gate's bias has ONE gradient, the sum of every token's signed
    # term, of which a thousandth is left at this init (9e-4 against the
    # kernel's 0.26): held to the kernel's scale, not to its own
    gate, want_gate = (g["loop"]["early_exit_gate"]
                       for g in (grads, want_grads))
    assert abs(float(gate["bias"][0] - want_gate["bias"][0])) < \
        0.01 * float(jnp.linalg.norm(want_gate["kernel"]))
    errs = jax.tree_util.tree_map(rel_l2, grads, want_grads)
    errs["loop"]["early_exit_gate"].pop("bias")
    assert max(jax.tree_util.tree_leaves(errs)) < 0.10, errs


# -- the interface ------------------------------------------------------------

def test_a_cache_an_early_exit_or_a_tied_head_raises(case):
    cfg, model, ids, params = case
    with pytest.raises(NotImplementedError, match="ROADMAP R14"):
        model.apply({"params": params}, ids, cache={})
    for over in (dict(early_exit_threshold=0.9), dict(sliding_window=8),
                 dict(tie_word_embeddings=True)):
        with pytest.raises(NotImplementedError):
            OuroForCausalLM(dataclasses.replace(cfg, **over)).apply(
                {"params": params}, ids)
    with pytest.raises(ValueError, match="at least 1"):
        OuroForCausalLM(dataclasses.replace(cfg, total_ut_steps=0)).apply(
            {"params": params}, ids)


def test_published_shapes():
    """A layer 51.39 M (four projections of 2048 x 2048, a SwiGLU of 5632,
    four scales), the two tables 201.3 M, the gate 2,049: 663.8 M at nine
    layers, 612.4 M at eight, whatever the number of passes."""
    count = lambda **over: sum(
        x.size for x in jax.tree_util.tree_leaves(jax.eval_shape(
            OuroForCausalLM(OuroConfig.ouro_2_6b(**over)).init,
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    rest = 2 * 49152 * 2048 + 2048 + 2049
    assert round(layer / 1e6, 2) == 51.39
    assert count(num_hidden_layers=9) == 9 * layer + rest == 663826433
    assert count(num_hidden_layers=8) == 8 * layer + rest == 612438017
    assert count(num_hidden_layers=8, total_ut_steps=1) == 8 * layer + rest
    cfg = OuroConfig.ouro_2_6b()
    assert (cfg.head_dim, cfg.total_ut_steps, cfg.exit_entropy_coef,
            cfg.early_exit_threshold, cfg.sliding_window) == \
        (128, 4, 0.1, 1.0, None)


def test_the_engine_trains_it_and_publishes_the_loop_gauges():
    cfg = OuroConfig.tiny(report_loop=True, remat=True, loss_chunk=64)
    model = OuroForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 32)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}},
        partition_rules=OuroForCausalLM.partition_rules(cfg))
    before = jax.tree_util.tree_map(np.asarray, engine.state.params)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert engine.perf.programs.program("train_step").compiles == 1
    moved = jax.tree_util.tree_map(
        lambda a, b: not np.array_equal(a, np.asarray(b)), before,
        engine.state.params)
    assert all(jax.tree_util.tree_leaves(moved))
    found = engine.registry.snapshot()
    assert 1 < found["loop_exit_step_mean"] < 4
    assert 0 < found["loop_exit_entropy"] <= np.log(4) + 1e-6
    assert found["loop_loss_first"] > 0 and found["loop_loss_last"] > 0


def test_step_names_the_loops_scopes():
    """The loop over passes under ``ds.loop_stack``, every pass's layers
    under ``ds.layer_stack`` inside it, its head under ``ds.lm_head_loss``,
    the gate and the mixing under ``ds.exit_gate``; four passes call the
    attention core four times a layer scan."""
    cfg = OuroConfig.tiny(remat=True, loss_chunk=16)
    model = OuroForCausalLM(cfg)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               ids))["params"]
    text = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, ids, labels=ids))).lower(params).as_text(
            debug_info=True)
    for path in ("ds.loop_stack/loop/ds.layer_stack/",
                 "ds.loop_stack/loop/ds.lm_head_loss/",
                 "ds.loop_stack/loop/ds.exit_gate/", "/ds.exit_gate/",
                 "block/self_attn/ds.attention", "block/mlp/ds.mlp",
                 "block/ds.norm", "block/ds.residual", "ds.embed"):
        assert path in text, path
