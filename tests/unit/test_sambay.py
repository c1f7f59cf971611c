"""``models/sambay.py`` (Phi-4-mini-flash-reasoning's decoder-hybrid-decoder)
against the plain reference ``benchmark/reference/sambay.py`` on seeded
weights at tiny sizes: logits, loss and GRADIENTS, whole and per kind of
layer; the layer pattern from config data; the published parameter count;
what the model refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from deepspeed_tpu.models import sambay
from deepspeed_tpu.models.sambay import (SambaYConfig, SambaYForCausalLM,
                                         lambda_init, layer_kinds,
                                         published_index)

REF = common.load_file_module("reference", "sambay")
IDS = np.random.RandomState(0).randint(0, 128, (2, 48)).astype(np.int32)


def sizes_of(cfg):
    out = {k: v for k, v in dataclasses.asdict(cfg).items()
           if isinstance(v, (int, float, bool)) or v is None}
    out["head_dim"] = cfg.head_dim
    return out


def seeded(cfg, seed=0, spread=0.05):
    """(model, params): the model's own init with every leaf moved by a
    seeded normal, so that no bias is zero and no scale one (at exactly
    those the forward pass would not notice them)."""
    model = SambaYForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), IDS)["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return model, jax.tree_util.tree_unflatten(tree, [
        leaf + spread * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


CONFIGS = {
    "six_layers": dict(),
    "eight_layers_cut": dict(num_hidden_layers=8, self_decoder_layers=2,
                             cross_decoder_first_index=16),
    "eight_layers_half": dict(num_hidden_layers=8),
    "remat_window_binds": dict(remat=True, sliding_window=4),
    "chunk_does_not_divide": dict(ssm_chunk=20),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def case(request):
    cfg = SambaYConfig.tiny(**CONFIGS[request.param])
    model, params = seeded(cfg)
    return cfg, model, params


def test_logits_and_loss_match_the_reference(case):
    cfg, model, params = case
    logits = model.apply({"params": params}, IDS)
    for row, ids in zip(logits, IDS):
        hidden = REF.hidden_states(params, sizes_of(cfg), jnp.asarray(ids))
        assert common.rel_l2(row, REF.logits(params, hidden)) < 2e-5
    loss = model.apply({"params": params}, IDS, labels=IDS)
    assert float(loss) == pytest.approx(
        float(REF.loss(params, sizes_of(cfg), IDS)), rel=1e-5)


def test_gradients_match_the_reference(case):
    """Every parameter's gradient of the loss, leaf by leaf: each kind of
    layer is held to the reference's arithmetic, the scan's six gradients
    and what crosses layers (the memory, the handed-on keys and values)
    among them."""
    cfg, model, params = case
    # one program a side, not an operation at a time
    got = jax.jit(jax.grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS)))(params)
    want = jax.jit(jax.grad(
        lambda p: REF.loss(p, sizes_of(cfg), IDS)))(params)
    worst = jax.tree_util.tree_map(
        lambda g, w: float(jnp.linalg.norm(g - w)
                           / (jnp.linalg.norm(w) + 1e-12)), got, want)
    flat = jax.tree_util.tree_flatten_with_path(worst)[0]
    assert len(flat) > 60
    bad = [(jax.tree_util.keystr(k), v) for k, v in flat if not v < 2e-3]
    assert not bad, bad


def test_unrolled_periods_compute_what_the_scans_do():
    """``scan_layers=False`` names a period ``<decoder>_<p>``; with the
    scanned tree's slices it gives the scanned model's loss."""
    over = dict(num_hidden_layers=10, self_decoder_layers=4)
    model, params = seeded(SambaYConfig.tiny(**over))
    inner = dict(params["model"])
    for name in ("self_decoder", "cross_decoder"):
        stack = inner.pop(name)
        for p in range(2):
            inner[f"{name}_{p}"] = jax.tree_util.tree_map(
                lambda a: a[p], stack)
    flat = SambaYForCausalLM(SambaYConfig.tiny(scan_layers=False, **over))
    shapes = jax.eval_shape(flat.init, jax.random.PRNGKey(0), IDS)["params"]
    assert jax.tree_util.tree_structure(shapes) == \
        jax.tree_util.tree_structure({"model": inner})
    assert float(flat.apply({"params": {"model": inner}}, IDS,
                            labels=IDS)) == pytest.approx(
        float(model.apply({"params": params}, IDS, labels=IDS)), rel=1e-6)


KINDS = ("mamba", "window", "memory", "full", "gmu", "cross")


@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_of_layer_reaches_the_loss(kind):
    """Zeroing one kind of layer's mixer output projection changes the loss,
    alike in model and reference: no kind is dead weight."""
    cfg = SambaYConfig.tiny()
    model, params = seeded(cfg, seed=3)
    where = {"mamba": ("self_decoder", "mamba"),
             "window": ("self_decoder", "window"),
             "memory": ("memory_layer",), "full": ("kv_layer",),
             "gmu": ("cross_decoder", "gmu"),
             "cross": ("cross_decoder", "cross")}[kind]
    cut = jax.tree_util.tree_map(lambda a: a, params)
    node = cut["model"]
    for key in where:
        node = node[key]
    node["mixer"]["out_proj"]["kernel"] = jnp.zeros_like(
        node["mixer"]["out_proj"]["kernel"])
    full = float(model.apply({"params": params}, IDS, labels=IDS))
    less = float(model.apply({"params": cut}, IDS, labels=IDS))
    assert abs(full - less) > 1e-4
    assert less == pytest.approx(
        float(REF.loss(cut, sizes_of(cfg), IDS)), rel=1e-5)


@pytest.mark.parametrize("layers,self_layers,want", [
    (6, 2, "mw|MF|gc"),
    (8, 2, "mw|MF|gcgc"),
    (8, None, "mwmw|MF|gc"),
    (32, None, "mw" * 8 + "|MF|" + "gc" * 7),
])
def test_layer_pattern_comes_from_config_data(layers, self_layers, want):
    cfg = SambaYConfig(num_hidden_layers=layers,
                       self_decoder_layers=self_layers)
    letter = {"mamba": "m", "window": "w", "memory": "M", "full": "F",
              "gmu": "g", "cross": "c"}
    got = "".join(letter[k] for k in layer_kinds(cfg))
    S = cfg.self_layers
    assert got[:S] + "|" + got[S:S + 2] + "|" + got[S + 2:] == want
    sambay._check(cfg)


def test_a_cut_keeps_each_layers_published_lambda_init():
    cut = SambaYConfig(num_hidden_layers=6, self_decoder_layers=2,
                       cross_decoder_first_index=16)
    assert [published_index(cut, i) for i in range(6)] == \
        [0, 1, 16, 17, 18, 19]
    assert [round(lambda_init(published_index(cut, i)), 3)
            for i in (1, 3, 5)] == [0.356, 0.796, 0.798]
    whole = SambaYConfig()
    assert [published_index(whole, i) for i in (0, 15, 16, 31)] == \
        [0, 15, 16, 31]


@pytest.mark.parametrize("over,millions", [
    (dict(), 3852.6),
    (dict(num_hidden_layers=6, self_decoder_layers=2, vocab_size=25008),
     697.1)])
def test_parameter_counts(over, millions):
    """9 x 119.90 M (Mamba) + 9 x 98.32 M (attention) + 7 x 104.87 M (gated
    memory unit) + 7 x 91.77 M (cross-attention) + the 512.2 M tied table =
    3.85 B against the published 3.8 B; the benchmark's cut 697.1 M."""
    model = SambaYForCausalLM(SambaYConfig(**over))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert round(count(shapes) / 1e6, 1) == millions
    m = shapes["model"]
    per = lambda tree, n: round(count(tree) / n / 1e6, 2)
    periods = m["self_decoder"]["mamba"]["mixer"]["D"].shape[0]
    assert per(m["self_decoder"]["mamba"], periods) == 119.90
    assert per(m["self_decoder"]["window"], periods) == 98.32
    assert per(m["memory_layer"], 1) == 119.90
    assert per(m["kv_layer"], 1) == 98.32
    crosses = m["cross_decoder"]["gmu"]["mixer"]["in_proj"]["kernel"].shape[0]
    assert per(m["cross_decoder"]["gmu"], crosses) == 104.87
    assert per(m["cross_decoder"]["cross"], crosses) == 91.77


def test_the_recurrence_starts_as_published():
    """``A_log = log(1 .. N)`` a channel, ``D = 1``, step sizes log-uniform
    in [1e-3, 1e-1]; lambda vectors seeded, not zero."""
    cfg = SambaYConfig.tiny(mamba_d_state=16)
    params = SambaYForCausalLM(cfg).init(jax.random.PRNGKey(2), IDS)["params"]
    mixer = params["model"]["memory_layer"]["mixer"]
    np.testing.assert_allclose(
        np.exp(mixer["A_log"]), np.broadcast_to(np.arange(1, 17.0),
                                                (cfg.d_inner, 16)), rtol=1e-6)
    assert bool((mixer["D"] == 1).all())
    steps = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 1e-3 * 0.999 <= steps.min() and steps.max() <= 1e-1 * 1.001
    lam = params["model"]["kv_layer"]["mixer"]["lambda_q1"]
    assert 0.02 < float(jnp.std(lam)) < 0.3


def test_a_cache_or_a_padding_mask_raises():
    cfg = SambaYConfig.tiny()
    model, params = seeded(cfg)
    with pytest.raises(NotImplementedError, match="training only"):
        model.apply({"params": params}, IDS, cache={})
    with pytest.raises(NotImplementedError, match="padding mask"):
        model.apply({"params": params}, IDS,
                    attention_mask=jnp.ones_like(IDS))


@pytest.mark.parametrize("over,match", [
    (dict(mb_per_layer=3), "mb_per_layer"),
    (dict(num_hidden_layers=7), "whole periods"),
    (dict(num_hidden_layers=6, self_decoder_layers=6), "whole periods"),
    (dict(num_key_value_heads=1, num_attention_heads=8), "pairs the heads"),
])
def test_a_pattern_that_is_no_model_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        sambay._check(SambaYConfig.tiny(**over))


def test_decay_scalar_rides_beside_the_loss_only_where_asked():
    cfg = SambaYConfig.tiny(report_ssm_decay=True)
    model, params = seeded(cfg)
    loss, named = model.apply({"params": params}, IDS, labels=IDS)
    assert set(named) == {"ssm_chunk_decay_max"}
    # by hand for the memory layer's part: the largest over layers is at
    # least every layer's own
    assert float(named["ssm_chunk_decay_max"]) > 0
    plain = SambaYForCausalLM(SambaYConfig.tiny()).apply(
        {"params": params}, IDS, labels=IDS)
    assert float(plain) == pytest.approx(float(loss), rel=1e-6)
    delta = jnp.full((1, 40, 3), 0.5)
    a = -jnp.asarray([[1.0, 2.0], [0.5, 4.0], [3.0, 1.0]])
    assert float(sambay.chunk_decay_max(delta, a, 16)) == \
        pytest.approx(16 * 0.5 * 4.0)


def test_no_array_of_every_position_channel_and_state_in_the_step():
    """The lowered gradient of the tiny model at 64 positions, 64 inner
    channels, 4 states, chunks of 16: no ``[T, d_inner, d_state]``."""
    cfg = SambaYConfig.tiny(remat=True)
    model, params = seeded(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    text = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, ids, labels=ids))).lower(params).as_text()
    assert (cfg.d_inner, cfg.mamba_d_state) == (64, 4)
    assert "x16x64x4x" in text            # a chunk's states
    for shape in ("64x64x4x", "x64x64x4x", "64x4x64x"):
        assert shape not in text, shape


def test_trains_through_initialize_and_train_batch():
    import deepspeed_tpu as ds

    model = SambaYForCausalLM(SambaYConfig.tiny(remat=True,
                                                report_ssm_decay=True))
    batch = {"input_ids": IDS[:, :32].repeat(4, 0),
             "labels": IDS[:, :32].repeat(4, 0)}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}}},
        partition_rules=SambaYForCausalLM.partition_rules(model.config))
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert engine.perf.programs.program("train_step").compiles == 1
    assert engine.registry.snapshot()["ssm_chunk_decay_max"] > 0
