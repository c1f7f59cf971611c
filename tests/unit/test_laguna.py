"""Laguna-XS.2's decoder (``models/laguna.py``: a GQA / sparse-expert stack
whose layer kinds differ in head count, behind a leading dense layer) at tiny
sizes in float32 on the CPU: the system against the benchmark's plain
reference (``benchmark/reference/laguna.py``, written from the catalog row's
equations, not from the system) at ONE CHIP'S SHARE — logits, loss and the
gradient of every parameter, under ``attention_impl="xla"`` and under the
flash kernels in interpret mode; the half-rotated YaRN table against a table
written out pair by pair; two head counts in one scan at two periods; the
eight shares adding up to the uncut layer; what the stack offers its remat
policy; the seeding helper the three pattern models share; the training path
through ``deepspeed_tpu.initialize``; what is not built raising."""

import dataclasses
import functools
import math
import re

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import common
from deepspeed_tpu.models import laguna, layers, mellum, qwen3_next
from deepspeed_tpu.models.laguna import (DENSE, FULL, WINDOW, LagunaBlock,
                                         LagunaConfig, LagunaForCausalLM)
from deepspeed_tpu.ops.pallas import REMAT_ATTN_OUT, REMAT_MLP, REMAT_QKV
from deepspeed_tpu.parallel import build_mesh

REF = common.load_file_module("reference", "laguna")
#: experts 2..4 of the router's 8
SHARE = dict(num_local_experts=2, router_experts=8, first_expert=2)
T = 48
IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, T)))


def tiny(**over):
    """One dense layer and two periods of three 16-window layers of 6 heads
    and one full layer of 4, over 2 key-value heads of 16 columns, the full
    layers' first 8 under YaRN (4 pairs: low 0, high 2)."""
    return LagunaConfig.tiny(**{**dict(
        sliding_window=16, yarn_original_max_position_embeddings=64,
        yarn_attention_factor=1.25, max_position_embeddings=512,
        report_expert_load=True, **SHARE), **over})


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def seeded(cfg, seed=3, ids=IDS):
    """(model, params): the model's own init with the norms' scales moved
    off one, so that leaving one out shows."""
    model = LagunaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 128))
    return model, jax.tree_util.tree_map_with_path(
        lambda kp, p: p + 0.3 * jax.random.normal(next(keys), p.shape)
        if str(getattr(kp[-1], "key", "")) == "scale" else p, params)


def paths(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from paths(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}"


def leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def loss_and_grads(model, params):
    return jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS),
        has_aux=True))(params)


@pytest.fixture(scope="module")
def share():
    cfg = tiny()
    model, params = seeded(cfg)
    sizes = sizes_of(cfg)
    (loss, named), grads = loss_and_grads(model, params)

    def reference(p):
        hidden, rows = zip(*(REF.hidden_states(p, sizes, IDS[b])
                             for b in range(IDS.shape[0])))
        return REF.loss(p, sizes, np.asarray(IDS)), sum(rows)

    # the reference's side of every comparison: ONE program (run operation
    # by operation it was some thousand, compiled by every worker that drew
    # a case of this file)
    (ref_loss, ref_rows), ref_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return dict(cfg=cfg, model=model, params=params, sizes=sizes, loss=loss,
                named=named, grads=grads, ref_loss=ref_loss,
                ref_rows=ref_rows, ref_grads=ref_grads)


# -- the system against the reference ----------------------------------------

def test_logits_match_the_reference(share):
    # (a forward program of its own, as it was: taken beside the loss from
    # the gradient's program, three logits move by 4e-5, twice the tolerance)
    got = jax.jit(lambda p: share["model"].apply({"params": p}, IDS))(
        share["params"])
    for b in range(IDS.shape[0]):
        hidden, _ = REF.hidden_states(share["params"], share["sizes"], IDS[b])
        np.testing.assert_allclose(
            got[b], REF.logits(share["params"], hidden), rtol=2e-5,
            atol=2e-5)


def test_loss_and_gauges_match_the_reference(share):
    np.testing.assert_allclose(share["loss"], share["ref_loss"], rtol=1e-5)
    rows = share["ref_rows"]
    # pairs routed to the held experts / tokens x top-k x held / routed,
    # summed over the 8 EXPERT layers (the dense layer routes nothing)
    named = share["named"]
    np.testing.assert_allclose(
        named["moe_held_rows_over_expected"],
        float(jnp.sum(rows)) / (8 * IDS.size * 2 * 2 / 8), rtol=1e-6)
    np.testing.assert_allclose(
        named["moe_rows_max_over_mean"],
        float(jnp.max(rows) / jnp.mean(rows)), rtol=1e-6)
    # sigmoid of a seeded projection of a normed input: about a half
    assert sorted(named) == ["attn_gate_mean", "moe_held_rows_over_expected",
                             "moe_rows_max_over_mean"]
    assert 0.45 < float(named["attn_gate_mean"]) < 0.55


def test_gradient_of_every_parameter_matches_the_reference(share):
    """Every parameter kind of the dense layer and of every position of the
    period, by norm and by value: attention with its gate's projection at
    either head count, the dense and the shared SwiGLU, router and held
    experts, the block norms, the table, the final norm and the head."""
    names = sorted(paths(share["grads"]))
    assert len(names) == 10 + 4 * 14 + 3
    for name in names:
        want, got = leaf(share["ref_grads"], name), leaf(share["grads"], name)
        assert got.shape[0] == 2 or "periods" not in name   # two periods
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(
            jnp.linalg.norm(got), jnp.linalg.norm(want), rtol=1e-3,
            err_msg=name)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6,
                                   err_msg=name)


def test_two_head_counts_stand_in_one_scan_at_two_periods(share):
    """Positions 0-2 of a period are sliding layers of 6 heads, position 3
    and the dense layer full ones of 4: q, the gate and o of another shape
    a kind, GQA groups of 3 and of 2 in one step; the reference with either
    kind's count on every layer is another model."""
    tree = jax.tree_util.tree_map(lambda a: a.shape, share["params"])
    period = tree["model"]["periods"]
    for i, heads in ((0, 6), (1, 6), (2, 6), (3, 4)):
        attn = period[f"block_{i}"]["self_attn"]
        assert attn["q_proj"]["kernel"] == (2, 32, heads * 16)
        assert attn["g_proj"]["kernel"] == (2, 32, heads)
        assert attn["o_proj"]["kernel"] == (2, heads * 16, 32)
        assert attn["k_proj"]["kernel"] == (2, 32, 2 * 16)
    dense = tree["model"]["leading"]["block_0"]
    assert dense["self_attn"]["q_proj"]["kernel"] == (32, 4 * 16)
    assert dense["mlp"]["gate_proj"]["kernel"] == (32, 64)
    assert "block_sparse_moe" not in dense and "shared_expert" not in dense
    cfg = share["cfg"]
    assert laguna.period_kinds(cfg) == (WINDOW, WINDOW, WINDOW, FULL)
    assert [laguna.layer_kind(cfg, l) for l in range(9)] == \
        [DENSE] + [WINDOW, WINDOW, WINDOW, FULL] * 2
    kinds = {k: laguna.kind_config(cfg, k) for k in (DENSE, WINDOW, FULL)}
    assert [(c.num_attention_heads, c.sliding_window, c.rotary_dim)
            for c in kinds.values()] == [(4, None, 8), (6, 16, None),
                                         (4, None, 8)]


def test_the_kinds_differ_where_the_reference_says(share, monkeypatch):
    """The reference with the full layers' table without its blend or its
    factor or over every column, the sliding layers under the full layers'
    theta, another window or no routed scale, is another model: each shows
    at this size; with every layer read as one kind it cannot even read the
    tree, whose projections have their kind's head count."""
    params, sizes = share["params"], share["sizes"]
    want, _ = REF.hidden_states(params, sizes, IDS[0])
    differs = lambda s: float(jnp.abs(
        REF.hidden_states(params, s, IDS[0])[0] - want).max()) > 1e-2
    assert differs({**sizes, "yarn_factor": None})
    assert differs({**sizes, "yarn_attention_factor": 1.0})
    assert differs({**sizes, "sliding_rope_theta": sizes["rope_theta"]})
    assert differs({**sizes, "routed_scaling_factor": 1.0})
    assert differs({**sizes, "sliding_window": 8})
    assert differs({**sizes, "partial_rotary_factor": 1.0})
    for every_layer in (True, False):
        monkeypatch.setattr(REF, "is_full", lambda s, l: every_layer)
        with pytest.raises(TypeError):      # another head count: no such q
            REF.hidden_states(params, sizes, IDS[0])


def test_flash_kernels_in_interpret_mode_match_the_reference(share,
                                                             monkeypatch):
    """``attention_impl="flash"`` forced to the Pallas kernels (interpret
    mode; the CPU's public entry would take the einsum reference): 6 heads
    under the window's tile table and 4 under the causal one, tiles of 16,
    loss and every gradient against the reference's."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, force_pallas=True))
    model = LagunaForCausalLM(dataclasses.replace(
        share["cfg"], attention_impl="flash", flash_block_q=16,
        flash_block_k=16))
    fn = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, IDS, labels=IDS), has_aux=True)
    assert "name=ds_flash_fwd" in str(jax.make_jaxpr(fn)(share["params"]))
    (loss, _), grads = jax.jit(fn)(share["params"])
    np.testing.assert_allclose(loss, share["loss"], rtol=1e-5)
    for name in sorted(paths(grads)):
        np.testing.assert_allclose(
            leaf(grads, name), leaf(share["ref_grads"], name), rtol=2e-3,
            atol=2e-6, err_msg=name)


# -- the half-rotated YaRN table, pair by pair ---------------------------------

def test_half_rotated_yarn_table_pair_by_pair():
    """A full layer's head of 16 columns: YaRN over the FIRST 8 (4 pairs,
    rotate-half over them: column i with column i + 4), theta 100, factor 4
    over an original length of 64, beta 4 and 1, 1.25 on cos and sin; the
    last 8 pass unrotated and unscaled. corr(4) = 8 ln(64 / (8 pi)) / (2 ln
    100) = 0.81 and corr(1) = 2.02: low 0, high 3, so pair i's ramp is i / 3
    and its frequency 100^(-i/4) (1 - ramp + ramp / 4)."""
    cfg = tiny()
    corr = lambda n: 8 * math.log(64 / (2 * math.pi * n)) / (2 * math.log(100))
    assert (math.floor(corr(4.0)), math.ceil(corr(1.0))) == (0, 3)
    freq = [100 ** (-i / 4) * ((1 - i / 3) + (i / 3) / 4) for i in range(4)]
    assert freq[0] == 1.0 and freq[3] == pytest.approx(100 ** -0.75 / 4)
    positions = jnp.arange(T)[None]
    cos, sin = laguna.rope_tables(cfg, positions, jnp.float32)[FULL]
    assert cos.shape == sin.shape == (1, T, 4)
    for t in (0, 1, 17, T - 1):
        for i in range(4):
            assert float(cos[0, t, i]) == pytest.approx(
                1.25 * math.cos(t * freq[i]), abs=1e-5)
            assert float(sin[0, t, i]) == pytest.approx(
                1.25 * math.sin(t * freq[i]), abs=1e-5)
    # the dense layer attends as a full layer; the sliding table is plain
    # RoPE at its own theta over all 8 pairs
    assert laguna.rope_tables(cfg, positions, jnp.float32)[DENSE] is not None
    wcos, _ = laguna.rope_tables(cfg, positions, jnp.float32)[WINDOW]
    assert wcos.shape == (1, T, 8)
    assert float(wcos[0, 5, 3]) == pytest.approx(
        math.cos(5 * 50.0 ** (-3 / 8)), abs=1e-6)
    # applied as the attention applies it
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, 1, 16))
    got = layers.apply_rotary_partial(x, cos, sin, 8)[0, :, 0]
    x = x[0, :, 0]
    np.testing.assert_array_equal(got[:, 8:], x[:, 8:])
    for t in (1, 17):
        for i in range(4):
            c, s = 1.25 * math.cos(t * freq[i]), 1.25 * math.sin(t * freq[i])
            assert float(got[t, i]) == pytest.approx(
                float(x[t, i]) * c - float(x[t, i + 4]) * s, abs=1e-5)
            assert float(got[t, i + 4]) == pytest.approx(
                float(x[t, i + 4]) * c + float(x[t, i]) * s, abs=1e-5)
    # the reference's own table is the same numbers
    ref_freq, factor = REF.rotary_table(sizes_of(cfg), True)
    np.testing.assert_allclose(ref_freq, freq, rtol=1e-6)
    assert factor == 1.25


def test_published_yarn_range_over_the_rotated_half():
    """d 64, theta 500,000, original length 4,096: corr(64) = 5.65 and
    corr(1) = 15.79, so of the 32 rotated pairs the ramp runs from 5 to 16;
    over all 128 columns it would run from 11 to 32."""
    cfg = LagunaConfig.laguna_xs2()
    assert laguna.kind_config(cfg, FULL).rotary_dim == 64
    assert laguna.kind_config(cfg, WINDOW).rotary_dim is None
    _, (low, high) = layers.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16)
    _, (low, high) = layers.yarn_inv_freq(128, 500000.0, 64.0, 4096, 64.0,
                                          1.0)
    assert (low, high) == (11, 32)
    assert 0.1 * math.log(64) + 1 == pytest.approx(cfg.yarn_attention_factor)


# -- the share, tied to the model ------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """The floor's arithmetic, made a test: eight chips each hold one of a
    sliding layer's eight experts; attention, its gate, the router and the
    shared expert are whole on every chip. The parts the eight shares'
    layers add, with what every chip computes alike counted once (a share
    whose held expert adds nothing), sum to the reference's layer with all
    eight held."""
    full = laguna.kind_config(tiny(num_local_experts=8, router_experts=8,
                                   first_expert=0), WINDOW)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    tables = laguna.rope_tables(full, jnp.broadcast_to(
        jnp.arange(T)[None], (2, T)), jnp.float32)[WINDOW]
    p = jax.jit(LagunaBlock(full).init)(jax.random.PRNGKey(2), x,
                                        *tables)["params"]
    whole = jnp.stack([REF._layer(
        x[b], p, REF.dense._static(sizes_of(full)), False, False)[0]
        for b in range(2)])

    def layer_of(s, w2=None):
        cfg = dataclasses.replace(full, num_local_experts=1, first_expert=s)
        moe = {**p["block_sparse_moe"], **{
            w: p["block_sparse_moe"][w][s:s + 1] for w in ("w1", "w2", "w3")}}
        if w2 is not None:
            moe["w2"] = w2 * moe["w2"]
        out, _, _, extra = LagunaBlock(cfg).apply(
            {"params": {**p, "block_sparse_moe": moe}}, x, *tables)
        assert 0.4 < float(extra["attn_gate"]) < 0.6
        return out

    alike = layer_of(0, w2=0.0)
    parts = alike + sum(layer_of(s) - alike for s in range(8))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=2e-5)
    assert float(jnp.abs(whole - alike).max()) > 1e-2    # the experts show
    assert float(jnp.abs(alike - x).max()) > 1e-2


def test_the_published_share_takes_the_compact_buffer():
    """32 of 256 held at 8,192 tokens and top-8: 16,384 compact rows for
    65,536; the parameters ISSUE 63 counts, layer by layer."""
    from deepspeed_tpu.models.mixtral import _compact_rows, _extra_stats

    assert _compact_rows(8192 * 8, 32, 256) == 16384
    cfg = LagunaConfig.laguna_xs2(
        num_local_experts=32, router_experts=256, num_hidden_layers=5,
        vocab_size=12544)
    assert _extra_stats(cfg, 8192 * 8) == ["compact_hit"]
    assert (cfg.head_dim, cfg.expert_width, cfg.router_width) == (128, 512,
                                                                  256)
    count = lambda cfg: sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(LagunaForCausalLM(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]))
    assert count(cfg) == 691623936
    whole = count(LagunaConfig.laguna_xs2(num_hidden_layers=37))
    # 37 = 1 + 9 x 4 layers are built; the three trailing sliding layers of
    # the published 40 are three more of a period's first
    sliding = 2048 * 128 * (64 + 8 + 8 + 64) + 2048 * 64 + 2 * 2048 \
        + 2048 * 256 + 3 * 2048 * 512 * 257
    assert round((whole + 3 * sliding) / 1e9, 2) == 33.44


# -- what the stack offers its remat policy ----------------------------------------

def test_remat_offers_count_each_kinds_own_heads():
    """The published cut: three sliding layers of 64 + 16 heads and two full
    ones (the dense layer among them) of 48 + 16, 128 wide, 8,192 bf16
    tokens; the dense SwiGLU's and the four shared experts' gate and up
    products under one name; the output projection of all five layers."""
    cfg = LagunaConfig.laguna_xs2(
        num_local_experts=32, router_experts=256, num_hidden_layers=5)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    offers = dict(laguna.remat_offers(cfg, x))
    assert offers[REMAT_QKV] == (3 * 80 + 2 * 64) * 128 * 8192 * 2 \
        == 771751936
    assert offers[REMAT_ATTN_OUT] == 5 * 2048 * 8192 * 2
    assert offers[REMAT_MLP] == 2 * (8192 + 4 * 512) * 8192 * 2
    assert list(offers)[:3] == [REMAT_ATTN_OUT, REMAT_MLP, REMAT_QKV]
    assert len(offers) == 5                  # and the expert layers' two
    one_count = dataclasses.replace(cfg, sliding_num_attention_heads=48)
    assert dict(laguna.remat_offers(one_count, x))[REMAT_QKV] \
        == 5 * 64 * 128 * 8192 * 2


# -- the seeding helper of the three pattern models --------------------------------

def _inline_embed(cfg, input_ids):
    """``embed_tokens`` as ``mellum.py`` and ``qwen3_next.py`` each wrote it
    out before the helper."""
    seeded = {} if cfg.embed_init_std is None else {
        "embedding_init": nn.initializers.normal(cfg.embed_init_std)}
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                    param_dtype=jnp.float32, **seeded)(input_ids)


def _inline_head(cfg, hidden):
    init = {} if cfg.head_init_std is None else {
        "kernel_init": nn.initializers.normal(cfg.head_init_std)}
    return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                    param_dtype=jnp.float32, **init)(hidden)


@pytest.mark.parametrize("module,model", [
    (mellum, lambda: mellum.MellumForCausalLM(mellum.MellumConfig.tiny(
        embed_init_std=1.0, head_init_std=0.0002, remat=True,
        num_hidden_layers=4, full_attention_period=2))),
    (qwen3_next, lambda: qwen3_next.Qwen3NextForCausalLM(
        qwen3_next.Qwen3NextConfig.tiny(
            embed_init_std=1.0, head_init_std=0.0002, remat=True,
            num_hidden_layers=4, full_attention_interval=2)))],
    ids=["mellum", "qwen3_next"])
def test_the_seeding_helper_keeps_seeded_weights_and_lowered_steps(
        module, model, monkeypatch):
    """``layers.seeded_embed_tokens`` / ``seeded_lm_head`` against the code
    each file held before them (written out above), on the tiny presets (two
    periods of two kinds) at the scales mellum2 8k and qwen3-next 8k state:
    every seeded weight bit for bit, and the gradient step's lowered text
    byte for byte."""
    ids = jnp.zeros((1, 16), jnp.int32)

    def built():
        m = model()
        params = jax.jit(m.init)(jax.random.PRNGKey(11), ids)["params"]
        loss = lambda p: m.apply({"params": p}, ids, labels=ids)
        return params, jax.jit(jax.grad(loss)).lower(params).as_text()

    params, text = built()
    monkeypatch.setattr(module, "seeded_embed_tokens", _inline_embed)
    monkeypatch.setattr(module, "seeded_lm_head", _inline_head)
    before, text_before = built()
    assert text == text_before
    jax.tree_util.tree_map(np.testing.assert_array_equal, params, before)
    assert float(jnp.std(params["model"]["embed_tokens"]["embedding"])) \
        == pytest.approx(1.0, rel=0.05)
    assert float(jnp.std(params["lm_head"]["kernel"])) \
        == pytest.approx(0.0002, rel=0.05)


# -- through the engine ------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = tiny(remat=True, router_trainable=False)
    model = LagunaForCausalLM(cfg)
    batch = {"input_ids": np.asarray(IDS), "labels": np.asarray(IDS)}
    # one device, as the benchmark's cell has it
    engine, *_ = ds.initialize(
        mesh=build_mesh(devices=jax.devices()[:1]),
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        partition_rules=LagunaForCausalLM.partition_rules(cfg),
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
    return cfg, engine, batch


def test_trains_through_initialize_and_names_its_scopes(engine):
    """``deepspeed_tpu.initialize`` -> ``train_batch``: the loss falls, the
    optimizer never moves the frozen router, one compile, the named scalars
    become gauges; the lowered step names the three kinds' outer scopes
    around every inner name, the gate's product and the shared expert."""
    cfg, engine, batch = engine
    gate = lambda: np.asarray(engine.state.params["model"]["periods"][
        "block_3"]["block_sparse_moe"]["gate"]["kernel"])
    before = gate()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(gate(), before)
    assert engine.perf.programs.program("train_step").compiles == 1
    found = engine.registry.snapshot()
    assert {"attn_gate_mean", "moe_rows_max_over_mean",
            "moe_held_rows_over_expected"} <= set(found)
    assert 0.4 < found["attn_gate_mean"] < 0.6
    text = engine._train_step.lower(
        engine.state, engine._shape_batch(batch),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("ds.rope_tables", "ds.layer_stack", "ds.layer_dense",
                  "ds.layer_window", "ds.layer_full", "ds.attention",
                  "ds.attn_proj", "ds.attn_gate", "ds.mlp", "ds.moe_router",
                  "ds.moe_experts", "ds.moe_shared", "ds.norm",
                  "ds.residual", "ds.lm_head_loss"):
        assert re.search(re.escape(scope) + r"\b", text), scope
    # an inner name under its kind's outer scope, in the forward pass and
    # in what the backward pass replays
    assert re.search(r"ds\.layer_dense[^\"]*ds\.mlp", text)
    assert re.search(r"ds\.layer_full[^\"]*ds\.attn_gate", text)
    assert re.search(r"ds\.layer_window[^\"]*ds\.moe_shared", text)
    assert re.search(
        r"ds\.layer_dense[^\"]*rematted_computation[^\"]*ds\.attn_proj", text)
    assert not re.search(r"ds\.layer_dense[^\"]*ds\.moe_", text)


def test_partition_rules_and_frozen_parameters_cover_the_new_names():
    cfg = tiny(router_trainable=False)
    rules = LagunaForCausalLM.partition_rules(cfg)
    shapes = jax.eval_shape(LagunaForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), IDS)["params"]
    resolved = {}
    for name in paths(shapes):
        spec = next((s for pattern, s in rules if re.search(pattern, name)),
                    None)
        if spec is not None:
            spec = spec(None) if callable(spec) else spec
            assert len(spec) == leaf(shapes, name).ndim, name
        resolved[name] = spec
    # every matrix but the router's has a rule; norms are whole
    bare = sorted(n for n, s in resolved.items() if s is None)
    assert all(n.endswith("/scale") or n.endswith("gate/kernel")
               for n in bare), bare
    block = "model/periods/block_0/"
    assert tuple(resolved[block + "self_attn/g_proj/kernel"]) == \
        (None, None, "model")
    assert tuple(resolved[block + "shared_expert/down_proj/kernel"]) == \
        (None, "model", None)
    assert tuple(resolved["model/leading/block_0/mlp/up_proj/kernel"]) == \
        (None, "model")
    assert tuple(resolved["model/leading/block_0/self_attn/o_proj/kernel"]) \
        == ("model", None)
    frozen = LagunaForCausalLM.frozen_parameters(cfg)
    assert len([n for n in resolved if re.search(frozen[0], n)]) == 4
    assert LagunaForCausalLM.frozen_parameters(tiny()) == []


# -- what is not built -------------------------------------------------------

def test_what_is_not_built_raises():
    """(Each raised before any arithmetic: parameters by shape.)"""
    model = LagunaForCausalLM(tiny())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), IDS)["params"]
    with pytest.raises(NotImplementedError, match="training"):
        model.apply({"params": params}, IDS, cache={}, cache_index=0)
    with pytest.raises(NotImplementedError, match="packed"):
        model.apply({"params": params}, IDS, attention_mask=jnp.ones_like(IDS))
    init = lambda cfg: jax.eval_shape(LagunaForCausalLM(cfg).init,
                                      jax.random.PRNGKey(0), IDS)
    # the published 40 layers end in three trailing sliding layers
    with pytest.raises(ValueError, match="trailing"):
        init(tiny(num_hidden_layers=8))
    with pytest.raises(ValueError, match="trailing"):
        init(LagunaConfig.laguna_xs2())
    with pytest.raises(ValueError, match="key-value head"):
        init(tiny(sliding_num_attention_heads=5))
    with pytest.raises(NotImplementedError, match="selection"):
        init(tiny(sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                                 q_chunk_size=16, kv_chunk_size=16, topk=8)))
    with pytest.raises(NotImplementedError, match="held share"):
        init(tiny(report_expert_load=True, router_experts=None,
                  num_local_experts=4))
    with pytest.raises(ValueError, match="router"):
        init(tiny(first_expert=7))
    with pytest.raises(NotImplementedError, match="chunked"):
        init(tiny(loss_chunk=16))
