"""``models/qwen3_next.py`` against the plain reference
(``benchmark/reference/qwen3_next.py``) at tiny sizes in float32 on the CPU:
logits, loss and every parameter's gradient (the delta-rule mixer in XLA and
through ``ops/pallas/gdn_mix.py``'s kernels); the chunked gated delta rule
against the token-by-token recurrence (chunks that do and do not divide the
length, decays of 20 nats a token, forward and backward); the gated attention
against a masked softmax with 64 of 256 columns rotated; the sixteen shares of
an expert layer against the uncut layer; and the interface the engine sees."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmark import common
from benchmark.reference import qwen3_next as ref
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.models.mixtral import MixtralSparseMoeBlock
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                             Qwen3NextForCausalLM)

HIGHEST = jax.default_matmul_precision("highest")


def sizes_of(cfg):
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def seeded(model, ids, seed=0, jitter=0.2):
    """The model's own init with every vector (norm weights seeded at 0 or
    1, ``A_log``, ``dt_bias``) moved off its seed, so that a test tells
    ``(1 + w)`` from ``w`` and a scale from none."""
    @jax.jit        # one program, not an operation a leaf
    def make(key, jitter_key):
        params = model.init(key, ids)["params"]
        leaves, tree = jax.tree_util.tree_flatten(params)
        keys = jax.random.split(jitter_key, len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            x + jitter * jax.random.normal(k, x.shape) if x.ndim <= 2 else x
            for x, k in zip(leaves, keys)])

    return make(jax.random.PRNGKey(seed), jax.random.PRNGKey(seed + 1))


@pytest.fixture(scope="module")
def case():
    cfg = Qwen3NextConfig.tiny(router_experts=16, first_expert=4,
                               report_expert_load=True)
    model = Qwen3NextForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 37)))
    params = seeded(model, ids)
    sizes = sizes_of(cfg)

    @jax.jit        # the reference's side of both comparisons: ONE program,
    def reference(p):   # whichever path the mixer takes
        def loss(p):
            logits = [ref.logits(p, ref.hidden_states(p, sizes, ids[b])[0])
                      for b in range(ids.shape[0])]
            return ref.loss(p, sizes, np.asarray(ids)), logits

        with HIGHEST:
            return jax.value_and_grad(loss, has_aux=True)(p)

    (loss, logits), grads = reference(params)
    return cfg, model, ids, params, dict(loss=loss, logits=logits,
                                         grads=grads, system={})


def loss_logits_and_gradients(case, mixer_path):
    """The system's side: ONE jitted ``value_and_grad`` with the logits
    beside the loss, not a forward, a loss and a gradient program, and run
    once a path for the two cases that read it."""
    _, model, ids, params, memo = case

    def loss(p):
        loss, named = model.apply({"params": p}, ids, labels=ids)
        return loss, (named, model.apply({"params": p}, ids))

    if mixer_path not in memo["system"]:
        with HIGHEST:
            memo["system"][mixer_path] = jax.jit(jax.value_and_grad(
                loss, has_aux=True))(params)
    return memo["system"][mixer_path]


# -- the model against the reference ------------------------------------------

@pytest.fixture(params=["xla", "kernels"])
def mixer_path(request, monkeypatch):
    """The delta-rule mixer around its rule as the CPU runs it (XLA), and
    with ``ops/pallas/gdn_mix.py``'s kernels forced on (interpret mode: the
    chooser answered with a tiling, three tiles over the 37 positions)."""
    if request.param == "kernels":
        from deepspeed_tpu.ops.pallas import gdn_mix

        monkeypatch.setattr(qn, "_mix_tiling", lambda *a: gdn_mix.Tiling(16))
    return request.param


def test_logits_and_loss_are_the_references(case, mixer_path):
    cfg, model, ids, params, want = case
    (loss, (named, logits)), _ = loss_logits_and_gradients(case, mixer_path)
    for b in range(ids.shape[0]):
        assert np.abs(np.asarray(logits[b] - want["logits"][b])).max() \
            < 2e-4 * float(jnp.abs(want["logits"][b]).max())
    assert float(loss) == pytest.approx(float(want["loss"]), rel=2e-6)
    assert sorted(named) == ["gdn_chunk_decay_max",
                             "moe_held_rows_over_expected",
                             "moe_rows_max_over_mean"]


def test_every_parameters_gradient_is_the_references(case, mixer_path):
    """Each kind by name: ``A_log``, ``dt_bias``, the convolution, both
    gates (the full layer's inside ``q_proj``, the shared expert's), the
    zero-centred weights, the delta rule's plain output scale, the frozen
    router (its gradient exists; the optimizer never applies it)."""
    cfg, model, ids, params, want = case
    _, got = loss_logits_and_gradients(case, mixer_path)
    want = want["grads"]
    flat = lambda t: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    got, want = flat(got), flat(want)
    assert set(got) == set(want)
    for kind in ("A_log", "dt_bias", "conv1d", "in_proj_ba", "in_proj_qkvz",
                 "norm_scale", "q_proj", "q_norm", "k_norm",
                 "shared_expert_gate", "input_layernorm", "gate']['kernel",
                 "w1", "embed_tokens", "lm_head", "['norm']"):
        assert any(kind in k for k in got), kind
    for k in got:
        scale = np.abs(want[k]).max()
        assert scale > 0, k
        assert np.abs(got[k] - want[k]).max() < 2e-3 * scale, k


# -- the chunked rule against the recurrence ---------------------------------

def rule_inputs(T, H=3, dk=8, dv=8, decay=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, T, H, dk))) / dk ** 0.5
    k = unit(jax.random.normal(ks[1], (1, T, H, dk)))
    v = jax.random.normal(ks[2], (1, T, H, dv))
    g = -decay * jax.random.uniform(ks[3], (1, T, H), minval=0.05, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, H)))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    return ref.delta_rule(q[0], k[0], v[0], g[0], beta[0])[None]


@pytest.mark.parametrize("T,chunk", [(32, 8), (37, 8), (5, 8), (64, 64),
                                     (100, 64)])
def test_chunked_rule_is_the_recurrence(T, chunk):
    x = rule_inputs(T)
    with HIGHEST:
        got, decay = jax.jit(functools.partial(qn.gated_delta_rule,
                                               chunk=chunk))(*x)
        want = jax.jit(recurrence)(*x)
    assert got.shape == want.shape
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    g = np.pad(np.asarray(x[3]), ((0, 0), (0, (-T) % chunk), (0, 0)))
    assert float(decay) == pytest.approx(
        -g.reshape(1, -1, chunk, g.shape[-1]).sum(2).min(), rel=1e-5)


@pytest.mark.parametrize("T,chunk", [(32, 8), (37, 8)])
def test_chunked_rule_has_the_recurrences_gradients(T, chunk):
    x = rule_inputs(T, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (1, T, 3, 8))
    with HIGHEST:
        got = jax.jit(jax.grad(lambda *a: jnp.sum(
            w * qn.gated_delta_rule(*a, chunk=chunk)[0]),
            argnums=range(5)))(*x)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(w * recurrence(*a)),
                                argnums=range(5)))(*x)
    for a, b in zip(got, want):
        assert np.abs(np.asarray(a - b)).max() < 1e-4 * max(
            1.0, float(jnp.abs(b).max()))


@pytest.mark.parametrize("T,chunk", [(64, 16), (50, 16)])
def test_twenty_nats_a_token_stay_finite_and_equal(T, chunk):
    """``A`` up to 16 gives -20 nats a token and -320 a chunk of 16 (-1,300
    at the published 64): ``exp(-gamma)`` would overflow float32 from 88.
    Every exponent here is a difference that is <= 0: forward and backward
    are finite and the recurrence's."""
    q, k, v, g, beta = rule_inputs(T, decay=20.0, seed=2)
    g = jnp.minimum(g, -15.0 * (jnp.arange(3) > 0))     # head 0 decays little
    w = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    with HIGHEST:
        (_, (got, decay)), dgot = jax.jit(jax.value_and_grad(
            lambda *a: (lambda o, d: (jnp.sum(w * o), (o, d)))(
                *qn.gated_delta_rule(*a, chunk=chunk)),
            argnums=range(5), has_aux=True))(q, k, v, g, beta)
        (_, want), dwant = jax.jit(jax.value_and_grad(
            lambda *a: (lambda o: (jnp.sum(w * o), o))(recurrence(*a)),
            argnums=range(5), has_aux=True))(q, k, v, g, beta)
    assert float(decay) > 88 * 2
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    for a, b in zip(dgot, dwant):
        assert np.isfinite(np.asarray(a)).all()
        assert np.abs(np.asarray(a - b)).max() < 1e-4 * max(
            1.0, float(jnp.abs(b).max()))


def test_alike_keys_do_not_break_the_solve():
    """Every key the same, ``beta`` 1, no decay: ``I + A`` is the all-ones
    lower triangle, whose inverse series holds terms of ``C(63, 31)``; the
    substitution stays exact (each token's correction erases the last)."""
    T, H, d = 64, 1, 8
    k = jnp.tile(jnp.eye(d)[0], (1, T, H, 1))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, H, d))
    zeros, ones = jnp.zeros((1, T, H)), jnp.ones((1, T, H))
    with HIGHEST:
        got, _ = qn.gated_delta_rule(k, k, v, zeros, ones, chunk=64)
    # S = k v_t^T after token t: o_t = v_t
    assert np.abs(np.asarray(got - v)).max() < 1e-5


# -- the gated attention ------------------------------------------------------

def test_gated_attention_rotates_64_of_256_columns():
    """At the published head width, against a masked softmax: the first 64
    columns of each head rotate, the gate is the second half of each head's
    ``q_proj`` columns, the head norms are zero-centred."""
    cfg = Qwen3NextConfig.tiny(hidden_size=32, num_attention_heads=2,
                               num_key_value_heads=1, head_dim_override=256,
                               rope_theta=1e7)
    assert cfg.rotary_dim == 64
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 32))
    mixer = qn.GatedAttention(cfg)
    pos = jnp.arange(24)[None]
    cos, sin = qn.rotary_embedding(pos, 64, cfg.rope_theta)
    params = mixer.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape) if a.ndim == 1 else a,
        params)
    sizes = sizes_of(cfg)
    with HIGHEST:
        got = mixer.apply({"params": params}, x, cos, sin)[0]
        want = ref.gated_attention(x[0], params, sizes)
        # the same with every column rotated, or no gate, is another result
        cos_all, sin_all = qn.rotary_embedding(pos, 256, cfg.rope_theta)
        rotated = qn.GatedAttention(dataclasses.replace(
            cfg, partial_rotary_factor=1.0)).apply(
                {"params": params}, x, cos_all, sin_all)[0]
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * max(
        1.0, float(jnp.abs(want).max()))
    assert np.abs(np.asarray(rotated - want)).max() > 1e-2


# -- the share ----------------------------------------------------------------

def test_sixteen_shares_and_the_shared_expert_once_make_the_whole_layer():
    """An expert layer of 32 experts over sixteen chips, two held each: the
    routed parts the sixteen shares compute (``MixtralSparseMoeBlock`` under
    ``first_expert`` 0, 2, ..) plus the shared expert, which every chip
    computes alike, counted ONCE, are the uncut reference's whole layer."""
    E, G, K = 32, 2, 4
    cfg = Qwen3NextConfig.tiny(num_local_experts=E, num_experts_per_tok=K)
    whole = sizes_of(cfg)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 40, cfg.hidden_size))
    moe = MixtralSparseMoeBlock(cfg).init(jax.random.PRNGKey(1), h)["params"]
    shared = qn.SharedExpert(cfg).init(jax.random.PRNGKey(2), h)["params"]
    with HIGHEST:
        want = ref.held_experts(h[0], moe, whole)[0] \
            + ref.shared_expert(h[0], shared)
        parts, rows = 0, 0
        for chip in range(E // G):
            share = dataclasses.replace(cfg, num_local_experts=G,
                                        router_experts=E,
                                        first_expert=chip * G)
            held = {"gate": moe["gate"], **{
                w: moe[w][chip * G:(chip + 1) * G] for w in ("w1", "w2",
                                                             "w3")}}
            out, _, _, r = MixtralSparseMoeBlock(share).apply(
                {"params": held}, h)
            parts, rows = parts + out[0], rows + int(r.sum())
            # and the reference's share is the system's
            assert np.abs(np.asarray(out[0] - ref.held_experts(
                h[0], held, sizes_of(share))[0])).max() < 1e-5
        got = parts + qn.SharedExpert(cfg).apply({"params": shared}, h)[0]
    assert rows == 40 * K                   # every pair computed once
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# -- the interface the engine sees -------------------------------------------

def test_a_cache_or_a_padding_mask_raises():
    """(Raised before any arithmetic: parameters by shape, no fixture.)"""
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny())
    ids = jnp.zeros((2, 37), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError, match="training only"):
        model.apply({"params": params}, ids, cache={})
    with pytest.raises(NotImplementedError, match="packed sequences"):
        model.apply({"params": params}, ids, attention_mask=jnp.ones_like(
            ids))


def test_layers_are_whole_periods():
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny(num_hidden_layers=6))
    with pytest.raises(ValueError, match="whole"):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))
    assert qn.period_kinds(Qwen3NextConfig.tiny()) == (
        qn.GDN, qn.GDN, qn.GDN, qn.FULL)


def test_published_shapes_and_the_published_column_layout():
    """The parameter tree of the benchmark's configuration, one period at
    published widths with 32 experts held (shapes alone):
    a delta-rule mixer 33.7 M, a full mixer 27.3 M, router and shared expert
    4.2 M, an expert 3.15 M; and ``in_proj_qkvz``'s columns grouped by key
    head, so that published weights would load."""
    file = common.load_json("configs", "qwen3-next-80b-a3b.json")
    _, model = common.build_model(file, common.sizes_of(file, "train"))
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]["model"]["periods"]
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    gdn = shapes["block_0"]["linear_attn"]
    assert gdn["in_proj_qkvz"]["kernel"].shape == (1, 2048, 12288)
    assert gdn["in_proj_ba"]["kernel"].shape == (1, 2048, 64)
    assert gdn["conv1d"].shape == (1, 4, 8192)
    assert gdn["out_proj"]["kernel"].shape == (1, 4096, 2048)
    assert round(count(gdn) / 1e6, 1) == 33.7
    full = shapes["block_3"]["self_attn"]
    assert full["q_proj"]["kernel"].shape == (1, 2048, 16 * 2 * 256)
    assert round(count(full) / 1e6, 1) == 27.3
    block = shapes["block_3"]
    assert round((count(block["shared_expert"])
                  + count(block["block_sparse_moe"]["gate"])) / 1e6, 1) == 4.2
    assert block["block_sparse_moe"]["w1"].shape == (1, 32, 2048, 512)
    # the layout: key head 1's query is columns 768 .. 896 of the projection
    tiny = Qwen3NextConfig.tiny()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 32))
    mixer = qn.GatedDeltaNet(tiny)
    params = mixer.init(jax.random.PRNGKey(1), x)["params"]
    group = 2 * 8 + 2 * 2 * 8          # dk + dk + r dv + r dv
    kernel = params["in_proj_qkvz"]["kernel"]
    moved = kernel.at[:, group:group + 8].set(0.0)      # head 1's query
    out = lambda k: mixer.apply({"params": {**params, "in_proj_qkvz":
                                            {"kernel": k}}}, x)[0]
    # value heads 2 and 3 are key head 1's: out_proj's rows 16 .. 32
    full_out, zero_q = out(kernel), out(moved)
    only = params["out_proj"]["kernel"].at[16:32].set(0.0)
    rest = lambda k: mixer.apply({"params": {
        **params, "in_proj_qkvz": {"kernel": k},
        "out_proj": {"kernel": only}}}, x)[0]
    assert np.abs(np.asarray(full_out - zero_q)).max() > 1e-4
    assert np.abs(np.asarray(rest(kernel) - rest(moved))).max() < 1e-7


def test_the_engine_trains_it_and_freezes_the_router():
    cfg = Qwen3NextConfig.tiny(router_experts=16, first_expert=4,
                               report_expert_load=True,
                               router_trainable=False, remat=True,
                               embed_init_std=1.0, head_init_std=0.02)
    model = Qwen3NextForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 32)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}},
        partition_rules=Qwen3NextForCausalLM.partition_rules(cfg))
    before = jax.tree_util.tree_map(np.asarray, engine.state.params)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    assert engine.perf.programs.program("train_step").compiles == 1
    after = engine.state.params
    block = lambda p: p["model"]["periods"]["block_1"]
    assert np.array_equal(block(before)["block_sparse_moe"]["gate"]["kernel"],
                          block(after)["block_sparse_moe"]["gate"]["kernel"])
    assert not np.array_equal(block(before)["linear_attn"]["A_log"],
                              block(after)["linear_attn"]["A_log"])
    assert np.std(before["model"]["embed_tokens"]["embedding"]) == \
        pytest.approx(1.0, rel=0.05)
    found = engine.registry.snapshot()
    assert found["gdn_chunk_decay_max"] > 0
    assert 0 < found["moe_held_rows_over_expected"] < 2.2


def test_step_names_the_familys_scopes():
    """Each block under its kind's outer scope, the mixer's three inner
    names, the gate and the shared expert; the rule's scope holds the
    scan over chunks and nothing of the projections."""
    cfg = Qwen3NextConfig.tiny(remat=True, router_experts=16, first_expert=4)
    model = Qwen3NextForCausalLM(cfg)
    ids = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               ids))["params"]
    text = jax.jit(jax.grad(lambda p, ids: model.apply(
        {"params": p}, ids, labels=ids))).lower(params, ids).as_text(
            debug_info=True)
    for scope in ("ds.layer_stack", "ds.layer_gdn", "ds.layer_full",
                  "ds.attn_proj", "ds.gdn_mix", "ds.gdn_rule", "ds.attn_gate",
                  "ds.attention", "ds.moe_router", "ds.moe_experts",
                  "ds.moe_shared", "ds.norm", "ds.residual", "ds.embed",
                  "ds.lm_head_loss"):
        assert re.search(re.escape(scope) + r"\b", text), scope
    assert re.search(r"ds\.layer_gdn[^\"]*ds\.gdn_rule[^\"]*while", text)
    assert re.search(r"ds\.layer_full[^\"]*ds\.attn_gate", text)
    assert not re.search(r"ds\.layer_full[^\"]*ds\.gdn_", text)
    assert not re.search(r"ds\.gdn_rule[^\"]*dot_general[^\n]*2048", text)
