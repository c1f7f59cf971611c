"""``models/kimi_linear.py`` on the CPU, seeded, float32: the chunked KDA rule
against the token-by-token recurrence (outputs and every gradient; a ragged
tail; a chunk whose decay passes float32's 88 nats on some channels and is 0
on others; the heads taken a few at a time), the rule with a head's channels
all equal against ``qwen3_next.gated_delta_rule``, the model against
``benchmark/reference/kimi_linear.py`` (logits, loss, every parameter's
gradient), the held ranges adding up to the uncut layer, the pattern from
the two published lists, and the latent attention's rotation as a datum that
leaves kimi-vl-a3b's program as it was."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from deepspeed_tpu.models import deepseek_v3, kimi_linear as kl, layers
from deepspeed_tpu.models.qwen3_next import gated_delta_rule
from deepspeed_tpu.ops.pallas import (REMAT_ATTN_OUT, REMAT_KDA_RULE,
                                      REMAT_MLP, REMAT_MOE_ROWS, REMAT_MOE_UP,
                                      REMAT_QKV)

HIGHEST = jax.default_matmul_precision("highest")


def recurrence(q, k, v, g, beta):
    """The rule token by token, ``[B, T, H, ...]``."""
    B, _, H, dk = q.shape

    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None] * S
        d = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]))
    return jnp.moveaxis(jax.lax.scan(token, S0, xs)[1], 0, 1)


def operands(seed, T, steep=False, B=2, H=3, dk=8, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (B, T, H, dk)))
    if steep:       # channels 0-2 lose hundreds of nats a chunk, 3-4 none
        g = g.at[..., :3].multiply(200.0).at[..., 3:5].set(0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


CASES = {"whole_chunks": (32, False), "ragged_tail": (37, False),
         "decay_past_88_nats": (40, True)}


@pytest.mark.parametrize("case", CASES)
def test_rule_equals_the_recurrence_outputs_and_gradients(case):
    T, steep = CASES[case]
    args = operands(1, T, steep)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(rule):
        def f(*a):
            o = rule(*a)
            return jnp.sum(o * ct), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    with HIGHEST:
        (_, o), grads = both(
            lambda *a: kl.kda_rule(*a, chunk=8, block=4)[0])(*args)
        (_, want), want_grads = both(recurrence)(*args)
        decay = kl.kda_rule(*args, chunk=8, block=4)[1]
    np.testing.assert_allclose(o, want, atol=2e-6)
    for name, got, ref in zip("q k v g beta".split(), grads, want_grads):
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, ref, rtol=2e-5,
                                   atol=2e-6 * float(jnp.abs(ref).max()),
                                   err_msg=name)
    # the largest -sum of g over a chunk of 8, over heads AND channels
    g = np.pad(np.asarray(args[3]), ((0, 0), (0, (-T) % 8), (0, 0), (0, 0)))
    assert float(decay) == pytest.approx(
        -g.reshape(2, -1, 8, 3, 8).sum(2).min(), rel=1e-5)
    if steep:
        assert float(decay) > 88 * 5


def test_rule_with_equal_channels_is_the_scalar_rule():
    q, k, v, g, beta = operands(2, 32)
    one = g[..., 0]
    with HIGHEST:
        o, decay = kl.kda_rule(q, k, v, jnp.broadcast_to(
            one[..., None], g.shape), beta, chunk=8, block=4)
        want, want_decay = gated_delta_rule(q, k, v, one, beta, chunk=8)
    np.testing.assert_allclose(o, want, atol=2e-6)
    assert float(decay) == pytest.approx(float(want_decay), rel=1e-6)


def test_heads_go_a_few_at_a_time_where_all_do_not_fit(monkeypatch):
    """8,192 positions of 128 channels take 4 of 32 heads a pass; a pass of
    fewer heads computes what one pass of all computes."""
    assert kl._heads_a_pass(1, 8192, 32, 128) == 4
    assert kl._heads_a_pass(1, 16384, 32, 128) == 2
    assert kl._heads_a_pass(2, 64, 4, 8) == 4
    args = operands(3, 24, H=4)
    grad = lambda: jax.jit(jax.grad(lambda *a: jnp.sum(
        kl.kda_rule(*a, chunk=8, block=4)[0] ** 2), argnums=(0, 3)))(*args)
    with HIGHEST:
        whole, whole_grads = kl.kda_rule(*args, chunk=8, block=4), grad()
        monkeypatch.setattr(kl, "_PASS_BYTES", 4 * 2 * 24 * 8 * 2)
        assert kl._heads_a_pass(2, 24, 4, 8) == 2
        passes, pass_grads = kl.kda_rule(*args, chunk=8, block=4), grad()
    np.testing.assert_allclose(passes[0], whole[0], atol=1e-6)
    assert float(passes[1]) == float(whole[1])
    for got, ref in zip(pass_grads, whole_grads):
        np.testing.assert_allclose(got, ref, atol=1e-5)


# -- the model against the plain reference ----------------------------------

SHARE = dict(router_experts=16, first_expert=4, n_routed_experts=4,
             num_experts_per_tok=4, num_hidden_layers=5)


def sizes_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if isinstance(getattr(cfg, f.name), (int, float, bool))}


@pytest.fixture(scope="module")
def seeded():
    cfg = kl.KimiLinearConfig.tiny(**SHARE)
    model = kl.KimiLinearForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, 21), 0,
                             cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), ids)["params"]
    # a trained model's decays and biases, not the seeds' alone
    return cfg, model, params, ids


def test_model_matches_the_reference_logits_loss_and_every_gradient(seeded):
    cfg, model, params, ids = seeded
    ref = common.load_file_module("reference", "kimi_linear")
    sizes = sizes_of(cfg)

    @jax.jit
    def system(p):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=ids))(p)
        return model.apply({"params": p}, ids), loss, grads

    @jax.jit        # one program: run operation by operation the
    def reference(p):   # reference was some thousand of them
        def loss(p):
            hidden, rows = ref.hidden_states(p, sizes, ids[0])
            return ref.loss(p, sizes, np.asarray(ids)), (
                ref.logits(p, hidden), rows)
        return jax.value_and_grad(loss, has_aux=True)(p)

    with HIGHEST:
        logits, loss, grads = system(params)
        (want_loss, (want_logits, rows)), want_grads = reference(params)
    assert common.rel_l2(logits[0], want_logits) < 1e-5
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(rows.sum()) > 0          # the held range computed pairs
    flat = jax.tree_util.tree_leaves_with_path(grads)
    want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert len(flat) == len(want) > 60
    for path, got in flat:
        name = jax.tree_util.keystr(path)
        # the selection bias has no gradient on either side
        scale = float(jnp.abs(want[path]).max())
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want[path], rtol=2e-3,
                                   atol=2e-5 * scale + 1e-9, err_msg=name)
    kda = params["model"]["periods"]["block_0"]["linear_attn"]
    assert set(kda) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "b_proj", "f_a_proj",
        "f_b_proj", "g_a_proj", "g_b_proj", "q_conv1d", "k_conv1d",
        "v_conv1d", "A_log", "dt_bias", "o_norm"}


def test_the_training_call_reports_the_share_and_the_rules_decay(seeded):
    cfg, _, params, ids = seeded
    cfg = dataclasses.replace(cfg, report_expert_load=True,
                              router_bias_update_rate=0.03,
                              router_trainable=False)
    model = kl.KimiLinearForCausalLM(cfg)
    loss, named = jax.jit(lambda p: model.apply(
        {"params": p}, ids, labels=ids))(params)
    deltas = named.pop("param_deltas")
    assert set(named) == {"moe_rows_max_over_mean",
                          "moe_held_rows_over_expected",
                          "kda_chunk_decay_max"}
    assert float(named["kda_chunk_decay_max"]) > 0 and np.isfinite(loss)
    # a step of the sign rule for every expert layer's bias, by its path
    assert set(deltas) == {
        f"model/periods/block_{i}/mlp/e_score_correction_bias"
        for i in range(4)}
    flat = {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(params)}
    for path, delta in deltas.items():
        assert delta.shape == flat[path].shape == (1, 16)
        assert set(np.unique(np.abs(delta))) <= {0.0, np.float32(0.03)}
    assert kl.KimiLinearForCausalLM.frozen_parameters(cfg) == [
        "e_score_correction_bias", r"mlp/gate$"]


def test_the_held_ranges_add_up_to_the_uncut_layer():
    """All 32 ranges of one expert of 32, the shared expert counted once:
    the partial results of a deployment's chips add up to the layer."""
    whole = kl.KimiLinearConfig.tiny(n_routed_experts=32,
                                     num_experts_per_tok=4)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, whole.hidden_size))
    layer = deepseek_v3.DeepseekV3MoE(whole)
    params = jax.jit(layer.init)(jax.random.PRNGKey(4), x)["params"]
    shared_only = dataclasses.replace(whole, num_experts_per_tok=1,
                                      routed_scaling_factor=0.0)
    with HIGHEST:
        want, rows, _ = layer.apply({"params": params}, x)
        shared, _, _ = deepseek_v3.DeepseekV3MoE(shared_only).apply(
            {"params": params}, x)
        total, pairs = shared, 0.0
        for first in range(32):
            part = dataclasses.replace(whole, n_routed_experts=1,
                                       router_experts=32, first_expert=first)
            held = {**params, **{w: params[w][first:first + 1]
                                 for w in ("w1", "w2", "w3")}}
            out, r, _ = deepseek_v3.DeepseekV3MoE(part).apply(
                {"params": held}, x)
            total, pairs = total + (out - shared), pairs + float(r.sum())
    assert pairs == float(rows.sum()) == 12 * 4
    np.testing.assert_allclose(total, want, atol=1e-5)


# -- the pattern, from the two published lists ------------------------------

K, M = (kl.KDA, False), (kl.MLA, False)


@pytest.mark.parametrize("depth", [5, 9])
def test_pattern_is_read_from_the_two_lists(depth):
    cfg = kl.KimiLinearConfig.tiny(num_hidden_layers=depth)
    assert (cfg.kda_layers, cfg.full_attn_layers) == (
        (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
         25, 26), (4, 8, 12, 16, 20, 24, 27))
    leading, period = kl.stack_kinds(cfg)
    assert leading == ((kl.KDA, True),) and period == (K, K, M, K)
    kl._check(cfg)
    shapes = jax.eval_shape(
        kl.KimiLinearForCausalLM(cfg).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]["model"]
    assert set(shapes["leading"]) == {"block_0"}
    assert "linear_attn" in shapes["leading"]["block_0"]
    assert shapes["leading"]["block_0"]["mlp"]["gate_proj"]["kernel"].shape \
        == (32, 64)
    mixers = ["linear_attn" if m == kl.KDA else "self_attn" for m, _ in period]
    for i, mixer in enumerate(mixers):
        block = shapes["periods"][f"block_{i}"]
        assert mixer in block and block["mlp"]["w1"].shape[0] == depth // 4


def test_the_published_depth_ends_two_layers_into_a_period_and_is_refused():
    with pytest.raises(ValueError, match="27 end two layers into one"):
        kl._check(kl.KimiLinearConfig.tiny(num_hidden_layers=27))
    with pytest.raises(ValueError, match="last 2 end 2 layers into"):
        kl._check(kl.KimiLinearConfig.tiny(num_hidden_layers=7))
    # 24 sparse layers behind the dense one are six whole periods
    kl._check(kl.KimiLinearConfig.tiny(num_hidden_layers=25))
    # a stack that starts behind the dense layer: K M K K, whole periods
    later = kl.KimiLinearConfig.tiny(num_hidden_layers=8, first_layer=3)
    assert kl.stack_kinds(later) == ((), (K, M, K, K))
    kl._check(later)
    with pytest.raises(ValueError, match="both lists"):
        kl.layer_kind(kl.KimiLinearConfig.tiny(full_attn_layers=(3, 4)), 2)
    with pytest.raises(ValueError, match="neither list"):
        kl.layer_kind(kl.KimiLinearConfig.tiny(kda_layers=(1, 2)), 2)


def test_the_block_offers_its_residuals_to_the_remat_rule():
    cfg = kl.KimiLinearConfig.tiny(**SHARE, remat=True)
    x = jax.ShapeDtypeStruct((1, 64, cfg.hidden_size), jnp.bfloat16)
    offers = kl.remat_offers(cfg, x)
    assert [n for n, _ in offers] == [
        REMAT_KDA_RULE, REMAT_ATTN_OUT, REMAT_MLP, REMAT_QKV, REMAT_MOE_UP,
        REMAT_MOE_ROWS]
    # four KDA layers' outputs, five layers' output projections and the one
    # latent layer's q, k, v, in bf16
    assert dict(offers)[REMAT_KDA_RULE] == 4 * 64 * 4 * 8 * 2
    assert dict(offers)[REMAT_ATTN_OUT] == 5 * 64 * 32 * 2
    assert dict(offers)[REMAT_QKV] == 64 * 4 * (2 * 12 + 8) * 2
    model = kl.KimiLinearForCausalLM(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    with layers.remat_room(1 << 30) as kept:
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: model.apply(
            p, ids, labels=ids)))(shapes)
    assert set(kept) == {n for n, _ in offers}
    assert f"name={REMAT_KDA_RULE}" in str(jaxpr)


# -- the rotation is a datum of deepseek_v3.py ------------------------------

def test_rotation_off_leaves_kimi_vl_a3bs_program_as_it_was():
    """``mla_use_nope`` defaults to False, and at the default the model of
    kimi-vl-a3b traces to the jaxpr it traced to before the field existed
    (the digest is the parent commit's, taken with this installation's
    jax); with it on no column rotates: the output no longer follows the
    rotary tables, and equals the reference's plain product."""
    cfg = deepseek_v3.DeepseekV3Config.tiny()
    assert cfg.mla_use_nope is False
    model = deepseek_v3.DeepseekV3ForCausalLM(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
    text = str(jax.make_jaxpr(lambda p, i: model.apply(p, i, labels=i))(
        shapes, ids))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        "0e72937b6774e50f9d12c9e6b5ae2e1fe259f71e422713747cd7b9b91b5d41cd"
    assert "cos" in text and "sin" in text

    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, cfg.hidden_size))
    tables = lambda theta: layers.rotary_embedding(
        jnp.arange(12)[None], cfg.qk_rope_head_dim, theta)
    attn = lambda nope: deepseek_v3.DeepseekV3Attention(
        dataclasses.replace(cfg, mla_use_nope=nope, attention_impl="xla"))
    params = attn(False).init(jax.random.PRNGKey(6), x, *tables(100.0), None)
    call = lambda nope, theta: attn(nope).apply(params, x, *tables(theta),
                                                None)
    assert not np.allclose(call(False, 100.0), call(False, 50.0), atol=1e-4)
    np.testing.assert_array_equal(call(True, 100.0), call(True, 50.0))
    nope_text = str(jax.make_jaxpr(lambda p: attn(True).apply(
        p, x, None, None, None))(params))
    assert "cos" not in nope_text and "sin" not in nope_text
    ref = common.load_file_module("reference", "kimi_linear")
    sizes = {**sizes_of(cfg), "kda_num_heads": 0}
    with HIGHEST:
        want = ref.latent_attention(x[0], params["params"], sizes)
        got = attn(True).apply(params, x, None, None, None)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
