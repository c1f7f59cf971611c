"""The ZAYA1-shaped decoder (``models/zaya.py``) at tiny sizes in float32 on
the CPU: the system against the benchmark's plain reference
(``benchmark/reference/zaya.py``, written from the published description, not
from the system) at ONE CHIP'S SHARE — logits, loss and the gradient of every
parameter; causality of the convolutions and the value's shift; the
convolutions against ``lax.conv_general_dilated``; the router's state through
the layer scan; the shares of both chips adding up to the uncut layer; the
flash kernels against the XLA path; and what the engine does with the router
subtree, the balancing rule and the three gauges."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
import deepspeed_tpu.models.zaya as zaya
from benchmark import common
from deepspeed_tpu.models.layers import (causal_conv, rotary_embedding,
                                         shift_tokens)
from deepspeed_tpu.models.zaya import (BIAS, ZayaBlock, ZayaConfig,
                                       ZayaForCausalLM)

REF = common.load_file_module("reference", "zaya")
L = 3
#: experts 4..8 of the router's 8 (and its skip column) held
SHARE = dict(n_routed_experts=4, router_experts=8, first_expert=4)
BIAS_PATH = f"model/layers/block/mlp/router/{BIAS}"


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def _paths(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _paths(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}"


def _seeded(cfg, seed, ids):
    """(model, params): the model's own init, the RMSNorm scales moved off
    one and the skip column's bias raised so that the tiny router takes every
    kind of choice (held, absent, skip)."""
    model = ZayaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda kp, p: p * (1 + 0.3 * jax.random.normal(next(keys), p.shape))
        if str(getattr(kp[-1], "key", "")) == "scale" else p, params)
    stack = params["model"].get("layers", {}).get("block")
    if stack is not None:
        bias = stack["mlp"]["router"][BIAS]
        stack["mlp"]["router"][BIAS] = bias.at[:, -1].add(0.1)
    return model, params


IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, 24)))


@pytest.fixture(scope="module")
def share():
    """System and reference at a share: gradients, logits, losses, counts."""
    cfg = ZayaConfig.tiny(**SHARE)
    model, params = _seeded(cfg, 3, IDS)
    sizes = sizes_of(cfg)
    # one compiled program a side (run operation by operation, each side
    # was some hundreds of one-operation programs, compiled in every worker
    # that drew a case of this file)
    (sys_loss, sys_logits), sys_g = jax.jit(jax.value_and_grad(
        lambda p: (model.apply({"params": p}, IDS, labels=IDS),
                   model.apply({"params": p}, IDS)), has_aux=True))(params)

    def reference(p):
        hidden, rows, skipped = zip(*(REF.hidden_states(p, sizes, ids)
                                      for ids in IDS))
        return REF.loss(p, sizes, IDS), (
            jnp.stack([REF.logits(p, h) for h in hidden]), sum(rows),
            sum(skipped))

    (ref_loss, (ref_logits, rows, skipped)), ref_g = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return {"sys_g": sys_g, "ref_g": ref_g, "params": params, "cfg": cfg,
            "rows": rows, "skipped": skipped, "sys_logits": sys_logits,
            "ref_logits": ref_logits, "sys_loss": sys_loss,
            "ref_loss": ref_loss}


def test_share_logits_and_loss_match_the_reference(share):
    np.testing.assert_allclose(np.asarray(share["sys_logits"]),
                               np.asarray(share["ref_logits"]), rtol=1e-4,
                               atol=1e-5)
    assert float(share["sys_loss"]) == pytest.approx(
        float(share["ref_loss"]), rel=1e-5)
    # some tokens go to the held experts, some to absent ones, some skip
    choices = IDS.size * L
    held, skipped = float(share["rows"].sum()), float(share["skipped"])
    assert 0 < held and 0 < skipped and held + skipped < choices


PATHS = sorted(_paths(jax.eval_shape(
    lambda: ZayaForCausalLM(ZayaConfig.tiny(**SHARE)).init(
        jax.random.PRNGKey(0), IDS))["params"]))


def test_every_parameter_is_listed():
    names = {p.split("/")[-1] for p in PATHS}
    assert {"conv_a_weight", "conv_a_bias", "conv_b_weight", "conv_b_bias",
            "temperature", "state_scale", "norm_scale", "down_kernel",
            "fc1_kernel", "fc2_bias", "fc3_kernel", BIAS, "residual_scale",
            "residual_bias", "output_scale", "output_bias", "w1", "w2",
            "w3", "embedding"} <= names
    assert len(PATHS) == 35 and "lm_head" not in names    # the head is tied


@pytest.mark.parametrize("path", [p for p in PATHS if not p.endswith(BIAS)])
def test_share_gradient_matches_the_reference(share, path):
    """Every parameter: the convolutions' taps and biases, the temperature,
    the router's state scale and MLP (through the chosen probability alone:
    the choice passes no gradient), the residual scaling, the held experts
    through the hand-written backward of ``mixtral._sorted_experts``, the
    tied table from both of its uses."""
    got, want = (np.asarray(_leaf(share[g], path))
                 for g in ("sys_g", "ref_g"))
    assert np.abs(want).max() > 1e-7, "a gradient that is not exercised"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


def test_bias_takes_no_gradient_and_the_table_has_two_uses(share):
    for g in (share["sys_g"], share["ref_g"]):
        assert not np.asarray(_leaf(g, BIAS_PATH)).any()
    # rows of ids the batch never reads still get the head's gradient
    table = np.asarray(_leaf(share["sys_g"], "model/embed_tokens/embedding"))
    unread = np.setdiff1d(np.arange(128), np.asarray(IDS))
    assert len(unread) and np.abs(table[unread]).max() > 0


# -- causality ---------------------------------------------------------------

@pytest.mark.parametrize("t", [0, 1, 7, 22])
def test_a_prefix_sees_nothing_after_it(share, t):
    """Logits of positions ``<= t`` are those of the prefix alone: the
    convolutions and the value's shift reach one token BACK, position 0 sees
    zeros before it (a wrapped row would bring the last token), and a
    change at ``t + 1`` moves nothing at or before ``t``."""
    model = ZayaForCausalLM(share["cfg"])
    full = np.asarray(share["sys_logits"])
    apply = jax.jit(lambda p, ids: model.apply({"params": p}, ids))
    alone = apply(share["params"], IDS[:, :t + 1])
    np.testing.assert_allclose(np.asarray(alone), full[:, :t + 1],
                               rtol=1e-4, atol=1e-5)
    changed = IDS.at[:, t + 1].set((IDS[:, t + 1] + 1) % 128)
    moved = np.asarray(apply(share["params"], changed))
    np.testing.assert_allclose(moved[:, :t + 1], full[:, :t + 1], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(moved[:, t + 1] - full[:, t + 1]).max() > 1e-3


def test_shift_is_exact_at_position_zero():
    x = jnp.arange(1.0, 25.0).reshape(2, 4, 3)
    got = np.asarray(shift_tokens(x))
    assert not got[:, 0].any()
    np.testing.assert_array_equal(got[:, 1:], np.asarray(x[:, :-1]))


# -- the convolutions --------------------------------------------------------

@pytest.mark.parametrize("taps", [1, 2, 3])
@pytest.mark.parametrize("kind", ["depthwise", "grouped"])
def test_convolution_matches_conv_general_dilated(kind, taps):
    B, T, G, D = 2, 9, 3, 4
    C = G * D
    rng = np.random.RandomState(taps)
    x = jnp.asarray(rng.randn(B, T, C), jnp.float32)
    bias = jnp.asarray(rng.randn(C), jnp.float32)
    if kind == "depthwise":
        w = jnp.asarray(rng.randn(taps, C), jnp.float32)
        rhs, groups = w[:, None, :], C
    else:
        w = jnp.asarray(rng.randn(taps, G, D, D), jnp.float32)
        rhs, groups = w.transpose(0, 2, 1, 3).reshape(taps, D, C), G
    want = jax.lax.conv_general_dilated(
        x, rhs, window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST) + bias
    np.testing.assert_allclose(np.asarray(causal_conv(x, w, bias)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    ref = {"depthwise": REF.conv_depthwise, "grouped": REF.conv_grouped}[kind]
    np.testing.assert_allclose(np.asarray(ref(x[0], w, bias)),
                               np.asarray(want[0]), rtol=1e-5, atol=1e-5)


# -- the router's state through the scan -------------------------------------

def test_scanned_layers_carry_the_router_state_as_an_unrolled_loop_does(
        share):
    """``scan_layers`` carries ``(x, state, ...)``; the unrolled model hands
    the same state from ``layers_0`` to ``layers_1``; the state matters."""
    cfg, params = share["cfg"], share["params"]
    unrolled = {"model": {
        **{k: v for k, v in params["model"].items() if k != "layers"},
        **{f"layers_{i}": jax.tree_util.tree_map(
            lambda a: a[i], params["model"]["layers"]["block"])
           for i in range(L)}}}
    loop = ZayaForCausalLM(dataclasses.replace(cfg, scan_layers=False))
    np.testing.assert_allclose(
        np.asarray(jax.jit(loop.apply)({"params": unrolled}, IDS)),
        np.asarray(share["sys_logits"]), rtol=1e-5, atol=1e-6)
    saved = zaya._carry_state
    try:
        zaya._carry_state = lambda r, gamma, state: r
        forgot = jax.jit(ZayaForCausalLM(cfg).apply)({"params": params}, IDS)
    finally:
        zaya._carry_state = saved
    assert np.abs(np.asarray(forgot)
                  - np.asarray(share["sys_logits"])).max() > 1e-3


# -- the shares add up -------------------------------------------------------

def _levelled(p, x, state, sizes, rate=0.004, steps=60):
    """The layer's parameters after ``steps`` of the sign rule on its
    balancing bias (the reference's router on the layer's own input): a
    tiny random router sends most tokens to two columns, the rule spreads
    them over all 17."""
    eps = sizes["rms_norm_eps"]
    mid = REF.residual(x, REF.attention(REF.dense.rms_norm(
        x, p["input_layernorm"]["scale"], eps), p["self_attn"], sizes),
        p["attn_residual"])
    h = REF.dense.rms_norm(mid, p["post_attention_layernorm"]["scale"], eps)
    router = dict(p["mlp"]["router"])

    @jax.jit
    def step(router):
        load = (REF.route(h, state, router, sizes)[0] > 0).sum(0)
        return {**router, BIAS: router[BIAS]
                + rate * jnp.sign(load.mean() - load)}, load

    for _ in range(steps):
        router, load = step(router)
    assert int((load > 0).sum()) >= 12
    return {**p, "mlp": {**p["mlp"], "router": router}}


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts 0..8 on one chip and 8..16 on the other, each routing over
    all 16 and the skip column: their layers' outputs, with what both chips
    compute alike (attention, the skip expert, the residual's scaled stream
    and output bias) counted once, are the uncut 16-expert reference layer."""
    full = ZayaConfig.tiny(n_routed_experts=16, router_bias_init=0.03)
    T, H = 40, full.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, H))
    state = 0.3 * jax.random.normal(jax.random.PRNGKey(1),
                                    (1, T, full.router_hidden_size))
    cos, sin = rotary_embedding(jnp.arange(T)[None], full.rotary_dim,
                                full.rope_theta)
    p = jax.jit(ZayaBlock(full).init)(jax.random.PRNGKey(2), x, state, cos,
                                      sin, None)["params"]
    sizes = sizes_of(full)
    p = _levelled(p, x[0], state[0], sizes)
    want, _, rows, skipped = jax.jit(lambda p: REF._layer(
        x[0], state[0], p, REF.dense._static(sizes)))(p)
    assert int(rows.sum()) + int(skipped) == T and int(skipped) > 0
    assert min((np.asarray(rows[:8]) > 0).sum(),
               (np.asarray(rows[8:]) > 0).sum()) >= 4

    total = 0
    for first in (0, 8):
        cfg = dataclasses.replace(full, n_routed_experts=8,
                                  router_experts=16, first_expert=first)
        mine = {**p, "mlp": {**p["mlp"], **{
            w: p["mlp"][w][first:first + 8] for w in ("w1", "w2", "w3")}}}
        out, _, share_rows, share_skipped, _ = jax.jit(ZayaBlock(cfg).apply)(
            {"params": mine}, x, state, cos, sin, None)
        np.testing.assert_array_equal(np.asarray(share_rows),
                                      np.asarray(rows[first:first + 8]))
        assert int(share_skipped) == int(skipped)
        total = total + out[0]
    # what both chips computed alike, from the reference's parts
    eps = sizes["rms_norm_eps"]

    @jax.jit
    def computed_alike(p):
        mid = REF.residual(x[0], REF.attention(REF.dense.rms_norm(
            x[0], p["input_layernorm"]["scale"], eps), p["self_attn"],
            sizes), p["attn_residual"])
        h = REF.dense.rms_norm(mid, p["post_attention_layernorm"]["scale"],
                               eps)
        _, skip, *_ = REF.moe_parts(h, state[0], p["mlp"], sizes)
        return REF.residual(mid, skip, p["mlp_residual"])

    alike = computed_alike(p)
    np.testing.assert_allclose(np.asarray(total - alike), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-2     # experts do add


# -- the flash kernels -------------------------------------------------------

def test_flash_path_equals_the_xla_path_and_runs_the_forward_once_a_layer(
        monkeypatch):
    """Unit-length queries and keys times a temperature through the Pallas
    kernels (interpret mode): loss and every gradient equal the XLA path's
    and the un-remat'd model's, and the remat'd gradient's jaxpr holds
    ``ds_flash_fwd`` once (the forward scan's; the replay reads the kept
    output and log-sum-exp) where three unrolled layers hold three."""
    from tests.unit.test_model_convergence import (assert_same_loss_and_grads,
                                                   remat_loss_and_grads)
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    ids = np.random.RandomState(0).randint(0, 128, (2, 48)).astype(np.int32)
    model_of = lambda remat, **over: ZayaForCausalLM(ZayaConfig.tiny(
        remat=remat, attention_impl="flash", flash_block_q=16,
        flash_block_k=16, **over))
    (loss, grads), (loss0, grads0) = remat_loss_and_grads(
        monkeypatch, model_of, ids)
    assert_same_loss_and_grads(loss, grads, loss0, grads0)
    params = jax.jit(model_of(False).init)(jax.random.PRNGKey(0),
                                           ids)["params"]
    xla = ZayaForCausalLM(ZayaConfig.tiny())
    loss_x, grads_x = jax.jit(jax.value_and_grad(lambda p: xla.apply(
        {"params": p}, ids, labels=ids)))(params)
    assert float(loss0) == pytest.approx(float(loss_x), rel=1e-5)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-3, atol=1e-5), grads0, grads_x)

    def count(model, params):
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)))(params))
        return [len(re.findall(rf"name={k}\b", text)) for k in
                ("ds_flash_fwd", "ds_flash_bwd", "ds_flash_bwd_dq",
                 "ds_flash_bwd_dkv")]
    assert count(model_of(True), params) == [1, 0, 1, 1]
    loop = model_of(True, scan_layers=False)
    loop_params = jax.eval_shape(loop.init, jax.random.PRNGKey(0),
                                 ids)["params"]       # only traced
    assert count(loop, loop_params) == [L, 0, L, L]
    # on the chip a head's dQ stays in VMEM (``fa.fused_backward`` answers
    # for the device kind, the CPU's here): one backward kernel a flash call
    from deepspeed_tpu.ops.pallas import grouped_matmul
    monkeypatch.setattr(grouped_matmul, "device_kind", lambda: "TPU v5 lite")
    assert count(model_of(True), params) == [1, 1, 0, 0]
    assert count(loop, loop_params) == [L, L, 0, 0]
    assert fa.flash_attention.keywords == {"force_pallas": True}


# -- the engine --------------------------------------------------------------

def _engine(cfg, lr=1e-2):
    ids = np.random.RandomState(0).randint(0, 128, (8, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=ZayaForCausalLM(cfg),
        example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0, "optimizer": {
            "type": "AdamW", "params": {"lr": lr, "weight_decay": 0.1}}},
        # one device: the rule's scatter-add under the partitioner aborts
        # XLA:CPU on the eight virtual devices (tests/unit/test_deepseek_v3)
        mesh=common.cell_mesh(1),
        partition_rules=ZayaForCausalLM.partition_rules(cfg))
    return engine, batch


def _flat(params):
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("trainable", [True, False])
def test_engine_moves_what_frozen_parameters_leaves(trainable):
    """Weight decay on and a learning rate that moves every leaf: the
    balancing bias stays where it was seeded; with ``router_trainable`` off
    so does the whole router subtree, whose gradient exists all the same."""
    cfg = ZayaConfig.tiny(router_trainable=trainable, **SHARE)
    assert ZayaForCausalLM.frozen_parameters(cfg) == (
        [BIAS] if trainable else [r"mlp/router/"])
    engine, batch = _engine(cfg)
    before = _flat(engine.state.params)
    grads = jax.jit(jax.grad(lambda p: ZayaForCausalLM(cfg).apply(
        {"params": p}, **batch)))(engine.state.params)
    router = {k: np.asarray(v) for k, v in _flat(grads).items()
              if "['router']" in k and BIAS not in k}
    assert len(router) == 9 and all(np.abs(g).max() > 0
                                    for g in router.values())
    losses = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    after = _flat(engine.state.params)
    assert losses[-1] < losses[0]
    still = {k for k in before if not np.abs(after[k] - before[k]).max()}
    assert still == {k for k in before
                     if (BIAS in k if trainable else "['router']" in k)}
    assert len(still) == (1 if trainable else 10)


# -- what the replay keeps (PR 65) -------------------------------------------

@pytest.mark.parametrize("scan", [
    True,       # unrolled: 34 s cold (PR 69); the scanned stack asks the same
    pytest.param(False, marks=pytest.mark.slow)], ids=["scan", "unrolled"])
def test_kept_names_change_no_loss_and_no_gradient(scan):
    """The remat'ed share with every offered value kept (``remat_offers``
    under a room: the expert sublayer's output, the projections, the experts'
    gate and up products, the router's float32 values, the mixer's) against
    the same model keeping nothing: one loss, and every parameter's gradient
    to float32 rounding -- a kept value is the value the replay computed."""
    from deepspeed_tpu.models.layers import remat_room

    cfg = ZayaConfig.tiny(num_hidden_layers=L, remat=True, scan_layers=scan,
                          **SHARE)
    model, params = _seeded(cfg, 3, IDS)
    grad = lambda: jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS))
    want_loss, want = jax.jit(grad())(params)
    with remat_room(10 ** 9) as kept:      # a fresh function: a fresh trace
        got_loss, got = jax.jit(grad())(params)
    assert len(kept) == len(zaya.remat_offers(
        cfg, jax.ShapeDtypeStruct((*IDS.shape, cfg.hidden_size), jnp.float32),
        L)) == 5
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)
    want, got = _flat(want), _flat(got)
    assert set(got) == set(want)
    assert len(got) == (len(PATHS) if scan else L * (len(PATHS) - 2) + 2)
    for path in want:
        scale = np.abs(want[path]).max()
        np.testing.assert_allclose(got[path], want[path], rtol=2e-5,
                                   atol=2e-6 * scale, err_msg=path)


def test_a_frozen_router_stays_frozen_with_its_values_kept(monkeypatch):
    """An engine that reads room on its device keeps all five of the
    remat'ed block's names, the router's float32 values among them; with
    ``router_trainable`` off the router subtree still receives no update,
    and the losses are those of the engine that kept nothing."""
    from deepspeed_tpu.runtime import engine as engine_module

    cfg = ZayaConfig.tiny(router_trainable=False, remat=True, **SHARE)
    engine, batch = _engine(cfg)
    want = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    assert engine.setup.record(0)["remat_kept_names"] == 0
    monkeypatch.setattr(engine_module, "_device_memory",
                        lambda device: (10 ** 8, 10 ** 6))
    engine, batch = _engine(cfg)
    before = _flat(engine.state.params)
    got = [float(engine.train_batch(batch=batch)) for _ in range(3)]
    record = engine.setup.record(0)
    assert record["remat_kept_names"] == 5 and record["remat_fallbacks"] == 0
    np.testing.assert_allclose(got, want, rtol=1e-6)
    after = _flat(engine.state.params)
    still = {k for k in before if not np.abs(after[k] - before[k]).max()}
    assert still == {k for k in before if "['router']" in k}
    assert len(still) == 10


def test_balancing_rule_moves_the_bias_against_the_load():
    """``router_bias_update_rate``: beside its loss the training call names
    what the sign rule adds to each layer's bias — minus the rate for a
    column (the skip expert's too) the step sent more than the mean number
    of tokens, plus it for one sent fewer — and the engine adds exactly that
    after the optimizer's update, which leaves the bias alone."""
    rate = 0.01
    cfg = ZayaConfig.tiny(router_bias_update_rate=rate, **SHARE)
    engine, batch = _engine(cfg)
    params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    loss, named = jax.jit(lambda p: ZayaForCausalLM(cfg).apply(
        {"params": p}, **batch))(params)
    delta = np.asarray(named["param_deltas"][BIAS_PATH])      # [L, E + 1]
    assert set(named) == {"param_deltas"} and delta.shape == (L, 9)
    assert np.all(np.isclose(np.abs(delta), rate) | (delta == 0))
    # the first layer against the reference's router on the same input
    sizes = sizes_of(cfg)
    first = REF.dense.f32(jax.tree_util.tree_map(
        lambda a: a[0], params["model"]["layers"]["block"]))
    @jax.jit
    def load_of(ids):
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(params["model"]["embed_tokens"]["embedding"])[ids]
            mid = REF.residual(x, REF.attention(REF.dense.rms_norm(
                x, first["input_layernorm"]["scale"], 1e-5),
                first["self_attn"], sizes), first["attn_residual"])
            h = REF.dense.rms_norm(
                mid, first["post_attention_layernorm"]["scale"], 1e-5)
            combine, _ = REF.route(h, jnp.zeros((16, 16)),
                                   first["mlp"]["router"], sizes)
            return (combine > 0).sum(0)

    load = sum(np.asarray(load_of(ids)) for ids in batch["input_ids"])
    assert load.sum() == batch["input_ids"].size and load.shape == (9,)
    np.testing.assert_allclose(delta[0], rate * np.sign(load.mean() - load),
                               atol=1e-7)
    engine.train_batch(batch=batch)
    after = np.asarray(_leaf(engine.state.params, BIAS_PATH))
    np.testing.assert_allclose(after - _leaf(params, BIAS_PATH), delta,
                               atol=1e-7)
    assert float(loss) > 0


def test_engine_publishes_the_three_gauges():
    cfg = ZayaConfig.tiny(report_expert_load=True, **SHARE)
    engine, batch = _engine(cfg)
    params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    engine.train_batch(batch=batch)
    gauges = {k: v for k, v in engine.registry.snapshot().items()
              if k.startswith("moe_")}
    assert sorted(gauges) == ["moe_held_rows_over_expected",
                              "moe_rows_max_over_mean", "moe_skip_share"]
    sizes = sizes_of(cfg)
    rows, skipped = 0, 0
    counts = jax.jit(lambda p, ids: REF.hidden_states(p, sizes, ids)[1:])
    for ids in batch["input_ids"]:
        r, s = counts(params, ids)
        rows, skipped = rows + np.asarray(r), skipped + int(s)
    choices = L * batch["input_ids"].size
    assert gauges["moe_skip_share"] == pytest.approx(skipped / choices)
    assert gauges["moe_held_rows_over_expected"] == pytest.approx(
        rows.sum() / (choices * 4 / 9))
    assert gauges["moe_rows_max_over_mean"] == pytest.approx(
        rows.max() / rows.mean())


def test_unbuilt_paths_say_so():
    ids = jnp.zeros((1, 8), jnp.int32)
    for over in ({"first_expert": 6, "router_experts": 8,
                  "n_routed_experts": 4},
                 {"num_key_value_heads": 1, "num_attention_heads": 4}):
        with pytest.raises(ValueError):
            jax.eval_shape(ZayaForCausalLM(ZayaConfig.tiny(**over)).init,
                           jax.random.PRNGKey(0), ids)
    model = ZayaForCausalLM(ZayaConfig.tiny())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError):
        model.apply({"params": params}, ids, cache={})


def test_published_shape_by_hand():
    """ZAYA1-8B as published: a layer's parameters by ISSUE 35's count."""
    cfg = ZayaConfig.zaya1_8b(num_hidden_layers=1)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.router_width) == (128, 64, 17)
    shapes = jax.eval_shape(lambda: ZayaForCausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    block = shapes["model"]["layers"]["block"]
    assert size(block["self_attn"]) == 5_242_880 + 332_802
    assert size(block["mlp"]["router"]) == 524_544 + 256 + 256 \
        + 2 * 65_792 + 256 * 17 + 17
    assert size(block["mlp"]) - size(block["mlp"]["router"]) == \
        16 * 3 * 2048 * 2048
    assert size(shapes) - size(block) == 262_272 * 2048 + 2048


# -- what a held share needs beyond the grouped layer: one copy --------------

def test_one_copy_of_the_held_share_helpers():
    """``zaya.py`` and ``deepseek_v3.py`` call ``mixtral.py``'s check, rule
    and gauges; neither keeps its own."""
    import deepspeed_tpu.models.deepseek_v3 as deepseek_v3
    import deepspeed_tpu.models.mixtral as mixtral

    for name in ("_check_held_share", "_balancing_delta",
                 "_held_load_gauges", "_routed_experts"):
        assert getattr(zaya, name) is getattr(deepseek_v3, name) \
            is getattr(mixtral, name), name


@pytest.mark.parametrize("idx,width,delta", [
    ([[0, 0], [0, 1]], 3, [-1, 1, 1]),      # loads 3, 1, 0 about 4/3
    ([[0], [1], [2]], 3, [0, 0, 0]),        # level: nothing moves
    ([[3, 3, 3, 3]], 4, [1, 1, 1, -1]),     # one column takes all
])
def test_balancing_delta_by_hand(idx, width, delta):
    from deepspeed_tpu.models.mixtral import _balancing_delta

    np.testing.assert_allclose(
        _balancing_delta(jnp.asarray(idx), width, 0.25),
        0.25 * np.asarray(delta, np.float32))


@pytest.mark.parametrize("first,held,experts,ok", [
    (0, 8, 16, True), (8, 8, 16, True), (9, 8, 16, False),
    (-1, 4, 8, False), (0, 16, 16, True)])
def test_held_range_is_checked(first, held, experts, ok):
    from deepspeed_tpu.models.mixtral import _check_held_share

    if ok:
        _check_held_share(first, held, experts)
    else:
        with pytest.raises(ValueError):
            _check_held_share(first, held, experts)


def test_held_load_gauges_by_hand():
    from deepspeed_tpu.models.mixtral import _held_load_gauges

    gauges = _held_load_gauges(jnp.asarray([1.0, 2.0, 6.0]), 12.0)
    assert {k: float(v) for k, v in gauges.items()} == {
        "moe_rows_max_over_mean": 2.0, "moe_held_rows_over_expected": 0.75}
