"""The DeepSeek-V3-shaped decoder (``models/deepseek_v3.py``; Kimi-VL-A3B's
language model) at tiny sizes in float32 on the CPU: the benchmark's plain
reference against the published modelling code (HF ``DeepseekV3ForCausalLM``
with all experts held and the whole vocabulary, weights mapped by hand); the
system against the reference at ONE CHIP'S SHARE — logits, loss and the
gradient of every kind of parameter; the selection bias, which changes which
experts are chosen and never their weights; the shares of all the chips
adding up to the uncut layer; and the engine leaving the bias where it was
seeded."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
import deepspeed_tpu.models.deepseek_v3 as dsv3
from benchmark import common
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                              DeepseekV3ForCausalLM,
                                              DeepseekV3MoE)

REF = common.load_file_module("reference", "deepseek_v3")
E, K, L = 8, 3, 3


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def _hf(seed):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no DeepseekV3")
    tiny = DeepseekV3Config.tiny()
    torch.manual_seed(seed)
    hf = transformers.DeepseekV3ForCausalLM(transformers.DeepseekV3Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=L,
        num_attention_heads=4, num_key_value_heads=4, n_shared_experts=2,
        n_routed_experts=E, routed_scaling_factor=2.446, kv_lora_rank=16,
        q_lora_rank=None, qk_rope_head_dim=4, v_head_dim=8,
        qk_nope_head_dim=8, n_group=1, topk_group=1, num_experts_per_tok=K,
        first_k_dense_replace=1, norm_topk_prob=True,
        rope_theta=tiny.rope_theta, max_position_embeddings=64,
        attention_dropout=0.0, rms_norm_eps=tiny.rms_norm_eps)).eval()
    assert hf.config.rope_interleave and hf.config.rope_scaling is None
    with torch.no_grad():   # norms start at one, the bias at zero: make
        for name, p in hf.named_parameters():           # them count
            if "norm" in name:
                p.copy_(1.0 + 0.3 * torch.randn_like(p))
        for name, b in hf.named_buffers():
            if name.endswith("e_score_correction_bias"):
                b.copy_(0.1 * torch.randn_like(b))
    return hf


def _our_tree(hf):
    """HF's state dict as ``DeepseekV3ForCausalLM``'s tree: the leading
    dense layer ``layers_0``, the expert layers stacked under
    ``layers/block`` (torch Linear weights are [out, in]; ours [in, out])."""
    sd = {k: jnp.asarray(v.detach().numpy())
          for k, v in hf.state_dict().items()}

    def attn(pre):
        out = {n: {"kernel": sd[f"{pre}.{n}.weight"].T}
               for n in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj",
                         "o_proj")}
        out["kv_a_layernorm"] = {"scale": sd[f"{pre}.kv_a_layernorm.weight"]}
        return out

    def swiglu(pre):
        return {n: {"kernel": sd[f"{pre}.{n}.weight"].T}
                for n in ("gate_proj", "up_proj", "down_proj")}

    def layer(l, mlp):
        pre = f"model.layers.{l}"
        return {"self_attn": attn(f"{pre}.self_attn"), "mlp": mlp,
                "input_layernorm": {
                    "scale": sd[f"{pre}.input_layernorm.weight"]},
                "post_attention_layernorm": {
                    "scale": sd[f"{pre}.post_attention_layernorm.weight"]}}

    def moe(l):
        pre = f"model.layers.{l}.mlp"
        stack = lambda proj: jnp.stack([
            sd[f"{pre}.experts.{e}.{proj}.weight"].T for e in range(E)])
        return {"gate": sd[f"{pre}.gate.weight"].T,
                "e_score_correction_bias":
                    sd[f"{pre}.gate.e_score_correction_bias"],
                "w1": stack("gate_proj"), "w3": stack("up_proj"),
                "w2": stack("down_proj"),
                "shared_experts": swiglu(f"{pre}.shared_experts")}

    blocks = [layer(l, moe(l)) for l in range(1, L)]
    return {"model": {
        "embed_tokens": {"embedding": sd["model.embed_tokens.weight"]},
        "layers_0": layer(0, swiglu("model.layers.0.mlp")),
        "layers": {"block": jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *blocks)},
        "norm": {"scale": sd["model.norm.weight"]}},
        "lm_head": {"kernel": sd["lm_head.weight"].T}}


@pytest.mark.parametrize("which", ["reference", "system"])
@pytest.mark.parametrize("seed", [0, 1])
def test_logits_and_loss_match_hf_deepseek_v3(seed, which):
    """All experts held, the whole vocabulary: the benchmark's reference IS
    the published forward pass and loss, and so is the system's module —
    latent attention with its de-interleaved rotary columns, the sigmoid
    router choosing by score + bias and weighting by score, the scale, the
    shared experts, the leading dense layer."""
    torch = pytest.importorskip("torch")
    hf = _hf(seed)
    params = _our_tree(hf)
    cfg = DeepseekV3Config.tiny(n_routed_experts=E, num_experts_per_tok=K)
    ids = np.random.RandomState(seed).randint(0, 128, (2, 16))
    with torch.no_grad():
        out = hf(torch.tensor(ids), labels=torch.tensor(ids))
    if which == "reference":
        sizes = sizes_of(cfg)
        hidden, rows = REF.hidden_states(params, sizes, jnp.asarray(ids[0]))
        logits = REF.logits(params, hidden)
        loss = REF.loss(params, sizes, ids)
        assert float(rows.sum()) == 16 * K * (L - 1)
    else:
        model = DeepseekV3ForCausalLM(cfg)
        logits = model.apply({"params": params}, jnp.asarray(ids))[0]
        loss = model.apply({"params": params}, jnp.asarray(ids),
                           labels=jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits), out.logits[0].numpy(),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(float(loss), float(out.loss), rtol=1e-4)


# -- the system against the reference at one chip's share --------------------

SHARE = dict(n_routed_experts=4, router_experts=16, first_expert=8,
             num_experts_per_tok=4)


def _seeded(cfg, seed, ids):
    model = DeepseekV3ForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)["params"]
    # norms start at one: make their gradients and their values count
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda kp, p: p * (1 + 0.3 * jax.random.normal(next(keys), p.shape))
        if "scale" in str(kp[-1]) else p, params)
    return model, params


@pytest.fixture(scope="module")
def share_grads():
    """(system gradients, reference gradients, logits x 2, losses x 2) at a
    share: experts 8..12 of 16 held, top-4."""
    cfg = DeepseekV3Config.tiny(**SHARE)
    ids = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, 24)))
    model, params = _seeded(cfg, 3, ids)
    sizes = sizes_of(cfg)
    # one program a side (run operation by operation a gradient was some
    # thousand one-operation programs, compiled by every worker that drew a
    # case of this file)
    sys_loss, sys_g = jax.jit(jax.value_and_grad(lambda p: model.apply(
        {"params": p}, ids, labels=ids)))(params)
    ref_loss, ref_g = jax.jit(jax.value_and_grad(
        lambda p: REF.loss(p, sizes, ids)))(params)
    hidden, rows = REF.hidden_states(params, sizes, ids[0])
    return {"sys_g": sys_g, "ref_g": ref_g, "rows": rows,
            "sys_logits": model.apply({"params": params}, ids)[0],
            "ref_logits": REF.logits(params, hidden),
            "sys_loss": sys_loss, "ref_loss": ref_loss}


def test_share_logits_and_loss_match_the_reference(share_grads):
    g = share_grads
    np.testing.assert_allclose(np.asarray(g["sys_logits"]),
                               np.asarray(g["ref_logits"]), rtol=1e-4,
                               atol=1e-5)
    assert float(g["sys_loss"]) == pytest.approx(float(g["ref_loss"]),
                                                 rel=1e-5)
    # some pairs go to the held experts and some do not: it IS a share
    assert 0 < float(g["rows"].sum()) < 24 * 4 * (L - 1)


def _leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


@pytest.mark.parametrize("path", [
    "model/layers/block/self_attn/kv_a_proj_with_mqa/kernel",
    "model/layers/block/self_attn/kv_a_layernorm/scale",
    "model/layers/block/self_attn/kv_b_proj/kernel",
    "model/layers/block/self_attn/q_proj/kernel",
    "model/layers/block/self_attn/o_proj/kernel",
    "model/layers/block/mlp/gate",
    "model/layers/block/mlp/w1", "model/layers/block/mlp/w3",
    "model/layers/block/mlp/w2",
    "model/layers/block/mlp/shared_experts/gate_proj/kernel",
    "model/layers/block/mlp/shared_experts/down_proj/kernel",
    "model/layers_0/mlp/up_proj/kernel",
    "model/layers_0/self_attn/kv_a_proj_with_mqa/kernel",
    "model/layers/block/post_attention_layernorm/scale",
    "model/embed_tokens/embedding", "lm_head/kernel"])
def test_share_gradient_matches_the_reference(share_grads, path):
    """Every kind of parameter: the low-rank kv path, the router weight
    (through the sigmoid weights alone: top-k passes no gradient), the held
    and the shared experts, the dense layer, through the hand-written
    backward of ``mixtral._sorted_experts``."""
    got, want = (np.asarray(_leaf(share_grads[g], path))
                 for g in ("sys_g", "ref_g"))
    assert np.abs(want).max() > 1e-6, "a gradient that is not exercised"
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=2e-4 * np.abs(want).max())


def test_bias_takes_no_gradient(share_grads):
    for g in (share_grads["sys_g"], share_grads["ref_g"]):
        assert not np.asarray(_leaf(
            g, "model/layers/block/mlp/e_score_correction_bias")).any()


# -- the selection bias ------------------------------------------------------

def test_bias_changes_which_experts_are_chosen_and_never_their_weights():
    cfg = DeepseekV3Config.tiny(n_routed_experts=16, num_experts_per_tok=4)
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(200, 16), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(16), jnp.float32)
    w0, i0 = dsv3.route(cfg, logits, None)
    w1, i1 = dsv3.route(cfg, logits, bias)
    scores = np.asarray(jax.nn.sigmoid(logits))
    changed = [set(a) != set(b) for a, b in zip(np.asarray(i0),
                                                np.asarray(i1))]
    assert 20 < sum(changed) < 200       # it chooses otherwise, not always
    for w, idx in ((w0, i0), (w1, i1)):
        s = np.take_along_axis(scores, np.asarray(idx), -1)
        np.testing.assert_allclose(
            np.asarray(w), 2.446 * s / s.sum(-1, keepdims=True), rtol=1e-6)
    # the choice is the top-k of score + bias
    want = np.argsort(-(scores + np.asarray(bias)), -1)[:, :4]
    assert all(set(a) == set(b) for a, b in zip(np.asarray(i1), want))
    # and where the bias changed nothing, nothing changed
    same = ~np.asarray(changed)
    order = lambda w, i: np.take_along_axis(np.asarray(w),
                                            np.argsort(np.asarray(i), -1), -1)
    np.testing.assert_allclose(order(w0, i0)[same], order(w1, i1)[same],
                               rtol=1e-6)


@pytest.mark.parametrize("over,wrong", [
    ({"scoring_func": "softmax"}, "softmax for sigmoid"),
    ({"routed_scaling_factor": 1.0}, "the scale left out"),
    ({"norm_topk_prob": False}, "no normalisation"),
    ({"topk_method": "greedy"}, "the bias left out of selection")])
def test_router_options_are_different_models(over, wrong):
    cfg = DeepseekV3Config.tiny(n_routed_experts=16, num_experts_per_tok=4)
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(64, 16), jnp.float32)
    bias = jnp.asarray(0.3 * rng.randn(16), jnp.float32)
    dense = lambda c, b: np.asarray(jnp.einsum(
        "tk,tke->te", *(lambda w, i: (w, jax.nn.one_hot(i, 16)))(
            *dsv3.route(c, logits, b))))
    other = dataclasses.replace(cfg, **over)
    right = dense(cfg, bias)
    got = dense(other, None if other.topk_method == "greedy" else bias)
    assert np.abs(got - right).max() > 0.05, wrong


# -- the shares add up -------------------------------------------------------

@pytest.mark.parametrize("held", [2, 4, 16])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of all ``16 / held`` shares, plus the shared
    experts counted once, equal the uncut reference's layer: each share
    routes over all 16, normalises over all 4 chosen, computes its own
    experts' part and nothing else."""
    full = DeepseekV3Config.tiny(n_routed_experts=16, num_experts_per_tok=4)
    layer = DeepseekV3MoE(full)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    p = layer.init(jax.random.PRNGKey(1), h)["params"]
    sizes = sizes_of(full)
    routed, shared, rows = REF.moe_parts(h.reshape(-1, 32), p, sizes)
    assert int(rows.sum()) == 24 * 4
    total = jnp.zeros_like(routed)
    for first in range(0, 16, held):
        cfg = dataclasses.replace(full, n_routed_experts=held,
                                  router_experts=16, first_expert=first)
        mine = {**p, **{w: p[w][first:first + held]
                        for w in ("w1", "w2", "w3")}}
        out, share_rows, _ = DeepseekV3MoE(cfg).apply({"params": mine}, h)
        np.testing.assert_array_equal(np.asarray(share_rows),
                                      np.asarray(rows[first:first + held]))
        total = total + out.reshape(-1, 32) - shared
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(routed + shared), rtol=1e-4,
                               atol=1e-5)
    assert float(jnp.abs(routed).max()) > 10 * 1e-5


# -- the engine --------------------------------------------------------------

def test_engine_never_moves_the_bias_and_publishes_the_gauges():
    """Weight decay on, a learning rate that moves every other leaf: the
    selection bias stays where it was seeded (``frozen_parameters``), and
    the registry names the held experts' load."""
    cfg = DeepseekV3Config.tiny(report_expert_load=True, **SHARE)
    model = DeepseekV3ForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "optimizer": {
            "type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.1}}},
        partition_rules=DeepseekV3ForCausalLM.partition_rules(cfg))
    flat = lambda: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                    jax.tree_util.tree_flatten_with_path(
                        engine.state.params)[0]}
    before = flat()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    after = flat()
    assert losses[-1] < losses[0]
    moved = {k for k in before if np.abs(after[k] - before[k]).max() > 0}
    frozen = {k for k in before if "e_score_correction_bias" in k}
    assert len(frozen) == 1 and np.abs(before[frozen.pop()]).max() > 0
    assert moved == {k for k in before
                     if "e_score_correction_bias" not in k}
    gauges = engine.registry.snapshot()
    assert gauges["moe_rows_max_over_mean"] >= 1
    assert 0 < gauges["moe_held_rows_over_expected"] < 4


COMPACT = dict(n_routed_experts=4, router_experts=32, first_expert=8,
               num_experts_per_tok=3, max_position_embeddings=256,
               report_expert_load=True)


@pytest.mark.parametrize("bias,share", [(0.0, 1.0), (10.0, 0.0)],
                         ids=["level_load", "every_token_on_the_held"])
def test_training_call_names_the_compact_hit_share(bias, share):
    """4 of 32 experts held, 2 x 256 tokens, top-3: a 1,536-row buffer whose
    compact form has 512 rows (``mixtral._compact_rows``). At the seeded
    router's load (about 192 held pairs a layer) both expert layers take it;
    with the selection bias sending every choice to the held experts (1,536
    pairs) both overflow onto the full buffer, and the gauge says so."""
    cfg = DeepseekV3Config.tiny(**COMPACT)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 128, (2, 256)))
    model, params = _seeded(cfg, 1, ids)
    held = jnp.zeros((32,)).at[8:12].set(bias)
    params = jax.tree_util.tree_map_with_path(
        lambda kp, p: p + held if dsv3.BIAS in str(kp[-1]) else p, params)
    loss, named = model.apply({"params": params}, ids, labels=ids)
    assert sorted(named) == ["moe_compact_hit_share",
                             "moe_held_rows_over_expected",
                             "moe_rows_max_over_mean"]
    assert float(named["moe_compact_hit_share"]) == share
    level = float(named["moe_held_rows_over_expected"])
    assert 0.5 < level < 1.5 if share else level == pytest.approx(8.0)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("family", ["deepseek_v3_half", "zaya", "mixtral"])
def test_no_compact_hit_share_without_a_compact_buffer(family):
    """Absent, not zero: a share over a quarter, ZAYA's call and Mixtral's
    have no layer with a compact buffer."""
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
    from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM

    model = {
        "deepseek_v3_half": lambda: DeepseekV3ForCausalLM(
            DeepseekV3Config.tiny(**{**COMPACT, "router_experts": 8,
                                     "first_expert": 4})),
        "zaya": lambda: ZayaForCausalLM(ZayaConfig.tiny(
            n_routed_experts=4, router_experts=8, report_expert_load=True,
            max_position_embeddings=256)),
        "mixtral": lambda: MixtralForCausalLM(MixtralConfig.tiny(
            report_expert_load=True, max_position_embeddings=256)),
    }[family]()
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 128, (2, 256)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    _, named = model.apply({"params": params}, ids, labels=ids)
    assert "moe_rows_max_over_mean" in named
    assert "moe_compact_hit_share" not in named


def test_compact_hit_gauge_by_hand():
    from deepspeed_tpu.models.mixtral import _compact_hit_gauge

    # 1,536 pairs, 4 of 32 held: 512 compact rows, the last the zero row
    rows = jnp.asarray([[100.0, 100, 100, 100], [200, 200, 100, 11],
                        [200, 200, 100, 12], [0, 0, 0, 0]])
    assert float(_compact_hit_gauge(rows, 1536, 32)[
        "moe_compact_hit_share"]) == 0.75
    assert _compact_hit_gauge(rows, 1536, 8) == {}
    assert _compact_hit_gauge(rows, 1536, None) == {}


def test_router_weights_stay_where_they_were_when_not_trainable():
    """``router_trainable=False``: the gate's gradient exists, the optimizer
    never applies it; every other leaf moves."""
    cfg = DeepseekV3Config.tiny(router_trainable=False, **SHARE)
    assert DeepseekV3ForCausalLM.frozen_parameters(cfg) == [
        "e_score_correction_bias", r"mlp/gate$"]
    assert DeepseekV3ForCausalLM.frozen_parameters(
        DeepseekV3Config.tiny()) == ["e_score_correction_bias"]
    model = DeepseekV3ForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "optimizer": {
            "type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.1}}},
        partition_rules=DeepseekV3ForCausalLM.partition_rules(cfg))
    flat = lambda: {jax.tree_util.keystr(k): np.asarray(v) for k, v in
                    jax.tree_util.tree_flatten_with_path(
                        engine.state.params)[0]}
    before = flat()
    grads = jax.grad(lambda p: model.apply({"params": p}, **batch))(
        engine.state.params)
    assert np.abs(np.asarray(_leaf(grads, "model/layers/block/mlp/gate"))
                  ).max() > 0
    engine.train_batch(batch=batch)
    after = flat()
    still = {k for k in before if not np.abs(after[k] - before[k]).max()}
    assert still == {k for k in before if k.endswith("['mlp']['gate']")
                     or "e_score_correction_bias" in k}
    assert len(still) == 2


def test_balancing_rule_moves_the_bias_against_the_load():
    """``router_bias_update_rate``: the training call names, beside its loss,
    what the published balancing rule adds to each layer's bias — minus the
    rate for an expert the step sent more than the mean number of tokens,
    plus it for one sent fewer, over ALL the router's experts — and the
    engine adds exactly that after the optimizer's update, which leaves the
    bias alone."""
    rate = 0.01
    cfg = DeepseekV3Config.tiny(router_bias_update_rate=rate, **SHARE)
    model = DeepseekV3ForCausalLM(cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "optimizer": {
            "type": "AdamW", "params": {"lr": 1e-2, "weight_decay": 0.1}}},
        # ONE device: over the eight virtual CPU devices this step (the
        # rule's scatter-add under the partitioner) aborts the process in
        # XLA:CPU about four times in ten when other workers load the
        # machine, and xdist then hangs the whole run (PR 34 met it on the
        # parent's tree too); the rule and the engine's add need no mesh
        mesh=common.cell_mesh(1),
        partition_rules=DeepseekV3ForCausalLM.partition_rules(cfg))
    path = "model/layers/block/mlp/e_score_correction_bias"
    params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    loss, named = model.apply({"params": params}, **batch)
    delta = np.asarray(named["param_deltas"][path])          # [layers, 16]
    assert set(named) == {"param_deltas"} and delta.shape == (L - 1, 16)
    assert np.all(np.isclose(np.abs(delta), rate) | (delta == 0))
    # layer by layer against the reference's router on the same input
    sizes = sizes_of(cfg)
    x = REF.dense.f32(params["model"]["embed_tokens"]["embedding"])[ids]
    first = jax.tree_util.tree_map(lambda a: a[0], _leaf(
        params, "model/layers/block"))
    with jax.default_matmul_precision("highest"):
        h = jnp.concatenate([REF._layer(
            seq, REF.dense.f32(params["model"]["layers_0"]),
            REF.dense._static(sizes), True)[0] for seq in x])
        h = REF.dense.rms_norm(
            h + jnp.concatenate([REF.attention(REF.dense.rms_norm(
                seq, first["input_layernorm"]["scale"], 1e-5),
                first["self_attn"], sizes) for seq in h.reshape(8, 16, -1)]),
            first["post_attention_layernorm"]["scale"], 1e-5)
        load = np.asarray((REF.route(h, first["mlp"], sizes) > 0).sum(0))
    assert load.sum() == ids.size * 4
    np.testing.assert_allclose(delta[0], rate * np.sign(load.mean() - load),
                               atol=1e-7)
    before = _leaf(params, path)
    engine.train_batch(batch=batch)
    after = np.asarray(_leaf(engine.state.params, path))
    np.testing.assert_allclose(after - before, delta, atol=1e-7)
    assert float(loss) > 0


def test_remat_reads_the_kept_flash_output_and_lse(monkeypatch):
    """Both kinds of layer under ``remat`` (the unrolled dense one, the
    scanned expert ones), latent attention through the Pallas kernels at
    their two widths: loss and every gradient equal the un-remat'd
    model's, so the values every remat policy keeps of the forward kernel
    are the ones the backward kernels read."""
    from tests.unit.test_model_convergence import (assert_same_loss_and_grads,
                                                   remat_loss_and_grads)

    ids = np.random.RandomState(0).randint(0, 128, (2, 48)).astype(np.int32)
    model_of = lambda remat: DeepseekV3ForCausalLM(DeepseekV3Config.tiny(
        remat=remat, attention_impl="flash", flash_block_q=16,
        flash_block_k=16))
    (loss, grads), (loss0, grads0) = remat_loss_and_grads(
        monkeypatch, model_of, ids)
    assert_same_loss_and_grads(loss, grads, loss0, grads0)


def test_unbuilt_paths_say_so():
    ids = jnp.zeros((1, 8), jnp.int32)
    for over, err in (({"q_lora_rank": 8}, NotImplementedError),
                      ({"n_group": 2, "topk_group": 2}, NotImplementedError),
                      ({"first_expert": 6, "router_experts": 8},
                       ValueError)):
        with pytest.raises(err):
            jax.eval_shape(
                DeepseekV3ForCausalLM(DeepseekV3Config.tiny(**over)).init,
                jax.random.PRNGKey(0), ids)
    model = DeepseekV3ForCausalLM(DeepseekV3Config.tiny())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    with pytest.raises(NotImplementedError):
        model.apply({"params": params}, ids, cache={})
