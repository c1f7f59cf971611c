"""OLMoE-1B-7B through the shared Llama/Mixtral code: ``qk_norm`` and
``norm_topk_prob`` against the published ``modeling_olmoe.py`` (HF
``OlmoeForCausalLM`` at a tiny size, weights mapped by hand), the
benchmark's plain reference against the same, and both flags at their
defaults leaving Mistral's and Mixtral's parameter trees and numbers where
the parent commit had them."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM

SIZES = dict(vocab_size=128, hidden_size=32, intermediate_size=16,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=4, max_position_embeddings=64)
E, K = 8, 4


def _hf_olmoe(seed):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "OlmoeForCausalLM"):
        pytest.skip("this transformers has no OLMoE")
    torch.manual_seed(seed)
    hf = transformers.OlmoeForCausalLM(transformers.OlmoeConfig(
        **SIZES, num_experts=E, num_experts_per_tok=K,
        attention_dropout=0.0)).eval()
    with torch.no_grad():      # the norms initialise to one: make them count
        for name, p in hf.named_parameters():
            if "norm" in name:
                p.copy_(1.0 + 0.3 * torch.randn_like(p))
    return hf


def _our_tree(hf):
    """HF OLMoE's state dict as ``MixtralForCausalLM``'s scanned tree
    (torch Linear weights are [out, in]; ours [in, out])."""
    sd = {k: v.detach().numpy() for k, v in hf.state_dict().items()}
    L = SIZES["num_hidden_layers"]
    layer = lambda fmt, t=False: jnp.stack([
        sd[fmt.format(l)].T if t else sd[fmt.format(l)] for l in range(L)])
    experts = lambda proj: jnp.stack([jnp.stack([
        sd[f"model.layers.{l}.mlp.experts.{e}.{proj}.weight"].T
        for e in range(E)]) for l in range(L)])
    attn = {f"{p}_proj": {"kernel": layer(
        "model.layers.{}.self_attn." + p + "_proj.weight", True)}
        for p in "qkvo"}
    attn.update({f"{p}_norm": {"scale": layer(
        "model.layers.{}.self_attn." + p + "_norm.weight")} for p in "qk"})
    block = {
        "self_attn": attn,
        "input_layernorm": {"scale": layer(
            "model.layers.{}.input_layernorm.weight")},
        "post_attention_layernorm": {"scale": layer(
            "model.layers.{}.post_attention_layernorm.weight")},
        "block_sparse_moe": {
            "gate": {"kernel": layer("model.layers.{}.mlp.gate.weight",
                                     True)},
            "w1": experts("gate_proj"), "w3": experts("up_proj"),
            "w2": experts("down_proj")}}
    return {"model": {"embed_tokens": {
        "embedding": jnp.asarray(sd["model.embed_tokens.weight"])},
        "layers": {"block": block},
        "norm": {"scale": jnp.asarray(sd["model.norm.weight"])}},
        "lm_head": {"kernel": jnp.asarray(sd["lm_head.weight"].T)}}


def _ours(**over):
    cfg = MixtralConfig.olmoe_1b_7b(**{
        **SIZES, "num_local_experts": E, "num_experts_per_tok": K,
        "remat": False, **over})
    return cfg, MixtralForCausalLM(cfg)


@pytest.mark.parametrize("seed", [0, 1])
def test_logits_and_training_loss_match_hf_olmoe(seed):
    """q/k norm over the whole projections before RoPE, un-normalised
    top-k weights, the load-balancing loss x 0.01: ours and HF's agree."""
    torch = pytest.importorskip("torch")
    hf = _hf_olmoe(seed)
    params = _our_tree(hf)
    _, model = _ours()
    ids = np.random.RandomState(seed).randint(0, 128, (2, 16))
    with torch.no_grad():
        out = hf(torch.tensor(ids), labels=torch.tensor(ids),
                 output_router_logits=True)
    ours = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, out.logits.numpy(), rtol=2e-3,
                               atol=2e-4)
    loss = model.apply({"params": params}, jnp.asarray(ids),
                       labels=jnp.asarray(ids))
    np.testing.assert_allclose(float(loss), float(out.loss), rtol=1e-4)


@pytest.mark.parametrize("seed", [0, 1])
def test_benchmark_reference_matches_hf_olmoe(seed):
    """``benchmark/reference/olmoe.py`` IS the published forward pass:
    logits of one sequence and the training loss of a batch."""
    torch = pytest.importorskip("torch")
    from benchmark import common

    ref = common.load_file_module("reference", "olmoe")
    hf = _hf_olmoe(seed)
    params = _our_tree(hf)
    sizes = {**SIZES, "num_local_experts": E, "num_experts_per_tok": K,
             "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "head_dim": 8,
             "router_aux_loss_coef": 0.01}
    ids = np.random.RandomState(seed).randint(0, 128, (2, 16))
    with torch.no_grad():
        out = hf(torch.tensor(ids), labels=torch.tensor(ids),
                 output_router_logits=True)
    hidden, routed, _ = ref.hidden_states(params, sizes, jnp.asarray(ids[0]))
    np.testing.assert_allclose(np.asarray(ref.logits(params, hidden)),
                               out.logits[0].numpy(), rtol=2e-3, atol=2e-4)
    assert float(routed.sum()) == 16 * K * SIZES["num_hidden_layers"]
    np.testing.assert_allclose(float(ref.loss(params, sizes, ids)),
                               float(out.loss), rtol=1e-4)


@pytest.mark.parametrize("wrong", [{"norm_topk_prob": True},
                                   {"qk_norm": False}],
                         ids=["topk_renormalised", "qk_norm_left_out"])
def test_each_flag_changes_the_logits(wrong):
    """Ignoring either flag is a different model, not a rounding: ten times
    the absolute tolerance of the parity tests above (HF's 0.02-scale
    initial weights keep the layer's share of a logit small)."""
    hf = _hf_olmoe(0)
    params = _our_tree(hf)
    if wrong.get("qk_norm") is False:
        attn = params["model"]["layers"]["block"]["self_attn"]
        del attn["q_norm"], attn["k_norm"]
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 16)))
    right = _ours()[1].apply({"params": _our_tree(hf)}, ids)
    got = _ours(**wrong)[1].apply({"params": params}, ids)
    assert float(jnp.abs(got - right).max()) > 2e-3


def _paths(tree):
    return sorted("/".join(str(getattr(k, "key", k)) for k in path)
                  for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0])


# the parent commit's numbers (PR 26's tree, CPU, float32): parameter paths
# hashed, training loss and the logits' absolute sum of the tiny presets
PARENT = {
    "llama": ("3b61b1b081bb5907", 12, 6.183221340179443, 9997.1123046875),
    "mixtral": ("698e80ba65ff613c", 13, 5.560386657714844, 4813.8291015625),
}


@pytest.mark.parametrize("family", sorted(PARENT))
def test_flags_off_leave_existing_models_as_the_parent_had_them(family):
    """``qk_norm=False`` creates no parameter and ``norm_topk_prob=True``
    renormalises as before: Mistral's and Mixtral's trees, logits and
    losses are the parent commit's."""
    model = {"llama": LlamaForCausalLM(LlamaConfig.tiny(sliding_window=16)),
             "mixtral": MixtralForCausalLM(MixtralConfig.tiny())}[family]
    assert (model.config.qk_norm,
            getattr(model.config, "norm_topk_prob", True)) == (False, True)
    ids = jnp.asarray(np.random.RandomState(7).randint(
        0, 128, (2, 24)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(3), ids)["params"]
    paths = _paths(params)
    digest, count, loss, total = PARENT[family]
    assert not [p for p in paths if "q_norm" in p or "k_norm" in p]
    assert len(paths) == count
    assert hashlib.sha256("\n".join(paths).encode()).hexdigest()[:16] == digest
    logits = np.asarray(model.apply({"params": params}, ids))
    assert float(np.abs(logits).sum()) == pytest.approx(total, rel=1e-6)
    assert float(model.apply({"params": params}, ids, labels=ids)) == \
        pytest.approx(loss, rel=1e-6)


def test_qk_norm_scales_follow_the_projection_columns():
    """The two new leaves exist only when asked for, with the widths of the
    whole projections, and the partition rules shard them with the q/k
    kernels' output columns."""
    import re

    cfg, model = _ours(num_key_value_heads=2)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    attn = params["model"]["layers"]["block"]["self_attn"]
    assert attn["q_norm"]["scale"].shape == (2, 32)     # [layers, Hq * D]
    assert attn["k_norm"]["scale"].shape == (2, 16)     # [layers, Hkv * D]
    for rules in (MixtralForCausalLM.partition_rules(cfg),
                  LlamaForCausalLM.partition_rules(cfg)):
        spec = next(s for pat, s in rules
                    if re.search(pat, "self_attn/q_norm/scale"))
        assert tuple(spec) == (None, "model")


@pytest.mark.parametrize("leaf,fan_in", [("w1", 32), ("w3", 32), ("w2", 16)])
def test_per_expert_init_seeds_each_expert_over_its_own_fan_in(leaf, fan_in):
    """OLMoE's preset seeds a stacked kernel ``[E, in, out]`` as E dense
    kernels of fan-in ``in``; Mixtral's default keeps counting the expert
    axis into the fan (its trees stay the parent's, above). Same key, same
    draw: the two differ by exactly sqrt(E), and the layer's output by
    E ** 1.5, which is what lets a logit see OLMoE's 64 experts at all."""
    ids = jnp.zeros((1, 8), jnp.int32)
    trees = {flag: _ours(per_expert_init=flag)[1].init(
        jax.random.PRNGKey(5), ids)["params"]["model"]["layers"]["block"][
            "block_sparse_moe"] for flag in (True, False)}
    own, stacked = (np.asarray(trees[flag][leaf]) for flag in (True, False))
    assert own.shape[1:3] == (E, fan_in)          # [layers, E, in, out]
    np.testing.assert_allclose(own, stacked * E ** 0.5, rtol=1e-6)
    assert own.std() == pytest.approx(fan_in ** -0.5, rel=0.05)
    assert MixtralConfig.olmoe_1b_7b().per_expert_init
    assert not MixtralConfig.mixtral_8x7b().per_expert_init
