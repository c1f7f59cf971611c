"""Mixtral sparse-MoE family: HF logits/greedy parity, EP sharding, training
(BASELINE north star: Mixtral-8x7B expert parallel)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _tiny_mixtral_hf(seed=0):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    torch.manual_seed(seed)
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, attention_dropout=0.0)
    return transformers.MixtralForCausalLM(cfg).eval()


def test_policy_auto_match_and_logits_parity():
    torch = pytest.importorskip("torch")
    from deepspeed_tpu.module_inject import match_policy, replace_transformer_layer

    hf = _tiny_mixtral_hf()
    assert type(match_policy(hf)).__name__ == "HFMixtralLayerPolicy"
    model, params = replace_transformer_layer(hf)

    ids = np.random.RandomState(1).randint(0, 128, (2, 12))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_generate_matches_hf_greedy():
    torch = pytest.importorskip("torch")
    import deepspeed_tpu as ds

    hf = _tiny_mixtral_hf()
    engine = ds.init_inference(hf, dtype="fp32", mp_size=1)
    ids = np.random.RandomState(2).randint(0, 128, (2, 8))
    with torch.no_grad():
        ref = hf.generate(torch.tensor(ids), max_new_tokens=6,
                          do_sample=False, pad_token_id=0).numpy()[:, 8:]
    ours = np.asarray(engine.generate(ids, max_new_tokens=6, do_sample=False))
    np.testing.assert_array_equal(ours, ref)


@pytest.mark.slow
def test_training_converges_with_expert_parallelism():
    """Expert weights shard over the ``expert`` mesh axis; training through
    the engine converges and the router aux loss is finite."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
    from deepspeed_tpu.parallel import build_mesh

    cfg = MixtralConfig.tiny()
    model = MixtralForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (8, 16))}
    mesh = build_mesh(data=2, expert=4)
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "steps_per_print": 0},
        example_batch={k: v[:1] for k, v in batch.items()}, mesh=mesh,
        partition_rules=MixtralForCausalLM.partition_rules(cfg))
    # EP placement is real: the stacked expert leaves split over "expert"
    w1 = engine.state.params["model"]["layers"]["block"]["block_sparse_moe"]["w1"]
    assert "expert" in str(w1.sharding.spec)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
    assert losses[-1] < losses[0] - 0.5, losses


@pytest.mark.slow
def test_cached_decode_matches_full_forward():
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig.tiny()
    model = MixtralForCausalLM(cfg)
    B, T = 2, 10
    ids = jnp.asarray(np.random.RandomState(0).randint(0, cfg.vocab_size,
                                                       (B, T)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    full_logits = model.apply({"params": params}, ids)

    cache = model.init_cache(B, T, dtype=jnp.float32)
    key_mask = jnp.zeros((B, T), jnp.int32).at[:, :6].set(1)
    logits, cache = model.apply({"params": params}, ids[:, :6],
                                attention_mask=key_mask, cache=cache,
                                cache_index=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, :6]),
                               rtol=2e-4, atol=2e-4)
    for t in range(6, T):
        key_mask = key_mask.at[:, t].set(1)
        step_logits, cache = model.apply(
            {"params": params}, ids[:, t:t + 1], attention_mask=key_mask,
            cache=cache, cache_index=jnp.int32(t))
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(full_logits[:, t]),
                                   rtol=2e-4, atol=2e-4)


def test_training_loss_matches_hf_including_aux():
    """LM loss + router aux matches HF's (load_balancing_loss_func product of
    concatenated-layer means, aux coef applied)."""
    torch = pytest.importorskip("torch")
    from deepspeed_tpu.module_inject import replace_transformer_layer

    hf = _tiny_mixtral_hf(seed=4)
    model, params = replace_transformer_layer(hf)
    ids = np.random.RandomState(5).randint(0, 128, (2, 12))
    with torch.no_grad():
        out = hf(torch.tensor(ids), labels=torch.tensor(ids),
                 output_router_logits=True)
    ours = model.apply({"params": params}, jnp.asarray(ids),
                       labels=jnp.asarray(ids))
    np.testing.assert_allclose(float(ours), float(out.loss), rtol=2e-3)


def test_sliding_window_logits_parity():
    """Windowed Mixtral (sliding_window < seq len) converts and matches HF
    logits for sequences LONGER than the window (r3: the window is modelled,
    not refused)."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from deepspeed_tpu.module_inject import replace_transformer_layer

    torch.manual_seed(0)
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, sliding_window=8, attention_dropout=0.0)
    hf = transformers.MixtralForCausalLM(cfg).eval()
    model, params = replace_transformer_layer(hf)
    assert model.config.sliding_window == 8

    ids = np.random.RandomState(7).randint(0, 128, (2, 24))  # 3x the window
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


_REPLICATE_TOKENS_SCRIPT = r"""
# an executable loaded from the persistent compile cache makes this
# multi-device CPU child SIGABRT in the collective thunk executor: turn
# the cache off for this process, wherever the environment placed it
from deepspeed_tpu.utils.jax_compat import force_cpu_devices
force_cpu_devices(8)
import jax
jax.config.update("jax_enable_compilation_cache", False)
import numpy as np
import deepspeed_tpu as ds
from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.parallel import build_mesh

cfg = MixtralConfig.tiny()
model = MixtralForCausalLM(cfg)
rs = np.random.RandomState(0)
batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 16)),
         "labels": rs.randint(0, cfg.vocab_size, (8, 16))}
mesh = build_mesh(data=2, expert=4)
engine, *_ = ds.initialize(
    model=model,
    config={"train_batch_size": 8, "moe": {"replicate_tokens": True},
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            "steps_per_print": 0},
    example_batch={k: v[:1] for k, v in batch.items()}, mesh=mesh,
    partition_rules=MixtralForCausalLM.partition_rules(cfg))
assert engine.dp_world_size == 2  # expert axis no longer counts as DP
w1 = engine.state.params["model"]["layers"]["block"]["block_sparse_moe"]["w1"]
assert "expert" in str(w1.sharding.spec)
losses = [float(engine.train_batch(batch=batch)) for _ in range(6)]
assert losses[-1] < losses[0] - 0.5, losses
print("REPLICATE-OK", losses[0], losses[-1])
"""


@pytest.mark.slow
def test_replicate_tokens_ep_layout_trains():
    """``{"moe": {"replicate_tokens": true}}``: tokens shard over `data`
    only (replicated across the expert axis) so the MoE block needs NO
    in-layer batch reshard — the collective-light EP layout the CPU thunk
    runtime can execute in a layer scan, and the layout that avoids the r3
    'involuntary full rematerialization' SPMD warning.

    Runs in a subprocess: a SECOND multi-device-collective engine in one
    XLA:CPU process can abort in the thunk executor's cross-module
    collective rendezvous (rendezvous.cc:127 'only 1 of 2 arrived') — an
    environmental CPU-runtime limit, not a framework property; standalone
    the same program is deterministic-green."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = {**os.environ, "PYTHONPATH": repo + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    r = subprocess.run([sys.executable, "-c", _REPLICATE_TOKENS_SCRIPT],
                       env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "REPLICATE-OK" in r.stdout


def test_ep_constraints_compile_on_cpu():
    """The TPU E+D layout pins (gather tokens over `expert` at MoE entry,
    reduce-scatter at exit) must at least LOWER + PARTITION cleanly; only
    execution is TPU-gated (the CPU thunk rendezvous limitation). Compiling
    with DS_EP_CONSTRAINTS=1 proves the sharding annotations are valid and
    that the partitioner places an explicit all-gather instead of the
    'involuntary full rematerialization' fallback."""
    import os
    from unittest import mock

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
    from deepspeed_tpu.parallel import build_mesh

    with mock.patch.dict(os.environ, {"DS_EP_CONSTRAINTS": "1"}):
        cfg = MixtralConfig.tiny()
        model = MixtralForCausalLM(cfg)
        rs = np.random.RandomState(0)
        batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 16)),
                 "labels": rs.randint(0, cfg.vocab_size, (8, 16))}
        mesh = build_mesh(data=2, expert=4)
        engine, *_ = ds.initialize(
            model=model,
            config={"train_batch_size": 8,
                    "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                    "steps_per_print": 0},
            example_batch={k: v[:1] for k, v in batch.items()}, mesh=mesh,
            partition_rules=MixtralForCausalLM.partition_rules(cfg))
        compiled = engine._train_step.lower(
            engine.state,
            {"input_ids": batch["input_ids"].reshape(1, 8, 16),
             "labels": batch["labels"].reshape(1, 8, 16)},
            jax.random.PRNGKey(0)).compile()
        hlo = compiled.as_text()
        assert "all-gather" in hlo  # the explicit entry gather is placed


def test_ep_inference_parity_and_expert_placement():
    """Expert-parallel serving (reference ``inference/engine.py:194``
    ``_create_ep_parallel_group``): ``init_inference(ep_size=N)`` shards the
    stacked expert leaves over the ``expert`` mesh axis — tokens must match
    the single-device engine exactly, and the placement must be real (each
    device group holds E/ep_size experts, not a full replica)."""
    torch = pytest.importorskip("torch")
    import deepspeed_tpu as ds

    hf = _tiny_mixtral_hf()
    ids = np.random.RandomState(3).randint(0, 128, (2, 8))
    ref_engine = ds.init_inference(hf, dtype="fp32", mp_size=1)
    ref = np.asarray(ref_engine.generate(ids, max_new_tokens=6,
                                         do_sample=False))

    engine = ds.init_inference(hf, dtype="fp32", ep_size=4)
    assert engine.ep_world_size == 4
    w1 = engine.params["model"]["layers"]["block"]["block_sparse_moe"]["w1"]
    assert "expert" in str(w1.sharding.spec)
    # placement is a real split: per-device bytes = 1/ep_size of the leaf
    shard = w1.addressable_shards[0].data
    assert shard.shape[w1.ndim - 3] == w1.shape[w1.ndim - 3] // 4
    out = np.asarray(engine.generate(ids, max_new_tokens=6, do_sample=False))
    np.testing.assert_array_equal(out, ref)


def test_ep_inference_composes_with_tensor_parallel():
    """ep_size x mp_size serving on one mesh: experts over ``expert``,
    attention Megatron-split over ``model``; greedy tokens unchanged."""
    torch = pytest.importorskip("torch")
    import deepspeed_tpu as ds

    hf = _tiny_mixtral_hf()
    ids = np.random.RandomState(4).randint(0, 128, (2, 8))
    ref = np.asarray(ds.init_inference(hf, dtype="fp32")
                     .generate(ids, max_new_tokens=5, do_sample=False))
    engine = ds.init_inference(hf, dtype="fp32", mp_size=2, ep_size=2)
    assert (engine.mp_world_size, engine.ep_world_size) == (2, 2)
    out = np.asarray(engine.generate(ids, max_new_tokens=5, do_sample=False))
    np.testing.assert_array_equal(out, ref)


def test_ep_inference_rejects_quantize():
    import deepspeed_tpu as ds

    hf = _tiny_mixtral_hf()
    with pytest.raises(ValueError, match="ep_size"):
        ds.init_inference(hf, dtype="int8", ep_size=4)


def test_decode_gather_path_computes_only_touched_experts():
    """T==1 with replicated experts takes the token-gather branch: only
    the K touched experts' weights are gathered and computed — the traced
    decode step must contain NO all-E ``[B, 1, E, I]`` intermediate (the
    reference's einsum_sec_sm_ecm-class saving: E/K x less expert-weight
    traffic per decode step) — and the branch must agree numerically with
    the all-E dense path (forced by faking an active expert axis)."""
    import deepspeed_tpu.models.mixtral as mx
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig.tiny()
    E, I = cfg.num_local_experts, cfg.intermediate_size
    model = MixtralForCausalLM(cfg)
    B, P = 1, 8
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (B, P)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    cache = model.init_cache(B, P + 4, dtype=jnp.float32)
    mask = jnp.ones((B, P + 4), jnp.int32).at[:, P:].set(0)

    def step(params, tok, cache):
        return model.apply({"params": params}, tok, attention_mask=mask,
                           cache=cache, cache_index=jnp.int32(P))

    tok = ids[:, :1]
    all_e = f"{B},1,{E},{I}"

    def has_all_e_intermediate(jaxpr):
        return all_e in str(jaxpr).replace(" ", "")

    # NB: make_jaxpr caches on the function object — trace through a FRESH
    # wrapper each time or the second trace returns the first's jaxpr
    assert not has_all_e_intermediate(
        jax.make_jaxpr(lambda p, t, c: step(p, t, c))(params, tok, cache)), \
        "gather decode path did not engage (all-E intermediate present)"

    orig = mx._expert_axis_active
    mx._expert_axis_active = lambda: True  # force the all-E dense branch
    try:
        assert has_all_e_intermediate(
            jax.make_jaxpr(lambda p, t, c: step(p, t, c))(params, tok,
                                                          cache))
        out_d, _ = step(params, tok, cache)
    finally:
        mx._expert_axis_active = orig
    out_g, _ = step(params, tok, cache)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)


def test_inference_engine_registers_explicit_mesh_globally():
    """r5 advisor finding: an InferenceEngine built with an explicitly
    passed expert-sharded mesh (already matching ep_size, so no rebuild
    happened) skipped set_mesh — _expert_axis_active() then read
    get_mesh()==None and the T==1 gather fast path engaged on SHARDED
    expert weights, adding per-decode-step cross-device weight gathers.
    The engine must always register its mesh."""
    import deepspeed_tpu as ds
    import deepspeed_tpu.models.mixtral as mx
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.parallel.topology import get_mesh, set_mesh

    cfg = MixtralConfig.tiny()
    model = MixtralForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (1, 4)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    mesh = build_mesh(data=2, expert=4)
    set_mesh(None, None)  # the engine gets the mesh ONLY via the argument
    engine = ds.init_inference(model, dtype="fp32", ep_size=4, mesh=mesh,
                               params=params)
    assert engine.ep_world_size == 4
    assert get_mesh() is engine.mesh
    # the decode-layout check now sees the expert axis → gather fast path
    # stays OFF for sharded experts
    assert mx._expert_axis_active()
