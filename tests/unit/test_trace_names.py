"""The names a profiler trace shows are part of the program's interface:
``ds.*`` scopes inside the jitted steps and the model code, a fixed ``name``
on every Pallas call. The benchmark's reducer (``benchmark/scope_reduce.py``)
and ``docs/observability.md`` match these strings, so a rename must fail here.

Scopes are checked in the lowered text of ``train_step`` and ``mixed_step``
at tiny sizes on the CPU; kernel names in each kernel's lowering for the TPU
platform (Mosaic's ``kernel_name``), which needs no chip and compiles nothing
(the block-sparse kernels, which Mosaic's block-shape rule still refuses, in
their traced program).
"""

import functools
import glob
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                              DeepseekV3ForCausalLM)
from deepspeed_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
from deepspeed_tpu.models.mellum import MellumConfig, MellumForCausalLM
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                             NemotronHForCausalLM)
from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                              KimiLinearForCausalLM)
from deepspeed_tpu.models.ouro import OuroConfig, OuroForCausalLM
from deepspeed_tpu.models.qwen3_next import (Qwen3NextConfig,
                                             Qwen3NextForCausalLM)
from deepspeed_tpu.models.sambay import SambaYConfig, SambaYForCausalLM
from deepspeed_tpu.models.sdar import SdarConfig, SdarForCausalLM
from deepspeed_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
from deepspeed_tpu.monitor import tracing
from deepspeed_tpu.ops import pallas as names

TRAIN_SCOPES = {
    "llama": ["ds.loss_and_grad", "ds.optimizer", "ds.embed", "ds.attn_proj",
              "ds.attention", "ds.mlp", "ds.lm_head_loss"],
    "mixtral": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
                "ds.attn_proj", "ds.attention", "ds.moe_router",
                "ds.moe_experts", "ds.lm_head_loss"],
    # ds.mlp is the leading dense layer, ds.moe_shared the shared experts
    "deepseek_v3": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
                    "ds.attn_proj", "ds.attention", "ds.mlp",
                    "ds.moe_router", "ds.moe_experts", "ds.moe_shared",
                    "ds.lm_head_loss"],
    # ds.cca_mix is what compressed attention adds ahead of the kernels,
    # ds.moe_skip the expert that computes nothing
    "zaya": ["ds.loss_and_grad", "ds.optimizer", "ds.embed", "ds.attn_proj",
             "ds.cca_mix", "ds.attention", "ds.moe_router", "ds.moe_experts",
             "ds.moe_skip", "ds.lm_head_loss"],
    # a learned selection: the indexer, the exact selection, the indexer's
    # loss; ds.attention is the core under the selection
    "keye": ["ds.loss_and_grad", "ds.optimizer", "ds.embed", "ds.attn_proj",
             "ds.sa_index", "ds.sa_select", "ds.attention", "ds.sa_loss",
             "ds.moe_router", "ds.moe_experts", "ds.lm_head_loss"],
    # a decoder-hybrid-decoder: ds.ssm_scan is the selective scan alone,
    # ds.ssm_mix the rest of a Mamba layer, ds.gmu the gated memory units,
    # ds.da_mix what differential attention adds behind the core
    "sambay": ["ds.loss_and_grad", "ds.optimizer", "ds.embed", "ds.ssm_mix",
               "ds.ssm_scan", "ds.attn_proj", "ds.attention", "ds.da_mix",
               "ds.gmu", "ds.mlp", "ds.lm_head_loss"],
    # a pattern of layer kinds over Mixtral's block: each block under its
    # kind's outer scope, the two rotary tables under their own
    "mellum": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
               "ds.rope_tables", "ds.layer_window", "ds.layer_full",
               "ds.attn_proj", "ds.attention", "ds.moe_router",
               "ds.moe_experts", "ds.lm_head_loss"],
    # a pattern of layer kinds of different head counts behind a dense
    # layer: ds.layer_dense is the leading block (its feed-forward ds.mlp),
    # ds.attn_gate the head gates' product, ds.moe_shared the shared expert
    "laguna": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
               "ds.rope_tables", "ds.layer_dense", "ds.layer_window",
               "ds.layer_full", "ds.attn_proj", "ds.attention",
               "ds.attn_gate", "ds.mlp", "ds.moe_router", "ds.moe_experts",
               "ds.moe_shared", "ds.lm_head_loss"],
    # every layer ONE branch under its kind's outer scope: ds.layer_mamba a
    # scalar-decay state-space mixer (ds.ssm_scan its recurrence in the
    # duality form, ds.ssm_mix the rest), ds.layer_full an attention layer
    # without rotation, ds.layer_moe the experts beside the shared one
    "nemotron_h": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
                   "ds.layer_mamba", "ds.layer_full", "ds.layer_moe",
                   "ds.ssm_mix", "ds.ssm_scan", "ds.attn_proj",
                   "ds.attention", "ds.moe_router", "ds.moe_experts",
                   "ds.moe_shared", "ds.lm_head_loss"],
    # two mixers by LIST over deepseek_v3.py's feed-forward parts: each block
    # under its mixer's outer scope (ds.layer_kda / ds.layer_mla) and, inside
    # it, the name other stacks give its kind (ds.layer_dense the leading
    # block, ds.layer_full a latent-attention one); ds.kda_rule is the
    # chunked delta rule under a decay a channel alone, ds.kda_mix the rest
    # of a KDA mixer but its projections
    "kimi_linear": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
                    "ds.layer_kda", "ds.layer_mla", "ds.layer_dense",
                    "ds.layer_full", "ds.attn_proj", "ds.kda_mix",
                    "ds.kda_rule", "ds.attention", "ds.mlp",
                    "ds.moe_router", "ds.moe_experts", "ds.moe_shared",
                    "ds.lm_head_loss"],
    # two mixers a period over Mixtral's expert layer: each block under its
    # kind's outer scope; ds.gdn_rule is the chunked delta rule alone,
    # ds.gdn_mix the rest of a delta-rule mixer, ds.attn_gate the full
    # layer's output gate, ds.moe_shared the gated shared expert
    "qwen3_next": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
                   "ds.layer_gdn", "ds.layer_full", "ds.attn_proj",
                   "ds.gdn_mix", "ds.gdn_rule", "ds.attention",
                   "ds.attn_gate", "ds.moe_router", "ds.moe_experts",
                   "ds.moe_shared", "ds.lm_head_loss"],
    # one stack run four times over shared weights: ds.loop_stack is the
    # loop over the passes beyond the passes' own scopes, ds.exit_gate the
    # gate after every pass and the mixing of the passes' losses
    "ouro": ["ds.loss_and_grad", "ds.optimizer", "ds.embed",
             "ds.loop_stack", "ds.attn_proj", "ds.attention", "ds.mlp",
             "ds.lm_head_loss", "ds.exit_gate"],
    # block diffusion's training pass over Mixtral's stack: ds.bd_noise is
    # the checksum, the draws, the masking and the doubled sequence,
    # ds.bd_gather the noised half taken before the head
    "sdar": ["ds.loss_and_grad", "ds.optimizer", "ds.embed", "ds.bd_noise",
             "ds.attn_proj", "ds.attention", "ds.moe_router",
             "ds.moe_experts", "ds.bd_gather", "ds.lm_head_loss"],
}
#: what every family names besides: the engine's cast of the master weights,
#: the loop over the layers, the block's two pre-norms and residual sums
for _scopes in TRAIN_SCOPES.values():
    _scopes += ["ds.param_cast", "ds.layer_stack", "ds.norm", "ds.residual"]
SERVE_SCOPES = ["ds.mixed_step", "ds.embed", "ds.attn_proj", "ds.kv_append",
                "ds.attention", "ds.mlp", "ds.lm_head", "ds.sample"]


#: a tiny remat'ed model of each family, as its step names its scopes
TRAIN_MODELS = {
    "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny(sliding_window=16)),
    "mixtral": lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)),
    "deepseek_v3": lambda: DeepseekV3ForCausalLM(
        DeepseekV3Config.tiny(remat=True)),
    "zaya": lambda: ZayaForCausalLM(ZayaConfig.tiny(remat=True)),
    "keye": lambda: MixtralForCausalLM(MixtralConfig.tiny(
        remat=True, router_experts=16, first_expert=4,
        sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                       q_chunk_size=16, kv_chunk_size=16, topk=8))),
    "sambay": lambda: SambaYForCausalLM(SambaYConfig.tiny(remat=True)),
    "mellum": lambda: MellumForCausalLM(MellumConfig.tiny(remat=True)),
    "laguna": lambda: LagunaForCausalLM(LagunaConfig.tiny(remat=True)),
    "nemotron_h": lambda: NemotronHForCausalLM(NemotronHConfig.tiny(
        remat=True)),
    "qwen3_next": lambda: Qwen3NextForCausalLM(Qwen3NextConfig.tiny(
        remat=True)),
    "kimi_linear": lambda: KimiLinearForCausalLM(KimiLinearConfig.tiny(
        num_hidden_layers=5, remat=True)),
    "ouro": lambda: OuroForCausalLM(OuroConfig.tiny(remat=True,
                                                    loss_chunk=64)),
    "sdar": lambda: SdarForCausalLM(SdarConfig.tiny(remat=True,
                                                    loss_chunk=16)),
}
FAMILIES = list(TRAIN_SCOPES)
assert set(FAMILIES) == set(TRAIN_MODELS)


@functools.lru_cache(maxsize=None)
def train_text(family):
    """The family's engine's ``train_step``, lowered. One engine a family a
    PROCESS and only for the families a worker is asked about: ``--dist
    load`` deals a file's cases out across the workers, and a module's
    fixture that built all thirteen engines was built in every one of them
    (PR 69: 717 of this file's 890 cold test-seconds). Nothing runs, so the
    parameters are zeros of the shapes ``init`` gives: the engine compiles
    no ``init`` program (half an engine's cost), and the step lowers to the
    same text, character for character, as over the engine's own."""
    batch = {"input_ids": np.zeros((8, 32), np.int32),
             "labels": np.zeros((8, 32), np.int32)}
    example = {k: v[:1] for k, v in batch.items()}
    model = TRAIN_MODELS[family]()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            **example)["params"]
    engine, *_ = ds.initialize(
        model=model, example_batch=example,
        model_parameters=jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype), shapes),
        config={"train_batch_size": 8, "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    return engine._train_step.lower(
        engine.state, engine._shape_batch(batch),
        jax.random.PRNGKey(0)).as_text(debug_info=True)


@pytest.mark.parametrize("family,scope", [
    (f, s) for f, scopes in TRAIN_SCOPES.items() for s in scopes])
def test_train_step_names_its_scopes(family, scope):
    assert re.search(re.escape(scope) + r"\b", train_text(family))


@pytest.mark.parametrize("family", FAMILIES)
def test_jitted_steps_are_named_like_the_kernels(family):
    """The module's name is in the compile cache's key; the scopes inside
    are metadata and are not. Were the steps still ``train_step`` and
    ``mixed_step``, an executable cached before the scopes existed would be
    reused, and a trace of it would show none of them; the names' version
    in the module's name does the same for every later name."""
    n = tracing.NAMES_VERSION
    assert f"module @jit_ds_train_step_n{n} " in train_text(family)


def test_the_mixed_step_is_named_like_the_kernels(mixed_text):
    assert f"module @jit_ds_mixed_step_n{tracing.NAMES_VERSION} " in mixed_text


def trace_names():
    """Every ``ds.*`` scope and every span name the package spells, sorted:
    string literals ``"ds.<name>"`` and the first argument of ``.span(``."""
    scopes, spans = set(), set()
    root = os.path.dirname(ds.__file__)
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        scopes |= set(re.findall(r'"(ds\.[a-z_0-9]+)"', text))
        spans |= set(re.findall(r'\.span\(\s*"([a-z_:]+)"', text))
    return sorted(scopes), sorted(spans)


#: ``tracing.NAMES_VERSION`` beside the digest of the names it stands for
#: (PR 41 added ``ds.ssm_scan``, ``ds.ssm_mix``, ``ds.gmu`` and ``ds.da_mix``
#: under version 3: they stand only in ``models/sambay.py``'s step, which no
#: cache held before them, and the six older cells' steps keep their module
#: name, their lowered text and their cache entries; PR 49 added
#: ``ds.layer_window``, ``ds.layer_full`` and ``ds.rope_tables`` the same way:
#: they stand only in ``models/mellum.py``'s step; PR 52 added
#: ``ds.layer_gdn``, ``ds.gdn_mix``, ``ds.gdn_rule`` and ``ds.attn_gate``,
#: which stand only in ``models/qwen3_next.py``'s step; PR 54 added the host
#: spans of set-up — ``init``, ``init_shapes``, ``init_params``,
#: ``init_opt_state``, ``init_step``, ``cost_capture`` and ``setup``: a host
#: span stands in no lowered step, so no cell's module name, lowered text or
#: cache entry changes, and a trace taken before them lacks only events no
#: reader of that time asked for; PR 56 added ``ds.loop_stack`` and
#: ``ds.exit_gate``, which stand only in ``models/ouro.py``'s step; PR 58
#: added ``ds.bd_noise`` and ``ds.bd_gather``, which stand only in
#: ``models/sdar.py``'s step; PR 63 added ``ds.layer_dense``, which stands
#: only in ``models/laguna.py``'s step, whose ``ds.attn_gate`` is PR 52's
#: name; PR 66 added ``ds.layer_mamba`` and ``ds.layer_moe``, which stand only
#: in ``models/nemotron_h.py``'s step, whose ``ds.ssm_scan`` and ``ds.ssm_mix``
#: are PR 41's names and ``ds.layer_full`` PR 49's; PR 68 added
#: ``ds.layer_kda``, ``ds.layer_mla``, ``ds.kda_mix`` and ``ds.kda_rule``,
#: which stand only in ``models/kimi_linear.py``'s step, whose
#: ``ds.layer_dense`` is PR 63's name and ``ds.layer_full`` PR 49's; PR 70
#: added the host span ``step_cost``, published beside ``setup``: a host
#: span stands in no lowered step, as PR 54's)
NAMES_PIN = (3, "4e27b79c87fe5e07")


def test_names_version_is_raised_with_the_names():
    """The compile cache's key strips the names and keeps the version: a
    name added or renamed without raising ``tracing.NAMES_VERSION`` would
    be read back from the cache as the old one. Raise the version, then
    put it and the new digest here."""
    scopes, spans = trace_names()
    assert {"ds.param_cast", "ds.layer_stack", "ds.norm", "ds.residual",
            "ds.sa_index", "ds.sa_select", "ds.sa_loss", "ds.ssm_scan",
            "ds.ssm_mix", "ds.gmu", "ds.da_mix", "ds.layer_window",
            "ds.layer_full", "ds.rope_tables", "ds.layer_gdn", "ds.gdn_mix",
            "ds.gdn_rule", "ds.attn_gate", "ds.loop_stack",
            "ds.exit_gate", "ds.bd_noise", "ds.bd_gather",
            "ds.layer_dense", "ds.layer_mamba", "ds.layer_moe",
            "ds.layer_kda", "ds.layer_mla", "ds.kda_mix",
            "ds.kda_rule"} <= set(scopes) \
        and {"counters", "init", "init_shapes", "init_params",
             "init_opt_state", "init_step", "cost_capture",
             "setup", "step_cost"} <= set(spans)
    digest = hashlib.sha256("\n".join(scopes + spans).encode()).hexdigest()
    assert (tracing.NAMES_VERSION, digest[:16]) == NAMES_PIN


@pytest.mark.parametrize("family,over,gauges", [
    ("mixtral", {"report_expert_load": True},
     ["moe_rows_max_over_mean", "moe_rows_min_over_mean"]),
    ("mixtral", {}, []),
    ("llama", {}, []),
    ("deepseek_v3", {"report_expert_load": True, "router_experts": 16,
                     "first_expert": 8},
     ["moe_held_rows_over_expected", "moe_rows_max_over_mean"]),
    ("deepseek_v3", {"report_expert_load": True, "n_routed_experts": 4,
                     "router_experts": 32, "first_expert": 8,
                     "num_experts_per_tok": 4},
     ["moe_compact_hit_share", "moe_held_rows_over_expected",
      "moe_rows_max_over_mean"]),
    ("deepseek_v3", {}, [])],
    ids=["mixtral_reporting", "mixtral", "llama", "deepseek_v3_reporting",
         "deepseek_v3_compact", "deepseek_v3"])
def test_moe_load_gauges_are_published_by_name(family, over, gauges):
    """The train engine's registry names how evenly the router spread the
    step's (token, expert) pairs (docs/observability.md), where the model is
    configured to report it: the training call names the two scalars beside
    its loss, the fused step hands them back, and the engine sets gauges of
    those names after the compile-carrying first step, where the host has
    waited anyway. Nothing else publishes anything."""
    cfg = {"llama": LlamaConfig.tiny, "mixtral": MixtralConfig.tiny,
           "deepseek_v3": DeepseekV3Config.tiny,
           "zaya": ZayaConfig.tiny}[family](
        **({"num_local_experts": 8, "remat": True} if family == "mixtral"
           else {}), **over)
    model = {"llama": LlamaForCausalLM, "mixtral": MixtralForCausalLM,
             "deepseek_v3": DeepseekV3ForCausalLM,
             "zaya": ZayaForCausalLM}[family](cfg)
    ids = np.random.RandomState(0).randint(0, 128, (8, 32)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    params = jax.tree_util.tree_map(np.asarray, engine.state.params)
    engine.train_batch(batch=batch)
    found = engine.registry.snapshot()
    # (no expert axis: no moe_chip_rows_max_over_mean either)
    assert sorted(k for k in found if k.startswith("moe_")) == gauges
    if family in ("deepseek_v3", "zaya") and gauges:
        # 8 of the router's 16 experts are held: about half the pairs (a
        # tiny random top-1 router is far from level: zaya's reads 0.1-2)
        assert 0 < found["moe_held_rows_over_expected"] < 2.2
        if "moe_compact_hit_share" in gauges:
            # 4 of 32 held: 256 tokens x top-4 sort 1,024 rows, the compact
            # buffer has 512, a level load is 128 a layer
            assert found["moe_compact_hit_share"] == 1.0
        elif family == "deepseek_v3":
            assert 0.5 < found["moe_held_rows_over_expected"] < 1.5
        else:
            assert 0 <= found["moe_skip_share"] < 1
        assert found["moe_rows_max_over_mean"] >= 1.0
    elif gauges:
        _, sown = jax.jit(lambda p: model.apply(
            {"params": p}, **batch, mutable=["intermediates"]))(params)
        rows = np.sum([np.asarray(v).reshape(-1, 8).sum(0) for v in
                       jax.tree_util.tree_leaves(sown)], 0)  # [E]
        assert rows.sum() == 2 * ids.size * 2    # layers x tokens x top-2
        assert found[gauges[0]] == pytest.approx(rows.max() / rows.mean())
        assert found[gauges[1]] == pytest.approx(rows.min() / rows.mean())
        assert found[gauges[1]] <= 1.0 <= found[gauges[0]]


#: the families whose step takes the chunked per-token loss
#: (``ouro.token_nll``), which the head's whole-logits loss is no part of
CHUNKED_LOSS = ("ouro", "sdar")


@pytest.mark.parametrize("family", FAMILIES)
def test_head_loss_backward_rule_stands_under_its_scope(family):
    """``layers.cross_entropy_loss`` is a ``custom_vjp``: the operations of
    its backward rule -- the one pass that makes the logits' cotangent and
    the barrier that holds it -- carry the CALLER's ``ds.lm_head_loss`` and
    the backward pass's mark, so ``train.head_loss_share`` reads the whole
    head and ``train.unnamed_share`` nothing of it."""
    text = train_text(family)
    held = re.findall(r'"([^"]*)/optimization_barrier"', text)
    of_the_loss = [n for n in held if "ds.lm_head_loss" in n]
    if family in CHUNKED_LOSS:
        assert not of_the_loss
        return
    assert len(set(of_the_loss)) == 1
    path = of_the_loss[0]
    # what scope_reduce.scope_of and phase_of read: the innermost ds.* name,
    # and the marks of the backward pass with none of a replay
    assert re.findall(r"ds\.[a-z_0-9]+", path)[-1] == "ds.lm_head_loss"
    assert "ds.loss_and_grad" in path and "transpose(" in path
    assert "rematted_computation" not in path
    for op in ("exp", "sub", "mul", "convert_element_type"):
        assert f'"{path}/{op}"' in text


def test_backward_and_recompute_leave_their_marks():
    """What ``scope_reduce.phase_of`` tells the phases apart by."""
    text = train_text("mixtral")
    assert "transpose(jvp(" in text
    assert "rematted_computation" in text


@pytest.fixture(scope="module")
def mixed_text():
    model = LlamaForCausalLM(LlamaConfig.tiny())
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    srv = ds.init_serving(model, params=params,
                          config={"dtype": "fp32"},
                          serving_config=ds.ServingConfig(
                              max_batch_size=2, num_blocks=16, block_size=8,
                              max_model_len=64, prefill_chunk_tokens=8))
    srv.submit(list(range(1, 12)), max_new_tokens=2)
    srv.run()
    (width, fn), = srv._mixed_fns.items()
    R, T = srv.config.max_batch_size, width
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    args = (srv.engine.params, srv.pool, jnp.asarray(srv._tables),
            i32(1, T), i32(1, T), i32(1, T), i32(R), i32(R), i32(R), i32(R),
            jnp.zeros((R,), bool), jax.random.PRNGKey(0))
    return fn.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SERVE_SCOPES)
def test_mixed_step_names_its_scopes(mixed_text, scope):
    assert re.search(re.escape(scope) + r"\b", mixed_text)


# -- kernels -----------------------------------------------------------------

H, HKV, D, BF16 = 8, 2, 128, jnp.bfloat16


def _on_v5e(patch):
    """``flash_attention.fused_backward`` asks the device kind, which is the
    CPU's here: answer for the chip, where a head's dQ stays in VMEM."""
    from deepspeed_tpu.ops.pallas import grouped_matmul

    patch.setattr(grouped_matmul, "device_kind", lambda: "TPU v5 lite")


def _flash(bwd, fused=False):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = ((1, 512, H, D), BF16)
    fwd = functools.partial(flash_attention, causal=True, interpret=False,
                            force_pallas=True, window=256)
    if not bwd:
        return fwd, [q, q, q]
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    grad = jax.grad(loss, argnums=(0, 1, 2))
    if not fused:   # the two kernels: a dQ too long for VMEM, an unknown chip
        return grad, [q, q, q]

    def traced_for_v5e(*args):
        with pytest.MonkeyPatch.context() as patch:
            _on_v5e(patch)
            return grad(*args)
    return traced_for_v5e, [q, q, q]


def _ragged():
    from deepspeed_tpu.ops.pallas.ragged_attention import \
        ragged_paged_attention

    pages, row = ((64, HKV, 16, D), BF16), ((4,), jnp.int32)
    fn = functools.partial(ragged_paged_attention, interpret=False,
                           force_pallas=True)
    return fn, [((24, H, D), BF16), pages, pages, ((4, 8), jnp.int32),
                row, row, row, row]


def _decode():
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    cache = ((2, HKV, 512, D), BF16)
    fn = functools.partial(decode_attention, interpret=False,
                           force_pallas=True)
    return fn, [((2, H, D), BF16), cache, cache, ((), jnp.int32)]


def _paged(prefill):
    from deepspeed_tpu.ops.pallas import decode_attention as mod

    pages = ((64, HKV, 16, D), BF16)
    tables, lens = ((2, 8), jnp.int32), ((2,), jnp.int32)
    if prefill:
        fn = functools.partial(mod.paged_prefill_attention, interpret=False,
                               force_pallas=True)
        return fn, [((2, 16, H, D), BF16), pages, pages, tables, lens, lens]
    fn = functools.partial(mod.paged_decode_attention, interpret=False,
                           force_pallas=True)
    return fn, [((2, H, D), BF16), pages, pages, tables, lens]


def _quant_matmul():
    from deepspeed_tpu.ops.pallas.quant_matmul import quant_matmul

    fn = functools.partial(quant_matmul, mode="int8", interpret=False)
    return fn, [((32, 512), BF16), ((512, 512), jnp.int8),
                ((1, 512), jnp.float32)]


def _int8_matmul():
    from deepspeed_tpu.ops.pallas.int8_matmul import int8_matmul

    fn = functools.partial(int8_matmul, interpret=False)
    return fn, [((32, 512), BF16), ((512, 512), jnp.int8),
                ((512,), jnp.float32)]


def _fused_adam():
    from deepspeed_tpu.ops.pallas.fused_adam import _run_leaf

    fn = functools.partial(_run_leaf, b1=0.9, b2=0.999, eps=1e-8,
                           weight_decay=0.1, adam_w_mode=True,
                           interpret=False)
    return fn, [((64, 1024), jnp.float32)] * 4 + [((3,), jnp.float32)]


def _block_sparse(bwd):
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        sparse_attention

    q = ((1, 512, 4, D), BF16)
    fwd = functools.partial(sparse_attention, causal=True, interpret=True,
                            force_pallas=True,
                            layout=np.ones((4, 4, 4), np.int64))
    if not bwd:
        return fwd, [q, q, q]
    loss = lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), [q, q, q]


def _sa_probs(bwd):
    from deepspeed_tpu.ops.pallas.sa_probs import index_kl

    q = ((1, 512, H, D), BF16)
    fwd = functools.partial(index_kl, interpret=False)
    args = [q, q, ((1, H, 512), jnp.float32), ((1, 512, 512), jnp.float32),
            ((1, 512, 512), jnp.int8)]
    return (jax.grad(fwd, argnums=3) if bwd else fwd), args


def _sa_index(bwd):
    from deepspeed_tpu.ops.pallas.sa_index import index_scores

    fwd = functools.partial(index_scores, interpret=False)
    args = [((1, 512, 2, 64), BF16), ((1, 512, 64), BF16),
            ((1, 512, 2), jnp.float32)]
    if not bwd:
        return fwd, args
    loss = lambda qi, ki, w: jnp.tril(fwd(qi, ki, w)[0]).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), args


def _ssm_scan(bwd):
    from deepspeed_tpu.ops.pallas.selective_scan import selective_scan

    seq, bc = ((1, 256, 256), BF16), ((1, 256, 16), BF16)
    args = [seq, ((1, 256, 256), jnp.float32), ((256, 16), jnp.float32), bc,
            bc, ((256,), jnp.float32)]
    fwd = functools.partial(selective_scan, interpret=False)
    if not bwd:
        return fwd, args
    return jax.grad(lambda *a: fwd(*a).sum(), argnums=tuple(range(6))), args


def _moe_gmm(outer):
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm

    rows, groups = ((512, 256), BF16), ((4,), jnp.int32)
    if outer:
        return functools.partial(gm.tgmm, rows=256, cols=256,
                                 interpret=False), \
            [rows, rows, groups]
    return functools.partial(gm.gmm, rows=256, cols=256, interpret=False), \
        [rows, ((4, 256, 256), BF16), groups]


def _gdn_rule(bwd):
    from deepspeed_tpu.ops.pallas import gdn_rule

    seq, row = ((1, 256, 2, D), BF16), ((1, 2, 4, 64), jnp.float32)
    args = [seq, seq, seq, row, row, ((1, 2, 4, 64, 64), jnp.float32)]
    fwd = functools.partial(gdn_rule.chunk_rule,
                            tiling=gdn_rule.Tiling(2, 2), interpret=False)
    if not bwd:
        return fwd, args
    return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(6))), args


def _gdn_mix(gate, bwd):
    """The mixer around the rule at two key heads of 128 + 128 + 256 + 256
    columns: ``premix`` (with ``gate`` reading its handle, so that ``dz``
    has somewhere to come from) or ``gate`` alone."""
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.ops.pallas import gdn_mix

    heads, tiling = gdn_mix.Heads(2, D, 2, D, 4), gdn_mix.Tiling(128)
    args = [((1, 256, 2 * 6 * D), BF16), ((4, 2 * 4 * D), jnp.float32),
            ((D,), jnp.float32)]

    def fwd(qkvz, taps, scale):
        q, k, v, z = gdn_mix.premix(qkvz, taps, heads, qn._conv_act,
                                    qn._unit_length, tiling, interpret=False)
        if not gate:
            return q.astype(jnp.float32).sum() + (k + v).astype(
                jnp.float32).sum()
        return gdn_mix.gate(v, z, qkvz, scale, 1e-6, heads, tiling,
                            interpret=False).astype(jnp.float32).sum()
    return (jax.grad(fwd, argnums=(0, 1, 2)) if bwd else fwd), args


def _selection():
    from deepspeed_tpu.models.indexed_attention import select_mask

    return functools.partial(select_mask, topk=64), \
        [((1, 512, 512), jnp.float32)]


KERNELS = {
    names.FLASH_FWD: ("ds_flash_fwd", lambda: _flash(False)),
    names.FLASH_BWD: ("ds_flash_bwd", lambda: _flash(True, fused=True)),
    names.FLASH_BWD_DQ: ("ds_flash_bwd_dq", lambda: _flash(True)),
    names.FLASH_BWD_DKV: ("ds_flash_bwd_dkv", lambda: _flash(True)),
    names.RAGGED_PAGED_ATTENTION: ("ds_ragged_paged_attention", _ragged),
    names.DECODE_ATTENTION: ("ds_decode_attention", _decode),
    names.PAGED_DECODE_ATTENTION: ("ds_paged_decode_attention",
                                   lambda: _paged(False)),
    names.PAGED_PREFILL_ATTENTION: ("ds_paged_prefill_attention",
                                    lambda: _paged(True)),
    names.QUANT_MATMUL: ("ds_quant_matmul", _quant_matmul),
    names.INT8_MATMUL: ("ds_int8_matmul", _int8_matmul),
    names.FUSED_ADAM: ("ds_fused_adam", _fused_adam),
    names.BLOCK_SPARSE_FWD: ("ds_block_sparse_fwd",
                             lambda: _block_sparse(False)),
    names.BLOCK_SPARSE_BWD_DQ: ("ds_block_sparse_bwd_dq",
                                lambda: _block_sparse(True)),
    names.BLOCK_SPARSE_BWD_DKV: ("ds_block_sparse_bwd_dkv",
                                 lambda: _block_sparse(True)),
    names.SA_PROBS: ("ds_sa_probs", lambda: _sa_probs(False)),
    names.SA_PROBS_BWD: ("ds_sa_probs_bwd", lambda: _sa_probs(True)),
    names.SA_INDEX_FWD: ("ds_sa_index_fwd", lambda: _sa_index(False)),
    names.SA_INDEX_BWD_DQ: ("ds_sa_index_bwd_dq", lambda: _sa_index(True)),
    names.SA_INDEX_BWD_DK: ("ds_sa_index_bwd_dk", lambda: _sa_index(True)),
    names.SSM_SCAN_FWD: ("ds_ssm_scan_fwd", lambda: _ssm_scan(False)),
    names.SSM_SCAN_BWD: ("ds_ssm_scan_bwd", lambda: _ssm_scan(True)),
    names.MOE_GMM: ("ds_moe_gmm", lambda: _moe_gmm(False)),
    names.MOE_GMM_T: ("ds_moe_gmm_t", lambda: _moe_gmm(True)),
    names.GDN_RULE_FWD: ("ds_gdn_rule_fwd", lambda: _gdn_rule(False)),
    names.GDN_RULE_BWD: ("ds_gdn_rule_bwd", lambda: _gdn_rule(True)),
    names.GDN_PREMIX_FWD: ("ds_gdn_premix_fwd",
                           lambda: _gdn_mix(False, False)),
    names.GDN_PREMIX_BWD: ("ds_gdn_premix_bwd",
                           lambda: _gdn_mix(False, True)),
    names.GDN_GATE_FWD: ("ds_gdn_gate_fwd", lambda: _gdn_mix(True, False)),
    names.GDN_GATE_BWD: ("ds_gdn_gate_bwd", lambda: _gdn_mix(True, True)),
}


#: the values every remat policy keeps (``layers.resolve_remat_policy``):
#: ``checkpoint_name``s, not kernels — the flash forward's two, a learned
#: selection's bit-packed mask and its loss's row statistics — with a program
#: that names each
CHECKPOINT_NAMES = {names.FLASH_OUT: ("ds_flash_out", lambda: _flash(True)),
                    names.FLASH_LSE: ("ds_flash_lse", lambda: _flash(True)),
                    names.SA_MASK: ("ds_sa_mask", _selection),
                    names.SA_KL_ROWS: ("ds_sa_kl_rows",
                                       lambda: _sa_probs(True))}


def _offering(model):
    """``(gradient of the model's loss, its parameters' shapes)``."""
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    return jax.grad(lambda p: model.apply({"params": p}, ids, labels=ids)), \
        params


def _offering_llama():
    return _offering(LlamaForCausalLM(LlamaConfig.tiny(remat=True)))


def _offering_mixtral():
    return _offering(MixtralForCausalLM(MixtralConfig.tiny(remat=True)))


def _offering_zaya():
    return _offering(ZayaForCausalLM(ZayaConfig.tiny(remat=True)))


def _offering_nemotron_h():
    return _offering(NemotronHForCausalLM(NemotronHConfig.tiny(remat=True)))


def _offering_kimi_linear():
    return _offering(KimiLinearForCausalLM(KimiLinearConfig.tiny(
        num_hidden_layers=5, remat=True)))


def _offering_qwen3_next():
    """The delta rule's kernels as on the chip (the choosers answered with a
    tiling, the kernels lowered, not interpreted: nothing runs)."""
    from deepspeed_tpu.models import qwen3_next as qn
    from deepspeed_tpu.ops.pallas import gdn_mix, gdn_rule

    model = qn.Qwen3NextForCausalLM(qn.Qwen3NextConfig.tiny(
        num_hidden_layers=4, remat=True))
    ids = jnp.zeros((1, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]

    def grad(p):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(qn, "_rule_tiling",
                          lambda *a: gdn_rule.Tiling(2, 2))
            patch.setattr(qn, "_mix_tiling", lambda *a: gdn_mix.Tiling(16))
            return jax.grad(lambda p: model.apply(
                {"params": p}, ids, labels=ids))(p)
    return grad, params


#: the values a remat'ed block OFFERS its policy (``layers.keep_for_room``
#: keeps what the engine's budget has room for): ``checkpoint_name``s that
#: stand in a program only where they were kept, with a model that offers each
OFFERED_NAMES = {
    names.REMAT_MLP: ("ds_mlp_gate_up", _offering_llama),
    names.REMAT_QKV: ("ds_attn_qkv", _offering_llama),
    names.REMAT_ATTN_OUT: ("ds_attn_o_proj", _offering_mixtral),
    names.REMAT_MOE_UP: ("ds_moe_gate_up", _offering_mixtral),
    names.REMAT_MOE_ROWS: ("ds_moe_rows", _offering_mixtral),
    names.REMAT_GDN_RULE: ("ds_gdn_rule_kept", _offering_qwen3_next),
    names.REMAT_GDN_QKVZ: ("ds_gdn_qkvz", _offering_qwen3_next),
    names.REMAT_GDN_MIX: ("ds_gdn_mix_out", _offering_qwen3_next),
    names.REMAT_CCA_MIX: ("ds_cca_mix_out", _offering_zaya),
    names.REMAT_ROUTER: ("ds_moe_router_kept", _offering_zaya),
    names.REMAT_MOE_OUT: ("ds_moe_out", _offering_zaya),
    names.REMAT_SSM_IN: ("ds_ssm_in_proj", _offering_nemotron_h),
    names.REMAT_KDA_RULE: ("ds_kda_rule_out", _offering_kimi_linear),
}


def test_every_kernel_name_is_listed():
    constants = {v for k, v in vars(names).items()
                 if k.isupper() and isinstance(v, str)}
    assert constants == set(KERNELS) | set(CHECKPOINT_NAMES) \
        | set(OFFERED_NAMES)


@pytest.mark.parametrize("constant", sorted(OFFERED_NAMES))
def test_offered_name_stands_where_it_is_kept_and_nowhere_else(constant):
    """Under a budget with room the model's gradient names the value by
    this spelling (the policy finds it by it); with none the program holds
    no such equation -- it is the program it was before names were offered
    (but for the delta rule's kernel, which names its output and boundary
    states wherever it runs, as the flash forward names its pair: there the
    name tells nothing apart)."""
    from deepspeed_tpu.models.layers import remat_room

    spelled, case = OFFERED_NAMES[constant]
    assert constant == spelled
    named = lambda text: text.count(f"name[name={constant}]")
    without, kept, text = _offering_texts(case)
    assert (named(without) == 0) == (constant != names.REMAT_GDN_RULE)
    assert constant in kept and named(text) > 0


@functools.lru_cache(maxsize=None)
def _offering_texts(case):
    """``(the gradient's jaxpr with no room stated, the names kept under a
    room for everything, the jaxpr under that room)`` of an offering model:
    traced once a model a process, whichever of its names is asked about."""
    from deepspeed_tpu.models.layers import remat_room

    grad, params = case()
    without = str(jax.make_jaxpr(grad)(params))
    grad, params = case()       # jax keeps a function's trace
    with remat_room(10 ** 9) as kept:
        text = str(jax.make_jaxpr(grad)(params))
    return without, dict(kept), text


@pytest.fixture(scope="module")
def zaya_replay():
    """``{budget: {scope: primitives}}`` of what the tiny remat'ed ZAYA's
    gradient replays (``rematted_computation`` in the operation's path, what
    ``scope_reduce.phase_of`` calls recompute), each under its innermost
    ``ds.*`` scope as ``scope_reduce.scope_of`` reads it: with no budget
    stated and under one with room for every offered value."""
    from deepspeed_tpu.models.layers import remat_room

    out = {}
    for budget in (0, 10 ** 9):
        grad, params = _offering_zaya()
        with remat_room(budget):
            text = jax.jit(grad).trace(params).lower().as_text(
                debug_info=True)
        found = {}
        for path in re.findall(r'loc\("([^"]*rematted_computation[^"]*)"',
                               text):
            scope = re.findall(r"ds\.[a-z_0-9]+", path)[-1]
            found.setdefault(scope, set()).add(
                "/".join(path.rsplit("/", 2)[-2:]))
        out[budget] = found
    return out


#: ``{scope: what its replay runs with nothing kept and no longer runs under
#: a stated budget}`` (the operation under the module or scope that made
#: it); None: the scope's replay is what it was (``repeat_kv`` and the
#: kernels' transposes, the norms, the residual scalings stay)
ZAYA_REPLAY = {
    # o_proj stays (its output is not offered: zaya.remat_offers), and RoPE
    "ds.attn_proj": {f"{p}_proj/dot_general" for p in ("q", "k", "v1", "v2")},
    "ds.cca_mix": {"btgi,gio->btgo/dot_general"},   # the unit length stays
    # the down-projection, the three products, the choice; the state's norm,
    # the GELUs of the kept products and the logits' softmax stay
    "ds.moe_router": {"bth,hr->btr/dot_general", "router/dot_general",
                      "router/top_k"},
    # all three grouped products and the combine; the sort, its scatter and
    # the rows' gather stay (the sorted rows are not offered)
    "ds.moe_experts": {"moe_gmm/ragged_dot_general", "moe_combine/gather"},
    "ds.attention": None, "ds.norm": None, "ds.residual": None,
}


@pytest.mark.parametrize("scope", sorted(ZAYA_REPLAY))
def test_under_a_budget_zayas_replay_stands_under_its_scopes(zaya_replay,
                                                             scope):
    """With every offered value kept the replay's operations still carry
    their layer's scope (a traced run's ``train.recompute_share`` splits by
    it): ``ds.attention``, ``ds.norm`` and ``ds.residual`` as before; under
    ``ds.attn_proj`` one product, ``o_proj``; under ``ds.cca_mix`` and
    ``ds.moe_router`` no product and no ``top_k`` -- what a name cannot
    reach there is element-wise (a softmax's and a norm's backward read
    their own raw values: PERF.md section 7); under ``ds.moe_experts`` the
    sort and the rows' gather, no grouped product."""
    plain, kept = zaya_replay[0], zaya_replay[10 ** 9]
    assert set(kept) == set(plain) == set(ZAYA_REPLAY) | {"ds.moe_skip"}
    gone = ZAYA_REPLAY[scope]
    if gone is None:
        assert kept[scope] == plain[scope]
    else:
        assert gone <= plain[scope] and not gone & kept[scope]
        assert kept[scope] < plain[scope]
    if scope != "ds.attention":     # (this CPU's XLA core is two products)
        assert {op for op in kept[scope] if op.endswith("dot_general")} == \
            ({"o_proj/dot_general"} if scope == "ds.attn_proj" else set())


@pytest.mark.parametrize("constant", sorted(CHECKPOINT_NAMES))
def test_flash_forward_names_what_the_backward_reads(constant):
    """A remat policy finds the kernel's output and log-sum-exp, and a
    selection's mask, by these spellings in the program."""
    spelled, case = CHECKPOINT_NAMES[constant]
    assert constant == spelled
    fn, args = case()
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in args]
    assert f"name[name={constant}]" in str(jax.make_jaxpr(fn)(*shapes))


@pytest.mark.parametrize("constant", sorted(KERNELS))
def test_kernel_lowers_under_its_name(constant):
    spelled, case = KERNELS[constant]
    assert constant == spelled      # the reducer matches this spelling
    fn, args = case()
    shapes = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in args]
    if "block_sparse" in spelled:
        assert re.search(spelled + r"\b", str(jax.make_jaxpr(fn)(*shapes)))
        return
    text = jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{spelled}"' in text


@pytest.fixture(scope="module")
def keye_step_text():
    """A ``sa_config`` model with ``attention_impl="flash"``, its gradient
    lowered for the TPU, the indexer's kernels as on the chip."""
    from deepspeed_tpu.ops.pallas import sa_index, sa_probs

    # the model asks jax.default_backend(), which is the CPU here
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sa_index, "index_scores", functools.partial(
            sa_index.index_scores, interpret=False))
        patch.setattr(sa_probs, "index_kl", functools.partial(
            sa_probs.index_kl, interpret=False))
        model = MixtralForCausalLM(MixtralConfig.tiny(
            remat=True, attention_impl="flash", hidden_size=128,
            num_attention_heads=2, num_key_value_heads=1,
            max_position_embeddings=512,
            sa_config=dict(indexer_head_dim=64, indexer_num_heads=2,
                           q_chunk_size=128, kv_chunk_size=128, topk=64)))
        ids = jnp.zeros((1, 256), jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
        loss = lambda p, ids: model.apply({"params": p}, ids, labels=ids)[0]
        return jax.jit(jax.grad(loss)).trace(params, ids).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)


def test_indexer_kernels_stand_under_their_scope(keye_step_text):
    """The scores' forward kernel stands twice under ``ds.sa_index`` (the
    forward pass and the layer's replay: 8 calls a step at keye 16k's depth
    4), each backward kernel once (4), and nowhere else —
    ``train.sa_index_share`` reads the whole indexer by that scope."""
    text = keye_step_text
    called = re.findall(r'kernel_name = "(ds_sa_index_\w+)"', text)
    assert sorted(called) == ["ds_sa_index_bwd_dk", "ds_sa_index_bwd_dq",
                              "ds_sa_index_fwd", "ds_sa_index_fwd"]
    scoped = re.findall(r"ds\.sa_index/(ds_sa_index_\w+)/pallas_call", text)
    assert sorted(scoped) == sorted(called)
    replayed = re.findall(
        r"rematted_computation/\S*ds\.sa_index/(ds_sa_index_\w+)/", text)
    assert replayed == ["ds_sa_index_fwd"]


@pytest.mark.parametrize("on_v5e,backward", [
    (True, ["ds_flash_bwd"]),
    (False, ["ds_flash_bwd_dkv", "ds_flash_bwd_dq"])],
    ids=["one_kernel", "two_kernels"])
def test_flash_backward_stands_under_its_scope(on_v5e, backward):
    """A remat'd step with ``attention_impl="flash"``, lowered for the TPU.
    Where the rule answers for a v5e a flash call's backward is ONE kernel,
    ``ds_flash_bwd``, where it answers for a chip it does not know the two
    it was: under ``ds.attention``, beside one ``ds_flash_fwd``, and none
    of them in the replay."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    with pytest.MonkeyPatch.context() as patch:
        if on_v5e:
            _on_v5e(patch)
        # the model asks jax.default_backend(), which is the CPU here
        patch.setattr(fa, "flash_attention", functools.partial(
            fa.flash_attention, interpret=False))
        model = LlamaForCausalLM(LlamaConfig.tiny(
            remat=True, attention_impl="flash", hidden_size=256,
            num_attention_heads=2, num_key_value_heads=1,
            max_position_embeddings=512))
        ids = jnp.zeros((1, 256), jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
        loss = lambda p, ids: model.apply({"params": p}, ids, labels=ids)
        text = jax.jit(jax.grad(loss)).trace(params, ids).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    called = re.findall(r'kernel_name = "(ds_flash_\w+)"', text)
    assert sorted(called) == backward + ["ds_flash_fwd"]
    scoped = re.findall(r'"(\S*)ds\.attention/(ds_flash_\w+)/pallas_call',
                        text)
    assert sorted(k for _, k in scoped) == sorted(called)
    assert not any("rematted_computation" in path for path, _ in scoped)


def test_loss_kernels_stand_once_a_layer_and_in_no_replay(keye_step_text):
    """The indexer's loss is ``ds_sa_probs`` once a layer and
    ``ds_sa_probs_bwd`` once (4 and 4 calls a step at keye 16k's depth 4),
    both under ``ds.sa_loss`` and neither inside the layer's replay: every
    remat policy keeps the forward kernel's one output (``ds_sa_kl_rows``),
    so ``jax.checkpoint`` drops the call. ``train.sa_loss_share`` reads the
    loss by that scope, ``kernel.sa_probs.roofline_share`` the first name."""
    text = keye_step_text
    called = re.findall(r'kernel_name = "(ds_sa_probs\w*)"', text)
    assert sorted(called) == ["ds_sa_probs", "ds_sa_probs_bwd"]
    scoped = re.findall(r"ds\.sa_loss/(ds_sa_probs\w*)/pallas_call", text)
    assert sorted(scoped) == sorted(called)
    assert not re.findall(r"rematted_computation/\S*/ds_sa_probs\w*/", text)


def test_scan_kernels_stand_alone_under_their_scope(monkeypatch):
    """A decoder-hybrid-decoder with ``ssm_impl="pallas"``, its gradient
    lowered for the TPU: under ``ds.ssm_scan`` stand the two kernels and no
    other kernel -- the forward twice a Mamba layer (the forward pass and
    the layer's replay), the backward once -- and they stand nowhere else:
    ``train.ssm_scan_share`` reads the scan by that scope."""
    from deepspeed_tpu.models import sambay

    # the model asks jax.default_backend(), which is the CPU here
    monkeypatch.setattr(sambay, "selective_scan", functools.partial(
        sambay.selective_scan, interpret=False))
    model = SambaYForCausalLM(SambaYConfig.tiny(
        remat=True, ssm_impl="pallas", ssm_chunk=128, hidden_size=64,
        mamba_d_state=16))
    ids = jnp.zeros((1, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    loss = lambda p, ids: model.apply({"params": p}, ids, labels=ids)
    text = jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    called = re.findall(r'kernel_name = "(ds_\w+)"', text)
    assert sorted(called) == 2 * ["ds_ssm_scan_bwd"] + 4 * ["ds_ssm_scan_fwd"]
    scoped = re.findall(r"ds\.ssm_scan/(ds_\w+)/pallas_call", text)
    assert sorted(scoped) == sorted(called)
    replayed = re.findall(
        r"rematted_computation/\S*ds\.ssm_scan/(ds_\w+)/", text)
    assert replayed == 2 * ["ds_ssm_scan_fwd"]


def test_delta_rule_kernels_stand_alone_under_their_scope(monkeypatch):
    """One period of ``models/qwen3_next.py`` at heads of 128 x 128 with the
    chooser answered for one v5e, its remat'ed gradient lowered for the TPU:
    the two kernels are called under ``ds.gdn_rule`` and nowhere else -- the
    forward twice a delta-rule layer (the forward pass and the block's
    replay: 6 calls a step at qwen3-next 8k's one period), the backward once
    (3): ``train.gdn_rule_share`` reads the rule,
    XLA's tables and inverse with it, by that scope. No loop over chunk
    boundaries is left (the layers are unrolled here: no scan of periods
    either)."""
    from deepspeed_tpu.ops.pallas import grouped_matmul

    # the chooser asks the backend and the device kind, the CPU's here
    monkeypatch.setattr(grouped_matmul, "backend", lambda: "tpu")
    _on_v5e(monkeypatch)
    model = Qwen3NextForCausalLM(Qwen3NextConfig.tiny(
        remat=True, scan_layers=False, num_hidden_layers=4,
        linear_key_head_dim=128, linear_value_head_dim=128, gdn_chunk=64))
    ids = jnp.zeros((1, 256), jnp.int32)
    # the step's bf16 compute copy: the kernels take two-byte operands
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, BF16), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))["params"])
    loss = lambda p, ids: model.apply({"params": p}, ids, labels=ids)
    text = jax.jit(jax.grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    sites = re.findall(r'"(\S*)ds\.gdn_rule/jit\(_rule_(fwd|bwd)\)"', text)
    assert sorted(k for _, k in sites) == 3 * ["bwd"] + 6 * ["fwd"]
    assert len(re.findall(r"call @_rule_(?:fwd|bwd)", text)) == len(sites)
    assert sorted(k for path, k in sites
                  if "rematted_computation" in path) == 3 * ["fwd"]
    # the entries are jitted: the module holds each kernel once (the forward
    # once more for the replay, whose jaxpr is its own), whatever the layers
    lowered = re.findall(r'kernel_name = "(ds_\w+)"', text)
    assert sorted(n for n in lowered if n.startswith("ds_gdn_rule_")) == [
        "ds_gdn_rule_bwd"] + 2 * ["ds_gdn_rule_fwd"]
    assert "stablehlo.while" not in text
    # what stands around the rule (``ops/pallas/gdn_mix.py``, PR 55): one
    # kernel before it and one after it, under ``ds.gdn_mix`` and nowhere
    # else, the forwards twice a layer and the backwards once --
    # ``train.gdn_mix_share`` reads them by that scope
    mix = re.findall(
        r'"(\S*)ds\.gdn_mix/jit\(_(premix|gate)_(fwd|bwd)\)"', text)
    assert sorted(f"{k}_{d}" for _, k, d in mix) == sorted(
        3 * ["premix_bwd", "gate_bwd"] + 6 * ["premix_fwd", "gate_fwd"])
    assert len(re.findall(r"call @_(?:premix|gate)_(?:fwd|bwd)", text)) \
        == len(mix)
    assert sorted(f"{k}_{d}" for path, k, d in mix
                  if "rematted_computation" in path) == sorted(
        3 * ["premix_fwd", "gate_fwd"])
    assert sorted(n for n in lowered if not n.startswith("ds_gdn_rule_")) \
        == sorted(["ds_gdn_premix_bwd", "ds_gdn_gate_bwd"]
                  + 2 * ["ds_gdn_premix_fwd", "ds_gdn_gate_fwd"])


# one case a family: it reads that family's step alone (``train_text``)

@pytest.mark.parametrize("family", FAMILIES)
def test_no_step_without_the_flash_indexer_holds_its_kernels(family):
    """The other families' steps, and a ``sa_config`` step on the XLA path,
    carry none of the indexer's five kernels: their programs are what they
    were."""
    text = train_text(family)
    assert "ds_sa_index" not in text and "ds_sa_probs" not in text


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_familys_step_holds_the_delta_rules_names(family):
    """``ds.layer_gdn``, ``ds.gdn_mix`` and ``ds.gdn_rule`` stand in
    ``models/qwen3_next.py``'s step alone, ``ds.attn_gate`` there and in
    ``models/laguna.py``'s (a gate a head for a gate a column),
    ``ds.layer_dense`` in the latter alone: the other families' programs
    are what they were, and ``NAMES_VERSION`` stays."""
    own = {"qwen3_next": {"ds.layer_gdn", "ds.gdn_mix", "ds.gdn_rule",
                          "ds.attn_gate"},
           "laguna": {"ds.attn_gate", "ds.layer_dense"},
           "kimi_linear": {"ds.layer_dense"}}
    found = set(re.findall(
        r"ds\.(?:layer_gdn|gdn_[a-z]+|attn_gate|layer_dense)\b",
        train_text(family)))
    assert found == own.get(family, set())


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_familys_step_holds_the_single_branch_layers_names(family):
    """``ds.layer_mamba`` and ``ds.layer_moe`` stand in
    ``models/nemotron_h.py``'s step alone (``ds.ssm_scan`` and ``ds.ssm_mix``
    there and in ``models/sambay.py``'s, whose names they are): the other
    families' programs are what they were, and ``NAMES_VERSION`` stays."""
    text = train_text(family)
    found = set(re.findall(r"ds\.(?:layer_mamba|layer_moe)\b", text))
    assert found == ({"ds.layer_mamba", "ds.layer_moe"}
                     if family == "nemotron_h" else set())
    assert bool(re.search(r"ds\.ssm_(?:scan|mix)\b", text)) == (
        family in ("nemotron_h", "sambay"))


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_familys_step_holds_the_vector_decay_rules_names(family):
    """``ds.layer_kda``, ``ds.layer_mla``, ``ds.kda_mix`` and ``ds.kda_rule``
    stand in ``models/kimi_linear.py``'s step alone: the other families'
    programs are what they were, and ``NAMES_VERSION`` stays."""
    found = set(re.findall(r"ds\.(?:layer_kda|layer_mla|kda_[a-z]+)\b",
                           train_text(family)))
    assert found == ({"ds.layer_kda", "ds.layer_mla", "ds.kda_mix",
                      "ds.kda_rule"} if family == "kimi_linear" else set())


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_familys_step_holds_the_loops_names(family):
    """``ds.loop_stack`` and ``ds.exit_gate`` stand in ``models/ouro.py``'s
    step alone: the other families' programs are what they were, and
    ``NAMES_VERSION`` stays."""
    found = set(re.findall(r"ds\.(?:loop_stack|exit_gate)\b",
                           train_text(family)))
    assert found == ({"ds.loop_stack", "ds.exit_gate"}
                     if family == "ouro" else set())


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_familys_step_holds_block_diffusions_names(family):
    """``ds.bd_noise`` and ``ds.bd_gather`` stand in ``models/sdar.py``'s
    step alone: the other families' programs are what they were, and
    ``NAMES_VERSION`` stays."""
    found = set(re.findall(r"ds\.bd_[a-z]+\b", train_text(family)))
    assert found == ({"ds.bd_noise", "ds.bd_gather"}
                     if family == "sdar" else set())


@pytest.fixture
def own_compile_cache(tmp_path):
    """A persistent compile cache of the test's own, empty: whatever it
    runs was compiled by this process on this machine."""
    from jax.experimental.compilation_cache import compilation_cache

    shared = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", shared)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("gas", [1, 2])
def test_named_scalars_of_any_model_become_gauges(gas, own_compile_cache):
    """The contract Mixtral's load report rides: a training call that
    returns ``(loss, {name: scalar})`` gets registry gauges of those names,
    the mean over micro-batches; what is no scalar is dropped.

    The case failed in the driver's runs of PR 51's and PR 55's trees and
    in no builder's (PR 56: alone, beside ``test_step_counters.py`` on two
    workers, and again on an empty cache directory: 3 x green). What it
    shared with its neighbours was ``tests/conftest.py``'s ONE persistent
    compile cache, six workers on a directory: with ``Dense(1)`` its init
    programs were, byte for byte, ``test_step_counters.py``'s (PR 51 made the
    head three wide for that). A half-written entry cannot be the cause --
    jax catches a failed read, warns and compiles -- but an entry another
    machine wrote can be read whole and run: this sandbox's XLA:CPU loader
    warns of exactly that ("compile machine features ... vs host machine
    features"). So the case compiles its own programs into a directory of
    its own (``own_compile_cache``) and shares nothing; should it fail
    again, the cache is not why."""
    import flax.linen as nn

    class Named(nn.Module):
        @nn.compact
        def __call__(self, x):
            loss = jnp.mean(nn.Dense(3)(x) ** 2)
            return loss, {"probe_first_feature": jnp.mean(x[:, 0]),
                          "not_a_scalar": x[0]}

    x = np.arange(32 * gas, dtype=np.float32).reshape(8 * gas, 4)
    engine, *_ = ds.initialize(
        model=Named(), example_batch={"x": x[:1]},
        config={"train_batch_size": 8 * gas, "steps_per_print": 0,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    engine.train_batch(batch={"x": x})
    found = engine.registry.snapshot()
    assert found["probe_first_feature"] == pytest.approx(x[:, 0].mean())
    assert "not_a_scalar" not in found
