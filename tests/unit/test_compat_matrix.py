"""Every ✗ in docs/compatibility_matrix.md must raise a loud ValueError at
initialize() time (VERDICT r2 weak #3: no silent feature islands)."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM


def _try(config, match):
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ex = {"input_ids": rs.randint(0, cfg.vocab_size, (1, 8)),
          "labels": rs.randint(0, cfg.vocab_size, (1, 8))}
    with pytest.raises(ValueError, match=match):
        ds.initialize(model=model,
                      config={"train_batch_size": 8, **config},
                      example_batch=ex)


OPT = {"optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
OFFLOAD = {"zero_optimization": {"stage": 2,
                                 "offload_optimizer": {"device": "cpu"}}}
WIRE = {"optimizer": {"type": "OnebitAdam",
                      "params": {"lr": 1e-3,
                                 "comm_backend_name": "compressed"}}}
MOQ = {"quantize_training": {"enabled": True}}
PLD = {"progressive_layer_drop": {"enabled": True}}
COMPRESS = {"compression_training": {"sparse_pruning": {
    "shared_parameters": {"schedule_offset": 0},
    "different_groups": {"g": {"params": {"dense_ratio": 0.5},
                               "modules": [".*"]}}}}}


@pytest.mark.parametrize("config,match", [
    # offload_optimizer exclusions
    ({**OPT, **OFFLOAD, **MOQ}, "fused device"),
    ({**OPT, **OFFLOAD, **COMPRESS}, "fused"),
    ({**OPT, **OFFLOAD, **PLD}, "offload_optimizer"),
    ({**OPT, **OFFLOAD, "sparse_gradients": True}, "does not compose"),
    # 1-bit wire exclusions
    ({**WIRE, "zero_optimization": {"stage": 2}}, "ZeRO stage 0"),
    ({**WIRE, **MOQ}, "does not compose"),
    ({**WIRE, **PLD}, "does not compose|pld"),
    ({**WIRE, **COMPRESS}, "does not compose"),
    ({**WIRE, "sparse_gradients": True}, "does not compose"),
    # sparse_gradients exclusions
    ({**OPT, "sparse_gradients": True,
      "zero_optimization": {"stage": 2}}, "ZeRO stage 0"),
    ({**OPT, "sparse_gradients": True, "fp16": {"enabled": True}},
     "bf16/fp32"),
    ({**OPT, "sparse_gradients": True, **MOQ}, "does not compose"),
])
def test_forbidden_pairs_raise(config, match):
    _try(config, match)


@pytest.mark.parametrize("config", [
    {**OPT, **OFFLOAD}, WIRE,
    {**OPT, "zero_optimization": {"stage": 1, "overlap_grad_sync": True}}],
    ids=["offload_optimizer", "onebit_wire", "overlap_grad_sync"])
def test_frozen_parameters_need_the_fused_step(config):
    """A model that declares frozen parameters (DeepseekV3's selection bias)
    is refused by the lanes that run optimizers of their own."""
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  DeepseekV3ForCausalLM)

    cfg = DeepseekV3Config.tiny()
    ex = {"input_ids": np.zeros((1, 8), np.int32),
          "labels": np.zeros((1, 8), np.int32)}
    with pytest.raises(ValueError, match="frozen parameters"):
        ds.initialize(model=DeepseekV3ForCausalLM(cfg),
                      config={"train_batch_size": 8, **config},
                      example_batch=ex)


def test_held_share_under_an_expert_axis_is_not_built():
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  DeepseekV3ForCausalLM)
    from deepspeed_tpu.parallel import build_mesh

    cfg = DeepseekV3Config.tiny()
    ex = {"input_ids": np.zeros((1, 8), np.int32),
          "labels": np.zeros((1, 8), np.int32)}
    with pytest.raises(NotImplementedError, match="expert"):
        ds.initialize(model=DeepseekV3ForCausalLM(cfg),
                      config={"train_batch_size": 8, **OPT},
                      example_batch=ex, mesh=build_mesh(data=2, expert=4))


def test_wire_over_model_axis_rejected():
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ex = {"input_ids": rs.randint(0, cfg.vocab_size, (1, 8)),
          "labels": rs.randint(0, cfg.vocab_size, (1, 8))}
    from deepspeed_tpu.parallel import build_mesh

    with pytest.raises(ValueError, match="pure-DP"):
        ds.initialize(model=model,
                      config={"train_batch_size": 8, **WIRE},
                      example_batch=ex, mesh=build_mesh(data=4, model=2))


def test_pipe_zero3_rejected():
    import flax.linen as nn

    from deepspeed_tpu.models.layers import cross_entropy_loss
    from deepspeed_tpu.pipe import LayerSpec, PipelineModule

    class B(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(8)(x)

    pipe = PipelineModule([LayerSpec(B), LayerSpec(B)], num_stages=2,
                          loss_fn=cross_entropy_loss)
    with pytest.raises(ValueError, match="ZeRO stage 3 is incompatible"):
        ds.initialize(model=pipe,
                      config={"train_batch_size": 8,
                              "zero_optimization": {"stage": 3}, **OPT},
                      example_batch={"inputs": np.zeros((4, 4), np.float32),
                                     "labels": np.zeros((4, 4), np.int32)})
