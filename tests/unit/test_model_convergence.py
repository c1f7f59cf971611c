"""Model-level convergence (SURVEY §4: the reference's ``tests/model``
tier — full training runs checking loss curves, e.g.
``tests/model/Megatron_GPT2/run_sanity_check.py``). Here: overfit a fixed
batch to near-zero loss through the REAL feature stack — ZeRO-3 sharding,
bf16, flash attention, remat, gradient clipping — not just "loss went down
a bit"."""

import numpy as np
import pytest

import jax


@pytest.mark.slow
@pytest.mark.parametrize("stack", ["zero3_flash_remat", "zero1_fp32"])
def test_llama_overfits_fixed_batch(stack):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    if stack == "zero3_flash_remat":
        cfg = LlamaConfig.tiny(remat=True, remat_policy="dots",
                               attention_impl="flash")
        config = {"train_batch_size": 8, "bf16": {"enabled": True},
                  "zero_optimization": {"stage": 3,
                                        "stage3_param_persistence_threshold": 0},
                  "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                  "gradient_clipping": 1.0, "steps_per_print": 0}
        tol = 0.15  # bf16 compute floor
    else:
        cfg = LlamaConfig.tiny(remat=False)
        config = {"train_batch_size": 8,
                  "zero_optimization": {"stage": 1},
                  "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                  "steps_per_print": 0}
        tol = 0.05

    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 32)),
             "labels": rs.randint(0, cfg.vocab_size, (8, 32))}
    engine, *_ = ds.initialize(
        model=model, config=config,
        example_batch={k: v[:1] for k, v in batch.items()},
        partition_rules=LlamaForCausalLM.partition_rules(cfg),
        rng=jax.random.PRNGKey(0))

    first = float(engine.train_batch(batch=batch))
    loss = first
    for step in range(400):
        loss = float(engine.train_batch(batch=batch))
        if loss < tol:
            break
    assert loss < tol, (f"{stack}: loss {loss:.4f} after {step + 1} steps "
                        f"(start {first:.4f}) — training is not converging "
                        f"to memorization")
    assert engine.get_skipped_steps() == 0


@pytest.mark.slow
def test_mixtral_overfits_fixed_batch():
    """The MoE stack converges too (routing + aux loss do not fight
    memorization)."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

    cfg = MixtralConfig.tiny()
    model = MixtralForCausalLM(cfg)
    rs = np.random.RandomState(1)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 32)),
             "labels": rs.randint(0, cfg.vocab_size, (8, 32))}
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 8,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
                "steps_per_print": 0},
        example_batch={k: v[:1] for k, v in batch.items()},
        rng=jax.random.PRNGKey(0))
    loss = None
    for step in range(400):
        loss = float(engine.train_batch(batch=batch))
        if loss < 0.2:
            break
    assert loss < 0.2, f"mixtral loss {loss:.4f} after {step + 1} steps"


def remat_loss_and_grads(monkeypatch, model_of, ids):
    """``(loss, grads)`` of ``model_of(remat)`` under ``remat`` True and
    False at one set of weights, with ``attention_impl="flash"`` forced to
    the Pallas kernels (interpret mode; on the CPU the public entry would
    take the einsum reference)."""
    import functools

    import deepspeed_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, force_pallas=True))
    params = jax.jit(model_of(False).init)(jax.random.PRNGKey(0),
                                           ids)["params"]

    def run(remat):     # ONE trace: the jaxpr that is read is the one run
        traced = jax.jit(jax.value_and_grad(
            lambda params: model_of(remat).apply(
                {"params": params}, ids, labels=ids))).trace(params)
        assert "name=ds_flash_fwd" in str(traced.jaxpr)
        return traced.lower().compile()(params)

    return run(True), run(False)


def assert_same_loss_and_grads(loss, grads, loss0, grads0):
    # a replay computes what the forward did, in float32 to the last bits; a
    # wrong kept value (another layer's, an unsliced one) is wrong by far more
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7),
        grads, grads0)


def test_llama_remat_reads_the_kept_flash_output_and_lse(monkeypatch):
    """The scanned, remat'd model's backward reads the flash kernel's KEPT
    output and log-sum-exp (every policy keeps them) and not a replayed
    call's: loss and every gradient equal the un-remat'd model's."""
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    ids = np.random.RandomState(0).randint(0, 256, (2, 48))
    model_of = lambda remat: LlamaForCausalLM(LlamaConfig.tiny(
        remat=remat, attention_impl="flash", sliding_window=20,
        flash_block_q=16, flash_block_k=16))
    (loss, grads), (loss0, grads0) = remat_loss_and_grads(
        monkeypatch, model_of, ids)
    assert_same_loss_and_grads(loss, grads, loss0, grads0)
