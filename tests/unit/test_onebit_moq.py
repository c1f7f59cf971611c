"""1-bit wire compression + MoQ quantize-aware training (VERDICT r1 #9).

Reference analogs: ``runtime/comm/nccl.py:51`` (compressed_allreduce),
``tests/onebit`` correctness suites, ``runtime/quantize.py:9`` (MoQ),
``runtime/eigenvalue.py:7``."""

import numpy as np
import pytest

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM


# ---------------------------------------------------------------------------
# compressed allreduce collective
# ---------------------------------------------------------------------------


def _run_compressed(xs, werr, serr):
    """xs: [W, n] per-rank inputs -> (result[W, n], new werr, new serr)."""
    from deepspeed_tpu.comm.compressed import compressed_allreduce
    from deepspeed_tpu.parallel import build_mesh

    W, n = xs.shape
    mesh = build_mesh(data=W)

    def spmd(x, we, se):
        out, we2, se2 = compressed_allreduce(x[0], we[0], se[0], "data")
        return out[None], we2[None], se2[None]

    fn = jax.jit(jax.shard_map(
        spmd, mesh=mesh, axis_names={"data"},
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data"))))
    return fn(jnp.asarray(xs), jnp.asarray(werr), jnp.asarray(serr))


def test_compressed_allreduce_error_feedback_converges():
    """One round is lossy (1 bit!), but the error feedback must capture the
    loss: quantized + error == input, per phase (unbiased memory)."""
    W, n = 8, 8 * 8 * 4
    rs = np.random.RandomState(0)
    xs = rs.randn(W, n).astype(np.float32)
    werr = np.zeros((W, n), np.float32)
    serr = np.zeros((W, n // W), np.float32)

    out, werr2, serr2 = _run_compressed(xs, werr, serr)
    out = np.asarray(out)
    # all ranks agree on the result (it came from an all_gather)
    for r in range(1, W):
        np.testing.assert_array_equal(out[0], out[r])
    # signs dominate: result correlates positively with the true mean
    true = xs.mean(0)
    corr = np.corrcoef(out[0], true)[0, 1]
    assert corr > 0.4, corr
    # error feedback identity: decompressed + error == comp input
    assert np.abs(werr2).max() > 0  # compression really was lossy


@pytest.mark.slow
def test_compressed_allreduce_repeated_rounds_track_mean():
    """With error feedback, REPEATED rounds on the same inputs accumulate to
    the true mean (the EF-SGD convergence property the reference relies on)."""
    W, n = 8, 8 * 8 * 4
    rs = np.random.RandomState(1)
    xs = rs.randn(W, n).astype(np.float32)
    werr = np.zeros((W, n), np.float32)
    serr = np.zeros((W, n // W), np.float32)
    acc = np.zeros(n, np.float32)
    for _ in range(40):
        out, werr, serr = _run_compressed(xs, np.asarray(werr), np.asarray(serr))
        acc += np.asarray(out)[0]
    acc /= 40
    true = xs.mean(0)
    err = np.abs(acc - true).mean() / np.abs(true).mean()
    assert err < 0.15, err


def test_onebit_wire_training_converges_and_compresses():
    """End-to-end: warmup uses plain allreduce; after freeze_step the
    compressed collective carries the momentum and its logged wire volume is
    >=10x smaller. Training still converges."""
    from deepspeed_tpu.comm.comm import comms_logger
    from deepspeed_tpu.parallel import topology

    comms_logger.comms_dict.clear()
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (16, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (16, 16))}
    config = {"train_batch_size": 16, "comms_logger": {"enabled": True},
              "optimizer": {"type": "OnebitAdam",
                            "params": {"lr": 3e-3, "freeze_step": 3,
                                       "comm_backend_name": "compressed"}}}
    engine, *_ = ds.initialize(model=model, config=config,
                               example_batch={k: v[:1] for k, v in batch.items()})
    losses = [float(engine.train_batch(batch=batch)) for _ in range(10)]
    assert losses[-1] < losses[0] - 1.0, losses

    logged = comms_logger.comms_dict
    plain = [k[0] for k in logged.get("allreduce", {})]
    comp = [k[0] for k in logged.get("compressed_allreduce", {})]
    assert plain and comp, logged.keys()
    assert max(comp) * 10 < max(plain), (comp, plain)


@pytest.mark.parametrize("opt_type,params", [
    # test_onebit_wire_training_converges_and_compresses is the fast
    # wire representative; the Lamb/0-1-Adam variants ride slow
    pytest.param("OnebitLamb", {"lr": 1e-2, "freeze_step": 3,
                                "comm_backend_name": "compressed"},
                 marks=pytest.mark.slow),
    pytest.param("ZeroOneAdam", {"lr": 3e-3, "var_update_scaler": 2,
                                 "comm_backend_name": "compressed"},
                 marks=pytest.mark.slow),
])
def test_onebit_wire_lamb_zoadam_converge_and_compress(opt_type, params):
    """VERDICT r2 #7: the compressed collective must carry OnebitLamb and
    ZeroOneAdam too (reference lamb.py:11 / zoadam.py:10 ship compressed
    backends for all three)."""
    from deepspeed_tpu.comm.comm import comms_logger

    comms_logger.comms_dict.clear()
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (16, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (16, 16))}
    config = {"train_batch_size": 16, "comms_logger": {"enabled": True},
              "optimizer": {"type": opt_type, "params": params}}
    engine, *_ = ds.initialize(model=model, config=config,
                               example_batch={k: v[:1] for k, v in batch.items()})
    losses = [float(engine.train_batch(batch=batch)) for _ in range(12)]
    assert losses[-1] < losses[0] - 1.0, losses

    logged = comms_logger.comms_dict
    comp = [k[0] for k in logged.get("compressed_allreduce", {})]
    assert comp, f"{opt_type}: compressed collective never used: {logged.keys()}"
    if opt_type == "ZeroOneAdam":
        # the exponentially-growing refresh interval must have taken effect
        vint = int(jax.device_get(engine.state.opt_state.var_interval))
        assert vint >= 4, vint


def test_onebit_wire_rejects_bad_configs():
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ex = {"input_ids": rs.randint(0, 256, (1, 8)),
          "labels": rs.randint(0, 256, (1, 8))}
    with pytest.raises(ValueError, match="ZeRO stage 0"):
        ds.initialize(model=model, config={
            "train_batch_size": 16, "zero_optimization": {"stage": 2},
            "optimizer": {"type": "OnebitAdam",
                          "params": {"comm_backend_name": "compressed"}}},
            example_batch=ex)


# ---------------------------------------------------------------------------
# MoQ
# ---------------------------------------------------------------------------


def test_moq_bits_schedule():
    from deepspeed_tpu.runtime.config import QuantizeTrainingConfig
    from deepspeed_tpu.runtime.quantize import Quantizer

    q = Quantizer(QuantizeTrainingConfig(
        enabled=True, quantize_bits={"start_bits": 16, "target_bits": 4},
        quantize_schedule={"quantize_period": 10, "schedule_offset": 5}))
    bits = [float(q.bits_at(s)) for s in (0, 5, 14, 15, 34, 35, 74, 75, 1000)]
    # drops at offset + 10*(2^k - 1): steps 15, 35, 75; floor at 4 bits
    assert bits == [16, 16, 16, 8, 8, 4, 4, 4, 4], bits


def test_moq_quantize_tree_reduces_distinct_values():
    from deepspeed_tpu.runtime.config import QuantizeTrainingConfig
    from deepspeed_tpu.runtime.quantize import Quantizer

    q = Quantizer(QuantizeTrainingConfig(
        enabled=True, quantize_bits={"start_bits": 4, "target_bits": 4},
        quantize_groups=2))
    w = jnp.asarray(np.random.RandomState(0).randn(16, 32), jnp.float32)
    out = q.quantize_tree({"k": w}, step=0, ste=False)["k"]
    # 4 bits symmetric -> at most 15 distinct levels per group
    assert len(np.unique(np.asarray(out))) <= 2 * 15
    # 1-D leaves (biases/scales) pass through untouched
    b = jnp.ones((7,))
    assert q.quantize_tree({"b": b}, 0)["b"] is b


@pytest.mark.slow
def test_moq_engine_training_applies_schedule():
    """The flag observably changes training: with an immediate aggressive
    schedule, the loss trajectory differs from baseline and weights used in
    compute are quantized — while fp32 masters stay full precision."""
    from deepspeed_tpu.parallel import topology

    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (8, 16))}
    base = {"train_batch_size": 8, "seed": 3,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    e_q, *_ = ds.initialize(
        model=model,
        config={**base, "quantize_training": {
            "enabled": True,
            "quantize_bits": {"start_bits": 3, "target_bits": 3}}},
        example_batch={k: v[:1] for k, v in batch.items()})
    topology.set_mesh(None, None)
    e_ref, *_ = ds.initialize(model=model, config=dict(base),
                              example_batch={k: v[:1] for k, v in batch.items()})
    lq = [float(e_q.train_batch(batch=batch)) for _ in range(3)]
    lr_ = [float(e_ref.train_batch(batch=batch)) for _ in range(3)]
    assert not np.allclose(lq, lr_), (lq, lr_)
    # masters remain un-quantized fp32 (many distinct values)
    kernel = np.asarray(jax.tree_util.tree_leaves(e_q.state.params)[1]).ravel()
    assert len(np.unique(kernel)) > 100


# ---------------------------------------------------------------------------
# eigenvalue (curvature) estimation
# ---------------------------------------------------------------------------


def test_eigenvalue_power_iteration_quadratic():
    """Known spectrum: f(x) = 0.5 x^T diag(d) x has max eigenvalue max(d)."""
    from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

    d = jnp.asarray([1.0, 4.0, 2.5, 9.0, 0.5])
    loss = lambda p: 0.5 * jnp.sum(d * p["x"] * p["x"])
    eig = Eigenvalue(max_iter=200, tol=1e-4).compute(
        loss, {"x": jnp.ones((5,))}, jax.random.PRNGKey(0))
    assert eig == pytest.approx(9.0, rel=1e-2)


@pytest.mark.slow
def test_eigenvalue_on_model_loss_is_finite():
    from deepspeed_tpu.runtime.eigenvalue import Eigenvalue

    cfg = LlamaConfig.tiny(remat=False, num_hidden_layers=1)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    eig = Eigenvalue(max_iter=8, tol=1e-1).compute(loss, params)
    assert np.isfinite(eig) and eig > 0


@pytest.mark.slow
def test_onebit_wire_with_gradient_accumulation():
    """gas > 1 composes with the wire path (r3: local grads accumulate over
    microbatches, ONE compressed exchange per optimizer step)."""
    from deepspeed_tpu.comm.comm import comms_logger

    comms_logger.comms_dict.clear()
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (32, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (32, 16))}
    engine, *_ = ds.initialize(
        model=model,
        config={"train_batch_size": 32, "gradient_accumulation_steps": 2,
                "comms_logger": {"enabled": True},
                "optimizer": {"type": "OnebitAdam",
                              "params": {"lr": 3e-3, "freeze_step": 2,
                                         "comm_backend_name": "compressed"}},
                "steps_per_print": 0},
        example_batch={k: v[:1] for k, v in batch.items()})
    losses = [float(engine.train_batch(batch=batch)) for _ in range(8)]
    assert losses[-1] < losses[0] - 1.0, losses
    assert "compressed_allreduce" in comms_logger.comms_dict


@pytest.mark.slow
def test_onebit_wire_fp16_trains_and_skips_on_overflow():
    """r4: fp16 composes with the compressed wire — the local loss is
    scaled before backward, scaled grads unscale + overflow-check globally
    BEFORE the error-feedback buffers advance, and the dynamic-scale
    automaton rides in TrainState.loss_scale."""
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    batch = {"input_ids": rs.randint(0, cfg.vocab_size, (16, 16)),
             "labels": rs.randint(0, cfg.vocab_size, (16, 16))}
    config = {"train_batch_size": 16,
              "fp16": {"enabled": True, "initial_scale_power": 8},
              "optimizer": {"type": "OnebitAdam",
                            "params": {"lr": 3e-3, "freeze_step": 3,
                                       "comm_backend_name": "compressed"}}}
    engine, *_ = ds.initialize(model=model, config=config,
                               example_batch={k: v[:1] for k, v in batch.items()})
    assert engine.fp16_enabled and engine._onebit_wire
    losses = [float(engine.train_batch(batch=batch)) for _ in range(10)]
    assert losses[-1] < losses[0] - 1.0, losses
    assert engine.loss_scale == 2.0 ** 8  # clean run: scale held

    # crafted overflow IN THE COMPRESSED PHASE (freeze_step=0 so the very
    # first step takes the compressed branch — an overflow during warmup
    # never touches worker_error, which would make the feedback assertion
    # vacuous): the step must SKIP (params unchanged, error feedback
    # provably untouched by the NaN-laden discarded branch) and halve the
    # scale
    config_ov = {"train_batch_size": 16,
                 "fp16": {"enabled": True, "initial_scale_power": 40,
                          "hysteresis": 1},
                 "optimizer": {"type": "OnebitAdam",
                               "params": {"lr": 3e-3, "freeze_step": 0,
                                          "comm_backend_name": "compressed"}}}
    e2, *_ = ds.initialize(model=model, config=config_ov,
                           example_batch={k: v[:1] for k, v in batch.items()})
    p_before = jax.device_get(e2.state.params)
    e2.train_batch(batch=batch)
    assert int(jax.device_get(e2.state.skipped_steps)) >= 1
    assert e2.loss_scale < 2.0 ** 40
    werr = np.asarray(jax.device_get(e2.state.opt_state.worker_error))
    assert not np.any(werr)  # error feedback untouched by the skipped step
    p_after = jax.device_get(e2.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(p_before),
                    jax.tree_util.tree_leaves(p_after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
