"""Quantized serving path (r16): int8/int4 weight storage with
consumer-fused dequant, EQuARX-style quantized TP collectives, and the
engine invariants on the quantized path.

Bands are pinned the way ``test_tp_numerics`` pins TP noise: measured
values get a committed lo..hi window so any movement — better or worse —
is visible, and the EXACT invariants (serving == generate token
identity, one resident compile, silent sentinel, zero leaks) are
asserted as equalities. Free-running cross-arm token identity is NOT a
meaningful bar on the tiny random-init model (near-uniform logits: one
flipped near-tie cascades), so cross-arm parity pins logit divergence
and first-token agreement instead — the same reasoning the r16 bench
artifact documents.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.ops.pallas.quant_matmul import (
    dequantize_linear_weight, effective_group_size, pack_int4,
    quant_matmul, quantize_linear_weight, resolve_group_size, unpack_int4)
from deepspeed_tpu.parallel import build_mesh, topology

pytestmark = [pytest.mark.serving]

#: pinned logit-divergence windows vs the fp forward on the fp32 tiny
#: model (fixed seed): measured int8 ~0.085, int4 ~1.0. Below the lo
#: edge = quantization silently stopped applying; above hi = got worse.
INT8_LOGIT_BAND = (1e-3, 0.5)
INT4_LOGIT_BAND = (0.05, 2.5)
#: quantized_psum vs exact psum relative error bound (two int8 wire
#: roundings; measured ~0.9% at block 256 on gaussian partials)
QPSUM_REL_TOL = 2e-2


def _reset_mesh():
    topology.set_mesh(None, None)
    topology._CURRENT_TOPOLOGY = None


@pytest.fixture(autouse=True)
def _clean_mesh():
    _reset_mesh()
    yield
    _reset_mesh()


def _setup():
    cfg = LlamaConfig.tiny(remat=False)
    params = jax.jit(LlamaForCausalLM(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = np.random.RandomState(23).randint(1, cfg.vocab_size, 8)[None]
    return cfg, params, prompt


# ---------------------------------------------------------------------------
# pack/unpack + quantize round trips
# ---------------------------------------------------------------------------


def test_int4_pack_unpack_roundtrip():
    rs = np.random.RandomState(1)
    v = rs.randint(-8, 8, size=(10, 6))
    assert (np.asarray(unpack_int4(pack_int4(jnp.asarray(v)))) == v).all()
    with pytest.raises(ValueError, match="even K"):
        pack_int4(jnp.zeros((3, 2), jnp.int32))


@pytest.mark.parametrize("mode,group,bound", [
    ("int8", 0, 0.01), ("int8", 32, 0.01),
    ("int4", 0, 0.15), ("int4", 32, 0.12), ("int4", 6, 0.12)])
def test_quantize_dequantize_error_bound(mode, group, bound):
    rs = np.random.RandomState(2)
    w = rs.randn(96, 80).astype(np.float32)
    q, s = quantize_linear_weight(jnp.asarray(w), mode, group)
    g = resolve_group_size(96, mode, group if group else 96)
    assert s.shape == (96 // g, 80)
    dq = np.asarray(dequantize_linear_weight(q, s, mode))
    rel = np.abs(dq - w).max() / np.abs(w).max()
    assert rel < bound, (mode, group, rel)


def test_int4_odd_k_raises_named_error():
    """An odd input-feature dim fails with the NAMED even-K precondition
    at every entry (quantizer, group resolution), never a cryptic
    ZeroDivisionError from the even-divisor walk."""
    with pytest.raises(ValueError, match="even K"):
        quantize_linear_weight(jnp.zeros((7, 4), jnp.float32), "int4")
    with pytest.raises(ValueError, match="even K"):
        resolve_group_size(7, "int4", 0)
    with pytest.raises(ValueError, match="even K"):
        effective_group_size(7, "int4", 0)


def test_dtype_int8_excludes_quantize_weights():
    """dtype="int8" auto-sets the LEGACY quantize flag; combining it with
    quantize_weights must hit the mutual-exclusion ValueError (the
    auto-set runs before the check), never a doubly-quantized tree."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    with pytest.raises(ValueError, match="mutually exclusive"):
        DeepSpeedInferenceConfig(dtype="int8", quantize_weights="int8")


def test_effective_group_size_tp_alignment():
    # row-parallel at mp=2: groups resolve against the PER-SHARD K, so
    # the group count divides the TP width
    assert effective_group_size(128, "int4", 0, shards=2) == 64
    assert effective_group_size(128, "int4", 48, shards=2) == 32
    # int8 defaults to one group (per-column scales)
    assert effective_group_size(128, "int8", 0) == 128
    # int4 groups stay even (nibble pairs never straddle a boundary)
    assert effective_group_size(12, "int4", 3) % 2 == 0


# ---------------------------------------------------------------------------
# Pallas kernel parity (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,group", [
    ("int8", 0), ("int8", 32), ("int4", 0), ("int4", 32)])
def test_quant_matmul_interpret_matches_reference(mode, group):
    rs = np.random.RandomState(3)
    w = rs.randn(96, 80).astype(np.float32)
    x = rs.randn(7, 96).astype(np.float32)
    q, s = quantize_linear_weight(jnp.asarray(w), mode, group)
    ref = x @ np.asarray(dequantize_linear_weight(q, s, mode))
    out = np.asarray(quant_matmul(jnp.asarray(x), q, s, mode,
                                  block_k=32, block_n=32, interpret=True))
    assert np.abs(out - ref).max() < 1e-3


# ---------------------------------------------------------------------------
# scale sharding: wscale leaves ride the partition rules
# ---------------------------------------------------------------------------


def test_wscale_partition_rules_and_shardings():
    import flax.traverse_util as trav
    from jax.sharding import PartitionSpec as P

    cfg, params, _ = _setup()
    eng = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                            dtype="fp32", quantize_weights="int8",
                            mp_size=2, mesh=build_mesh(data=4, model=2))
    flat = trav.flatten_dict(eng.param_shardings, sep="/",
                             is_leaf=lambda _, v: hasattr(v, "spec"))
    pre = "model/layers/block/"
    # column-parallel scales shard on N exactly like their kernels
    assert flat[pre + "self_attn/q_proj/wscale"].spec == \
        P(None, None, "model")
    assert flat[pre + "mlp/up_proj/wscale"].spec == P(None, None, "model")
    # row-parallel scales replicate (G may be 1 — nothing to shard);
    # a fully-unsharded spec canonicalizes to the empty PartitionSpec
    assert flat[pre + "self_attn/o_proj/wscale"].spec == P()
    # kernel specs unchanged by quantization (trailing Nones canonicalize
    # away in PartitionSpec equality)
    assert flat[pre + "self_attn/o_proj/kernel"].spec == P(None, "model")
    assert flat[pre + "self_attn/q_proj/kernel"].spec == \
        P(None, None, "model")
    # the quantized leaves themselves: int8 codes + fp32 scales
    shapes = trav.flatten_dict(jax.tree_util.tree_map(
        lambda x: (x.dtype, x.shape), eng.params), sep="/")
    kdt, _ = shapes[pre + "self_attn/q_proj/kernel"]
    sdt, sshape = shapes[pre + "self_attn/q_proj/wscale"]
    assert kdt == jnp.int8 and sdt == jnp.float32
    assert sshape[0] == cfg.num_hidden_layers  # scanned leading axis


def test_quant_report_names_every_projection():
    cfg, params, _ = _setup()
    eng = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                            dtype="fp32", quantize_weights="int8")
    report = eng.quant_report
    names = {r["param"].rsplit("/", 2)[-2] for r in report}
    assert names == {"q_proj", "k_proj", "v_proj", "o_proj",
                     "gate_proj", "up_proj", "down_proj"}
    assert all(0.0 < r["rel_err"] < 0.02 for r in report)
    assert eng.quant_summary["quant_weight_bytes"] < \
        eng.quant_summary["fp_bytes"]
    # legacy grouped-flat quantize and the TP-sliceable mode are
    # mutually exclusive at the config layer
    with pytest.raises(ValueError, match="mutually exclusive"):
        ds.init_inference(LlamaForCausalLM(cfg), params=params,
                          dtype="fp32", quantize_weights="int8",
                          quantize=True)


# ---------------------------------------------------------------------------
# quantized_psum numerics
# ---------------------------------------------------------------------------


def test_quantized_psum_matches_psum_within_band():
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import quantized_psum

    mesh = build_mesh(data=2, model=4)
    x = np.random.RandomState(0).randn(8, 4, 260).astype(np.float32)

    def run(fn):
        f = jax.jit(jax.shard_map(fn, mesh=mesh,
                                  in_specs=P(None, None, "model"),
                                  out_specs=P(None, None, None),
                                  check_vma=False))
        return np.asarray(f(jnp.asarray(x)))

    out = run(lambda xl: quantized_psum(xl, "model"))
    exact = run(lambda xl: lax.psum(xl, "model"))
    rel = np.abs(out - exact).max() / np.abs(exact).max()
    assert 0.0 < rel < QPSUM_REL_TOL, rel  # quantized, but close


def test_quantized_psum_world_one_is_exact():
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.comm import quantized_psum

    mesh = build_mesh(data=8, model=1)
    x = np.random.RandomState(1).randn(4, 130).astype(np.float32)
    f = jax.jit(jax.shard_map(lambda xl: quantized_psum(xl, "model"),
                              mesh=mesh, in_specs=P(None, "model"),
                              out_specs=P(None, None), check_vma=False))
    assert np.array_equal(np.asarray(f(jnp.asarray(x))), x)


# ---------------------------------------------------------------------------
# end-to-end: quantized engines, mp 1 and >= 2
# ---------------------------------------------------------------------------


def _serve(eng, prompts, **cfg_over):
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine

    srv = ServingEngine(eng, ServingConfig(
        max_batch_size=4, block_size=8, num_blocks=64, max_model_len=64,
        **cfg_over))
    rids = [srv.submit(p, max_new_tokens=n) for p, n in prompts]
    outs = srv.run()
    assert all(outs[r].state == "finished" for r in rids)
    assert srv.compile_counts == {"mixed_step": 1}, srv.compile_counts
    assert srv.perf.recompile_total == 0, "recompile sentinel fired"
    assert srv.block_pool.used_count == 0
    return [outs[r].tokens for r in rids]


def _traffic(seed=5, n=4):
    rs = np.random.RandomState(seed)
    return [(rs.randint(1, 256, int(rs.choice([5, 9, 14, 21]))),
             int(rs.choice([4, 8]))) for _ in range(n)]


@pytest.mark.parametrize("mode,band", [
    ("int8", INT8_LOGIT_BAND),
    # int8 is the fast representative; int4 packing is still covered
    # fast by the pack/unpack + kernel interpret-parity tests
    pytest.param("int4", INT4_LOGIT_BAND, marks=pytest.mark.slow)])
def test_quantized_mp1_logit_band_and_serving_identity(mode, band):
    """mp=1: the quantized forward's logit divergence vs fp sits in its
    pinned window, and the quantized SERVING stream is token-identical
    to the same engine's offline generate (the serving path never
    changes the math — exact, not banded)."""
    cfg, params, prompt = _setup()
    fp = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                           dtype="fp32")
    lg_fp = np.asarray(fp.forward(jnp.asarray(prompt)))
    _reset_mesh()
    q = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                          dtype="fp32", quantize_weights=mode)
    lg_q = np.asarray(q.forward(jnp.asarray(prompt)))
    d = np.abs(lg_fp - lg_q).max()
    assert band[0] < d < band[1], (
        f"{mode} logit divergence {d:.4g} left its pinned window {band}")
    traffic = _traffic()
    toks = _serve(q, traffic)
    for (p, n), st in zip(traffic, toks):
        g = np.asarray(q.generate(jnp.asarray(p)[None],
                                  max_new_tokens=n))[0]
        assert list(g[:n]) == list(st)


def test_quantized_collectives_mp2_band_and_invariants():
    """mp=2 with int8 weights + quantized collectives: the TP forward's
    divergence vs the SAME-mode single-shard forward is the quantized
    wire's rounding (pinned window), greedy argmax agreement stays
    high, and the serving engine keeps ONE resident compile with the
    sentinel silent and zero leaks."""
    cfg, params, prompt = _setup()
    q1 = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                           dtype="fp32", quantize_weights="int8")
    lg_1 = np.asarray(q1.forward(jnp.asarray(prompt)))
    t_1 = _serve(q1, _traffic())
    _reset_mesh()
    q2 = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                           dtype="fp32", quantize_weights="int8",
                           quantized_collectives=True, mp_size=2,
                           mesh=build_mesh(data=4, model=2))
    lg_2 = np.asarray(q2.forward(jnp.asarray(prompt)))
    d = np.abs(lg_1 - lg_2).max()
    # wire-rounding window: ~0.075 measured; well below the int8 weight
    # loss would be suspicious (collectives silently off), well above =
    # the quantizer regressed
    assert 1e-3 < d < 0.5, d
    assert (lg_1.argmax(-1) == lg_2.argmax(-1)).mean() >= 0.9
    traffic = _traffic()
    t_2 = _serve(q2, traffic)
    # first tokens (the richest-context predictions) agree across the
    # quantized wire; full streams legitimately cascade after a flipped
    # near-tie on this model — the bench pins teacher-forced agreement
    # for that, so here the EXACT invariant is serving == generate on
    # the quantized-collectives engine itself
    assert [a[0] for a in t_1] == [b[0] for b in t_2]
    for (p, n), st in zip(traffic, t_2):
        g = np.asarray(q2.generate(jnp.asarray(p)[None],
                                   max_new_tokens=n))[0]
        assert list(g[:n]) == list(st)


def test_quantized_collectives_noop_at_world_one():
    """quantized_collectives at mp=1 must change NOTHING: the QuantDense
    seam short-circuits before shard_map, so logits are bit-identical
    to the same engine without the flag."""
    cfg, params, prompt = _setup()
    a = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                          dtype="fp32", quantize_weights="int8")
    lg_a = np.asarray(a.forward(jnp.asarray(prompt)))
    _reset_mesh()
    b = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                          dtype="fp32", quantize_weights="int8",
                          quantized_collectives=True)
    lg_b = np.asarray(b.forward(jnp.asarray(prompt)))
    assert np.array_equal(lg_a, lg_b)


@pytest.mark.slow  # llama is the fast quantized-serving representative
def test_gpt2_quantized_serving_identity():
    """The GPT-2 family rides the same QuantDense projections: int8
    serving stays token-identical to the same engine's generate."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config.tiny()
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    q = ds.init_inference(GPT2LMHeadModel(cfg), params=params,
                          dtype="fp32", quantize_weights="int8")
    assert q.quant_summary["leaves"] > 0
    traffic = _traffic(seed=7, n=3)
    toks = _serve(q, traffic)
    for (p, n), st in zip(traffic, toks):
        g = np.asarray(q.generate(jnp.asarray(p)[None],
                                  max_new_tokens=n))[0]
        assert list(g[:n]) == list(st)


# ---------------------------------------------------------------------------
# chaos storm on the quantized engine
# ---------------------------------------------------------------------------


def test_quantized_engine_chaos_storm(monkeypatch):
    """The resilience ladder must hold unchanged on the quantized path:
    a probabilistic storm (flaky prefill + NaN logits + slow steps under
    a watchdog) leaves every request terminal, zero leaked pages, ONE
    resident compile and the recompile sentinel silent — chaos is data,
    never a shape."""
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.utils import fault_injection

    cfg, params, _ = _setup()
    eng = ds.init_inference(LlamaForCausalLM(cfg), params=params,
                            dtype="fp32", quantize_weights="int8")
    srv = ServingEngine(eng, ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=16, max_model_len=32,
        step_watchdog_s=0.4))
    # warm (first step carries the compile; watchdog first-beat rule)
    rid = srv.submit([3, 5, 7], max_new_tokens=2)
    while srv.has_work():
        srv.step()
    assert srv.poll(rid).state == "finished"

    monkeypatch.setenv(fault_injection.ENV_VAR,
                       "flaky_prefill:p=0.3,corrupt_logits:p=0.15,"
                       "slow_step:p=0.2:seconds=0.02")
    fault_injection.reset()
    rs = np.random.RandomState(29)
    rids = [srv.submit(rs.randint(1, 256, int(rs.randint(3, 9))),
                       max_new_tokens=4) for _ in range(10)]
    steps = 0
    while srv.has_work():
        srv.step()
        steps += 1
        assert steps < 400, "quantized engine wedged under chaos"
    monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
    fault_injection.reset()
    states = {srv.poll(r).state for r in rids}
    assert states <= {"finished", "failed", "timeout"}
    assert "finished" in states
    srv.block_pool.check_consistent()
    assert srv.block_pool.used_count == 0
    assert srv.compile_counts == {"mixed_step": 1}, srv.compile_counts
    assert srv.perf.recompile_total == 0, "recompile sentinel fired"
    # and fresh traffic completes after the storm
    rid = srv.submit([2, 4, 6], max_new_tokens=2)
    while srv.has_work():
        srv.step()
    assert srv.poll(rid).state == "finished"
