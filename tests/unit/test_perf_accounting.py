"""Performance accounting (``monitor/perf.py``): fingerprints, the
recompile sentinel, cost-model capture, MFU arithmetic, hand-rolled
transformer estimates, device peaks and watermarks (the compile ledger and
the set-up record: ``test_setup_record.py``).

FLOPs pinning strategy: the 5% hand-computed bar runs against programs
whose FLOPs are EXACTLY countable by hand (matmul chains — XLA's cost
model counts a dot at 2·M·N·K, nothing hidden). Attention kernels are
deliberately NOT pinned that tight: the paged-attention lowering fuses
its score/AV contractions into ops the XLA cost model prices differently
from the textbook formula, so cost-model-vs-estimate there gets a wide
drift band in the serving suite instead of a fake-precise one here."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import perf
from deepspeed_tpu.monitor.registry import MetricsRegistry
from deepspeed_tpu.monitor.tracing import Tracer


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_spec_arrays_statics_and_pytrees():
    assert perf.spec(np.zeros((4, 2), np.int32)) == "int32[4,2]"
    assert perf.spec(jnp.zeros((3,), jnp.float32)) == "float32[3]"
    assert perf.spec(7) == "7"
    assert perf.spec((False, 1.0)) == repr((False, 1.0))  # no array leaves
    # pytrees collapse runs of identical leaf specs
    tree = {"a": [np.zeros((2, 2), np.float32)] * 3,
            "b": np.zeros((5,), np.int8)}
    s = perf.spec(tree)
    assert s.startswith("pytree[4:")
    assert "float32[2,2] x3" in s and "int8[5]" in s


def test_fingerprint_diff_names_changed_added_removed():
    old = {"x": "f32[2]", "y": "f32[3]"}
    new = {"x": "f32[2]", "y": "f32[4]", "z": "i32[1]"}
    d = perf.fingerprint_diff(old, new)
    assert set(d) == {"y", "z"}
    assert d["y"] == ("f32[3]", "f32[4]")
    assert d["z"] == (None, "i32[1]")


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------

def test_sentinel_fires_once_per_change_and_names_offender():
    tracer = Tracer(capacity=64)
    metrics = MetricsRegistry()
    reg = perf.ProgramRegistry(tracer=tracer, metrics=metrics, scope="t")
    fp = perf.fingerprint(tables=np.zeros((8, 16), np.int32),
                          lens=np.zeros((8,), np.int32))
    assert reg.observe_call("decode", fp) is None       # registration
    assert reg.observe_call("decode", dict(fp)) is None  # stable: no alarm
    changed = perf.fingerprint(tables=np.zeros((8, 17), np.int32),
                               lens=np.zeros((8,), np.int32))
    diff = reg.observe_call("decode", changed)
    assert diff is not None and set(diff) == {"tables"}
    assert diff["tables"] == ("int32[8,16]", "int32[8,17]")
    assert reg.program("decode").recompiles == 1
    assert reg.recompile_total == 1
    assert metrics.counter("recompiles", program="decode").value == 1
    evs = [e for e in tracer.events() if e["name"] == "recompile"]
    assert len(evs) == 1
    assert evs[0]["args"]["program"] == "decode"
    assert evs[0]["args"]["args"] == ["tables"]
    assert evs[0]["args"]["changed"]["tables"] == ["int32[8,16]",
                                                  "int32[8,17]"]
    # the new fingerprint is now the registered one: calling with it
    # again is stable, flipping back alarms again
    assert reg.observe_call("decode", dict(changed)) is None
    assert reg.observe_call("decode", fp) is not None
    assert reg.program("decode").recompiles == 2


def test_program_table_rows_and_fingerprint_hash():
    reg = perf.ProgramRegistry(scope="s")
    reg.note_compile("p")
    reg.observe_call("p", {"x": "f32[2]"})
    reg.set_cost("p", 123.0, 456.0, "cost_model")
    (row,) = reg.table()
    assert row["name"] == "s/p" and row["compiles"] == 1
    assert row["flops"] == 123.0 and row["cost_source"] == "cost_model"
    assert len(row["fingerprint"]) == 10


def test_live_program_table_is_weak():
    before = {r["name"] for r in perf.live_program_table()}
    reg = perf.ProgramRegistry(scope="ephemeral")
    reg.observe_call("gone", {"x": "1"})
    assert any(r["name"] == "ephemeral/gone"
               for r in perf.live_program_table())
    del reg
    gc.collect()
    after = {r["name"] for r in perf.live_program_table()}
    assert "ephemeral/gone" not in after
    assert before <= after | before  # no unrelated rows were dropped


# ---------------------------------------------------------------------------
# cost capture + MFU arithmetic (the hand-computed 5% bar)
# ---------------------------------------------------------------------------

def test_cost_model_matches_hand_computed_matmul_exactly():
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    f(a, b)  # populate the lowering cache
    cost = perf.cost_analysis_of(f, a, b)
    assert cost is not None
    hand = 2 * 64 * 128 * 32
    assert cost["flops"] == pytest.approx(hand, rel=0.01)


def test_mfu_accounting_matches_hand_computed_flops_within_5pct():
    """End-to-end through PerfAccounting on a hand-countable matmul
    chain: captured FLOPs and the derived MFU must land within 5% of the
    pencil-and-paper numbers (a faked known device peak makes the MFU
    denominator deterministic)."""
    N = 256

    def chain(a, b, c):
        return (a @ b) @ c

    f = jax.jit(chain)
    args = tuple(jnp.ones((N, N), jnp.float32) for _ in range(3))
    f(*args)
    acc = perf.PerfAccounting(scope="t", n_devices=1, device_kind="cpu")
    acc.peak_flops = 100e12          # pretend chip: 100 TFLOPs
    acc.peak_hbm_bw = 1e12           # 1 TB/s
    acc.capture_cost("chain", f, args)
    prog = acc.programs.program("chain")
    hand_flops = 2 * N ** 3 * 2      # two square matmuls
    assert prog.cost_source == "cost_model"
    assert prog.flops == pytest.approx(hand_flops, rel=0.05)
    vals = acc.on_program_step("chain", dt_s=1e-3, tokens=N)
    hand_mfu = hand_flops / (1e-3 * 100e12)
    assert vals["mfu"] == pytest.approx(hand_mfu, rel=0.05)
    assert vals["tokens_per_sec_per_chip"] == pytest.approx(N / 1e-3)
    assert vals["mbu"] is not None and vals["mbu"] > 0


def test_capture_cost_falls_back_to_estimate(monkeypatch):
    monkeypatch.setattr(perf, "cost_analysis_of", lambda *a, **k: None)
    acc = perf.PerfAccounting(scope="t", n_devices=1, device_kind="cpu")
    acc.capture_cost("p", None, (), fallback=lambda: {"flops": 42.0})
    prog = acc.programs.program("p")
    assert prog.flops == 42.0 and prog.cost_source == "estimate"
    # captured once: a later call with a different fallback is a no-op
    acc.capture_cost("p", None, (), fallback=lambda: {"flops": 7.0})
    assert acc.programs.program("p").flops == 42.0


def test_capture_cost_never_raises(monkeypatch):
    acc = perf.PerfAccounting(scope="t", n_devices=1, device_kind="cpu")

    def boom():
        raise RuntimeError("estimator bug")

    monkeypatch.setattr(perf, "cost_analysis_of", lambda *a, **k: None)
    acc.capture_cost("p", None, (), fallback=boom)
    assert acc.programs.program("p").cost_source is None


def test_transformer_flops_estimate_matches_hand_arithmetic():
    from deepspeed_tpu.models import LlamaConfig

    cfg = LlamaConfig.tiny()
    # tiny llama: L=2, h=64, i=128, H=4, Hkv=2, D=16, V=256; ctx=256
    qkv = 2 * 64 * (4 * 16 + 2 * 2 * 16)
    o = 2 * 64 * 64
    mlp = 2 * 64 * 128 * 3
    attn = 2 * 2 * 4 * 16 * 256
    hand = 2 * (qkv + o + mlp + attn) + 2 * 64 * 256
    assert perf.transformer_flops_per_token(cfg, 256) == hand
    assert perf.estimate_decode_step_flops(cfg, 8, 256) == 8 * hand


# ---------------------------------------------------------------------------
# device peaks / watermarks / meta
# ---------------------------------------------------------------------------

def test_device_peaks_lookup():
    assert perf.device_peaks("TPU v5 lite") == (197e12, 819e9)
    assert perf.device_peaks("TPU v4") == (275e12, 1228e9)
    assert perf.device_peaks("cpu") == (None, None)
    assert perf.device_peaks(None) == (None, None)
    # an accelerator the table does not know is an error, never a default
    with pytest.raises(KeyError, match="TPU v9"):
        perf.device_peaks("TPU v9")


def test_memory_watermarks_graceful_without_allocator_stats():
    # CPU backend exposes no memory_stats: absent, not zero
    if jax.devices()[0].platform == "cpu":
        assert perf.device_memory_stats() == []
        assert perf.hbm_watermarks() == (None, None)
        acc = perf.PerfAccounting(scope="t")
        assert acc.memory_watermarks() == (None, None)
        assert acc._mem_capable is False  # probed once, then free


# ---------------------------------------------------------------------------
# training engine integration
# ---------------------------------------------------------------------------

def test_training_engine_registers_train_step_with_cost_and_gauges():
    from tests.unit.simple_model import SimpleModel, batch_of

    engine, _, _, _ = ds.initialize(
        model=SimpleModel(),
        config={"train_batch_size": 16, "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                # a progress line every step: each call publishes its step
                # (the gauges follow two publications, not one call's wall
                # clock, which holds no device time while nobody waits)
                "steps_per_print": 1},
        example_batch=batch_of(2))
    for i in range(3):
        engine.train_batch(batch=batch_of(16, seed=i))
    prog = engine.perf.programs.program("train_step")
    assert prog.compiles == 1          # ONE resident compile
    assert prog.recompiles == 0
    assert prog.calls == 3
    assert prog.flops and prog.flops > 0
    # the step's own count of its matrix work (``StepCost``, walked off the
    # jaxpr the lowering came from), exact: 6 x weights x batch -- forward,
    # weight gradient, input gradient -- less the first layer's input
    # gradient, which nobody asks for; biases and Adam are no matrix work
    assert prog.cost_source == "jaxpr" and prog.bytes_accessed is None
    kernels = [p for p in jax.tree_util.tree_leaves(engine.state.params)
               if p.ndim == 2]
    first = max(kernels, key=lambda p: p.shape[0] * p.shape[1])
    assert prog.flops == 16 * (6 * sum(int(np.prod(p.shape)) for p in kernels)
                               - 2 * int(np.prod(first.shape)))
    row = next(r for r in engine.perf.programs.table()
               if r["name"] == "train/train_step")
    assert row["step_cost"]["matmul_flops"] == prog.flops
    assert row["step_cost"]["replayed_flops"] == 0
    snap = engine.registry.snapshot()
    assert snap.get("train_tflops_per_chip", 0) > 0
    # CPU has no known peak: the MFU gauge must be absent, not garbage
    if jax.devices()[0].platform == "cpu":
        assert "train_mfu" not in snap


def test_dense_generate_registers_per_bucket_programs():
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ds.init_inference(model, params=params, dtype="fp32")
    ids = np.arange(1, 9)[None]
    eng.generate(ids, max_new_tokens=4)
    eng.generate(ids, max_new_tokens=4)           # same bucket: cached
    eng.generate(np.arange(1, 21)[None], max_new_tokens=4)  # new bucket
    table = {r["name"]: r for r in eng.perf.programs.table()}
    small = table["inference/generate[b1,t8,n4]"]
    assert small["compiles"] == 1 and small["calls"] == 2
    assert small["recompiles"] == 0
    assert small["flops"] and small["flops"] > 0  # captured on call two
    assert "inference/generate[b1,t32,n4]" in table  # bucket churn visible


def test_program_table_is_point_in_time_under_registration():
    """``table()`` feeds /statusz from the admin thread while the engine
    registers per-bucket programs; it must materialize a snapshot
    (``list()`` first — the same law ``recompile_total`` already
    follows) instead of sorting a live dict view. The hammer pins the
    no-exception contract and that every returned row is whole."""
    import sys
    import threading

    reg = perf.ProgramRegistry()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    stop = threading.Event()

    def register():
        i = 0
        while not stop.is_set():
            reg.program(f"prog{i}")
            i += 1
            if i % 128 == 0:
                # bound the table size while keeping key churn hot;
                # writers follow the same lock discipline readers rely
                # on (the pre-lock sorted-live-view version of table()
                # raised RuntimeError under exactly this churn)
                with reg._lock:
                    for j in range(i - 128, i):
                        reg.programs.pop(f"prog{j}", None)

    t = threading.Thread(target=register, daemon=True)
    t.start()
    try:
        for _ in range(400):
            rows = reg.table()
            assert all(isinstance(r, dict) and "name" in r for r in rows)
            reg.recompile_total
    finally:
        stop.set()
        t.join()
        sys.setswitchinterval(old)
