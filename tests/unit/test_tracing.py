"""Tracer ring-buffer properties + flight-recorder post-mortems
(``monitor/tracing.py``).

The contracts pinned here are the ones the serving/training engines lean
on: bounded memory under unbounded events, append order == time order for
instants, concurrent writers (the step watchdog thread traces from off
the main thread), a disabled tracer that allocates nothing, and a flight
recorder whose dumps are whole-or-absent and never raise.
"""

import json
import os
import threading

import pytest

from deepspeed_tpu.monitor import tracing
from deepspeed_tpu.monitor.tracing import (FlightRecorder, Tracer,
                                           validate_event)
from deepspeed_tpu.utils import fault_injection


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

def test_ring_bounded_under_unbounded_events():
    tr = Tracer(capacity=64)
    for i in range(1000):
        tr.instant("e", args={"i": i})
    assert len(tr) == 64
    assert tr.dropped == 1000 - 64
    evs = tr.events()
    assert len(evs) == 64
    # the newest events win: exactly the last 64, still in append order
    assert [e["args"]["i"] for e in evs] == list(range(936, 1000))


def test_ring_under_capacity_keeps_everything_in_order():
    tr = Tracer(capacity=128)
    for i in range(50):
        tr.instant("e", args={"i": i})
    assert len(tr) == 50 and tr.dropped == 0
    assert [e["args"]["i"] for e in tr.events()] == list(range(50))


def test_instant_ring_order_is_time_order():
    # ts is captured under the ring lock, so the snapshot is monotone
    tr = Tracer(capacity=256)
    for _ in range(200):
        tr.instant("e")
    ts = [e["ts"] for e in tr.events()]
    assert ts == sorted(ts)


def test_concurrent_writers_from_threads():
    """The watchdog thread and the main loop write the same ring: no
    events torn, per-thread order preserved, memory still bounded."""
    tr = Tracer(capacity=512)
    n_threads, per_thread = 8, 400

    def writer(k):
        for i in range(per_thread):
            tr.instant("w", args={"k": k, "i": i})

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr._count == n_threads * per_thread
    assert len(tr) == 512
    evs = tr.events()
    assert all(validate_event(e) is None for e in evs)
    # within each writer, kept events appear in that writer's emit order
    per_k = {}
    for e in evs:
        per_k.setdefault(e["args"]["k"], []).append(e["args"]["i"])
    for seq in per_k.values():
        assert seq == sorted(seq)
    # and ring order is time order even across writers
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)


def test_capacity_validated():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


# ---------------------------------------------------------------------------
# disabled tracer: zero work
# ---------------------------------------------------------------------------

def test_disabled_tracer_writes_nothing():
    tr = Tracer(capacity=8, enabled=False)
    # a span still opens (its profiler annotation is live either way) and
    # takes late arguments, but nothing reaches the ring
    with tr.span("a", args={"step": 1}) as sp:
        sp.set(tokens=3)
    tr.instant("x", args={"big": list(range(10))})
    tr.complete("y", 0.0, 1.0)
    assert len(tr) == 0 and tr._count == 0


def test_span_records_complete_event():
    tr = Tracer(capacity=8)
    with tr.span("op", cat="test", args={"rid": "r1"}):
        pass
    (ev,) = tr.events()
    assert ev["name"] == "op" and ev["ph"] == "X" and ev["dur"] >= 0
    assert ev["args"] == {"rid": "r1"} and ev["cat"] == "test"
    assert validate_event(ev) is None


@pytest.mark.parametrize("kwargs,late,want_name,want_args", [
    (dict(name="plan", cat="host", args={"step": 3}), {},
     "plan", {"step": 3}),
    (dict(name="plan", cat="host", args={"step": 3}), {"grants": 2},
     "plan", {"step": 3, "grants": 2}),
    (dict(name="dispatch", cat="engine", ring="mixed_step"), {"rows": 4},
     "mixed_step", {"rows": 4}),
    (dict(name="train_batch", cat="train", step=7), {},
     "train_batch", {"step": 7}),
    (dict(name="bare"), {}, "bare", None),
])
def test_span_writes_the_event_complete_would(kwargs, late, want_name,
                                              want_args):
    """``span`` and ``complete`` leave the same ring event: schema, name
    (``ring`` keeps an older spelling), category, arguments, late ones
    included."""
    tr, ref = Tracer(capacity=8), Tracer(capacity=8)
    with tr.span(**kwargs) as sp:
        if late:
            sp.set(**late)
    (ev,) = tr.events()
    ref.complete(want_name, ev["ts"] / 1e6, (ev["ts"] + ev["dur"]) / 1e6,
                 cat=kwargs.get("cat", ""), args=want_args)
    (want,) = ref.events()
    assert validate_event(ev) is None
    assert {k: v for k, v in ev.items() if k != "dur"} == \
        {k: v for k, v in want.items() if k != "dur"}
    assert ev["dur"] == pytest.approx(want["dur"], abs=1e-3)


def test_nested_spans_close_inner_first_and_contain():
    tr = Tracer(capacity=8)
    with tr.span("step", cat="engine", step=1):
        with tr.span("plan", cat="host", args={"step": 1}):
            pass
        with tr.span("pack", cat="host", args={"step": 1}):
            pass
    plan, pack, step = tr.events()
    assert [e["name"] for e in (plan, pack, step)] == ["plan", "pack", "step"]
    assert step["ts"] <= plan["ts"] <= plan["ts"] + plan["dur"] <= pack["ts"]
    assert pack["ts"] + pack["dur"] <= step["ts"] + step["dur"]


def test_span_reaches_the_profiler_whatever_the_tracer(tmp_path):
    """The second sink: with a profiler recording, every span is a
    ``ds.<name>`` event on its clock, arguments as stats, late ones
    included — tracer enabled or not."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for tr in (Tracer(capacity=8, enabled=False), Tracer(capacity=8)):
            with tr.span("step", cat="engine", step=5):
                with tr.span("dispatch", ring="mixed_step",
                             args={"step": 5, "enabled": int(tr.enabled)}
                             ) as sp:
                    sp.set(rows=3)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("ds.")]
    steps = [f for f in found if f[0] == "ds.step"]
    inner = [f for f in found if f[0] == "ds.dispatch"]
    assert len(steps) == len(inner) == 2       # never the ring's spelling
    assert sorted(f[1]["enabled"] for f in inner) == [0, 1]
    for (_, stats, s, d), (_, outer, s0, d0) in zip(inner, steps):
        assert stats["step"] == 5 and stats["rows"] == 3
        assert outer["step_num"] == 5
        assert s0 <= s and s + d <= s0 + d0


# ---------------------------------------------------------------------------
# schema + export
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ev,needle", [
    ("not a dict", "expected object"),
    ({"ph": "X", "ts": 0, "dur": 1}, "name"),
    ({"name": "e", "ph": "Q", "ts": 0}, "'ph'"),
    ({"name": "e", "ph": "i", "ts": -5}, "'ts'"),
    ({"name": "e", "ph": "X", "ts": 0}, "'dur'"),
    ({"name": "e", "ph": "i", "ts": 0, "args": [1]}, "'args'"),
    ({"name": "e", "ph": "i", "ts": 0, "tid": "t"}, "'tid'"),
])
def test_validate_event_rejects_malformed(ev, needle):
    problem = validate_event(ev)
    assert problem is not None and needle in problem


def test_chrome_export_loads_and_validates(tmp_path):
    tr = Tracer(capacity=32)
    tr.instant("a", cat="c")
    tr.complete("b", 1.0, 2.0, args={"rid": "r"})
    path = tr.dump(str(tmp_path / "sub" / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:
        assert validate_event(ev) is None
        assert ev["pid"] == os.getpid()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _read_dump(path):
    lines = open(path).read().splitlines()
    return json.loads(lines[0]), [json.loads(l) for l in lines[1:]]


def test_flight_dump_contains_header_metrics_and_last_n(tmp_path):
    tr = Tracer(capacity=1024)
    for i in range(300):
        tr.instant("e", args={"i": i})
    fr = FlightRecorder(str(tmp_path), tr, last_n=100,
                        metrics_fn=lambda: {"queue_depth": 3.0})
    path = fr.record("watchdog_trip", {"rids": ["req-7"], "step": 42})
    assert path is not None and os.path.exists(path)
    assert fr.dumps == [path]
    header, events = _read_dump(path)
    assert header["kind"] == "flight_recorder"
    assert header["trigger"] == "watchdog_trip"
    assert header["detail"] == {"rids": ["req-7"], "step": 42}
    assert header["metrics"] == {"queue_depth": 3.0}
    # exactly the last 100 ring events, schema-valid
    assert header["events"] == 100 and len(events) == 100
    assert [e["args"]["i"] for e in events] == list(range(200, 300))
    assert all(validate_event(e) is None for e in events)


def test_two_recorders_same_dir_never_collide(tmp_path):
    """Two recorder instances sharing one out dir (training + serving
    engines in one process) dumping the SAME trigger within the same
    second must write distinct files — the dump sequence is
    process-global, so os.replace can never discard a post-mortem."""
    tr = Tracer(capacity=8)
    tr.instant("e")
    fr_a = FlightRecorder(str(tmp_path), tr)
    fr_b = FlightRecorder(str(tmp_path), tr)
    p_a = fr_a.record("fault_corrupt_logits")
    p_b = fr_b.record("fault_corrupt_logits")
    assert p_a != p_b and os.path.exists(p_a) and os.path.exists(p_b)


def test_flight_dump_never_raises(tmp_path):
    tr = Tracer(capacity=8)
    tr.instant("e")
    # metrics_fn exploding must not lose the dump
    fr = FlightRecorder(str(tmp_path), tr,
                        metrics_fn=lambda: 1 / 0)
    path = fr.record("incident")
    header, _ = _read_dump(path)
    assert "_metrics_error" in header["metrics"]
    # an unwritable out_dir (a FILE is in the way) returns None, no raise
    blocker = tmp_path / "blocked"
    blocker.write_text("not a dir")
    fr2 = FlightRecorder(str(blocker), tr)
    assert fr2.record("incident") is None


def test_flight_recorder_dumps_on_ds_fault(tmp_path, monkeypatch):
    """Every DS_FAULT firing leaves a post-mortem while armed — the
    chaos-drill contract (fault name + context land in the header)."""
    tr = Tracer(capacity=64)
    tr.instant("before_fault")
    fr = FlightRecorder(str(tmp_path), tr)
    fr.arm_faults()
    try:
        monkeypatch.setenv(fault_injection.ENV_VAR, "flaky_save:fails=1")
        fault_injection.reset()
        with pytest.raises(OSError):
            fault_injection.maybe_fail("flaky_save", tag="t1")
    finally:
        fr.disarm()
        monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
        fault_injection.reset()
    assert len(fr.dumps) == 1
    header, events = _read_dump(fr.dumps[0])
    assert header["trigger"] == "fault_flaky_save"
    assert header["detail"]["tag"] == "t1"
    assert events and events[-1]["name"] == "before_fault"
    # disarmed: further firings leave no new dumps
    monkeypatch.setenv(fault_injection.ENV_VAR, "flaky_save:fails=1")
    fault_injection.reset()
    with pytest.raises(OSError):
        fault_injection.maybe_fail("flaky_save")
    monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
    fault_injection.reset()
    assert len(fr.dumps) == 1


def test_fault_arming_exclusive_per_dir(tmp_path, monkeypatch):
    """Two live recorders sharing one out dir (an env-armed global next
    to an engine's own) must produce ONE post-mortem per firing per
    directory; a recorder on its own dir still dumps independently, and
    a freed slot (disarm) is claimable by the other recorder."""
    tr = Tracer(capacity=8)
    tr.instant("e")
    fr_a = FlightRecorder(str(tmp_path / "shared"), tr)
    fr_b = FlightRecorder(str(tmp_path / "shared"), tr)
    other = FlightRecorder(str(tmp_path / "own"), tr)

    def fire():
        monkeypatch.setenv(fault_injection.ENV_VAR, "flaky_save:fails=1")
        fault_injection.reset()
        try:
            with pytest.raises(OSError):
                fault_injection.maybe_fail("flaky_save")
        finally:
            monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
            fault_injection.reset()

    fr_a.arm_faults()
    fr_b.arm_faults()  # refused: fr_a already covers the dir
    other.arm_faults()
    try:
        fire()
        assert len(fr_a.dumps) == 1 and len(fr_b.dumps) == 0
        assert len(other.dumps) == 1
        fr_a.disarm()   # frees the shared slot
        fr_b.arm_faults()  # now claimable
        fire()
        assert len(fr_a.dumps) == 1 and len(fr_b.dumps) == 1
        assert len(other.dumps) == 2
    finally:
        fr_a.disarm()
        fr_b.disarm()
        other.disarm()


def test_armed_recorder_is_garbage_collectable(tmp_path, monkeypatch):
    """The fault listener holds only a weak reference: an armed recorder
    (and the engine behind its metrics_fn) can be dropped and collected;
    the next firing self-removes the dead listener and leaves no dump."""
    import gc
    import weakref

    tr = Tracer(capacity=8)
    tr.instant("e")
    fr = FlightRecorder(str(tmp_path), tr)
    fr.arm_faults()
    n_before = len(fault_injection._listeners)
    ref = weakref.ref(fr)
    del fr
    gc.collect()
    assert ref() is None  # nothing in the arming machinery pins it
    monkeypatch.setenv(fault_injection.ENV_VAR, "flaky_save:fails=1")
    fault_injection.reset()
    try:
        with pytest.raises(OSError):
            fault_injection.maybe_fail("flaky_save")
    finally:
        monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
        fault_injection.reset()
    assert len(fault_injection._listeners) == n_before - 1
    assert list(tmp_path.iterdir()) == []  # no post-mortem from a ghost


def test_fault_listener_failure_does_not_alter_drill(monkeypatch):
    """A broken observer must never change fault semantics."""
    def bad_listener(name, ctx):
        raise RuntimeError("observer bug")

    fault_injection.add_listener(bad_listener)
    try:
        monkeypatch.setenv(fault_injection.ENV_VAR, "flaky_save:fails=1")
        fault_injection.reset()
        with pytest.raises(OSError):  # the fault still fires normally
            fault_injection.maybe_fail("flaky_save")
    finally:
        fault_injection.remove_listener(bad_listener)
        monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
        fault_injection.reset()


# ---------------------------------------------------------------------------
# process-global default (env arming)
# ---------------------------------------------------------------------------

def test_env_arms_global_tracer_and_flight(tmp_path, monkeypatch):
    tracing.reset_default()
    try:
        monkeypatch.setenv(tracing.ENV_TRACE_DIR, str(tmp_path))
        tr = tracing.get_tracer()
        assert tr.enabled
        assert tracing.default_flight_recorder() is not None
        tr.instant("global_event")
        path = tracing.flight_dump("unit_test", {"why": "env"})
        assert path is not None and os.path.exists(path)
        header, events = _read_dump(path)
        assert header["trigger"] == "unit_test"
        assert events[-1]["name"] == "global_event"
    finally:
        tracing.reset_default()


def test_no_env_means_disabled_global_tracer(monkeypatch):
    monkeypatch.delenv(tracing.ENV_TRACE_DIR, raising=False)
    tracing.reset_default()
    try:
        assert not tracing.get_tracer().enabled
        assert tracing.flight_dump("nobody_listens") is None
    finally:
        tracing.reset_default()


# ---------------------------------------------------------------------------
# training engine: step spans + checkpoint I/O spans + registry
# ---------------------------------------------------------------------------

def _train_engine(tmp_path=None, **tracing_over):
    import deepspeed_tpu as ds
    from tests.unit.simple_model import SimpleModel, batch_of

    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "steps_per_print": 0,
    }
    if tracing_over:
        cfg["tracing"] = tracing_over
    engine, _, _, _ = ds.initialize(model=SimpleModel(), config=cfg,
                                    example_batch=batch_of(2))
    return engine, batch_of


def test_training_step_and_checkpoint_spans(tmp_path):
    engine, batch_of = _train_engine(dir=str(tmp_path / "traces"))
    try:
        assert engine.tracer.enabled and engine.flight is not None
        for i in range(2):
            engine.train_batch(batch=batch_of(8, seed=i))
        engine.save_checkpoint(str(tmp_path / "ckpt"))
        names = [e["name"] for e in engine.tracer.events()]
        assert names.count("train_batch") == 2
        assert names.count("train_step") == 2
        assert "checkpoint_save" in names
        assert all(validate_event(e) is None
                   for e in engine.tracer.events())
        # the registry's step histogram observed both steps (and flows to
        # monitor backends via write_registry)
        snap = engine.registry.snapshot()
        assert snap["train_batch_s_count"] == 2.0
        assert snap["checkpoint_save_s_count"] == 1.0
        assert "train_batch_s_p50" in snap
    finally:
        engine.flight.disarm()


def test_checkpoint_verify_incident_dumps_once(tmp_path, monkeypatch):
    """Engine recorder + env-armed global recorder both alive: a verify
    failure with no fallback leaves exactly ONE post-mortem — manifest.py
    dumps through the global recorder and the engine skips its own."""
    from deepspeed_tpu.checkpoint import manifest as M

    traces = tmp_path / "traces"
    monkeypatch.setenv(tracing.ENV_TRACE_DIR, str(traces))
    tracing.reset_default()
    engine, batch_of = _train_engine(dir=str(traces))
    try:
        engine.train_batch(batch=batch_of(8))
        d = str(tmp_path / "ckpt")
        engine.save_checkpoint(d)
        tag = M.read_latest_tag(d)
        with open(M.manifest_path(d, tag), "r+b") as f:
            f.write(b"XXgarbage")  # explicit bad tag: raises, no fallback
        with pytest.raises(M.CheckpointCorruptionError):
            engine.load_checkpoint(d, tag=tag)
    finally:
        if engine.flight is not None:
            engine.flight.disarm()
        tracing.reset_default()
    dumps = [p.name for p in traces.iterdir()
             if "checkpoint_verify" in p.name]
    assert len(dumps) == 1, dumps


def test_training_tracing_disabled_by_default():
    engine, batch_of = _train_engine()
    assert not engine.tracer.enabled and engine.flight is None
    engine.train_batch(batch=batch_of(8))
    assert engine.tracer._count == 0
    # the registry still measures (histograms are not tracing)
    assert engine.registry.snapshot()["train_batch_s_count"] == 1.0
