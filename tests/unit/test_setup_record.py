"""Set-up seen from inside (``monitor/perf.py`` ``CompileLedger`` and
``SetupRecord``, ``runtime/engine.py``'s set-up spans and ``_publish_setup``):
what ``jax.monitoring`` reports of every compile is charged to the innermost
open set-up span or to ``outside``, the engine keeps its set-up as a flat
record whether or not the ring is enabled, and publishes it once a profiler
session as ``ds.setup``. CPU only: counts and order, never a time's size."""

import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import perf
from deepspeed_tpu.monitor.export import LEDGER_HEADER, ledger_columns
from deepspeed_tpu.monitor.tracing import Tracer
from tests.unit.simple_model import SimpleModel, batch_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHILDREN = ("init_shapes", "init_params", "init_opt_state", "init_step")
SECONDS = ("trace_s", "lower_s", "backend_s")


def fresh(name, k):
    """A jitted function no test compiled before: its own name (the
    ledger's per-function key) and its own constant."""
    def f(x):
        return x * k + 1.0

    f.__name__ = name
    return jax.jit(f)


# -- the ledger --------------------------------------------------------------

@pytest.mark.parametrize("spelled,key", [
    ("jit(ds_train_step_n3)", "train_step"),     # lowered / compiled module
    ("ds_train_step_n3", "train_step"),          # the traced function
    ("train_step", "train_step"),                # the registry's program
    ("jit(ds_mixed_step_n12)", "mixed_step"),
    ("mixed_step[64]", "mixed_step"),            # a bucket of one function
    ("pmap(decode)", "decode"),
    ("jit(_threefry_split)", "_threefry_split"),
    ("ds_norm", "norm"),
])
def test_one_program_has_one_key_however_jax_spells_it(spelled, key):
    assert perf._fun_key(spelled) == key


def test_a_compile_is_charged_to_the_open_span_or_to_outside():
    rec, tr = perf.SetupRecord(), Tracer(capacity=8, enabled=False)
    assert rec.ledger is perf.compile_ledger()       # one a process
    x = jnp.ones((3,), jnp.float32)
    outside0 = rec.ledger.snapshot()["outside"]
    with rec.span(tr.span("init", cat="setup")):
        fresh("setup_test_a", 2.0)(x)
        with rec.span(tr.span("init_params", cat="setup")):
            fresh("setup_test_b", 3.0)(x)
            # a plain span names no set-up part: the innermost stays
            with tr.span("dispatch"):
                fresh("setup_test_c", 4.0)(x)
        fresh("setup_test_d", 5.0)(x)
    assert rec.sums["init"]["programs"] == 2
    assert rec.sums["init_params"]["programs"] == 2
    for row in rec.sums.values():
        assert all(row[k] > 0 for k in SECONDS)
    assert rec.ledger.snapshot()["outside"] == outside0
    fresh("setup_test_e", 6.0)(x)
    outside1 = rec.ledger.snapshot()["outside"]
    assert outside1["programs"] == outside0.get("programs", 0) + 1
    assert all(outside1[k] > outside0.get(k, 0) for k in SECONDS)
    assert sum(r["programs"] for r in rec.sums.values()) == 4
    # the function's own row, under the registry's spelling of it
    row = rec.ledger.program("setup_test_b")
    assert set(row) == {"trace_s", "lower_s", "backend_s", "cache_hit"}
    assert all(row[k] > 0 for k in SECONDS)
    assert rec.ledger.program("never_compiled") == {
        "trace_s": None, "lower_s": None, "backend_s": None,
        "cache_hit": None}
    # the span's own seconds, with the ring off
    assert rec.seconds["init"] >= rec.seconds["init_params"] > 0
    assert len(tr) == 0


def test_a_warm_call_fires_no_listener():
    ledger = perf.compile_ledger()
    f = fresh("setup_test_warm", 7.0)
    x = jnp.ones((5,), jnp.float32)
    calls0 = ledger.snapshot()["calls"]
    f(x).block_until_ready()
    calls1 = ledger.snapshot()["calls"]
    assert calls1 > calls0                  # the compile was heard
    for _ in range(5):
        f(x).block_until_ready()
    assert ledger.snapshot()["calls"] == calls1
    assert ledger.snapshot()["listener_s"] > 0


def test_a_span_sums_the_outermost_traces_alone():
    """A jitted function traces the jitted functions it calls, and jax
    reports each with its own inclusive duration: the span's ``trace_s`` is
    the outer function's, not the sum of all."""
    inner = fresh("setup_test_inner", 8.0)

    def outer(x):
        return inner(inner(x) + jnp.tanh(x))

    outer.__name__ = "setup_test_outer"
    rec, tr = perf.SetupRecord(), Tracer(capacity=8, enabled=False)
    x = jnp.ones((7,), jnp.float32)
    with rec.span(tr.span("init_step", cat="setup")):
        jax.jit(outer)(x)
    by_fun = rec.ledger.snapshot()["by_fun"]
    assert by_fun["setup_test_inner"]["trace_s"] > 0
    assert rec.sums["init_step"]["trace_s"] == pytest.approx(
        by_fun["setup_test_outer"]["trace_s"])
    assert rec.sums["init_step"]["programs"] == 1


CACHE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from deepspeed_tpu.monitor import perf
from deepspeed_tpu.monitor.tracing import Tracer

def cached_fn(x):
    return jnp.tanh(x) @ x.T

rec, tr = perf.SetupRecord(), Tracer(capacity=4, enabled=False)
x = jnp.ones((8, 8), jnp.float32)
out = {}
for name in ("init_params", "init_step"):
    with rec.span(tr.span(name, cat="setup")):
        jax.jit(cached_fn)(x).block_until_ready()
    out[name + "_program"] = rec.ledger.program("cached_fn")["cache_hit"]
    jax.clear_caches()
out["sums"] = rec.sums
out["record"] = rec.record(3)
print(json.dumps(out))
"""


def test_a_second_fresh_compile_reads_the_persistent_cache(tmp_path):
    """A process-fresh compile (``jax.clear_caches()``) of a program the
    persistent cache holds: a hit, ``cache_read_s`` above 0 — and the miss
    before it makes the program's row read not-from-the-cache."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(tmp_path / "cache")],
        env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    cold, warm = got["sums"]["init_params"], got["sums"]["init_step"]
    assert cold["cache_misses"] >= 1 and not cold.get("cache_hits")
    assert warm["cache_hits"] >= 1 and not warm.get("cache_misses")
    assert warm["cache_read_s"] > 0 and not cold.get("cache_read_s")
    assert warm["backend_s"] >= warm["cache_read_s"]     # the read is inside
    # every compile of the program so far missed; then one hit among them
    assert got["init_params_program"] is False
    assert got["init_step_program"] is False
    rec = got["record"]
    assert rec["cache_hits"] == warm["cache_hits"]
    assert rec["cache_misses"] == cold["cache_misses"]
    assert rec["import_s"] == 0.0 and rec["steps_before"] == 3
    assert rec["init_params_s"] > 0 and rec["init_s"] == 0.0


# -- the engine at tiny size -------------------------------------------------

RECORD_KEYS = (
    [f"{k}_s" for k in perf.SetupRecord.PARTS]
    + list(perf.SetupRecord.COUNTS) + list(perf.LEDGER_COLUMNS)
    + [f"outside_{k}" for k in perf.LEDGER_COLUMNS] + ["steps_before"])


def tiny_engine(tracing):
    engine, _, _, _ = ds.initialize(
        model=SimpleModel(),
        config={"train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "tracing": {"enabled": tracing}},
        example_batch=batch_of(2))
    return engine


@pytest.fixture(scope="module")
def traced_engine():
    engine = tiny_engine(True)
    for _ in range(3):
        engine.train_batch(batch=batch_of(16))
    return engine


def test_set_up_spans_stand_in_the_ring(traced_engine):
    events = traced_engine.tracer.events()
    names = [e["name"] for e in events]
    for name in ("init",) + CHILDREN + ("compile", "cost_capture", "setup"):
        assert names.count(name) == 1, name
    by = {e["name"]: e for e in events if e["name"] in
          ("init", "compile", "cost_capture", "setup") + CHILDREN}
    inside = lambda a, b: b["ts"] <= a["ts"] and \
        a["ts"] + a["dur"] <= b["ts"] + b["dur"]
    for child in CHILDREN:
        assert inside(by[child], by["init"])
    # the children in the order the constructor runs them
    starts = [by[c]["ts"] for c in CHILDREN]
    assert starts == sorted(starts)
    first = next(e for e in events if e["name"] == "train_batch")
    dispatch = next(e for e in events if e["name"] == "train_step")
    assert inside(dispatch, by["compile"]) and inside(by["compile"], first)
    assert inside(by["cost_capture"], first) and inside(by["setup"], first)
    assert by["compile"]["ts"] + by["compile"]["dur"] <= \
        by["cost_capture"]["ts"] <= by["setup"]["ts"]
    # the ring's ``setup`` event carries the whole record
    assert list(by["setup"]["args"]) == RECORD_KEYS


def test_the_record_adds_up(traced_engine):
    rec = traced_engine.setup.record(7)
    assert list(rec) == RECORD_KEYS and len(rec) == 33
    assert all(isinstance(v, (int, float)) and v >= 0 for v in rec.values())
    assert rec["steps_before"] == 7
    # the imports on the way to an engine were stamped, and what lay
    # between them and the constructor
    assert rec["import_s"] == sum(ds.IMPORT_SECONDS.values()) > 0
    assert ds.IMPORT_SECONDS["package"] > 0 <= ds.IMPORT_SECONDS["engine"]
    assert rec["pre_init_s"] > 0
    assert rec["init_s"] >= sum(rec[f"{c}_s"] for c in CHILDREN) > 0
    assert rec["first_step_s"] >= \
        rec["first_dispatch_s"] + rec["first_wait_s"] > 0
    assert rec["cost_capture_s"] > 0
    # the step's compile and the init program's, at the least
    assert rec["programs"] >= 2 and rec["backend_s"] > 0
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    # a later step changes no part
    before = dict(traced_engine.setup.seconds)
    traced_engine.train_batch(batch=batch_of(16))
    assert traced_engine.setup.seconds == before


def test_set_up_ends_where_the_first_step_returns():
    """From there on the record stands: a step the sentinel flags (another
    batch shape: a recompile under the ``compile`` span) and a function the
    caller compiles later are charged to the ledger — ``outside`` grows, the
    program's row does — and to no engine's set-up."""
    engine = tiny_engine(True)
    engine.train_batch(batch=batch_of(16))
    rec = engine.setup.record(1)
    outside = dict(engine.setup.ledger.snapshot()["outside"])
    row = engine.setup.ledger.program("train_step")
    fresh("late_outside_f", 11.0)(jnp.ones(5)).block_until_ready()
    half = batch_of(16)
    engine.train_batch(batch={**half, "x": half["x"].astype("float16")})
    assert engine.perf.programs.program("train_step").recompiles == 1
    names = [e["name"] for e in engine.tracer.events()]
    assert names.count("compile") == 2 and names.count("setup") == 1
    assert engine.setup.record(1) == rec
    later = engine.setup.ledger.snapshot()["outside"]
    assert later["programs"] > outside["programs"]
    assert engine.setup.ledger.program("train_step")["backend_s"] > \
        row["backend_s"]
    # the first wait is the first ``counters`` span's seconds
    counters = next(e for e in engine.tracer.events()
                    if e["name"] == "counters")
    assert rec["first_wait_s"] == pytest.approx(counters["dur"] / 1e6,
                                                abs=1e-5)


def test_a_disabled_ring_stays_empty_and_the_record_is_kept():
    engine = tiny_engine(False)
    engine.train_batch(batch=batch_of(16))
    assert len(engine.tracer) == 0 and not engine.tracer.enabled
    rec = engine.setup.record(0)
    assert rec["init_s"] >= sum(rec[f"{c}_s"] for c in CHILDREN) > 0
    assert rec["first_step_s"] > 0 and rec["cost_capture_s"] > 0
    gauges = engine.registry.snapshot()
    for key in RECORD_KEYS:
        assert f"setup_{key}" in gauges, key
    assert gauges["setup_init_s"] == rec["init_s"]
    assert gauges["setup_steps_before"] == 0


def test_program_rows_show_the_ledgers_columns(traced_engine, capsys):
    """What ``/statusz`` and ``ds_report`` print of a resident program:
    what its compile cost and whether the cache served it."""
    (row,) = traced_engine.perf.programs.table()
    assert row["name"] == "train/train_step"
    assert {"trace_s", "lower_s", "backend_s", "cache_hit"} <= set(row)
    assert all(row[k] > 0 for k in SECONDS)
    assert row["cache_hit"] in (True, False)     # the suite's cache is on
    assert traced_engine.perf.summary()["programs"][0]["backend_s"] > 0
    assert LEDGER_HEADER.split() == ["trace_s", "lower_s", "backend_s",
                                     "cache"]
    assert ledger_columns({"trace_s": 1.234, "lower_s": 0.5,
                           "backend_s": 61.0, "cache_hit": False}).split() \
        == ["1.23", "0.50", "61.00", "miss"]
    assert ledger_columns({"cache_hit": True}).split() == ["-"] * 3 + ["hit"]
    assert ledger_columns({}).split() == ["-"] * 4
    from deepspeed_tpu.env_report import perf_report

    perf_report()
    said = capsys.readouterr().out
    assert LEDGER_HEADER in said
    line = next(l for l in said.splitlines()
                if l.startswith("train/train_step"))
    assert {"hit", "miss"} & set(line.split())


# -- publication on the profiler's clock -------------------------------------

def setup_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in ("ds.setup", "ds.train_batch")]
    return ([f for f in found if f[0] == "ds.setup"],
            [f for f in found if f[0] == "ds.train_batch"])


def test_one_setup_event_a_profiler_session(tmp_path):
    engine = tiny_engine(False)
    for _ in range(2):
        engine.train_batch(batch=batch_of(16))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for session, steps_before in (("one", 2), ("two", 6)):
        jax.profiler.start_trace(str(tmp_path / session),
                                 profiler_options=opts)
        try:
            for _ in range(3):
                engine.train_batch(batch=batch_of(16))
        finally:
            jax.profiler.stop_trace()
        engine.train_batch(batch=batch_of(16))      # between the sessions
        published, steps = setup_events(str(tmp_path / session))
        assert len(steps) == 3
        (event,) = published
        _, stats, start, dur = event
        assert {k for k in stats if not k.startswith("_")} == \
            set(RECORD_KEYS)
        assert float(stats["steps_before"]) == steps_before
        assert float(stats["init_s"]) == pytest.approx(
            engine.setup.seconds["init"])
        assert float(stats["programs"]) >= 2
        # inside the session's first ds.train_batch
        s0, d0 = min((s, d) for _, _, s, d in steps)
        assert s0 <= start and start + dur <= s0 + d0
    assert len(engine.tracer) == 0
