"""Per-request span timelines + flight-recorder drills on a live
ServingEngine (the observability acceptance suite).

Contracts pinned here:

1. timeline completeness — EVERY terminal request has a submit instant, a
   terminal ``request`` umbrella span, and queue/prefill/decode phase
   spans that tile submit -> terminal (contiguous, non-overlapping,
   summing to the request's wall time);
2. flight-recorder chaos drills — a watchdog trip and a logit quarantine
   each produce a post-mortem dump NAMING the offending rid;
3. a disabled tracer emits nothing and allocates nothing on the decode
   hot path;
4. ``dump_trace`` writes Perfetto-loadable Chrome-trace JSON that
   ``tools/trace_view.py`` validates and decomposes.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
from deepspeed_tpu.monitor.tracing import validate_event
from deepspeed_tpu.utils import fault_injection

pytestmark = [pytest.mark.serving]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

MAX_DRAIN_STEPS = 400

#: phase tiling tolerance: transitions share one clock read, so the sum
#: mismatch is float rounding, not scheduling jitter
TILE_TOL_S = 2e-3


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ds.init_inference(model, params=params, dtype="fp32")
    srv = ServingEngine(eng, ServingConfig(
        max_batch_size=2, block_size=8, num_blocks=32, max_model_len=64,
        step_watchdog_s=0.4, trace_dir=trace_dir))
    assert srv.tracer.enabled and srv.flight is not None
    # warm the resident programs (first decode carries the XLA compile)
    rid = srv.submit([3, 5, 7], max_new_tokens=2)
    _drain(srv)
    assert srv.poll(rid).state == "finished"
    yield srv
    srv.flight.disarm()


@pytest.fixture()
def chaos(srv, monkeypatch):
    def arm(spec):
        monkeypatch.setenv(fault_injection.ENV_VAR, spec)
        fault_injection.reset()

    yield arm
    monkeypatch.delenv(fault_injection.ENV_VAR, raising=False)
    fault_injection.reset()
    _drain(srv)


def _drain(srv):
    steps = 0
    while srv.has_work():
        srv.step()
        steps += 1
        assert steps < MAX_DRAIN_STEPS, "engine wedged"


def _prompts(seed, n, lo=3, hi=9):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 256, int(rs.randint(lo, hi))) for _ in range(n)]


def _request_events(srv, rid):
    return [e for e in srv.tracer.events()
            if (e.get("args") or {}).get("rid") == rid]


def _newest_dump(srv, trigger):
    dumps = [p for p in srv.flight.dumps
             if os.path.basename(p).startswith(f"flight_{trigger}")]
    assert dumps, (trigger, srv.flight.dumps)
    return dumps[-1]


# ---------------------------------------------------------------------------
# 1. timeline completeness
# ---------------------------------------------------------------------------

def test_every_terminal_request_has_complete_timeline(srv):
    """Mixed traffic (more requests than slots, so queue waits are real):
    every terminal request's trace decomposes submit -> terminal into
    contiguous, non-overlapping phases that sum to wall time."""
    rids = [srv.submit(p, max_new_tokens=4) for p in _prompts(101, 6)]
    _drain(srv)
    for rid in rids:
        assert srv.poll(rid).state == "finished"
        evs = _request_events(srv, rid)
        names = [e["name"] for e in evs]
        assert "submit" in names, rid
        umbrellas = [e for e in evs if e["name"] == "request"]
        assert len(umbrellas) == 1, (rid, names)
        req = umbrellas[0]
        assert req["args"]["state"] == "finished"
        phases = sorted((e for e in evs
                         if e["name"].startswith("phase:")),
                        key=lambda e: e["ts"])
        assert phases, rid
        # the TTFT decomposition exists: a queue phase then a prefill
        # phase (decode present whenever >1 token was generated)
        kinds = [p["name"] for p in phases]
        assert kinds[0] == "phase:queue"
        assert "phase:prefill" in kinds
        # contiguous + non-overlapping: each phase starts where the
        # previous ended; first starts at the umbrella start, last ends
        # at its end; durations tile the request's wall time
        t = req["ts"]
        for p in phases:
            assert abs(p["ts"] - t) <= TILE_TOL_S * 1e6, (rid, kinds)
            t = p["ts"] + p["dur"]
        assert abs(t - (req["ts"] + req["dur"])) <= TILE_TOL_S * 1e6
        total_phase_s = sum(p["dur"] for p in phases) / 1e6
        assert abs(total_phase_s - req["dur"] / 1e6) <= TILE_TOL_S
        # TTFT = queue + prefill by construction (single-admission case)
        ttft = req["args"]["ttft_s"]
        if ttft is not None and req["args"]["preemptions"] == 0:
            qp = sum(p["dur"] for p in phases
                     if p["name"] in ("phase:queue", "phase:prefill")) / 1e6
            assert abs(qp - ttft) <= TILE_TOL_S


def test_trace_schema_valid_for_all_events(srv):
    evs = srv.tracer.events()
    assert evs
    for i, ev in enumerate(evs):
        assert validate_event(ev) is None, (i, ev)


# ---------------------------------------------------------------------------
# 2. flight-recorder chaos drills
# ---------------------------------------------------------------------------

def test_watchdog_trip_dumps_flight_record_naming_rid(srv, chaos):
    chaos("slow_step:seconds=1.2:fails=1")
    rids = [srv.submit(p, max_new_tokens=6) for p in _prompts(11, 2)]
    _drain(srv)
    failed = [r for r in rids
              if srv.poll(r).finish_reason == "step_watchdog"]
    assert failed
    header = json.loads(open(_newest_dump(srv, "watchdog_trip"))
                        .readline())
    assert header["trigger"] == "watchdog_trip"
    for r in failed:
        assert r in header["detail"]["rids"]
    # the dump carries the metrics snapshot at incident time
    assert header["metrics"]["watchdog_trips"] >= 1.0


def test_logit_quarantine_dumps_flight_record_naming_rid(srv, chaos):
    chaos("corrupt_logits:fails=1:slot=0")
    r0 = srv.submit(_prompts(17, 1)[0], max_new_tokens=6)
    r1 = srv.submit(_prompts(19, 1)[0], max_new_tokens=6)
    _drain(srv)
    bad = [r for r in (r0, r1)
           if srv.poll(r).finish_reason == "corrupt_logits"]
    assert len(bad) == 1
    header = json.loads(open(_newest_dump(srv, "logit_quarantine"))
                        .readline())
    assert header["detail"]["rid"] == bad[0]
    assert header["metrics"]["logit_quarantines"] >= 1.0
    # the quarantine also landed in the trace ring as an instant
    assert any(e["name"] == "quarantine" for e in
               _request_events(srv, bad[0]))


def test_ds_fault_firing_itself_dumps(srv, chaos):
    """arm_faults(): the DS_FAULT firing leaves its own post-mortem in
    addition to whatever the engine-level trigger dumps."""
    chaos("slow_step:seconds=0.05:fails=1")
    rid = srv.submit(_prompts(23, 1)[0], max_new_tokens=3)
    _drain(srv)
    assert srv.poll(rid).state == "finished"  # within watchdog budget
    header = json.loads(open(_newest_dump(srv, "fault_slow_step"))
                        .readline())
    assert header["trigger"] == "fault_slow_step"


# ---------------------------------------------------------------------------
# 3. disabled tracing = zero work on the hot path
# ---------------------------------------------------------------------------

def test_disabled_tracer_emits_nothing(srv):
    tracer = srv.tracer
    enabled_before = tracer.enabled
    count_before = tracer._count
    try:
        tracer.enabled = False
        rid = srv.submit(_prompts(31, 1)[0], max_new_tokens=4)
        _drain(srv)
        assert srv.poll(rid).state == "finished"
        assert tracer._count == count_before  # not one event appended
    finally:
        tracer.enabled = enabled_before


# ---------------------------------------------------------------------------
# 3b. the spans of one step nest and carry one step number
# ---------------------------------------------------------------------------

#: the host phases of a unified step, in the order step() opens them
STEP_PHASES = ("expire", "admit", "plan", "pack", "mixed_step", "fetch",
               "harvest", "bookkeeping")


def test_spans_of_one_step_nest_and_share_its_number(srv):
    srv.tracer.clear()
    rid = srv.submit(_prompts(41, 1)[0], max_new_tokens=3)
    _drain(srv)
    assert srv.poll(rid).state == "finished"
    spans = [e for e in srv.tracer.events() if e["ph"] == "X"
             and e["cat"] in ("engine", "host")]
    steps = [e for e in spans if e["name"] == "step"]
    assert steps and len({e["args"]["step"] for e in steps}) == len(steps)
    for step in steps:
        n = step["args"]["step"]
        inside = [e for e in spans if e is not step
                  and e["args"]["step"] == n]
        # the ring appends a span when it CLOSES: children first, in the
        # order they ran, the enclosing step last
        assert tuple(e["name"] for e in inside) == STEP_PHASES
        lo, hi = step["ts"], step["ts"] + step["dur"]
        ends = [lo]
        for e in inside:
            assert ends[-1] <= e["ts"] and e["ts"] + e["dur"] <= hi
            ends.append(e["ts"] + e["dur"])
    dispatch = next(e for e in spans if e["name"] == "mixed_step")
    assert {"decode_tokens", "verify_tokens", "prefill_tokens", "width",
            "rows", "context_tokens"} <= set(dispatch["args"])
    late = next(e for e in spans if e["name"] == "harvest")
    assert late["args"]["committed"] >= 1     # set before the span closed


# ---------------------------------------------------------------------------
# 4. export: Perfetto-loadable, trace_view-parsable
# ---------------------------------------------------------------------------

def test_dump_trace_perfetto_loadable_and_viewable(srv):
    path = srv.dump_trace()
    assert path.startswith(srv.config.trace_dir)
    # default filenames carry the process-global dump sequence: a second
    # dump in the same second must not overwrite the first
    path2 = srv.dump_trace()
    assert path2 != path and os.path.exists(path) and os.path.exists(path2)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs and all(validate_event(e) is None for e in evs)
    assert {"mixed_step", "request", "submit"} <= {e["name"] for e in evs}
    # tools/trace_view.py accepts it and reconstructs request timelines
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_view
        assert trace_view.validate(evs) is None
        reqs = trace_view.request_breakdown(evs)
    finally:
        sys.path.pop(0)
    done = [r for r in reqs.values() if r["complete"]]
    assert done
    for r in done:
        if r["ttft_s"] is not None and not r["preemptions"]:
            assert abs(r["queue_s"] + r["prefill_s"] - r["ttft_s"]) \
                <= TILE_TOL_S
    # and it validates flight-recorder JSONL dumps too
    if srv.flight.dumps:
        evs2, header = trace_view.load_events(srv.flight.dumps[-1])
        assert header is not None and trace_view.validate(evs2) is None
