"""Structured tracing + flight recorder (the observability spine).

A :class:`Tracer` is a low-overhead, thread-safe span/event recorder over a
**bounded ring buffer**: unbounded traffic costs O(capacity) memory, the
newest events win, and every timestamp comes from the monotonic
``time.perf_counter`` clock (the same clock the serving scheduler stamps
``submit_time``/``deadline`` with, so spans and deadlines line up exactly).
Export is Chrome-trace JSON — load a dump straight into Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``, or inspect it with
``tools/trace_view.py`` (schema validation + per-request phase breakdown).

Two sinks, one call: ``span()`` is THE way to open a span. It always enters
a ``jax.profiler.TraceAnnotation("ds.<name>")`` — a TraceMe, one flag check
while no profiler records — so a ``/profilez`` capture or a benchmark's
traced run shows the program's own spans on the profiler's clock beside the
device operations; with the tracer enabled it also records the ring event.
This module is the only place that constructs a ``TraceAnnotation``.

Cost discipline: a disabled tracer never touches the ring — ``instant`` and
``complete`` return first, and a span then costs one small object and the
TraceMe's flag check (PERF.md has the measured nanoseconds).

The :class:`FlightRecorder` is the post-mortem half: incident triggers
(watchdog trips, logit quarantines, ``DS_FAULT`` firings, checkpoint-verify
failures) dump the last N trace events plus a full metrics snapshot to a
timestamped JSONL file under a configurable directory — the answer to
"what was the engine doing in the 2s before the watchdog fired?". Dumps
never raise: a failing post-mortem must not take down the engine it is
documenting.

Process-global default: setting ``DS_TRACE_DIR`` arms a process-wide
tracer + flight recorder (see :func:`get_tracer` / :func:`flight_dump`) so
subsystems without their own tracer handle — the checkpoint manifest
verifier, ``fault_injection`` — can still leave evidence. Engines own
their OWN tracer instances (per-engine rings; tests stay isolated).
"""

import itertools
import json
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..utils.logging import logger

#: env var that arms the process-global tracer + flight recorder
ENV_TRACE_DIR = "DS_TRACE_DIR"

#: Chrome-trace phases this tracer emits: complete spans and instants
EVENT_PHASES = ("X", "i")


def now_s() -> float:
    """The tracer clock: monotonic seconds (``time.perf_counter``)."""
    return time.perf_counter()


def validate_event(ev: Any) -> Optional[str]:
    """One event against the trace schema; returns a problem description
    (None = valid). THE schema definition — ``tools/trace_view.py`` and the
    tests both call this, so the contract cannot fork."""
    if not isinstance(ev, dict):
        return f"event is {type(ev).__name__}, expected object"
    name = ev.get("name")
    if not isinstance(name, str) or not name:
        return "missing/empty 'name' (must be a non-empty string)"
    ph = ev.get("ph")
    if ph not in EVENT_PHASES:
        return f"'ph' is {ph!r}, expected one of {list(EVENT_PHASES)}"
    ts = ev.get("ts")
    if not isinstance(ts, (int, float)) or ts < 0:
        return f"'ts' is {ts!r}, expected a non-negative number (us)"
    if ph == "X":
        dur = ev.get("dur")
        if not isinstance(dur, (int, float)) or dur < 0:
            return f"'dur' is {dur!r}, required >= 0 for a complete span"
    if not isinstance(ev.get("tid", 0), int):
        return f"'tid' is {ev.get('tid')!r}, expected an int"
    if not isinstance(ev.get("pid", 0), int):
        return f"'pid' is {ev.get('pid')!r}, expected an int"
    cat = ev.get("cat", "")
    if not isinstance(cat, str):
        return f"'cat' is {cat!r}, expected a string"
    args = ev.get("args", {})
    if not isinstance(args, dict):
        return f"'args' is {type(args).__name__}, expected an object"
    return None


#: every profiler-side span name starts with this (the ring keeps the bare
#: names, so old dumps and tools/trace_view.py parse unchanged)
PROFILER_PREFIX = "ds."

#: version of the names a trace shows (``ds.*`` scopes and spans). Scopes are
#: metadata and the persistent compile cache's key strips metadata: an
#: executable cached before a name existed would be read back without it.
#: ``versioned`` puts this number into each jitted step's module name, which
#: the key does hold. Raise it with every added or renamed name
#: (tests/unit/test_trace_names.py pins it beside a digest of the names).
NAMES_VERSION = 3


def versioned(step):
    """``step`` named ``<name>_n<NAMES_VERSION>``: ``jax.jit`` calls the XLA
    module after the function it is given."""
    step.__name__ = f"{step.__name__}_n{NAMES_VERSION}"
    return step


def profiler_recording() -> bool:
    """Whether a profiler session would record a span opened now."""
    return TraceAnnotation.is_enabled()


class _Span:
    """One open span: a profiler annotation ``ds.<name>`` and, with the
    tracer enabled, a ring event ``ring or name`` (``ring`` keeps a span's
    older ring spelling where the profiler name is new). ``set()`` adds
    arguments known only late (a token count, a verdict) before it
    closes."""

    __slots__ = ("_tracer", "_name", "_ring", "_cat", "_args", "_step",
                 "_t0", "_t1", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], ring: Optional[str],
                 step: Optional[int]):
        self._tracer = tracer
        self._name = name
        self._ring = ring or name
        self._cat = cat
        # a step span carries its number like every span inside it
        self._args = args if step is None else {**(args or {}), "step": step}
        self._step = step

    def set(self, **args) -> "_Span":
        self._annotation.set_metadata(**args)
        if self._tracer.enabled:
            self._args = {**(self._args or {}), **args}
        return self

    def __enter__(self):
        args = self._args or {}
        if self._step is None:
            self._annotation = TraceAnnotation(
                PROFILER_PREFIX + self._name, **args)
        else:
            # a step span: the profiler groups device work under it
            self._annotation = StepTraceAnnotation(
                PROFILER_PREFIX + self._name, step_num=self._step, **args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = self._t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._tracer.complete(self._ring, self._t0, t1, cat=self._cat,
                              args=self._args)
        return False

    @property
    def name(self) -> str:
        return self._name

    @property
    def seconds(self) -> float:
        """What the closed span took, from the two clock readings it makes
        anyway — there whether or not the ring is enabled (the engine's
        set-up record, ``monitor/perf.py SetupRecord``)."""
        return self._t1 - self._t0


class Tracer:
    """Thread-safe span/event recorder over a bounded ring buffer.

    - ``instant(name)`` — point event;
    - ``span(name)`` — context manager: a profiler annotation always, the
      ring event when enabled; spans nest by containment on their thread;
    - ``complete(name, start_s, end_s)`` — ring-only span with explicit
      monotonic endpoints, for spans that do not nest on one thread (the
      per-request ``phase:*`` and ``request`` spans);
    - ``events()`` / ``to_chrome()`` / ``dump(path)`` — ring snapshot and
      Chrome-trace/Perfetto JSON export.

    Timestamps are ``perf_counter`` microseconds; append order is the ring
    order (the lock covers both the ring write and, for instants, the
    timestamp capture, so ``events()`` is monotone in append time).
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self._lock = threading.Lock()
        self._ring: List[Optional[Dict[str, Any]]] = [None] * capacity  # dslint: guarded-by=_lock
        #: monotone: total events ever appended
        self._count = 0  # dslint: guarded-by=_lock

    # -- emission ------------------------------------------------------

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._ring[self._count % self.capacity] = ev
            self._count += 1

    def instant(self, name: str, cat: str = "",
                args: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "ts": 0.0, "tid": threading.get_ident()
              & 0x7FFFFFFF, "cat": cat, "s": "t"}
        if args:
            ev["args"] = args
        with self._lock:
            # ts captured under the lock so ring order == time order
            ev["ts"] = time.perf_counter() * 1e6
            self._ring[self._count % self.capacity] = ev
            self._count += 1

    def complete(self, name: str, start_s: float, end_s: float,
                 cat: str = "", args: Optional[Dict[str, Any]] = None) -> None:
        """Record a complete span from two ``perf_counter()`` readings."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "X", "ts": start_s * 1e6,
              "dur": max(0.0, (end_s - start_s) * 1e6),
              "tid": threading.get_ident() & 0x7FFFFFFF, "cat": cat}
        if args:
            ev["args"] = args
        self._append(ev)

    def span(self, name: str, cat: str = "",
             args: Optional[Dict[str, Any]] = None,
             ring: Optional[str] = None, step: Optional[int] = None):
        """Open a span: ``with tracer.span("plan", cat="host") as sp: ...;
        sp.set(grants=3)``. ``step`` makes it a profiler step span."""
        return _Span(self, name, cat, args, ring, step)

    # -- inspection / export -------------------------------------------

    @property
    def dropped(self) -> int:
        """Events overwritten by ring wrap-around (bounded-memory proof)."""
        with self._lock:
            return max(0, self._count - self.capacity)

    def __len__(self) -> int:
        with self._lock:
            return min(self._count, self.capacity)

    def events(self) -> List[Dict[str, Any]]:
        """Ring snapshot, oldest kept event first."""
        with self._lock:
            n = self._count
            if n <= self.capacity:
                return [e for e in self._ring[:n]]
            start = n % self.capacity
            return self._ring[start:] + self._ring[:start]

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._count = 0

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome-trace JSON object (Perfetto-loadable)."""
        pid = os.getpid()
        events = []
        for ev in self.events():
            ev = dict(ev)
            ev["pid"] = pid
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"producer": "deepspeed_tpu.monitor.tracing",
                              "dropped_events": self.dropped}}

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path`` (dirs created)."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


#: shared disabled tracer — the default wiring target when tracing is off,
#: so call sites never need a None check
NULL_TRACER = Tracer(capacity=1, enabled=False)


#: dump sequence shared by ALL recorder instances in the process: two
#: recorders pointed at the same dir (training + serving engines in one
#: process) dumping the same trigger within the same second must never
#: collide on a filename — os.replace would silently discard the first
#: post-mortem
_dump_seq = itertools.count(1)


def dump_seq() -> int:
    """Next value of the process-global dump sequence — any filename
    that embeds a second-resolution timestamp must also embed this, or
    two dumps in the same second silently overwrite each other."""
    return next(_dump_seq)

#: fault-arming is EXCLUSIVE per output directory: a DS_FAULT firing is a
#: process-global event, so two recorders sharing one dir (an env-armed
#: global recorder next to an engine's own) must produce ONE post-mortem
#: per firing, not one per recorder. Weak refs: holding an armed-dir slot
#: never keeps a dropped engine alive.
_arm_lock = threading.Lock()
_fault_armed_dirs: Dict[str, "weakref.ref[FlightRecorder]"] = {}  # dslint: guarded-by=_arm_lock


class FlightRecorder:
    """Post-mortem capture: on an incident trigger, dump the last N trace
    events plus a full metrics snapshot to a timestamped JSONL file.

    File format (one incident per file, ``flight_<trigger>_<stamp>.jsonl``):
    line 1 is the header record (``kind=flight_recorder``, trigger, detail,
    wall time, metrics snapshot, dropped-event count); every following line
    is one trace event (schema of :func:`validate_event`).

    ``record()`` NEVER raises — a failing dump logs and returns None.
    ``arm_faults()`` subscribes to ``fault_injection`` so every DS_FAULT
    firing (including ``maybe_crash``, notified before ``os._exit``) leaves
    a dump; ``disarm()`` unsubscribes.
    """

    def __init__(self, out_dir: str, tracer: Tracer,
                 metrics_fn: Optional[Callable[[], Dict[str, Any]]] = None,
                 last_n: int = 512):
        self.out_dir = out_dir
        self.tracer = tracer
        self.metrics_fn = metrics_fn
        self.last_n = last_n
        self.dumps: List[str] = []  # paths written (newest last)
        self._fault_cb: Optional[Callable[[str, Dict[str, Any]], None]] = None

    def record(self, trigger: str, detail: Optional[Dict[str, Any]] = None
               ) -> Optional[str]:
        """Dump one incident; returns the path (None on I/O failure —
        never raises: the post-mortem must not kill the patient)."""
        try:
            trigger_slug = "".join(c if c.isalnum() or c in "-_" else "_"
                                   for c in trigger) or "incident"
            metrics: Dict[str, Any] = {}
            if self.metrics_fn is not None:
                try:
                    metrics = dict(self.metrics_fn())
                except Exception as e:  # metrics must not block the dump
                    metrics = {"_metrics_error": repr(e)}
            events = self.tracer.events()[-self.last_n:]
            seq = dump_seq()  # process-global: filenames never collide
            stamp = time.strftime("%Y%m%d-%H%M%S")
            path = os.path.join(
                self.out_dir, f"flight_{trigger_slug}_{stamp}_{seq:04d}"
                              f"_{os.getpid()}.jsonl")
            os.makedirs(self.out_dir, exist_ok=True)
            header = {"kind": "flight_recorder", "trigger": trigger,
                      "detail": dict(detail or {}),
                      "wall_time": time.time(),  # dslint: ignore[determinism] post-mortem header wants the wall clock of record; spans stay on perf_counter
                      "monotonic_us": time.perf_counter() * 1e6,
                      "pid": os.getpid(), "events": len(events),
                      "events_dropped": self.tracer.dropped,
                      "metrics": metrics}
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                f.write(json.dumps(header) + "\n")
                for ev in events:
                    f.write(json.dumps(ev) + "\n")
            os.replace(tmp, path)  # a dump is whole or absent, never torn
            self.dumps.append(path)
            logger.error(f"flight recorder: {trigger} -> {path} "
                         f"({len(events)} events)")
            return path
        except Exception as e:
            logger.error(f"flight recorder: dump for {trigger!r} failed: "
                         f"{type(e).__name__}: {e}")
            return None

    # -- DS_FAULT integration ------------------------------------------

    def arm_faults(self) -> None:
        """Dump on every DS_FAULT firing (crash dumps land BEFORE the
        injected ``os._exit`` — the classic post-mortem).

        Arming is exclusive per output directory: when another live
        recorder already covers ``out_dir`` this call is a no-op, so one
        firing produces ONE dump per directory, not one per recorder.
        The registered listener holds only a weak reference — an armed
        recorder (and the engine behind its ``metrics_fn``) stays
        garbage-collectable, and a dead recorder's listener removes
        itself on the next firing."""
        from ..utils import fault_injection

        key = os.path.abspath(self.out_dir)
        with _arm_lock:
            cur = _fault_armed_dirs.get(key)
            holder = cur() if cur is not None else None
            if holder is not None and holder is not self:
                return  # another live recorder already covers this dir
            _fault_armed_dirs[key] = weakref.ref(self)
        if self._fault_cb is None:
            ref = weakref.ref(self)

            def cb(name: str, ctx: Dict[str, Any]) -> None:
                fr = ref()
                if fr is None:  # recorder died: self-remove, free the slot
                    fault_injection.remove_listener(cb)
                    with _arm_lock:
                        slot = _fault_armed_dirs.get(key)
                        if slot is not None and slot() is None:
                            del _fault_armed_dirs[key]
                    return
                fr.record(f"fault_{name}", ctx)

            self._fault_cb = cb
        fault_injection.add_listener(self._fault_cb)

    def disarm(self) -> None:
        from ..utils import fault_injection

        if self._fault_cb is not None:
            fault_injection.remove_listener(self._fault_cb)
        with _arm_lock:
            key = os.path.abspath(self.out_dir)
            slot = _fault_armed_dirs.get(key)
            if slot is not None and slot() in (None, self):
                del _fault_armed_dirs[key]


# ---------------------------------------------------------------------------
# Process-global default (env-armed): subsystems without an engine handle
# ---------------------------------------------------------------------------

_default_tracer: Optional[Tracer] = None
_default_flight: Optional[FlightRecorder] = None
_default_lock = threading.Lock()


def configure(trace_dir: Optional[str] = None, capacity: int = 8192,
              flight_events: int = 512, enabled: bool = True) -> Tracer:
    """Install the process-global tracer (+ flight recorder when
    ``trace_dir`` is given). Idempotent per call; tests use
    :func:`reset_default` for isolation."""
    global _default_tracer, _default_flight
    with _default_lock:
        if _default_flight is not None:
            _default_flight.disarm()
        _default_tracer = Tracer(capacity=capacity, enabled=enabled)
        _default_flight = None
        if trace_dir:
            _default_flight = FlightRecorder(trace_dir, _default_tracer,
                                             last_n=flight_events)
            _default_flight.arm_faults()
        return _default_tracer


def get_tracer() -> Tracer:
    """The process-global tracer; on first use, arms itself from
    ``DS_TRACE_DIR`` (tracing + flight recorder) or stays disabled."""
    global _default_tracer
    if _default_tracer is None:
        d = os.environ.get(ENV_TRACE_DIR)
        if d:
            configure(trace_dir=d)
        else:
            with _default_lock:
                if _default_tracer is None:
                    _default_tracer = Tracer(capacity=1, enabled=False)
    return _default_tracer


def default_flight_recorder() -> Optional[FlightRecorder]:
    get_tracer()  # ensure env arming ran
    return _default_flight


def flight_dump(trigger: str, detail: Optional[Dict[str, Any]] = None
                ) -> Optional[str]:
    """Dump through the process-global flight recorder (no-op unless
    ``DS_TRACE_DIR``/:func:`configure` armed one). Used by subsystems that
    have no engine handle — e.g. the checkpoint manifest verifier."""
    fr = default_flight_recorder()
    if fr is None:
        return None
    return fr.record(trigger, detail)


def reset_default() -> None:
    """Drop the process-global tracer/recorder (test isolation; the next
    :func:`get_tracer` re-reads ``DS_TRACE_DIR``)."""
    global _default_tracer, _default_flight
    with _default_lock:
        if _default_flight is not None:
            _default_flight.disarm()
        _default_tracer = None
        _default_flight = None
