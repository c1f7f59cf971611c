"""From the profiler's trace to device time by the program's own names:
``ds.*`` scopes, phases, ``ds_*`` kernels, and the host's ``ds.*`` spans.

The program names what runs (``jax.named_scope`` in the jitted steps and the
model code, ``name=`` on every Pallas call, ``Tracer.span`` on the host);
nothing here knows a compiler number (``fusion.N``): a reader asks for a
scope or a kernel by the string the program gave it.

On the v5e an ``XLA Ops`` event carries no scope: its name is the whole HLO
instruction and its stats are offsets and durations. The scope path is the
instruction's ``op_name`` metadata, which the profiler stores with each
module's ``Hlo Proto`` in the ``/host:metadata`` plane. ``load`` joins the two:
the ``XLA Modules`` line says which module ran when, the module's proto says
which ``op_name`` each instruction has. ``jax.profiler.ProfileData`` does not
expose a plane's event metadata, so that one part is read from the file's
protobuf wire format directly (field numbers of ``xplane.proto`` and
``hlo.proto``); the events come through ``ProfileData`` as in
``trace_reduce.load``.

``reduce`` works on plain events alone, so the tests feed it recorded JSON.
A device event is ``[name, start_ns, duration_ns, op_name]``, a host event
``[name, start_ns, duration_ns, {stat: value}, thread]``; a trace is
``{"devices": {plane: [event]}, "host": [event]}``. The window is
``bench.traced_window``, clipped exactly as ``trace_reduce.reduce`` clips.
"""

import bisect
import json
import math
import os
import re
import statistics

from benchmark import common, trace_reduce
from benchmark.trace_reduce import (COLLECTIVE, CONTAINERS, OP_LINES, WINDOW,
                                    length, subtract, union)

SCOPE = re.compile(r"ds\.[a-z_0-9]+")
KERNEL = re.compile(r"ds_[a-z_0-9]*[a-z]")
HOST_PREFIXES = ("ds.", "bench.")
STEP_SPANS = ("ds.train_batch", "ds.step")
UNSCOPED = "(unscoped)"
PHASES = ("forward", "backward", "recompute", "optimizer", "other")
MODULE_LINE = "XLA Modules"


# -- the file's wire format: only what ProfileData does not expose -----------

def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, wire, value


def _sub(buf, number):
    """The length-delimited fields ``number`` of a message."""
    return [v for f, w, v in _fields(buf) if f == number and w == 2]


def _op_names(hlo_proto):
    """{instruction name: op_name} over every computation of an HloProto
    (hlo_module = 1; computations = 3; instructions = 2; name = 1,
    metadata = 7; OpMetadata.op_name = 2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name, op_name = None, ""
                for f, w, v in _fields(instruction):
                    if f == 1 and w == 2:
                        name = v.decode()
                    elif f == 7 and w == 2:
                        op_name = "".join(x.decode() for x in _sub(v, 2))
                if name:
                    out[name] = op_name
    return out


def module_op_names(xplane_bytes):
    """{module name as the ``XLA Modules`` line spells it: {instruction:
    op_name}} from the ``/host:metadata`` plane (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, stats = 5; XStat.metadata_id = 1,
    bytes_value = 6)."""
    out = {}
    for plane in _sub(xplane_bytes, 1):
        if [v for v in _sub(plane, 2)][:1] != [b"/host:metadata"]:
            continue
        stat_names = {}
        for entry in _sub(plane, 5):
            for meta in _sub(entry, 2):
                sid = [v for f, w, v in _fields(meta) if f == 1 and w == 0]
                name = _sub(meta, 2)
                if sid and name:
                    stat_names[sid[0]] = name[0]
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                name = _sub(meta, 2)
                for stat in _sub(meta, 5):
                    sid = [v for f, w, v in _fields(stat)
                           if f == 1 and w == 0]
                    payload = _sub(stat, 6)
                    if name and payload and sid and \
                            stat_names.get(sid[0]) == b"Hlo Proto":
                        out[name[0].decode()] = _op_names(payload[0])
    return out


# -- loading -----------------------------------------------------------------

_LOADED = {}


def load(xplane_path):
    """The trace as plain events, device operations joined with their
    ``op_name``. One parse a process however many readers ask."""
    if xplane_path in _LOADED:
        return _LOADED[xplane_path]
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        names = module_op_names(f.read())
    data = ProfileData.from_file(xplane_path)
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                              e.name) for e in lines.get(MODULE_LINE, []))
            starts = [m[0] for m in modules]
            events = []
            for line_name in OP_LINES:
                for e in lines.get(line_name, []):
                    name, start = trace_reduce.short(e.name), int(e.start_ns)
                    k = bisect.bisect_right(starts, start) - 1
                    module = modules[k][2] if k >= 0 and \
                        start < modules[k][1] else None
                    op_name = names.get(module, {}).get(name, "")
                    events.append([name, start, int(e.duration_ns), op_name])
            if events:
                trace["devices"][plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        stats = {k: v for k, v in e.stats
                                 if isinstance(v, (int, float, str))
                                 and not k.startswith("_")}
                        trace["host"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns),
                             stats, line.name])
    _LOADED[xplane_path] = trace
    return trace


def load_run():
    """The trace the traced run of THIS process left, or None (no traced
    run, or a program that writes no trace at all)."""
    path = trace_reduce.xplane_path(os.path.join(common.ROOT, ".bench_trace"))
    return load(path) if path else None


# -- classification ----------------------------------------------------------

def scope_of(op_name):
    """The innermost ``ds.`` scope of an ``op_name`` path."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else UNSCOPED


def kernel_of(name, op_name):
    """The ``ds_*`` kernel an operation is, or None. The compiler names a
    Pallas custom call after its kernel (``ds_flash_fwd.15``); should it
    not, the path still ends ``.../ds_flash_fwd/pallas_call``."""
    m = KERNEL.match(name)
    if not m and op_name.endswith("/pallas_call"):
        m = KERNEL.fullmatch(op_name.split("/")[-2])
    return m.group(0) if m else None


REMAT_CLONE = re.compile(r"\.remat\d*(\.|$)")


def phase_of(op_name, name=""):
    """forward / backward / recompute / optimizer / other, from the marks
    the transformations leave: ``ds.optimizer`` in the path is the
    optimizer; inside ``ds.loss_and_grad``, recomputation is either what
    ``jax.checkpoint``'s backward replays (``rematted_computation`` in the
    path) or an operation the compiler cloned to save memory (it names the
    clone ``<instruction>.remat``, ``.remat2``, ... and leaves it the
    original's path); otherwise ``transpose(`` is the backward pass, and
    the rest the forward pass."""
    if "ds.optimizer" in op_name:
        return "optimizer"
    if "ds.loss_and_grad" not in op_name:
        return "other"
    if "rematted_computation" in op_name or REMAT_CLONE.search(name):
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


# -- reduction ---------------------------------------------------------------

def exclusive(work):
    """Each instant of the union of ``work`` given to ONE operation: the
    one that started last (operations on a line nest or follow each
    other). ``work`` rows are (name, start, end, ...); returns
    [(index into work, nanoseconds)]."""
    order = sorted(range(len(work)), key=lambda i: (work[i][1], -work[i][2]))
    out, stack, cursor = [], [], None

    def spend(until):
        nonlocal cursor
        while stack and cursor < until:
            top = stack[-1]
            end = min(work[top][2], until)
            if end > cursor:
                out.append((top, end - cursor))
                cursor = end
            if work[top][2] <= until:
                stack.pop()
            else:
                break

    for i in order:
        s = work[i][1]
        if cursor is not None:
            spend(s)
        cursor = s if cursor is None else max(cursor, s)
        stack.append(i)
    spend(math.inf)
    return out


def innermost_covering(spans, s, e):
    """Of ``spans`` ([name, start, end]) the shortest that covers most (over
    half) of [s, e), or None."""
    inner, inner_len = None, math.inf
    for name, hs, he in spans:
        cover = min(e, he) - max(s, hs)
        if 2 * cover > e - s and he - hs < inner_len:
            inner, inner_len = name, he - hs
    return inner


def most_covering(spans, s, e):
    """The span that covers the largest part of [s, e), or None."""
    best, best_cover = None, 0
    for name, hs, he in spans:
        cover = min(e, he) - max(s, hs)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def span_table(host, lo, hi):
    """Per span name: count, total and self seconds, and the samples of self
    time (ms) — self time is a span's duration less what the spans nested in
    it on its thread cover. Spans are clipped to [lo, hi)."""
    table = {}
    threads = {}
    for name, s, d, _, thread in host:
        if name == WINDOW:
            continue
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            threads.setdefault(thread, []).append((s, -e, name))
    for spans in threads.values():
        spans.sort()
        stack = []       # [name, start, end, children's covered ns]

        def close(until):
            while stack and stack[-1][2] <= until:
                name, s, e, covered = stack.pop()
                row = table.setdefault(name, {"count": 0, "total_s": 0.0,
                                              "self_ms": []})
                row["count"] += 1
                row["total_s"] += (e - s) / 1e9
                row["self_ms"].append((e - s - covered) / 1e6)
                if stack:
                    stack[-1][3] += e - s

        for s, neg_e, name in spans:
            close(s)
            stack.append([name, s, -neg_e, 0])
        close(math.inf)
    for row in table.values():
        row["self_s"] = sum(row["self_ms"]) / 1e3
        row["self_ms_p50"] = statistics.median(row["self_ms"])
    return table


def reduce(trace, top=12):
    """Device time by scope, phase and kernel; exposed collective time by
    the scope of the collective; steps; idle gaps by the program's spans;
    the span table. Times are seconds, averaged over the devices that ran
    anything, inside the ``bench.traced_window`` annotation."""
    span = [(s, s + d) for n, s, d, *_ in trace["host"] if n == WINDOW]
    lo, hi = span[0] if span else (-math.inf, math.inf)
    by_scope, by_phase, by_kernel, scope_phase, exposed = {}, {}, {}, {}, {}
    per_dev = []
    for events in trace["devices"].values():
        # (name, start, end, op_name) of the leaf operations in the window
        work = [(n, max(s, lo), min(s + d, hi), op) for n, s, d, op in events
                if not CONTAINERS.match(n)]
        work = [w for w in work if w[2] > w[1]]
        if not work:
            continue
        kernels = [kernel_of(n, op) for n, _, _, op in work]
        for k, ns in exclusive(work):
            name, _, _, op_name = work[k]
            scope, phase = scope_of(op_name), phase_of(op_name, name)
            by_scope[scope] = by_scope.get(scope, 0) + ns
            by_phase[phase] = by_phase.get(phase, 0) + ns
            row = scope_phase.setdefault(scope, {})
            row[phase] = row.get(phase, 0) + ns
            if kernels[k]:
                row = by_kernel.setdefault(kernels[k], {"ns": 0, "calls": 0})
                row["ns"] += ns
        for kernel in filter(None, kernels):
            by_kernel[kernel]["calls"] += 1
        compute = union([s, e] for n, s, e, _ in work
                        if not COLLECTIVE.search(n))
        for n, s, e, op_name in work:
            if COLLECTIVE.search(n):
                scope = scope_of(op_name)
                exposed[scope] = exposed.get(scope, 0) + length(
                    subtract([[s, e]], compute))
        per_dev.append(union([s, e] for _, s, e, _ in work))
    if not per_dev:
        return None
    t0, t1 = span[0] if span else (min(b[0][0] for b in per_dev),
                                   max(b[-1][1] for b in per_dev))
    n = len(per_dev)
    busy_s = sum(length(b) for b in per_dev) / n / 1e9
    host = [ev for ev in trace["host"] if ev[0] != WINDOW]
    spans = {p: [[ev[0], ev[1], ev[1] + ev[2]] for ev in host
                 if ev[0].startswith(p)] for p in HOST_PREFIXES}
    gaps = {}
    for s, e in subtract([[t0, t1]], per_dev[0]):
        # the program's innermost span over most of the gap; the
        # benchmark's own where the program was not running; else whatever
        # covers the largest part of it
        name = innermost_covering(spans["ds."], s, e) or \
            innermost_covering(spans["bench."], s, e) or \
            most_covering(spans["ds."] + spans["bench."], s, e) or "(none)"
        gaps[name] = gaps.get(name, 0) + (e - s)
    steps = sum(1 for ev in host if ev[0] in STEP_SPANS and t0 <= ev[1] < t1)
    dispatches = [ev[3] for ev in host
                  if ev[0] == "ds.dispatch" and t0 <= ev[1] < t1]
    sec = lambda d: {k: v / n / 1e9 for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])}
    table = span_table(host, t0, t1)
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_s,
        "idle_s": (t1 - t0) / 1e9 - busy_s,
        "devices": n,
        "steps": steps,
        "dispatch_args": dispatches,
        "by_scope": sec(by_scope),
        "by_phase": {p: by_phase.get(p, 0) / n / 1e9 for p in PHASES},
        "scope_phase": {k: sec(v) for k, v in scope_phase.items()},
        "by_kernel": {k: {"s": v["ns"] / n / 1e9, "calls": v["calls"] // n}
                      for k, v in by_kernel.items()},
        "exposed_by_scope": sec(exposed),
        "unscoped_share": by_scope.get(UNSCOPED, 0) / n / 1e9 / busy_s,
        # of the first device, as trace_reduce names its gaps
        "idle_gaps": {k: v / 1e9 for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:top]},
        "spans": {k: {"count": v["count"], "total_s": v["total_s"],
                      "self_s": v["self_s"],
                      "self_ms_p50": v["self_ms_p50"]}
                  for k, v in sorted(table.items())},
    }


# -- what the readers share --------------------------------------------------

_REDUCED = {}


def reduced(run):
    """``reduce`` of the traced run's trace (``run["scope_trace"]`` where a
    test hands one in), or None where there is none. The first call prints
    the observation line with the whole table; it is not the result line."""
    trace = run.get("scope_trace") if "scope_trace" in run else load_run()
    if trace is None:
        return None
    if id(trace) not in _REDUCED:
        out = reduce(trace)
        _REDUCED[id(trace)] = (trace, out)   # the trace kept: ids stay apart
        if out:
            observe(out)
    return _REDUCED[id(trace)][1]


def observe(r):
    pct = lambda d: {k: round(100 * v / r["busy_s"], 2) for k, v in d.items()}
    print(json.dumps({
        "observation": "scope_reduce",
        "window_s": r["window_s"], "busy_s": r["busy_s"],
        "steps": r["steps"], "devices": r["devices"],
        "scope_share_pct": pct(r["by_scope"]),
        "phase_share_pct": pct(r["by_phase"]),
        "scope_phase_pct": {k: pct(v) for k, v in r["scope_phase"].items()},
        "unscoped_share_pct": round(100 * r["unscoped_share"], 2),
        "kernel_ms_per_call": {k: round(1e3 * v["s"] / max(v["calls"], 1), 4)
                               for k, v in r["by_kernel"].items()},
        "kernel_calls": {k: v["calls"] for k, v in r["by_kernel"].items()},
        "exposed_collective_ms_by_scope": {
            k: round(1e3 * v, 3) for k, v in r["exposed_by_scope"].items()},
        "idle_ms_by_span": {k: round(1e3 * v, 3)
                            for k, v in r["idle_gaps"].items()},
        "span_self_ms_p50": {k: round(v["self_ms_p50"], 4)
                             for k, v in r["spans"].items()},
        "span_count": {k: v["count"] for k, v in r["spans"].items()},
    }), flush=True)


def share(run, kind, key, table="by_scope"):
    """100 x device time under ``key`` / busy time, for a run of ``kind``
    whose trace names it; None otherwise (another kind, no trace, a
    program without that scope)."""
    if run["observed"]["kind"] != kind:
        return None
    r = reduced(run)
    if not r or key not in r[table] or not r["busy_s"]:
        return None
    if set(r["by_scope"]) <= {UNSCOPED}:
        return None        # a program that names nothing
    return 100.0 * r[table][key] / r["busy_s"]


def span_self_ms_p50(run, kind, name):
    """Median self time (ms) of the host span ``name`` in the window."""
    if run["observed"]["kind"] != kind:
        return None
    r = reduced(run)
    if not r or name not in r["spans"]:
        return None
    return r["spans"][name]["self_ms_p50"]
