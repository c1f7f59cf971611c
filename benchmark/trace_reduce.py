"""From the profiler's trace to device busy time, idle share, exposed
collective time, the operations that took most time and the longest idle
gaps named by what the host was doing.

``load`` turns an ``.xplane.pb`` into plain events; ``reduce`` works on those
events alone, so the tests feed it a small recorded trace kept as JSON.
An event is ``[name, start_ns, duration_ns]``; a trace is
``{"devices": {plane: [event]}, "host": [event]}`` where ``host`` holds the
benchmark's own ``bench.*`` annotations. One of them, ``bench.traced_window``,
spans everything the runner did under the profiler: it IS the window, so
the device's idle time before its first and after its last operation counts.
"""

import contextlib
import glob
import math
import os
import re
import shutil

#: device-plane lines that hold leaf operations; "XLA Modules" and "Steps"
#: span whole programs and would hide every gap inside them
OP_LINES = ("XLA Ops",)
#: operations that only wrap others: their interval covers their children's
#: gaps, so they are not work
CONTAINERS = re.compile(r"^(while|conditional|call)([.\d]*)$")
COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.traced_window"


@contextlib.contextmanager
def traced(trace_dir):
    """The profiler on for the body, under one ``bench.traced_window``
    annotation. Starting and stopping it stall the host for seconds, so a
    runner does this after the part of its window that it clocks, and
    parses the result (``reduce_dir``) after that."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # only our annotations on the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with annotate(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def annotate(name):
    """A host span on the profiler's clock (cheap no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def xplane_path(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return paths[0] if paths else None


def reduce_dir(trace_dir):
    path = xplane_path(trace_dir)
    return reduce(load(path)) if path else None


def short(name):
    """The trace names a device operation by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); keep the instruction's name."""
    return name.split(" = ")[0].lstrip("%")


def load(xplane_path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    trace = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            events = [[short(e.name), int(e.start_ns), int(e.duration_ns)]
                      for line in plane.lines if line.name in OP_LINES
                      for e in line.events]
            if events:
                trace["devices"][plane.name] = events
        elif plane.name.startswith("/host:"):
            trace["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                              for line in plane.lines for e in line.events
                              if e.name.startswith(ANNOTATION_PREFIX)]
    return trace


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(merged, holes):
    """The part of ``merged`` no interval of ``holes`` covers (both merged)."""
    out, k = [], 0
    for s, e in merged:
        while k < len(holes) and holes[k][1] <= s:
            k += 1
        j, cur = k, s
        while j < len(holes) and holes[j][0] < e:
            if holes[j][0] > cur:
                out.append([cur, holes[j][0]])
            cur = max(cur, holes[j][1])
            j += 1
        if cur < e:
            out.append([cur, e])
    return out


def host_activity(host, s, e):
    """The annotation that covers most of [s, e); ``(none)`` if none does.
    Of nested annotations the innermost (shortest) wins a tie."""
    best, best_cover = "(none)", 0
    for name, hs, hd in sorted(host, key=lambda ev: -ev[2]):
        if name == WINDOW:
            continue
        cover = min(e, hs + hd) - max(s, hs)
        if cover > 0 and cover >= best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace, top=10):
    """The window is the ``bench.traced_window`` annotation, and device
    operations are clipped to it (a trace without one: from the first
    operation's start to the last one's end). Busy and exposed times are
    averaged over the devices that ran anything."""
    span = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    lo, hi = span[0] if span else (-math.inf, math.inf)
    per_dev = []
    op_time = {}
    for events in trace["devices"].values():
        work = [(n, max(s, lo), min(s + d, hi)) for n, s, d in events
                if not CONTAINERS.match(n)]
        work = [(n, s, e) for n, s, e in work if e > s]
        if not work:
            continue
        for n, s, e in work:
            op_time[n] = op_time.get(n, 0) + (e - s)
        busy = union([s, e] for _, s, e in work)
        coll = union([s, e] for n, s, e in work if COLLECTIVE.search(n))
        other = union([s, e] for n, s, e in work if not COLLECTIVE.search(n))
        per_dev.append({"busy": busy, "exposed": subtract(coll, other)})
    if not per_dev:
        return None
    t0, t1 = span[0] if span else (
        min(d["busy"][0][0] for d in per_dev),
        max(d["busy"][-1][1] for d in per_dev))
    n = len(per_dev)
    gaps = {}
    for s, e in subtract([[t0, t1]], per_dev[0]["busy"]):
        name = host_activity(trace["host"], s, e)
        gaps[name] = gaps.get(name, 0) + (e - s)
    ranked = lambda d: [[k, v / 1e9] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    busy_s = sum(length(d["busy"]) for d in per_dev) / n / 1e9
    window_s = (t1 - t0) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "collective_exposed_s":
            sum(length(d["exposed"]) for d in per_dev) / n / 1e9,
        "devices": n,
        "device_ops": ranked({k: v / n for k, v in op_time.items()}),
        "idle_gaps": ranked(gaps),
    }
