"""The train step's named scalars as a traced run carries them: the engine
publishes each step's (``runtime/engine.py _drain_counters``) as one
``ds.counters`` host event on the profiler's clock, with the step's number and
one stat per scalar (``moe_rows_max_over_mean``, ...), a fence after the step
ran. ``scope_reduce.load`` keeps every ``ds.*`` host event with its stats, so a
reader asks for a scalar by the name the program gave it. A program that
publishes none (the parent of the PR that added the span) reads None."""

import json
import math

from benchmark import scope_reduce, trace_reduce

SPAN = "ds.counters"
#: fewer events than this in the window are no mean
MIN_EVENTS = 4
_OBSERVED = {}


def events(run):
    """The stats of each ``ds.counters`` event that starts inside the
    traced window, in time order, or None where there is no trace. The
    first call on a trace prints the observation line."""
    trace = run.get("scope_trace") if "scope_trace" in run \
        else scope_reduce.load_run()
    if trace is None:
        return None
    span = [(s, s + d) for n, s, d, *_ in trace["host"]
            if n == trace_reduce.WINDOW]
    lo, hi = span[0] if span else (-math.inf, math.inf)
    found = sorted((s, d, stats) for n, s, d, stats, *_ in trace["host"]
                   if n == SPAN and lo <= s < hi)
    if id(trace) not in _OBSERVED:
        _OBSERVED[id(trace)] = trace      # kept: ids stay apart
        observe([stats for _, _, stats in found],
                sum(d for _, d, _ in found) / 1e6)
    return [stats for _, _, stats in found]


def numbers(found, name):
    out = []
    for stats in found:
        try:
            out.append(float(stats[name]))
        except (KeyError, TypeError, ValueError):
            pass
    return out


def observe(found, total_ms):
    """The observation line: how many events, which steps, and every
    scalar's mean, least and largest over the window."""
    names = sorted({k for stats in found for k in stats} - {"step"})
    steps = [int(x) for x in numbers(found, "step")]
    table = {}
    for name in names:
        v = numbers(found, name)
        if v:
            table[name] = {"n": len(v), "mean": sum(v) / len(v),
                           "min": min(v), "max": max(v)}
    print(json.dumps({
        "observation": "counters", "events": len(found),
        "steps": [min(steps), max(steps)] if steps else None,
        "distinct_steps": len(set(steps)), "span_total_ms": total_ms,
        "scalars": table}), flush=True)


def mean(run, name, scale=1.0):
    """``scale`` x the mean of the scalar ``name`` over the window's
    ``ds.counters`` events; None for another kind of run, without a trace,
    or below ``MIN_EVENTS`` events that carry it."""
    if run["observed"]["kind"] != "train":
        return None
    found = events(run)
    values = numbers(found or [], name)
    if len(values) < MIN_EVENTS:
        return None
    return scale * sum(values) / len(values)
