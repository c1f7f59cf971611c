"""Device time of a traced run by the compiler's own instruction names, for
the operations that carry no scope path and no ``ds_*`` kernel name
(XLA:TPU's ``ragged-dot-*`` grouped-matmul kernels): what
``scope_reduce.reduce`` files under ``(unscoped)``. The window and the
exclusive time are ``scope_reduce``'s own."""

import math

from benchmark import scope_reduce, trace_reduce


def by_instruction(run, prefix):
    """{instruction name: {"s", "calls"}} of the device operations named
    ``prefix...`` inside the traced window, averaged over the devices that
    ran anything (time exclusive, as ``scope_reduce.reduce`` gives it), or
    None where there is no trace."""
    trace = run.get("scope_trace") if "scope_trace" in run \
        else scope_reduce.load_run()
    if trace is None:
        return None
    span = [(s, s + d) for n, s, d, *_ in trace["host"]
            if n == trace_reduce.WINDOW]
    lo, hi = span[0] if span else (-math.inf, math.inf)
    out, devices = {}, 0
    for events in trace["devices"].values():
        work = [(n, max(s, lo), min(s + d, hi)) for n, s, d, *_ in events
                if not trace_reduce.CONTAINERS.match(n)]
        work = [w for w in work if w[2] > w[1]]
        devices += bool(work)
        for k, ns in scope_reduce.exclusive(work):
            if work[k][0].startswith(prefix):
                row = out.setdefault(work[k][0], {"s": 0.0, "calls": 0})
                row["s"] += ns / 1e9
        for name, _, _ in work:
            if name in out:
                out[name]["calls"] += 1
    return {k: {"s": v["s"] / devices, "calls": v["calls"] // devices}
            for k, v in out.items()}
