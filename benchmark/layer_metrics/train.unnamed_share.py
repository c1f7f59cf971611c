"""Share of device busy time that no name of the program reaches: operations
whose innermost scope is the bare ``ds.loss_and_grad`` plus those with no
``ds.`` scope at all, less XLA:TPU's ``ragged-dot*`` kernels (they carry no
scope path but ``moe.grouped_matmul_share`` names them). The observation line
splits it: bare, copies the compiler made (no ``op_name``, instruction
``copy*``), other operations without an ``op_name``, and paths outside the
step's scopes."""

import json
import math

from benchmark import scope_reduce, trace_reduce

NAMED_ELSEWHERE = "ragged-dot"
BARE = "ds.loss_and_grad"


def part_of(name, op_name):
    """Which part of the unnamed time an operation is, or None (named)."""
    scope = scope_reduce.scope_of(op_name)
    if scope == BARE:
        return "bare"
    if scope != scope_reduce.UNSCOPED or name.startswith(NAMED_ELSEWHERE):
        return None
    if op_name:
        return "other_paths"
    return "compiler_copies" if name.startswith("copy") else "no_op_name"


def parts(trace):
    """{part: seconds}, exclusive time inside the traced window averaged over
    the devices that ran anything, as ``scope_reduce.reduce`` counts."""
    span = [(s, s + d) for n, s, d, *_ in trace["host"]
            if n == trace_reduce.WINDOW]
    lo, hi = span[0] if span else (-math.inf, math.inf)
    out, devices = {}, 0
    for events in trace["devices"].values():
        work = [(n, max(s, lo), min(s + d, hi), op) for n, s, d, op in events
                if not trace_reduce.CONTAINERS.match(n)]
        work = [w for w in work if w[2] > w[1]]
        devices += bool(work)
        for k, ns in scope_reduce.exclusive(work):
            part = part_of(work[k][0], work[k][3])
            if part:
                out[part] = out.get(part, 0.0) + ns / 1e9
    return {k: v / devices for k, v in out.items()} if devices else {}


def read(run):
    if run["observed"]["kind"] != "train":
        return None
    r = scope_reduce.reduced(run)
    if not r or not r["busy_s"] or set(r["by_scope"]) <= {scope_reduce.UNSCOPED}:
        return None
    trace = run.get("scope_trace") if "scope_trace" in run \
        else scope_reduce.load_run()
    split = {k: 100.0 * v / r["busy_s"] for k, v in parts(trace).items()}
    print(json.dumps({"observation": "train.unnamed_share",
                      "share_pct": {k: round(v, 3)
                                    for k, v in sorted(split.items())}}),
          flush=True)
    return sum(split.values())
