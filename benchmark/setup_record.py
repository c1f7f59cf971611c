"""The engine's set-up record as a traced run carries it: the program
publishes it (``runtime/engine.py _publish_setup``) as one ``ds.setup`` host
event on the profiler's clock, on the first ``train_batch`` of the traced
window, with one stat per number — the seconds of the package's imports, from them to
the constructor (``pre_init_s``), of the constructor (``init_s``) and its four
children, of the first step and its parts, what ``jax.monitoring`` summed of traces, lowerings, backend compiles
and cache reads inside the program's set-up spans and ``outside_*`` them, and
``steps_before``. ``scope_reduce.load`` keeps every ``ds.*`` host event with
its stats, so a reader asks for a number by the name the program gave it. A
program that publishes none (the parent of the PR that added the span) reads
None."""

import json

from benchmark import scope_reduce

SPAN = "ds.setup"
_READ = {}


def record(run):
    """{name: number} of the trace's last ``ds.setup`` event, or None:
    another kind of run, no trace, a program without the span. The first
    call on a trace prints the observation line."""
    if run["observed"]["kind"] != "train":
        return None
    trace = run.get("scope_trace") if "scope_trace" in run \
        else scope_reduce.load_run()
    if trace is None:
        return None
    if id(trace) not in _READ:
        found = sorted((s, stats) for n, s, _, stats, *_ in trace["host"]
                       if n == SPAN)
        rec = numbers(found[-1][1]) if found else None
        _READ[id(trace)] = (trace, rec)      # the trace kept: ids stay apart
        if rec:
            observe(run, rec)
    return _READ[id(trace)][1]


def numbers(stats):
    out = {}
    for name, value in stats.items():
        try:
            out[name] = float(value)
        except (TypeError, ValueError):
            pass
    return out


def observe(run, rec):
    """The observation line: the whole record, and the run's ``setup_s``
    split into the program's parts (imports, constructor, first step, cost
    capture), the time between the package's import and the constructor
    (``pre_init_s``: here backend start and this harness's own work before
    the engine), what compiled ``outside`` the program's spans (the check's
    reference and ``model_logits``: trace, lowering, backend) and the
    remainder — the check's execution and the warm-up steps."""
    setup_s = run.get("end_to_end", {}).get("setup_s")
    program_s = sum(rec.get(k, 0.0) for k in (
        "import_s", "init_s", "first_step_s", "cost_capture_s"))
    outside_s = sum(rec.get(f"outside_{k}", 0.0) for k in (
        "trace_s", "lower_s", "backend_s"))
    pre_init_s = rec.get("pre_init_s", 0.0)
    print(json.dumps({
        "observation": "setup", "setup_s": setup_s, "program_s": program_s,
        "pre_init_s": pre_init_s, "outside_compile_s": outside_s,
        "remainder_s": None if setup_s is None
        else setup_s - program_s - pre_init_s - outside_s,
        "record": rec}), flush=True)


def value(run, *names):
    """The sum of the record's ``names``; None without a record or where
    it lacks one of them."""
    rec = record(run)
    if not rec or any(n not in rec for n in names):
        return None
    return sum(rec[n] for n in names)
