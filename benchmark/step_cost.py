"""The train step's own count of its matrix work as a traced run carries it:
the program publishes it (``runtime/engine.py _publish_setup``, from
``monitor/perf.py StepCost``) as one ``ds.step_cost`` host event on the
profiler's clock, on the first ``train_batch`` of the traced window, with one
stat per number -- ``matmul_flops_<scope>`` (a step's matrix operations under
the ``ds.<scope>`` the device's time is filed under, forward, backward and
replayed together, over all devices), ``replayed_flops_<scope>``, the totals
``matmul_flops`` / ``replayed_flops``, ``cond_spread_flops``,
``uncounted_kernel_calls`` (and ``uncounted_<kernel>``), ``walk_s``.
``scope_reduce.load`` keeps every ``ds.*`` host event with its stats, so a
reader asks for a number by the name the program gave it. A program that
publishes none (the parent of the PR that added the span) reads None."""

import json

from benchmark import common, scope_reduce, setup_record

SPAN = "ds.step_cost"
_READ = {}


def record(run):
    """{name: number} of the trace's last ``ds.step_cost`` event, or None:
    another kind of run, no trace, a program without the span. The first
    call on a trace prints the observation line."""
    if run["observed"]["kind"] != "train":
        return None
    trace = run.get("scope_trace") if "scope_trace" in run \
        else scope_reduce.load_run()
    if trace is None:
        return None
    if id(trace) not in _READ:
        found = sorted((s, d, stats) for n, s, d, stats, *_ in trace["host"]
                       if n == SPAN)
        rec = setup_record.numbers(found[-1][2]) if found else None
        _READ[id(trace)] = (trace, rec)      # the trace kept: ids stay apart
        if rec:
            observe(run, rec, len(found), found[-1][1] / 1e3)
    return _READ[id(trace)][1]


def observe(run, rec, events, span_us):
    """The observation line: the whole record, how many events the trace
    holds and the last one's microseconds, the operations the model asks
    for a token, and -- on a chip -- every counted scope's share of the
    MXU's peak over the traced window, listed by a metric or not."""
    o = run["observed"]
    model = rec.get("matmul_flops", 0.0) - rec.get("replayed_flops", 0.0)
    tokens = o["tokens_per_s"] * o["window_s"] / o["steps"] \
        if o.get("steps") else None      # a step's, by the clocked window
    scopes = sorted(k[len("matmul_flops_"):] for k in rec
                    if k.startswith("matmul_flops_"))
    print(json.dumps({
        "observation": "step_cost", "events": events, "span_us": span_us,
        "model_flops_per_token": model / tokens if tokens else None,
        "mxu_share_pct": {s: mxu_share(run, f"ds.{s}") for s in scopes
                          if s != "unscoped"},
        "record": rec}), flush=True)


def mxu_share(run, scope):
    """100 x the matrix operations the MODEL asks for under ``scope`` a step
    (``matmul_flops_<scope> - replayed_flops_<scope>``: forward and
    backward) x the steps of the traced window, over the device seconds
    filed under that scope there LESS its replays' (``scope_reduce``'s
    ``by_scope`` less ``scope_phase``'s ``recompute``, a device's) x the
    chip's bf16 peak x the devices. The replays stay out on both sides: the
    compiler merges some with the forward pass (kimi 8k's unrolled dense
    layer, most of phi4 8k's), so the count would hold operations no device
    ran -- a share of the peak has to stand under 100 whatever XLA does.
    None without the event or the scope, for a serve run, and off a TPU (no
    peak to be a share of)."""
    rec = record(run)
    name = scope[len("ds."):]
    if not rec or f"matmul_flops_{name}" not in rec \
            or run["device"]["platform"] != "tpu":
        return None
    r = scope_reduce.reduced(run)
    seconds = r and r["by_scope"].get(scope, 0.0) \
        - r["scope_phase"].get(scope, {}).get("recompute", 0.0)
    if not seconds or not r["steps"]:
        return None
    flops = rec[f"matmul_flops_{name}"] - rec.get(f"replayed_flops_{name}", 0)
    peak = common.peak_flops(run["device"]["kind"]) * r["devices"]
    return 100.0 * flops * r["steps"] / (seconds * peak)
