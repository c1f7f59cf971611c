#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or per-layer
metric is a file found by its name in BENCHMARK.json:
``workloads/<cell>.json``, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``kinds/<kind>.py``, ``layer_metrics/<metric>.py``. Earlier lines of the
output are observations; the LAST line is the one result object. Without a
TPU, or with fewer chips than the cell asks, the exit code is 2 and no result
is printed.

Builder's switches (the driver passes none of them): ``--rehearse-cpu`` runs
the cell at its tiny size on virtual CPU devices, prints no result and never
exits 0; ``--check-seeds N`` runs only the correctness check on N seeds;
``--control NAME`` makes that check run a deliberately wrong computation.
"""

import argparse
import gc
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402


def emit(obj):
    print(json.dumps(obj), flush=True)


def applies(metric, cell):
    return cell in metric.get("workloads", [cell])


def free_device_memory():
    import jax

    from deepspeed_tpu.parallel import topology

    topology.set_mesh(None, None)
    gc.collect()
    jax.clear_caches()
    gc.collect()


def context(bench, cell_name, seed, seconds=None, trace=0, tiny=False):
    """Everything a kind's runner needs, from the files the cell names.
    ``tiny`` lays the files' own tiny sizes over them (CPU rehearsals and
    the tests); it changes no file."""
    from benchmark.traffic import generator

    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    workload = common.load_json("workloads", f"{cell['name']}.json")
    config = common.load_json("configs", f"{cell['config']}.json")
    mix = generator.load_mix(cell["traffic"])
    if tiny:
        workload = {**workload, **workload.get("tiny", {})}
        mix = {**mix, **workload.get("tiny_mix", {})}
    seconds = bench["run_seconds"] if seconds is None else seconds
    return {
        "cell": cell, "workload": workload, "config": config, "mix": mix,
        "sizes": common.sizes_of(config, workload["depth"], tiny),
        "seed": seed, "seconds": seconds, "trace": bool(trace),
        "trace_seconds": min(seconds / 2, workload.get("trace_seconds", 5)),
        "trace_dir": os.path.join(common.ROOT, ".bench_trace"),
        "t_start": T_START, "emit": emit, "control": None,
        "check_only": False,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--check-seeds", type=int, default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args()
    tiny = args.rehearse_cpu

    bench = common.load_benchmark()
    ctx = context(bench, args.workload, args.seed, args.seconds, args.trace,
                  tiny)
    cell, workload = ctx["cell"], ctx["workload"]
    kind = common.load_file_module("kinds", workload["kind"])
    if args.control and args.control not in kind.CONTROLS:
        ap.error(f"--control: one of {kind.CONTROLS}")

    from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,
                                                force_cpu_devices)
    if tiny:
        force_cpu_devices(cell["chips"])
    import jax

    device = common.device_info()
    if not tiny and (device["platform"] != "tpu"
                     or device["count"] < cell["chips"]):
        print(f"benchmark: {cell['name']} needs {cell['chips']} TPU chip(s), "
              f"jax found {device}", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ctx.update(control=args.control, check_only=bool(args.check_seeds))
    seconds = ctx["seconds"]
    emit({"phase": "start", "cell": cell["name"], **device,
          "jax": jax.__version__, "compile_cache": cache_dir,
          "seed": args.seed, "seconds": seconds, "rehearsal": tiny})

    if args.check_seeds:
        ok = True
        for seed in range(args.seed, args.seed + args.check_seeds):
            out = kind.run({**ctx, "seed": seed})
            emit({"check_seed": seed, "control": args.control,
                  "correct": out["correct"], **out["stats"]})
            ok = ok and out["correct"]
            del out
            free_device_memory()
        return 0 if ok and not tiny else 1

    run = kind.run(ctx)
    run["device"] = device
    if args.trace:
        if run["trace"] is None and not tiny:
            print("benchmark: the traced window held no device operation",
                  file=sys.stderr)
            return 3
        names = [m["name"] for m in bench["per_layer"]
                 if applies(m, cell["name"])]
        values = {n: common.load_file_module("layer_metrics", n).read(run)
                  for n in names}
    else:
        values = {m["name"]: run["end_to_end"].get(m["name"])
                  for m in bench["end_to_end"] if applies(m, cell["name"])}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {n: {"value": v, "unit": units[n]}
               for n, v in values.items() if v is not None}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": {**device,
                         "memory_peak_bytes": common.memory_peak_bytes()}}
    if args.trace and run["trace"]:
        t = run["trace"]
        result["device"].update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    if run.get("compared"):
        # what `correct` compared, each beside its limit: the result's last
        # key and the last lines on standard error, which the driver keeps
        # of a run that is not correct
        result["compared"] = run["compared"]
        for name, c in run["compared"].items():
            print(f"benchmark: compared {name} {c['value']!r} "
                  f"limit {c['limit']!r}", file=sys.stderr)
    if tiny:
        emit({"rehearsal": "passed" if run["correct"] else "failed",
              "would_print": result})
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
