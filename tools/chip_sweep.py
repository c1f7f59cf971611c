"""One-command on-chip evidence sweep, resumable across short chip windows.

The round-2/3 failure mode was a TPU backend that stayed unreachable for an
entire round. Round 4 revealed the second failure mode: the backend answers
for a few MINUTES, then drops — the first r4 window was spent on a single
hung all-kernels job while the headline bench never ran, and after the drop
every remaining step still burned its full subprocess cap against a dead
backend. This version is built for short windows:

  1. steps run money-first: bench (headline TFLOPs) before everything else;
  2. a 60 s re-probe runs BEFORE every step — the moment the backend stops
     answering the sweep exits (rc 2) instead of burning caps;
  3. the kernels step runs per-kernel (one capped subprocess per entry in
     KERNEL_NAMES, merged into one KERNELS_<tag>.json) so one hung Mosaic
     compile can't eat a window;
  4. state persists in CHIP_SWEEP_STATE_<tag>.json: on the next window,
     --resume skips every step already captured ok.

tools/chip_watch.py loops probe → sweep --resume → probe, so multiple short
windows accumulate the full artifact set.

Usage: python tools/chip_sweep.py [--tag r04] [--resume] [--skip bench,...]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KERNEL_NAMES = ["flash_fwd", "flash_bwd_dq", "block_sparse_fwd",
                "decode_attention", "decode_attention_int8", "int8_matmul",
                "fused_adam", "fused_lamb"]

PROBE = ("import json, time\nt0=time.time()\nimport jax\n"
         "d=jax.devices()\nprint(json.dumps({'n': len(d), "
         "'kind': str(d[0]), 'init_s': round(time.time()-t0,1)}))\n")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def probe(py, deadline):
    try:
        r = subprocess.run([py, "-c", PROBE], capture_output=True, text=True,
                           timeout=deadline)
        if r.returncode == 0 and "{" in r.stdout:
            return json.loads(r.stdout.strip().splitlines()[-1])
    except (subprocess.TimeoutExpired, ValueError):
        pass
    return None


def _tee_log(log_name, cmd, stdout, stderr):
    """Keep full per-step diagnostics (the r4 window lost the per-candidate
    bench stderr; the winner's "why" was unrecoverable)."""
    if not log_name:
        return
    os.makedirs(os.path.join(REPO, "chip_logs"), exist_ok=True)
    with open(os.path.join(REPO, "chip_logs", log_name + ".log"), "w") as f:
        f.write(f"# cmd: {cmd}\n# stdout:\n{stdout or ''}\n"
                f"# stderr:\n{stderr or ''}\n")


def _text(b):
    return b.decode(errors="replace") if isinstance(b, bytes) else (b or "")


def run_capped(cmd, cap_s, out_path=None, log_name=None):
    t0 = time.time()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=cap_s,
                           cwd=REPO)
    except subprocess.TimeoutExpired as e:
        # the dominant failure mode IS the timeout — keep its partial output
        _tee_log(log_name, cmd, _text(e.stdout), _text(e.stderr))
        return {"ok": False, "error": f"timeout after {cap_s:.0f}s",
                "elapsed_s": round(time.time() - t0, 1)}
    _tee_log(log_name, cmd, r.stdout, r.stderr)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip().startswith("{")]
    # a tool that could not measure still prints a JSON line with an
    # "error" field — that line must never clobber a good artifact
    # captured in an earlier window
    failed_record = False
    if lines:
        try:
            last = json.loads(lines[-1])
            failed_record = bool(last.get("error")) or last.get("value", 0) is None
        except ValueError:
            failed_record = True
    rec = {"ok": r.returncode == 0 and bool(lines) and not failed_record,
           "elapsed_s": round(time.time() - t0, 1)}
    if not rec["ok"]:
        rec["error"] = (r.stderr.strip().splitlines() or ["no output"])[-1][:300]
    if lines and out_path and (rec["ok"]
                               or not os.path.exists(os.path.join(REPO, out_path))):
        with open(os.path.join(REPO, out_path), "w") as f:
            f.write("\n".join(lines) + "\n")
        rec["artifact"] = out_path
    return rec


# bench_decode's non-tiny sweep: (1,128), (8,512), (32,1024), (64,2048)
DECODE_POINTS = 4


def _merge_decode_lines(stdout, merged, rec):
    """Fold bench_decode stdout into the per-window point store.

    Understands both the streamed per-point lines ({"point": {...}}) and the
    final summary ({"points": [...], "error"/"point_errors": ...}); tolerant
    of truncation (an outer kill mid-line)."""
    for ln in (stdout or "").splitlines():
        if not ln.strip().startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        pts = [obj["point"]] if "point" in obj else obj.get("points", [])
        for pt in pts:
            merged[f"b{pt['batch']},p{pt['prompt']}"] = pt
        for k in ("error", "point_errors"):
            if obj.get(k):
                rec[k] = str(obj[k])[:300]


def run_decode_merged(py, tag, state, impl, cap=1800, model="llama"):
    """Run bench_decode and merge its points into per-window state, so a
    window that captures 1 of 4 points still counts, never clobbers a
    fuller artifact, and the missing points retry next window.

    cap covers bench_decode's own worst case (60s probe + 4 x 420s point
    caps); the merge path reads streamed per-point lines out of a timed-out
    process's partial stdout, so even the outer kill keeps finished points."""
    key = f"decode_points_{impl}" if model == "llama" \
        else f"decode_points_{model}_{impl}"
    merged = state.setdefault(key, {})
    cmd = [py, "tools/bench_decode.py"]
    if impl != "xla":
        cmd += ["--impl", impl]
    if model != "llama":
        cmd += ["--model", model]
    t0 = time.time()
    rec = {"elapsed_s": None}
    log_name = f"decode_{impl}" if model == "llama" \
        else f"decode_{model}_{impl}"
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=cap,
                           cwd=REPO)
        _merge_decode_lines(r.stdout, merged, rec)
        _tee_log(log_name, cmd, r.stdout, r.stderr)
        if r.returncode != 0 and "error" not in rec:
            rec["error"] = "rc={}: {}".format(
                r.returncode,
                (r.stderr.strip().splitlines() or ["?"])[-1][:250])
    except subprocess.TimeoutExpired as e:
        rec["error"] = f"timeout after {cap}s"
        _merge_decode_lines(_text(e.stdout), merged, rec)
        _tee_log(log_name, cmd, _text(e.stdout), _text(e.stderr))
    rec["elapsed_s"] = round(time.time() - t0, 1)
    if merged:
        stem = f"DECODE_{tag}" if model == "llama" else f"DECODE_{tag}_{model}"
        out = f"{stem}.json" if impl == "xla" else f"{stem}_{impl}.json"
        metric = ("llama400m_decode" if model == "llama"
                  else f"{model}_small_decode")
        with open(os.path.join(REPO, out), "w") as f:
            f.write(json.dumps({"metric": metric, "impl": impl,
                                "model": model,
                                "points": list(merged.values())}) + "\n")
        rec["artifact"] = out
    rec["ok"] = len(merged) >= DECODE_POINTS
    rec["points_captured"] = len(merged)
    return rec


def run_kernels_split(py, tag, state, per_kernel_cap=420):
    """Each kernel in its own capped subprocess; merge into one artifact.

    Returns the merged step record. Individual kernel results (or their
    timeout/error records) accumulate in ``state['kernel_results']``.
    """
    results = state.setdefault("kernel_results", {})
    meta = None
    for name in KERNEL_NAMES:
        if results.get(name, {}).get("allclose"):
            continue  # captured in an earlier window
        log(f"chip_sweep: kernels:{name} (cap {per_kernel_cap}s)")
        t0 = time.time()
        try:
            r = subprocess.run(
                [py, "tools/bench_kernels.py", "--only", name],
                capture_output=True, text=True, timeout=per_kernel_cap,
                cwd=REPO)
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.strip().startswith("{")]
            if lines:
                rec = json.loads(lines[-1])
                meta = {k: rec[k] for k in ("backend", "mode", "shapes")}
                for kr in rec.get("kernels", []):
                    results[kr["kernel"]] = kr
            else:
                results[name] = {
                    "kernel": name, "allclose": False,
                    "error": (r.stderr.strip().splitlines() or ["?"])[-1][:300]}
        except subprocess.TimeoutExpired:
            results[name] = {"kernel": name, "allclose": False,
                             "error": f"timeout after {per_kernel_cap}s"}
        log(f"chip_sweep: kernels:{name}: "
            f"{results.get(name)} ({time.time() - t0:.0f}s)")
        # a hung kernel usually means the backend dropped — check cheaply
        if "timeout" in str(results.get(name, {}).get("error", "")):
            if probe(py, 60) is None:
                log("chip_sweep: backend gone mid-kernels")
                break
    if meta is None:  # nothing captured this window — keep any existing artifact
        return {"ok": False, "error": "no kernel captured",
                "per_kernel": {n: bool(results.get(n, {}).get("allclose"))
                               for n in KERNEL_NAMES}}
    merged = dict(meta)
    merged["metric"] = "pallas_kernels"
    merged["kernels"] = [results[n] for n in KERNEL_NAMES if n in results]
    merged["all_allclose"] = bool(merged["kernels"]) and all(
        r.get("allclose") for r in merged["kernels"])
    out = f"KERNELS_{tag}.json"
    with open(os.path.join(REPO, out), "w") as f:
        f.write(json.dumps(merged) + "\n")
    done = all(results.get(n, {}).get("allclose") is not None
               and "timeout" not in str(results.get(n, {}).get("error", ""))
               for n in KERNEL_NAMES)
    return {"ok": done and merged["all_allclose"], "artifact": out,
            "per_kernel": {n: bool(results.get(n, {}).get("allclose"))
                           for n in KERNEL_NAMES}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="r04")
    ap.add_argument("--skip", default="",
                    help="comma list: bench,decode,kernels,profile,"
                         "overlap,zero1,infinity,longctx")
    ap.add_argument("--resume", action="store_true",
                    help="skip steps already captured ok (state file)")
    ap.add_argument("--probe_s", type=float, default=60.0)
    ap.add_argument("--dry-run", action="store_true",
                    help="print the step plan (names, caps, artifacts) "
                         "as JSON and exit without probing the backend")
    args = ap.parse_args()
    skip = set(filter(None, args.skip.split(",")))
    py = sys.executable
    t = args.tag
    state_path = os.path.join(REPO, f"CHIP_SWEEP_STATE_{t}.json")
    state = {}
    if args.resume and os.path.exists(state_path):
        with open(state_path) as f:
            state = json.load(f)
    steps = state.setdefault("steps", {})

    def save_state():
        with open(state_path, "w") as f:
            json.dump(state, f, indent=1)

    # money-first order; caps sized so the headline survives a short window
    plan = [
        ("bench", [py, "bench.py"], 1800, f"BENCH_{t}_local.json"),
        # diag separates device capability from per-dispatch cost — it
        # explains whatever number bench just produced
        ("diag", [py, "tools/diag_chip.py"], 420, f"DIAG_{t}.json"),
        # 1800s covers bench_decode's own worst case (probe + 4x420s); the
        # streamed per-point merge keeps finished points on an outer kill
        ("decode", None, 1800, f"DECODE_{t}.json"),          # merge-aware
        ("decode_pallas", None, 1800, f"DECODE_{t}_pallas.json"),
        ("decode_pallas_int8", None, 1800, f"DECODE_{t}_pallas_int8.json"),
        ("decode_mixtral", None, 1800, f"DECODE_{t}_mixtral.json"),
        # MoE decode-MLP isolation: XLA-fusion-vs-kernel evidence for the
        # reference's moe_res_matmul / einsum_sec_sm_ecm counterparts
        ("moe_decode", [py, "tools/bench_moe_decode.py"], 600,
         f"MOE_DECODE_{t}.json"),
        ("kernels", None, None, f"KERNELS_{t}.json"),  # per-kernel splitter
        ("profile", [py, "tools/profile_train.py", "--quick"], 1200,
         f"PROFILE_{t}.json"),
        # explicit-lane evidence (PR 19): bucketed per-layer reduce-scatter
        # overlap vs kill-switch vs fused, and the ZeRO-1 data-axis sharded
        # optimizer update — each one artifact gateable by perfdiff
        ("overlap_grad_sync",
         [py, "tools/profile_train.py", "--lane", "overlap_grad_sync"],
         900, f"OVERLAP_{t}.json"),
        ("zero1_sharded_update",
         [py, "tools/profile_train.py", "--lane", "zero1_sharded_update"],
         900, f"ZERO1_{t}.json"),
        ("infinity", [py, "tools/bench_infinity.py"], 900,
         f"INFINITY_{t}_chip.json"),
        ("longctx", [py, "tools/bench_longctx.py"], 1200, f"LONGCTX_{t}.json"),
        # the reference's OTHER kernel headline: BERT-Large layer TFLOPs
        # (64 TFLOPS seq128 / 53 seq512 on V100) vs our ops.transformer layer
        ("bert_layer", [py, "tools/bench_bert_layer.py"], 900,
         f"BERT_{t}.json"),
    ]
    if steps.get("bench", {}).get("ok"):
        # the captured bench predates THIS sweep process (resume from an
        # earlier window): re-run the ladder FIRST — the headline is the
        # verdict's #1 item and window 1's 27.14 winner predates the
        # per-step-fence fix and the gas-scan candidates (whose gas-vs-plain
        # ratio doubles as the dispatch-cost diagnosis if the window dies
        # before diag). Budget 900s (not the full 1500s default) so a
        # ~12-min window still reaches the next steps. On a fresh sweep the
        # first bench step already runs the current ladder. Named bench_v2
        # so `--skip bench` (prefix match) covers it.
        plan.insert(1, ("bench_v2",
                        ["env", "DS_BENCH_BUDGET_S=900", py, "bench.py"],
                        1100, f"BENCH_{t}_v2.json"))
    if args.dry_run:
        print(json.dumps({
            "metric": "chip_sweep_plan", "tag": t, "dry_run": True,
            "steps": [{"name": n, "cmd": c, "cap_s": cap, "artifact": a}
                      for n, c, cap, a in plan
                      if n.split("_")[0] not in skip]}, indent=1),
            flush=True)
        return 0

    log(f"chip_sweep: probing backend ({args.probe_s:.0f}s deadline)")
    info = probe(py, args.probe_s)
    if info is None:
        print(json.dumps({"metric": "chip_sweep", "tag": t,
                          "backend": "unavailable", "steps": steps}),
              flush=True)
        return 1
    log(f"chip_sweep: backend UP: {info}")

    backend_lost = False
    for name, cmd, cap, artifact in plan:
        if name.split("_")[0] in skip:
            continue
        if steps.get(name, {}).get("ok"):
            log(f"chip_sweep: {name}: already captured, skipping")
            continue
        if backend_lost:
            break
        # cheap liveness check before committing a long cap to this step
        if name != "bench" and probe(py, args.probe_s) is None:
            log(f"chip_sweep: backend lost before {name}; stopping")
            backend_lost = True
            break
        if name == "kernels":
            steps[name] = run_kernels_split(py, t, state)
        elif name.startswith("decode"):
            impl = {"decode": "xla", "decode_pallas": "pallas",
                    "decode_pallas_int8": "pallas_int8",
                    "decode_mixtral": "xla"}[name]
            model = "mixtral" if name == "decode_mixtral" else "llama"
            log(f"chip_sweep: {name} (cap {cap}s, merge-aware)")
            steps[name] = run_decode_merged(py, t, state, impl, cap,
                                            model=model)
        else:
            log(f"chip_sweep: {name} (cap {cap}s)")
            steps[name] = run_capped(cmd, cap, artifact, log_name=name)
        log(f"chip_sweep: {name}: {steps[name]}")
        save_state()
    save_state()
    all_done = all(steps.get(n, {}).get("ok") for n, *_ in plan
                   if n.split("_")[0] not in skip)
    print(json.dumps({"metric": "chip_sweep", "tag": t, "backend": "up",
                      "complete": all_done, "steps": steps}), flush=True)
    return 0 if all_done else 2


if __name__ == "__main__":
    sys.exit(main())
