"""Benchmark: Llama decoder training throughput on the real TPU chip.

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline"}.
Headline comparison: achieved model TFLOPs/chip on a causal-LM train step vs
the reference's headline "ZeRO-3 >157 TFLOPs/GPU" (A100) number
(reference docs/_posts/2022-07-26-deepspeed-azure.md:37).

Every step that can hang — backend init, compile, run — happens in a
*subprocess* with a wall-clock deadline enforced by the parent, which never
touches jax itself (a chip belongs to one process at a time):

  1. a <=60s device probe runs before any candidate,
  2. each candidate runs in its own subprocess under a per-candidate cap
     (the persistent compile cache is shared, so repeat candidates start
     fast),
  3. the parent ALWAYS prints a JSON line: a measurement when one exists,
     otherwise {"value": null, "error": ...} — and then exits non-zero: a
     run that measured nothing has failed, and no earlier run's number
     rides on it.

Candidates are tried best-first (dots-remat saves matmul outputs — ~no
recompute FLOPs — and bigger batches fill the MXU; full remat is the safe
fallback). Diagnostics go to stderr; stdout carries only the final JSON line.
"""

import json
import os
import subprocess
import sys
import time

BASELINE_TFLOPS = 157.0  # reference ZeRO-3 headline (A100)
SEQ = 1024
METRIC = "llama400m_train_tflops_per_chip"


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def stray_bench_processes():
    """PIDs (with cmdlines) of OTHER live bench.py processes on this box.

    The PR 8 de-flake post-mortem: a test's timeout killed a bench.py
    parent but its candidate grandchild survived as a ~400s 100%-CPU
    stray that silently poisoned every later timing run on this 1-core
    machine. Numbers taken next to such a stray are not noisy — they are
    wrong — so the pre-flight ABORTS with the named PID instead of
    measuring. Own process and direct ancestors are excluded (pytest
    drives bench.py as a child; the chain above us is not contention)."""
    me = os.getpid()
    ancestors = set()
    pid = me
    while pid > 1:
        ancestors.add(pid)
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().split(")")[-1].split()[1])  # ppid
        except (OSError, ValueError, IndexError):
            break
    out = []
    try:
        entries = os.listdir("/proc")
    except OSError:
        return out  # no procfs (not linux): the guard degrades to off
    for entry in entries:
        if not entry.isdigit() or int(entry) in ancestors:
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                argv = [a for a in
                        f.read().decode("utf-8", "replace").split("\0") if a]
        except OSError:
            continue  # raced a process exit
        if not argv:
            continue
        # only processes EXECUTING bench.py count: argv0 is bench.py
        # itself, or a python interpreter whose script arg is bench.py.
        # An editor or pager with bench.py on its command line ('vim
        # bench.py') is idle, not contention
        exe = os.path.basename(argv[0])
        running_it = exe == "bench.py" or (
            exe.startswith("python")
            and any(os.path.basename(a) == "bench.py" for a in argv[1:3]))
        if running_it:
            out.append((int(entry), " ".join(argv)))
    return out


def model_flops_per_step(n_params: int, batch: int, seq: int, n_layer: int,
                         hidden: int) -> float:
    """fwd+bwd FLOPs: 6*N*tokens + attention 12*L*B*T^2*H (PaLM appendix B)."""
    tokens = batch * seq
    return 6.0 * n_params * tokens + 12.0 * n_layer * batch * seq * seq * hidden


def run_candidate(spec, steps=8, warmup=2):
    """Runs IN the child process; returns the result record dict.

    ``spec`` keys (all but ``tag``/``policy``/``batch`` optional):
      tag, policy (remat policy name), batch,
      fq/fk   — flash attention block_q/block_k tile sizes,
      padam   — route the optimizer update through the Pallas fused-Adam
                kernel instead of optax/XLA.
    The round-3 verdict flagged that the candidate ladder only swept
    remat × batch while the actual perf levers (flash tiles, Pallas Adam,
    host-offload residuals) were never candidates; this widens the ladder.
    """
    import numpy as np
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.parallel import topology

    tag = spec["tag"]
    remat_policy = spec["policy"]
    batch = int(spec["batch"])
    steps = int(spec.get("steps", steps))
    warmup = int(spec.get("warmup", warmup))
    gas = int(spec.get("gas", 1))  # micro-steps per compiled call: the GAS
    # scan amortizes any fixed cost per dispatched train_batch call
    fq = int(spec.get("fq", 512))
    fk = int(spec.get("fk", 512))
    padam = bool(spec.get("padam", False))
    attn = spec.get("attn", "flash")
    lchunk = int(spec.get("lchunk", 0))  # chunked xent: no [B,T,V] logits
    global_bs = batch * gas

    topology.set_mesh(None, None)
    if os.environ.get("DS_BENCH_TINY"):  # harness smoke test (CPU)
        cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=4, max_position_embeddings=SEQ,
                          remat=True, remat_policy=remat_policy,
                          attention_impl=attn,
                          flash_block_q=fq, flash_block_k=fk,
                          loss_chunk=lchunk)
    else:
        cfg = LlamaConfig.llama_400m(max_position_embeddings=SEQ, remat=True,
                                     remat_policy=remat_policy,
                                     attention_impl=attn,
                                     flash_block_q=fq, flash_block_k=fk,
                                     loss_chunk=lchunk)
    model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (global_bs, SEQ)).astype(np.int32)

    opt_params = {"lr": 1e-4, "weight_decay": 0.1}
    if padam:
        opt_params["pallas"] = True
    config = {
        # GLOBAL batch semantics: on the one-chip bench dp=1 so micro=batch;
        # the CI smoke runs under an 8-device CPU mesh where the config
        # derives micro = batch/dp (per-gpu micro semantics would silently
        # 8x the batch there)
        "train_batch_size": global_bs,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": opt_params},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
    }
    engine, *_ = ds.initialize(model=model, config=config,
                               example_batch={"input_ids": ids[:2], "labels": ids[:2]})
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(
        engine.state.params))

    b = {"input_ids": ids, "labels": ids}
    for _ in range(warmup):  # compile
        loss = engine.train_batch(batch=b)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=b)
    loss_val = float(loss)  # forces the whole donated-state chain
    dt = (time.perf_counter() - t0) / steps

    flops = gas * model_flops_per_step(n_params, batch, SEQ,
                                       cfg.num_hidden_layers, cfg.hidden_size)
    return {
        "tag": tag, "tflops": flops / dt / 1e12, "dt": dt, "loss": loss_val,
        "n_params": n_params, "batch": global_bs,
        "tokens_per_sec": global_bs * SEQ / dt,
    }


def _probe_src():
    return (
        "import json, time\n"
        "t0 = time.time()\n"
        "import jax\n"
        "d = jax.devices()\n"
        "print(json.dumps({'n': len(d), 'kind': str(d[0]),"
        " 'init_s': round(time.time() - t0, 1)}))\n"
    )


def _run_sub(argv_or_src, timeout_s, is_src=False):
    """Run a python subprocess; return (ok, parsed_json_or_None, why)."""
    cmd = [sys.executable] + (["-c", argv_or_src] if is_src else argv_or_src)
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr or b""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        for line in stderr.splitlines()[-20:]:
            log(f"  | {line}")
        return False, None, f"timeout after {timeout_s:.0f}s"
    for line in r.stderr.splitlines():
        log(f"  | {line}")
    if r.returncode != 0:
        tail = (r.stderr.strip().splitlines() or ["?"])[-1]
        return False, None, f"rc={r.returncode}: {tail[:300]}"
    out = [ln for ln in r.stdout.splitlines() if ln.strip().startswith("{")]
    if not out:
        return False, None, "no JSON on stdout"
    try:
        return True, json.loads(out[-1]), ""
    except ValueError as e:
        return False, None, f"bad JSON: {e}"


def emit(value, vs_baseline, detail=None, error=None):
    rec = {"metric": METRIC, "value": value, "unit": "TFLOPs/chip",
           "vs_baseline": vs_baseline}
    if detail is not None:
        rec["detail"] = detail
    if error is not None:
        rec["error"] = error
    print(json.dumps(rec), flush=True)


def main():
    tiny = bool(os.environ.get("DS_BENCH_TINY"))
    budget = float(os.environ.get("DS_BENCH_BUDGET_S",
                                  "360" if tiny else "1500"))
    probe_deadline = float(os.environ.get("DS_BENCH_PROBE_S", "60"))
    # tiny cap carries headroom over the ~95s quiet-machine candidate time:
    # a loaded CI host (the slow tier runs benches alongside) doubled it
    # past the old 120s cap and produced value=null flakes
    cand_cap = float(os.environ.get("DS_BENCH_CANDIDATE_S",
                                    "170" if tiny else "420"))
    t_start = time.time()

    # 0) stray-process pre-flight: refuse to time anything while another
    # bench.py (or a leaked candidate child of one) is alive — on this
    # box that stray owns the core and every number would be quietly
    # contended. DS_BENCH_IGNORE_STRAYS=1 overrides for deliberate
    # side-by-side runs.
    if not os.environ.get("DS_BENCH_IGNORE_STRAYS"):
        strays = stray_bench_processes()
        if strays:
            pid, cmd = strays[0]
            log(f"bench: ABORT — stray bench process pid={pid} is alive "
                f"({cmd[:120]}); kill it (or set DS_BENCH_IGNORE_STRAYS=1) "
                f"before timing")
            emit(None, None,
                 error=f"stray bench process pid={pid} alive: {cmd[:200]}")
            return 1

    # 1) fail-fast device probe (skipped in tiny/CPU smoke mode)
    if not tiny:
        log(f"bench: probing backend (deadline {probe_deadline:.0f}s) ...")
        ok, info, why = _run_sub(_probe_src(), probe_deadline, is_src=True)
        if not ok:
            log(f"bench: backend unavailable: {why}")
            emit(None, None, error=f"backend unavailable: {why}")
            return 1
        log(f"bench: backend up: {info}")

    # 2) candidates, best-first, each in a capped subprocess. The ladder
    # covers every lever built since r1 (r3 verdict weak #1): remat policy
    # (incl. host-offload residuals), batch, flash tile sizes, Pallas Adam.
    # A committed BENCH_LADDER.json (written by tools/attack_mfu.py from
    # MEASURED results) overrides the static order, so the driver's
    # round-end run tries the proven-best configs first.
    override = None
    if not tiny:
        try:
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "BENCH_LADDER.json")) as f:
                override = json.load(f)
            assert isinstance(override, list) and all(
                "tag" in c and "policy" in c and "batch" in c
                for c in override)
            log(f"bench: using measured ladder ({len(override)} candidates "
                f"from BENCH_LADDER.json)")
        except (OSError, ValueError, AssertionError):
            override = None
    if override:
        candidates = override
    elif tiny:
        # CPU smoke: prove the harness + the lever plumbing at shapes the
        # interpret-mode kernels can run in seconds. offload policies need
        # TPU memory-space placement, so they are chip-only candidates.
        # tiny mode proves the lever plumbing, not throughput: 3 timed steps
        # + 1 warmup per candidate keeps the whole ladder inside the slow
        # tier's budget even on a loaded CI host
        candidates = [
            {"tag": "dots,B8,f512", "policy": "dots", "batch": 8,
             "steps": 3, "warmup": 1},
            {"tag": "dots,m8xgas2,f512", "policy": "dots", "batch": 8,
             "gas": 2, "steps": 3, "warmup": 1},
            {"tag": "dots,B8,f512,lc128", "policy": "dots", "batch": 8,
             "lchunk": 128, "steps": 3, "warmup": 1},
            {"tag": "dots,B8,f512,padam", "policy": "dots", "batch": 8,
             "padam": True, "steps": 3, "warmup": 1},
            {"tag": "full-remat,B8", "policy": "nothing", "batch": 8,
             "steps": 3, "warmup": 1},
        ]
    else:
        candidates = [
            # gas-first: the GAS scan runs `gas` micro-steps inside ONE
            # compiled call, amortizing any fixed cost per dispatched call
            # without changing math
            {"tag": "dots,m8xgas8,f512,lc2048", "policy": "dots", "batch": 8,
             "gas": 8, "lchunk": 2048},  # + chunked xent: no [B,T,V] logits
            {"tag": "dots,m8xgas8,f512", "policy": "dots", "batch": 8,
             "gas": 8},
            {"tag": "dots,m16xgas4,f512,lc2048", "policy": "dots", "batch": 16,
             "gas": 4, "lchunk": 2048},
            # if dispatch turns out fully synchronous even without
            # fences, deeper gas is the only amortization left
            {"tag": "dots,m8xgas32,f512,lc2048", "policy": "dots", "batch": 8,
             "gas": 32, "lchunk": 2048},
            # xla-attention insurance: if Mosaic hangs or mis-tiles on this
            # chip, every flash candidate fails and the headline would read
            # null even with a healthy MXU; XLA attention at 1k is competitive
            {"tag": "dots,m8xgas8,xla-attn", "policy": "dots", "batch": 8,
             "gas": 8, "attn": "xla", "insurance": True},
            {"tag": "dots,m32xgas4,f512", "policy": "dots", "batch": 32,
             "gas": 4},
            {"tag": "dots,m8xgas8,padam", "policy": "dots", "batch": 8,
             "gas": 8, "padam": True},
            {"tag": "dots,B32,f512", "policy": "dots", "batch": 32},
            {"tag": "dots,m8xgas8,fq1024k512", "policy": "dots", "batch": 8,
             "gas": 8, "fq": 1024, "fk": 512},
            {"tag": "offload-dots,B32", "policy": "offload_dots_no_batch",
             "batch": 32},  # r4 window-1 winner; host residuals free HBM
            {"tag": "dots,B8,f512", "policy": "dots", "batch": 8},  # r1 shape
            {"tag": "full-remat,B8", "policy": "nothing", "batch": 8},  # r1
        ]
    best = None
    errors = []
    ladder = []  # every candidate outcome, kept in the emitted detail —
    # the r4 chip window produced ONE number with no record of why the
    # other nine candidates lost; this makes the artifact self-diagnosing
    overshot = False
    for spec in candidates:
        tag, policy = spec["tag"], spec["policy"]
        elapsed = time.time() - t_start
        remaining = budget - elapsed
        if best is not None and remaining < cand_cap * 0.5:
            log(f"bench: budget ({elapsed:.0f}s) — stopping with {best['tag']}")
            break
        if remaining <= 0:
            # with nothing measured yet, allow ONE over-budget attempt (a
            # cold first compile can eat the whole budget); never more, so
            # the driver's deadline still sees our JSON line
            if best is not None or overshot:
                log(f"bench: budget exhausted ({elapsed:.0f}s) — stopping")
                break
            overshot = True
        if policy == "nothing" and best is not None:
            # the full-remat fallback is strictly dominated by any successful
            # dots-remat run (same-or-smaller batch, more recompute)
            break
        if spec.get("insurance") and best is not None:
            # the xla-attn insurance only matters when Mosaic is failing;
            # with a flash number in hand, spend the budget on real levers
            continue
        # with no success yet, never shrink the cap below what a cold
        # PJRT-init + first-compile needs — overshooting the soft budget
        # beats emitting value=null with a working backend
        cap = cand_cap if best is None else min(cand_cap, max(remaining, 30.0))
        log(f"bench: trying {tag} (cap {cap:.0f}s) ...")
        ok, rec, why = _run_sub(
            [os.path.abspath(__file__), "--candidate", json.dumps(spec)],
            cap)
        if not ok:
            log(f"bench: {tag} FAILED: {why}")
            errors.append(f"{tag}: {why}")
            ladder.append({"tag": tag, "error": why[:160]})
            # a backend can drop mid-run: after a timeout, a quick
            # re-probe decides whether to
            # keep spending the budget or emit what we have right now
            if why.startswith("timeout after") and not tiny:
                ok_p, _, _ = _run_sub(_probe_src(), probe_deadline,
                                      is_src=True)
                if not ok_p:
                    log("bench: backend gone mid-sweep — stopping early")
                    errors.append("backend lost mid-sweep")
                    break
            continue
        log(f"bench: {tag}: {rec['tflops']:.1f} TFLOPs "
            f"({rec['dt'] * 1e3:.0f} ms/step)")
        ladder.append({"tag": tag, "tflops": round(rec["tflops"], 2),
                       "ms_per_step": round(rec["dt"] * 1e3, 1)})
        if best is None or rec["tflops"] > best["tflops"]:
            best = rec

    if best is None:
        emit(None, None, detail={"ladder": ladder} if ladder else None,
             error="; ".join(errors) or "no candidate ran")
        return 1
    val = round(best["tflops"], 2 if best["tflops"] >= 1 else 5)
    emit(val, round(best["tflops"] / BASELINE_TFLOPS, 6),
         detail={
             "config": best["tag"],
             "params": best["n_params"],
             "tokens_per_sec_per_chip": round(best["tokens_per_sec"], 1),
             "step_time_s": round(best["dt"], 4),
             "batch": best["batch"], "seq": SEQ,
             "loss": best["loss"],
             "ladder": ladder,
         })
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--candidate":
        from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,
                                                    force_cpu_devices)

        if os.environ.get("DS_BENCH_TINY"):
            force_cpu_devices(None)
        configure_compile_cache()
        print(json.dumps(run_candidate(json.loads(sys.argv[2]))), flush=True)
    else:
        try:
            rc = main()
        except Exception as e:  # guaranteed JSON on any parent failure
            emit(None, None, error=f"{type(e).__name__}: {e}")
            rc = 1
        sys.exit(rc)
