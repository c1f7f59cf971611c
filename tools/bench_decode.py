"""Inference decode benchmark: TTFT + decode throughput on the real chip.

Counterpart of the reference DS-Inference latency/throughput numbers
(``docs/_posts/2021-05-05-inference-kernel-optimization.md:53-67``): measures
time-to-first-token (prefill) and steady-state decode tokens/sec for the
flagship Llama decode graph via ``init_inference`` (whole generation loop in
one jit), at several (batch, prompt) points.

Hardened like ``bench.py``: the parent probes the backend with a short
deadline, runs every measurement point in a capped subprocess (shared compile
cache), and ALWAYS prints one final JSON summary line on stdout —
measurements when they exist, ``{"points": [], "error": ...}`` otherwise.
Commit the output as ``DECODE_r{N}.json``.

Usage:
  python tools/bench_decode.py                 # sweep on the real chip
  python tools/bench_decode.py --tiny          # CPU smoke (CI)
  python tools/bench_decode.py --one B P N     # child: a single point
"""

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def run_point(batch: int, prompt: int, new: int, tiny: bool,
              impl: str = "xla", model_family: str = "llama",
              ep: int = 1) -> dict:
    import jax

    from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,
                                                force_cpu_devices)

    if tiny:
        # smoke mode must not wait on a real accelerator. ep<=1 keeps the
        # caller's device-count configuration untouched.
        force_cpu_devices(ep if ep > 1 else None)
    configure_compile_cache()

    import deepspeed_tpu as ds

    attn_impl = "pallas" if impl == "pallas_int8" else impl
    kv_int8 = impl == "pallas_int8"
    if model_family == "mixtral":
        # MoE serving point (reference: Mixtral-8x7B is a BASELINE config;
        # ep>1 shards the stacked expert leaves via init_inference ep_size)
        from deepspeed_tpu.models import MixtralConfig, MixtralForCausalLM

        if tiny:
            cfg = MixtralConfig.tiny(decode_attention_impl=attn_impl)
        else:
            # prefill_flash_from_empty: the XLA cached prefill at
            # (64, 2048) would materialize [B, H, T, S] fp32 logits in the
            # tens of GB; the flash prefill path never does
            cfg = MixtralConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=3584,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=8, num_local_experts=8,
                num_experts_per_tok=2, max_position_embeddings=prompt + new,
                remat=False, decode_attention_impl=attn_impl,
                prefill_flash_from_empty=True)
        model = MixtralForCausalLM(cfg)
    else:
        from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

        if tiny:
            cfg = LlamaConfig.tiny(remat=False,
                                   decode_attention_impl=attn_impl)
        else:
            # prefill_flash_from_empty (see mixtral note)
            cfg = LlamaConfig.llama_400m(
                max_position_embeddings=prompt + new, remat=False,
                decode_attention_impl=attn_impl,
                prefill_flash_from_empty=True)
        model = LlamaForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (batch, prompt))
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jax.numpy.asarray(ids[:1]))["params"]
    # bucket_shapes=False: the bench measures EXACTLY the requested
    # (prompt, new) shape — pow-of-two padding would silently time a
    # different program (max_new_tokens=1 would run 8 decode steps)
    engine = ds.init_inference(model, params=params, dtype="bf16",
                               max_out_tokens=prompt + new,
                               kv_cache_int8=kv_int8, ep_size=ep,
                               bucket_shapes=False)

    def best_of(fn, n=3):
        """min over repeats — single-shot timings at millisecond scale are
        jitter-dominated and produced dt<ttft (null throughput) records."""
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return min(times)

    # TTFT: generation of ONE new token = prefill + single decode step
    np.asarray(engine.generate(ids, max_new_tokens=1))  # compile
    ttft = best_of(lambda: np.asarray(engine.generate(ids, max_new_tokens=1)))

    # decode throughput from the DIFFERENCE of two full runs (new vs 1 new
    # token): (new - 1) extra decode steps; avoids subtracting measurements
    # from differently-compiled programs' overheads
    np.asarray(engine.generate(ids, max_new_tokens=new))  # compile
    dt = best_of(lambda: np.asarray(engine.generate(ids, max_new_tokens=new)))
    extra_steps = new - 1
    decode_tps = (batch * extra_steps / (dt - ttft)
                  if extra_steps > 0 and dt > ttft else None)

    return {
        "impl": impl, "model": model_family, "ep": ep,
        # off-TPU the pallas impl silently falls back to the XLA reference;
        # record the backend so committed numbers can't mislabel what ran
        "backend": jax.default_backend(),
        "ttft_ms": round(ttft * 1e3, 1),
        "decode_tokens_per_sec":
            round(decode_tps, 1) if decode_tps else None,
        "per_seq_decode_ms_per_token":
            round((dt - ttft) / extra_steps * 1e3, 2)
            if extra_steps > 0 and dt > ttft else None,
        "end_to_end_s": round(dt, 3),
        "batch": batch, "prompt": prompt, "new_tokens": new,
    }


def _run_sub(extra_argv, timeout_s):
    cmd = [sys.executable, os.path.abspath(__file__)] + extra_argv
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr or b""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        for line in stderr.splitlines()[-10:]:
            log(f"  | {line}")
        return None, f"timeout after {timeout_s:.0f}s"
    for line in r.stderr.splitlines():
        log(f"  | {line}")
    if r.returncode != 0:
        tail = (r.stderr.strip().splitlines() or ["?"])[-1]
        return None, f"rc={r.returncode}: {tail[:300]}"
    out = [ln for ln in r.stdout.splitlines() if ln.strip().startswith("{")]
    if not out:
        return None, "no JSON on stdout"
    try:
        return json.loads(out[-1]), ""
    except ValueError as e:
        return None, f"bad JSON: {e}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CPU smoke test")
    ap.add_argument("--one", nargs=3, type=int, metavar=("B", "P", "N"),
                    help="child mode: measure a single (batch,prompt,new) point")
    ap.add_argument("--impl", default="xla", choices=("xla", "pallas", "pallas_int8"),
                    help="decode attention: XLA repeat_kv path, the Pallas "
                         "softmax_context-equivalent kernel, or the kernel "
                         "over an int8 KV cache (half the cache bandwidth)")
    ap.add_argument("--model", default="llama", choices=("llama", "mixtral"),
                    help="flagship dense decode or the MoE serving graph")
    ap.add_argument("--ep", type=int, default=1,
                    help="expert-parallel degree for --model mixtral "
                         "(init_inference ep_size)")
    args = ap.parse_args()

    if args.one:
        b, p, n = args.one
        print(json.dumps(run_point(b, p, n, args.tiny, args.impl,
                                   args.model, args.ep)), flush=True)
        return

    probe_deadline = float(os.environ.get("DS_BENCH_PROBE_S", "60"))
    point_cap = float(os.environ.get("DS_BENCH_CANDIDATE_S",
                                     "120" if args.tiny else "420"))
    # latency point (bs=1), the reference-blog-like serving point, and a
    # throughput point — TTFT + decode t/s at each
    # tiny decode runs long enough (64 new tokens) that the 2-run
    # difference is decode-dominated — 8 tokens sat inside timer jitter
    # and produced null throughput records
    # latency point (bs=1), the reference-blog-like serving points, and
    # realistic batch/prompt (r4 verdict: batch 8-64, prompt 512-2048)
    points = ([(1, 16, 64), (2, 16, 64)] if args.tiny
              else [(1, 128, 128), (8, 512, 128), (32, 1024, 128),
                    (64, 2048, 128)])

    metric = ("mixtral_small_decode" if args.model == "mixtral"
              else "llama400m_decode")
    summary = {"metric": metric, "impl": args.impl, "model": args.model,
               "ep": args.ep, "points": []}
    if not args.tiny:
        log(f"bench_decode: probing backend (deadline {probe_deadline:.0f}s)")
        probe = ("import json, time\nt0 = time.time()\nimport jax\n"
                 "d = jax.devices()\nprint(json.dumps({'n': len(d)}))\n")
        try:
            r = subprocess.run([sys.executable, "-c", probe],
                               capture_output=True, text=True,
                               timeout=probe_deadline)
            ok = r.returncode == 0 and "{" in r.stdout
        except subprocess.TimeoutExpired:
            ok = False
        if not ok:
            summary["error"] = "backend unavailable"
            print(json.dumps(summary), flush=True)
            return

    errors = []
    for b, p, n in points:
        tag = f"b{b},p{p},n{n}"
        log(f"bench_decode: point {tag} (cap {point_cap:.0f}s)")
        argv = ["--one", str(b), str(p), str(n), "--impl", args.impl,
                "--model", args.model, "--ep", str(args.ep)] \
            + (["--tiny"] if args.tiny else [])
        rec, why = _run_sub(argv, point_cap)
        if rec is None:
            log(f"bench_decode: {tag} FAILED: {why}")
            errors.append(f"{tag}: {why}")
            continue
        log(f"bench_decode: {tag}: TTFT {rec['ttft_ms']}ms, "
            f"{rec['decode_tokens_per_sec']} decode tok/s")
        # stream each point as its own JSON line the moment it lands, so an
        # OUTER kill (chip_sweep's cap, a dropped backend) loses nothing —
        # the merger reads these from the dead process's partial stdout
        print(json.dumps({"point": rec}), flush=True)
        summary["points"].append(rec)
    if errors and not summary["points"]:
        # only a full failure is an "error" (the sweep treats an error
        # record as not-captured); a partial hardware capture keeps its
        # points and notes the failed ones separately
        summary["error"] = "; ".join(errors)
    elif errors:
        summary["point_errors"] = "; ".join(errors)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    if "--one" in sys.argv:
        main()  # child: failures must exit non-zero so the parent records
                # them as point errors instead of parsing garbage
    else:
        try:
            main()
        except Exception as e:  # guaranteed JSON on any parent failure
            metric = ("mixtral_small_decode"
                      if "mixtral" in sys.argv else "llama400m_decode")
            print(json.dumps({"metric": metric, "points": [],
                              "error": f"{type(e).__name__}: {e}"}), flush=True)
