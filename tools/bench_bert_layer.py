"""BERT-Large transformer-layer throughput — the reference's kernel headline.

Reference: "fastest BERT training" measures the fused DeepSpeedTransformerLayer
stack at 64 TFLOPS (seq 128, 272 samples/s) and 53 TFLOPS (seq 512) on one
V100 (``docs/_posts/2020-05-28-fastest-bert-training.md:14,37``). This bench
runs OUR ``deepspeed_tpu.ops.DeepSpeedTransformerLayer`` at the same model
shape (BERT-Large: hidden 1024, heads 16, intermediate 4096, 24 layers) and
prints achieved TFLOPs for a full fwd+bwd pass, per (seq, batch) point.

Same hardening as the other chip tools: backend probe, per-point caps via the
parent, fence-by-value-fetch timing, one JSON line on stdout.

Usage: python tools/bench_bert_layer.py [--tiny]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def run_point(batch, seq, tiny):
    import jax
    import jax.numpy as jnp
    import numpy as np

    if tiny:
        jax.config.update("jax_platforms", "cpu")

    from _timing import time_fn
    from deepspeed_tpu.ops import (DeepSpeedTransformerConfig,
                                   DeepSpeedTransformerLayer)

    if tiny:
        H, I, heads, L = 64, 256, 4, 2
    else:
        H, I, heads, L = 1024, 4096, 16, 24  # BERT-Large
    cfg = DeepSpeedTransformerConfig(batch_size=batch, hidden_size=H,
                                     intermediate_size=I, heads=heads,
                                     num_hidden_layers=L, fp16=True,
                                     pre_layer_norm=True)
    layer = DeepSpeedTransformerLayer(cfg)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, seq, H), jnp.bfloat16)
    mask = jnp.ones((batch, seq), jnp.int32)
    params = [layer.init(jax.random.PRNGKey(i), x, mask)["params"]
              for i in range(L)]

    def stack(ps, x):
        for p in ps:
            x = layer.apply({"params": p}, x, mask)
        return x

    def loss(ps, x):
        return jnp.sum(stack(ps, x).astype(jnp.float32) ** 2)

    fwd = jax.jit(stack)
    fwdbwd = jax.jit(jax.grad(loss))

    t_f = time_fn(fwd, params, x, steps=5, warmup=2)
    t_fb = time_fn(fwdbwd, params, x, steps=5, warmup=2)

    # FLOPs: per layer per token 2*(4H^2 + 2HI) matmul MACs*2... use the
    # standard 6*P*tokens (fwd+bwd) + attention 12*L*B*S^2*H (PaLM app. B)
    p_layer = 4 * H * H + 2 * H * I
    tokens = batch * seq
    fb_flops = 6.0 * p_layer * L * tokens + 12.0 * L * batch * seq * seq * H
    f_flops = fb_flops / 3.0

    return {
        "batch": batch, "seq": seq, "layers": L, "hidden": H,
        "backend": jax.default_backend(),
        "fwd_ms": round(t_f * 1e3, 1),
        "fwdbwd_ms": round(t_fb * 1e3, 1),
        "fwd_tflops": round(f_flops / t_f / 1e12, 2),
        "fwdbwd_tflops": round(fb_flops / t_fb / 1e12, 2),
        "samples_per_sec": round(batch / t_fb, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--one", nargs=2, type=int, metavar=("B", "S"))
    args = ap.parse_args()

    if args.one:
        from deepspeed_tpu.utils.jax_compat import configure_compile_cache

        configure_compile_cache()
        print(json.dumps(run_point(args.one[0], args.one[1], args.tiny)),
              flush=True)
        return

    # reference points: seq 128 (their 64-TFLOPS headline) and seq 512
    points = [(4, 32), (2, 64)] if args.tiny else [(64, 128), (16, 512)]
    cap = float(os.environ.get("DS_BENCH_CANDIDATE_S",
                               "240" if args.tiny else "420"))
    summary = {"metric": "bert_large_layer_tflops", "points": [],
               "baseline": {"v100_seq128_tflops": 64.0,
                            "v100_seq512_tflops": 53.0}}
    errors = []
    for b, s in points:
        argv = [sys.executable, os.path.abspath(__file__),
                "--one", str(b), str(s)] + (["--tiny"] if args.tiny else [])
        log(f"bench_bert_layer: point b{b},s{s} (cap {cap:.0f}s)")
        try:
            r = subprocess.run(argv, capture_output=True, text=True,
                               timeout=cap)
            lines = [ln for ln in r.stdout.splitlines()
                     if ln.strip().startswith("{")]
            if r.returncode == 0 and lines:
                rec = json.loads(lines[-1])
                summary["points"].append(rec)
                print(json.dumps({"point": rec}), flush=True)
                log(f"bench_bert_layer: b{b},s{s}: "
                    f"{rec['fwdbwd_tflops']} TFLOPs fwd+bwd")
            else:
                errors.append(f"b{b},s{s}: rc={r.returncode}: "
                              + (r.stderr.strip().splitlines() or ["?"])[-1][:200])
        except subprocess.TimeoutExpired:
            errors.append(f"b{b},s{s}: timeout after {cap:.0f}s")
    if errors and not summary["points"]:
        summary["error"] = "; ".join(errors)
    elif errors:
        summary["point_errors"] = "; ".join(errors)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
