"""Per-kernel Pallas validation: parity + timing vs the XLA fallback.

r3 VERDICT #3: every Pallas kernel had only ever executed in interpret mode
on CPU — a Mosaic compile can fail or mis-tile where interpret succeeds.
This tool runs each kernel (flash fwd/bwd, block-sparse, decode attention,
fused Adam/LAMB) against its XLA reference:

- on TPU (``jax.default_backend() == "tpu"``): the REAL Mosaic kernel, at
  serving-class shapes, with wall-clock speedup vs the XLA path;
- elsewhere: interpret mode at tiny shapes, so the artifact pipeline and
  parity assertions stay proven between chip windows (the committed record
  carries ``mode`` so a CPU artifact can never be mistaken for hardware
  evidence).

Prints ONE JSON line; commit as ``KERNELS_r{N}.json``. Run via
``tools/chip_sweep.py`` or directly: ``python tools/bench_kernels.py``.
``--only flash_fwd,decode`` restricts to named kernels (the r4 chip window
showed the all-in-one run can exceed a subprocess cap without revealing
which kernel stalled — per-kernel runs isolate that).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # tools/ for _timing


def _timeit(fn, *args, reps=5):
    """Best-of-reps latency, fenced by the shared device fence."""
    from _timing import fence

    fence(fn(*args))  # compile + land
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fence(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3  # ms


def _record(name, mode, ref, got, t_pallas, t_xla, tol):
    import numpy as np

    err = float(np.max(np.abs(np.asarray(ref, np.float32)
                              - np.asarray(got, np.float32))))
    return {"kernel": name, "mode": mode, "allclose": bool(err <= tol),
            "max_abs_err": round(err, 6), "tol": tol,
            "t_pallas_ms": round(t_pallas, 3), "t_xla_ms": round(t_xla, 3),
            "speedup_vs_xla": round(t_xla / t_pallas, 3) if t_pallas else None}


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list of kernel names to run (default: all)")
    args = ap.parse_args()
    only = set(filter(None, args.only.split(",")))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.utils.jax_compat import configure_compile_cache

    configure_compile_cache()

    on_tpu = jax.default_backend() == "tpu"
    mode = "hardware" if on_tpu else "interpret"
    # interpret mode is orders slower — tiny shapes off-chip
    B, T, H, D = (4, 2048, 8, 64) if on_tpu else (2, 256, 4, 64)
    S = T
    rs = np.random.RandomState(0)
    results = []

    def run(name, fn):
        if only and name not in only:
            return
        _log(f"bench_kernels: {name} ...")
        t0 = time.time()
        try:
            results.append(fn())
        except Exception as e:  # record the failure, keep sweeping
            results.append({"kernel": name, "mode": mode, "allclose": False,
                            "error": f"{type(e).__name__}: {str(e)[:300]}"})
        _log(f"bench_kernels: {name} done in {time.time() - t0:.1f}s")

    # ---- flash attention fwd + bwd -----------------------------------
    from deepspeed_tpu.ops.pallas.flash_attention import (_reference_attention,
                                                          flash_attention)

    q = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, T, H, D), jnp.float32)

    def flash_fwd():
        pal = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                                      force_pallas=True))
        xla = jax.jit(lambda a, b, c: _reference_attention(
            a, b, c, True, 1.0 / D ** 0.5))
        got, ref = pal(q, k, v), xla(q, k, v)
        return _record("flash_fwd", mode, ref, got,
                       _timeit(pal, q, k, v), _timeit(xla, q, k, v), 2e-3)

    def flash_bwd():
        pal = jax.jit(jax.grad(lambda a: flash_attention(
            a, k, v, causal=True, force_pallas=True).sum()))
        xla = jax.jit(jax.grad(lambda a: _reference_attention(
            a, k, v, True, 1.0 / D ** 0.5).sum()))
        got, ref = pal(q), xla(q)
        return _record("flash_bwd_dq", mode, ref, got,
                       _timeit(pal, q), _timeit(xla, q), 5e-3)

    run("flash_fwd", flash_fwd)
    run("flash_bwd_dq", flash_bwd)

    # ---- block-sparse attention --------------------------------------
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        _reference_sparse, sparse_attention)

    nb = T // 64
    layout = np.zeros((H, nb, nb), np.int64)
    for i in range(nb):  # banded + global-first-block
        layout[:, i, max(0, i - 2):i + 1] = 1
        layout[:, i, 0] = 1

    def bsa():
        pal = jax.jit(lambda a, b, c: sparse_attention(
            a, b, c, layout=layout, causal=True, force_pallas=True))
        tri = layout * np.tril(np.ones((nb, nb), np.int64))
        xla = jax.jit(lambda a, b, c: _reference_sparse(
            a, b, c, tri, T // nb, True, 1.0 / D ** 0.5))
        got, ref = pal(q, k, v), xla(q, k, v)
        return _record("block_sparse_fwd", mode, ref, got,
                       _timeit(pal, q, k, v), _timeit(xla, q, k, v), 2e-3)

    run("block_sparse_fwd", bsa)

    # ---- decode attention (softmax_context equivalent) ---------------
    from deepspeed_tpu.ops.pallas.decode_attention import (_reference_decode,
                                                           decode_attention)

    Hkv = H // 2
    qd = jnp.asarray(rs.randn(B, H, D), jnp.float32)
    # head-major [B, Hkv, S, D] cache layout (models/layers.py)
    kc = jnp.asarray(rs.randn(B, Hkv, S, D), jnp.float32)
    vc = jnp.asarray(rs.randn(B, Hkv, S, D), jnp.float32)
    cidx = jnp.int32(S // 2)
    kmask = jnp.asarray(np.arange(S)[None, :] <= S // 2, jnp.int32)
    kmask = jnp.broadcast_to(kmask, (B, S))

    def decode():
        pal = jax.jit(lambda a, b, c: decode_attention(
            a, b, c, cidx, key_mask=kmask, force_pallas=True))
        xla = jax.jit(lambda a, b, c: _reference_decode(
            a, jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2), cidx,
            kmask, 1.0 / D ** 0.5))
        got, ref = pal(qd, kc, vc), xla(qd, kc, vc)
        return _record("decode_attention", mode, ref, got,
                       _timeit(pal, qd, kc, vc), _timeit(xla, qd, kc, vc),
                       2e-3)

    run("decode_attention", decode)

    # ---- decode attention over an int8 KV cache ----------------------
    from deepspeed_tpu.models.layers import _quantize_kv, dequantize_kv

    def decode_int8():
        kq, ks = _quantize_kv(kc)
        vq, vs = _quantize_kv(vc)
        pal = jax.jit(lambda a, b, c, bs, cs: decode_attention(
            a, b, c, cidx, key_mask=kmask, k_scale=bs, v_scale=cs,
            force_pallas=True))
        xla = jax.jit(lambda a, b, c, bs, cs: _reference_decode(
            a, jnp.swapaxes(dequantize_kv(b, bs), 1, 2),
            jnp.swapaxes(dequantize_kv(c, cs), 1, 2), cidx, kmask,
            1.0 / D ** 0.5))
        got = pal(qd, kq, vq, ks, vs)
        ref = xla(qd, kq, vq, ks, vs)
        return _record("decode_attention_int8", mode, ref, got,
                       _timeit(pal, qd, kq, vq, ks, vs),
                       _timeit(xla, qd, kq, vq, ks, vs), 2e-3)

    run("decode_attention_int8", decode_int8)

    # ---- weight-int8 matmul (vector_matmul_int8 / dequantize.cu) -----
    from deepspeed_tpu.ops.pallas.int8_matmul import (int8_matmul,
                                                      quantize_weight_per_col)

    def int8_mm():
        mk, mn = (1024, 4096) if on_tpu else (128, 256)
        xb = jnp.asarray(rs.randn(8, mk), jnp.float32)
        wf = jnp.asarray(rs.randn(mk, mn) * 0.1, jnp.float32)
        wq, sc = quantize_weight_per_col(wf)
        pal = jax.jit(lambda x, w, s: int8_matmul(
            x, w, s, interpret=not on_tpu))
        # highest-precision reference: TPU default matmul precision is
        # bf16-pass (error O(mag * 2^-9) >> tol at K=1024); the kernel
        # accumulates in fp32, so the reference must too
        xla = jax.jit(lambda x, w, s: jax.lax.dot(
            x, (w.astype(jnp.float32) * s[None, :]).astype(x.dtype),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(x.dtype))
        got = pal(xb, wq, sc)
        ref = xla(xb, wq, sc)
        return _record("int8_matmul", mode, ref, got,
                       _timeit(pal, xb, wq, sc), _timeit(xla, xb, wq, sc),
                       2e-3)

    run("int8_matmul", int8_mm)

    # ---- fused Adam / LAMB -------------------------------------------
    import optax

    from deepspeed_tpu.ops.optimizers import FusedLamb
    from deepspeed_tpu.ops.pallas.fused_adam import (scale_by_fused_adam,
                                                     scale_by_fused_lamb)

    n = 1_000_000 if on_tpu else 10_000
    params = {"w": jnp.asarray(rs.randn(n), jnp.float32),
              "b": jnp.asarray(rs.randn(n // 4), jnp.float32)}
    grads = {"w": jnp.asarray(rs.randn(n), jnp.float32),
             "b": jnp.asarray(rs.randn(n // 4), jnp.float32)}

    def opt_parity(name, pallas_tx, xla_tx, tol):
        def one(tx):
            st = tx.init(params)

            @jax.jit
            def step(g, s):
                up, s2 = tx.update(g, s, params)
                return optax.apply_updates(params, up), s2

            out, _ = step(grads, st)
            t = _timeit(lambda g: step(g, st)[0], grads)
            return out, t

        got, t_p = one(pallas_tx)
        ref, t_x = one(xla_tx)
        errs = [float(jnp.max(jnp.abs(got[k] - ref[k]))) for k in got]
        err = max(errs)
        return {"kernel": name, "mode": mode, "allclose": bool(err <= tol),
                "max_abs_err": round(err, 7), "tol": tol,
                "t_pallas_ms": round(t_p, 3), "t_xla_ms": round(t_x, 3),
                "speedup_vs_xla": round(t_x / t_p, 3) if t_p else None}

    run("fused_adam", lambda: opt_parity(
        "fused_adam",
        scale_by_fused_adam(1e-3, weight_decay=0.01),
        optax.adamw(1e-3, weight_decay=0.01), 1e-5))
    run("fused_lamb", lambda: opt_parity(
        "fused_lamb",
        scale_by_fused_lamb(1e-3, weight_decay=0.01),
        FusedLamb(1e-3, weight_decay=0.01), 1e-5))

    ok = all(r.get("allclose") for r in results)
    print(json.dumps({"metric": "pallas_kernels", "backend":
                      jax.default_backend(), "mode": mode,
                      "shapes": {"B": B, "T": T, "H": H, "D": D},
                      "all_allclose": ok, "kernels": results}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
