#!/usr/bin/env python3
"""The quickest proof that deepspeed_tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4  # four chips: ONLY the sharded trainer
                                    # and its one-device comparison

Drives the system's main path once through the entry points a user calls —
``deepspeed_tpu.initialize`` -> ``train_batch`` and ``init_inference`` ->
``ServingEngine`` — at Mistral-7B-v0.1's published widths (hidden 4096, 32
query / 8 kv heads of 128, intermediate 14336, vocab 32000, window 4096).
Widths are never cut; depth is cut to what one 16 GB chip holds and the
weights are random, made from ``--seed``. Every cut is printed.

One process, one JSON line per phase (observations, not metrics), and as the
LAST line exactly ``{"ok": true, "device": {...}}`` — only when every check of
every phase passed on a ``tpu`` platform. Anything else exits non-zero.

``--tiny`` is the CPU rehearsal (on-chip-measurement guide §2): the same
phases at toy size on ``--chips`` virtual CPU devices. It never prints
``"ok": true`` and never exits 0 — exit 1 with ``"rehearsal": "passed"`` in
the last line is its best outcome.
"""

import argparse
import gc
import json
import sys
import time

#: depth is the only cut (model-configs guide §4); 16 B/param of fp32
#: master + Adam moments + grads bounds training, bf16 weights + the KV
#: pool bound serving. Chosen from memory_analysis() of the step programs
#: compiled for a described v5e chip (see CHANGES.md, PR 21).
FULL = {
    "train": dict(layers=2, seq=2048, batch=2, steps=5),
    "serve": dict(layers=16, slots=8, block_size=16, num_blocks=2048,
                  max_model_len=1024, chunk=128, new_tokens=16,
                  prefix=256, prompt_lens=(40, 150, 333, 700)),
}
TINY = {
    "train": dict(layers=2, seq=64, batch=4, steps=5),
    "serve": dict(layers=2, slots=4, block_size=8, num_blocks=64,
                  max_model_len=128, chunk=16, new_tokens=6,
                  prefix=24, prompt_lens=(5, 17, 30, 61)),
}
#: |loss - reference loss| / max(reference loss, 1), for flash against xla
#: attention and for the sharded trainer against one device: bf16 keeps 8
#: mantissa bits (eps 3.9e-3) and the mean over thousands of tokens sits
#: well inside one eps. Below a loss of 1 the band is absolute — the steps
#: memorize one batch, and a loss near 0 would turn any rounding into a
#: large ratio
LOSS_REL_TOL = 4e-3


def loss_gap(loss: float, ref: float) -> float:
    return abs(loss - ref) / max(abs(ref), 1.0)
#: max |logit(kernel) - logit(reference)| over max |logit(reference)|, per
#: layer of the bf16 stack: the two paths round differently (the kernel
#: keeps probabilities in fp32, the reference casts them to bf16), so they
#: may drift by one bf16 eps a layer — far below the O(1) error of a wrong
#: mask or a dropped page
LOGIT_REL_TOL_PER_LAYER = 4e-3


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


class Checks(dict):
    """name -> {"ok": bool, ...evidence}; a phase passes when all do."""

    def add(self, name, ok, **evidence):
        self[name] = {"ok": bool(ok), **evidence}

    @property
    def ok(self) -> bool:
        return all(c["ok"] for c in self.values())


def device_info():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_stats():
    """Per-device allocator stats; [] where the backend reports none
    (CPU)."""
    from deepspeed_tpu.monitor.perf import device_memory_stats

    return device_memory_stats()


def free_device_memory() -> None:
    """Drop every array the finished phase left behind (engines hold
    reference cycles through their jitted closures)."""
    import jax

    from deepspeed_tpu.parallel import topology

    topology.set_mesh(None, None)
    gc.collect()
    jax.clear_caches()
    gc.collect()


def model_config(tiny: bool, **over):
    from deepspeed_tpu.models import LlamaConfig

    if tiny:
        return LlamaConfig.tiny(sliding_window=96, **over)
    return LlamaConfig.mistral_7b(**over)


def depth_cut(cfg, tiny: bool):
    published = model_config(tiny).num_hidden_layers
    return {"num_hidden_layers": {"published": published,
                                  "used": cfg.num_hidden_layers},
            "weights": "random from --seed"}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_steps(cfg, sizes, seed, parallel=None, zero_stage=0, mesh=None):
    """``initialize`` + ``steps`` x ``train_batch`` on one seeded batch.
    Returns (engine, batch, losses, seconds of each step)."""
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import LlamaForCausalLM

    rs = np.random.RandomState(seed)
    ids = rs.randint(0, cfg.vocab_size, (sizes["batch"], sizes["seq"]))
    batch = {"input_ids": ids, "labels": ids}
    config = {"train_batch_size": sizes["batch"],
              "gradient_accumulation_steps": 1,
              "bf16": {"enabled": True},
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
              "zero_optimization": {"stage": zero_stage},
              "steps_per_print": 0, "seed": seed}
    if parallel:
        config["parallel"] = parallel
    engine, _, _, _ = ds.initialize(
        model=LlamaForCausalLM(cfg), config=config, mesh=mesh,
        partition_rules=LlamaForCausalLM.partition_rules(cfg),
        example_batch={k: v[:1] for k, v in batch.items()})
    losses, step_s = [], []
    for _ in range(sizes["steps"]):
        t0 = time.perf_counter()
        loss = engine.train_batch(batch=batch)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(round(time.perf_counter() - t0, 4))
    return engine, batch, losses, step_s


def train_step_text(engine, batch) -> str:
    """StableHLO of the resident train step, lowered from what
    ``train_batch`` passes it."""
    import jax

    return engine._train_step.lower(
        engine.state, engine._shape_batch(batch),
        jax.random.split(engine._rng)[1]).as_text()


def fence_comparison(engine, batch, steps=3):
    """ROADMAP S2's open question, settled where it can be: one window of
    ``steps`` train_batch calls closed by each fence convention."""
    import jax

    from deepspeed_tpu.utils import timer

    fences = {"block_until_ready": jax.block_until_ready,
              "scalar_fetch": float,
              "timer_synchronize": lambda _: timer._synchronize()}
    out = {}
    for name, fence in fences.items():
        jax.block_until_ready(engine.train_batch(batch=batch))
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch=batch)
        fence(loss)
        out[name] = round((time.perf_counter() - t0) / steps, 4)
        jax.block_until_ready(loss)
    return out


def phase_train(args, sizes, on_tpu):
    import math

    checks = Checks()
    cfg = model_config(args.tiny, num_hidden_layers=sizes["layers"],
                       attention_impl="flash")
    engine, batch, losses, step_s = train_steps(cfg, sizes, args.seed)
    prog = engine.perf.programs.program("train_step")
    checks.add("loss_finite", all(math.isfinite(x) for x in losses))
    checks.add("loss_falling", losses[-1] < losses[0],
               first=losses[0], last=losses[-1])
    checks.add("one_train_step_compile",
               prog.compiles == 1 and prog.recompiles == 0,
               compiles=prog.compiles, recompiles=prog.recompiles)
    if on_tpu:
        checks.add("train_step_has_tpu_custom_call",
                   "tpu_custom_call" in train_step_text(engine, batch))
    fences = fence_comparison(engine, batch)
    mem = memory_stats()
    del engine, prog
    free_device_memory()

    # the same first step through the XLA attention path, same chip
    ref_cfg = model_config(args.tiny, num_hidden_layers=sizes["layers"],
                           attention_impl="xla")
    ref_engine, _, ref_losses, _ = train_steps(
        ref_cfg, dict(sizes, steps=1), args.seed)
    gap = loss_gap(losses[0], ref_losses[0])
    checks.add("first_loss_flash_vs_xla", gap <= LOSS_REL_TOL,
               flash=losses[0], xla=ref_losses[0], gap=gap, tol=LOSS_REL_TOL)
    del ref_engine
    free_device_memory()
    return {"phase": "train", "ok": checks.ok, "depth": sizes["layers"],
            "cuts": depth_cut(cfg, args.tiny), "seq": sizes["seq"],
            "batch": sizes["batch"], "losses": losses,
            "compile_s": round(step_s[0] - min(step_s[1:]), 2),
            "step_s": step_s[1:],
            "fence_window_s_per_step": fences, "memory": mem,
            "memory_after_free": memory_stats(), "checks": checks}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def seeded_bf16_params(model, seed, mesh):
    """Random bf16 weights born on the mesh, leaf by leaf (an fp32
    ``model.init`` of a 16-layer stack would not fit beside its bf16
    cast): uniform matrices of standard deviation 0.02, unit norm scales."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    replicated = NamedSharding(mesh, PartitionSpec())

    def make(path, shape, key):
        if str(getattr(path[-1], "key", "")) == "scale":
            return jnp.ones(shape.shape, jnp.bfloat16)
        a = 0.02 * 3 ** 0.5
        return jax.random.uniform(key, shape.shape, jnp.bfloat16, -a, a)

    # "rbg" bits and a uniform draw: threefry and the normal's erf_inv
    # each spend half a minute of chip time on 3.7e9 weights
    keys = jax.random.split(jax.random.key(seed, impl="rbg"), len(leaves))
    out = [jax.jit(lambda k, p=p, s=s: make(p, s, k),
                   out_shardings=replicated)(k)
           for (p, s), k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


def serve_requests(srv, prompts, new_tokens):
    """The verify recipe: the prefix-bearing seed request runs to completion
    first (pages index as chunks land), then the rest together."""
    t0 = time.perf_counter()
    rids = [srv.submit(prompts[0], max_new_tokens=new_tokens)]
    srv.run()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rids += [srv.submit(p, max_new_tokens=new_tokens) for p in prompts[1:]]
    srv.run()
    return [srv.poll(r) for r in rids], first_s, time.perf_counter() - t0


def mixed_step_text(srv) -> str:
    """StableHLO of the resident mixed step, lowered from the shapes
    ``ServingEngine._step_mixed`` dispatches."""
    import jax
    import jax.numpy as jnp

    T, R = srv.mixed_step_tokens, srv.config.max_batch_size
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return srv._build_mixed_step(T).lower(
        srv.engine.params, srv.pool, i32(R, srv.nb_max), i32(1, T),
        i32(1, T), i32(1, T), i32(R), i32(R), i32(R), i32(R),
        jax.ShapeDtypeStruct((R,), jnp.bool_), srv._rng).as_text()


def first_token_logits(module, params, prompt, block_size):
    """Last-position logits of one prompt prefilled as a single ragged
    row through the model's paged mixed-step branch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.layers import paged_cache_index

    n = len(prompt)
    nb = -(-n // block_size)
    pool = module.init_paged_cache(nb + 1, block_size, dtype=jnp.bfloat16)
    idx = paged_cache_index(
        np.arange(nb, dtype=np.int32)[None], np.arange(n)[None], [n],
        chunk_start=[0], token_rows=np.zeros((1, n), np.int32),
        query_start=[0], query_len=[n])

    @jax.jit
    def run(params, pool, ids, idx):
        logits, _ = module.apply({"params": params}, ids, cache=pool,
                                 cache_index=idx)
        return logits[0, -1].astype(jnp.float32)

    return np.asarray(run(params, pool, jnp.asarray([prompt], jnp.int32),
                          idx))


def phase_serve(args, sizes, on_tpu):
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.models import LlamaForCausalLM
    from deepspeed_tpu.parallel import build_mesh

    checks = Checks()
    cfg = model_config(args.tiny, num_hidden_layers=sizes["layers"],
                       decode_attention_impl="pallas", remat=False)
    ref_cfg = model_config(args.tiny, num_hidden_layers=sizes["layers"],
                           decode_attention_impl="xla", remat=False)
    model, ref_model = LlamaForCausalLM(cfg), LlamaForCausalLM(ref_cfg)
    mesh = build_mesh()
    t0 = time.perf_counter()
    params = seeded_bf16_params(model, args.seed, mesh)
    init_s = time.perf_counter() - t0

    rs = np.random.RandomState(args.seed)
    tok = lambda n: rs.randint(0, cfg.vocab_size, n).tolist()
    prefix = tok(sizes["prefix"])
    # the seed request, 4 more behind the same prefix, 4 unrelated
    prompts = [prefix + tok(9)] + [prefix + tok(7 + 5 * i) for i in range(4)] \
        + [tok(n) for n in sizes["prompt_lens"]]
    scfg = ServingConfig(
        max_batch_size=sizes["slots"], block_size=sizes["block_size"],
        num_blocks=sizes["num_blocks"], max_model_len=sizes["max_model_len"],
        prefix_cache=True, prefill_chunk_tokens=sizes["chunk"])

    def serve(module):
        engine = ds.init_inference(module, params=params, dtype="bf16",
                                   mesh=mesh)
        srv = ServingEngine(engine, scfg)
        outs, first_s, rest_s = serve_requests(srv, prompts,
                                               sizes["new_tokens"])
        return srv, outs, first_s, rest_s

    srv, outs, first_s, rest_s = serve(model)
    states = [o.state for o in outs]
    checks.add("all_requests_finished",
               all(s == "finished" for s in states), states=states,
               reasons=[o.finish_reason for o in outs])
    checks.add("all_tokens_delivered",
               all(len(o.tokens) == sizes["new_tokens"] for o in outs))
    checks.add("one_mixed_step_compile",
               srv.compile_counts == {"mixed_step": 1},
               compile_counts=dict(srv.compile_counts))
    srv.block_pool.check_consistent()
    checks.add("pool_drained", srv.block_pool.used_count == 0,
               used_count=srv.block_pool.used_count)
    checks.add("prefix_cache_hit", srv.metrics.prefix_hits > 0,
               prefix_hits=srv.metrics.prefix_hits,
               cached_prefill_tokens=srv.metrics.cached_prefill_tokens)
    if on_tpu:
        checks.add("mixed_step_has_tpu_custom_call",
                   "tpu_custom_call" in mixed_step_text(srv))
    mem = memory_stats()
    steps = srv._step_no
    del srv
    free_device_memory()

    # the same requests through the XLA reference attention, same chip
    ref_srv, ref_outs, _, _ = serve(ref_model)
    checks.add("reference_requests_finished",
               all(o.state == "finished" for o in ref_outs))
    pairs = [(a, b) for o, r in zip(outs, ref_outs)
             for a, b in zip(o.tokens, r.tokens)]
    firsts = [o.tokens[:1] == r.tokens[:1] for o, r in zip(outs, ref_outs)]
    del ref_srv
    free_device_memory()

    probe = prompts[-2]
    got = first_token_logits(model, params, probe, sizes["block_size"])
    ref = first_token_logits(ref_model, params, probe, sizes["block_size"])
    rel = float(np.abs(got - ref).max() / np.abs(ref).max())
    tol = LOGIT_REL_TOL_PER_LAYER * sizes["layers"]
    checks.add("first_token_logits_kernel_vs_xla",
               bool(np.isfinite(got).all()) and rel <= tol,
               rel=rel, tol=tol, shape=list(got.shape),
               argmax_equal=bool(got.argmax() == ref.argmax()))
    del params
    free_device_memory()
    return {"phase": "serve", "ok": checks.ok, "depth": sizes["layers"],
            "cuts": depth_cut(cfg, args.tiny),
            "kv_pool_tokens": sizes["num_blocks"] * sizes["block_size"],
            "requests": len(prompts),
            "prompt_lens": [len(p) for p in prompts],
            "param_init_s": round(init_s, 2),
            "first_request_s_with_compile": round(first_s, 2),
            "rest_requests_s": round(rest_s, 2), "steps": steps,
            "token_agreement_vs_xla": {
                "first_token": sum(firsts) / len(firsts),
                "all_positions": sum(a == b for a, b in pairs) / len(pairs)},
            "memory": mem, "checks": checks}


# ---------------------------------------------------------------------------
# four chips: the sharded trainer against one device of the same host
# ---------------------------------------------------------------------------

def phase_sharded_train(args, sizes, on_tpu):
    import jax

    from deepspeed_tpu.parallel import build_mesh

    checks = Checks()
    n = len(jax.devices())
    # attention_impl="flash" is refused in any jit over more than one
    # device ("Mosaic kernels cannot be automatically partitioned" —
    # ROADMAP S5); the sharded phase runs the XLA attention path
    cfg = model_config(args.tiny, num_hidden_layers=sizes["layers"],
                       attention_impl="xla")
    engine, batch, losses, step_s = train_steps(
        cfg, sizes, args.seed, parallel={"data": 2, "model": 2},
        zero_stage=3)
    leaves = jax.tree_util.tree_leaves(
        (engine.state.params, engine.state.opt_state))
    spread = [len(x.sharding.device_set) for x in leaves
              if getattr(x, "ndim", 0) >= 2]
    checks.add("state_leaves_cover_all_devices",
               bool(spread) and min(spread) == n, min_devices=min(spread),
               leaves=len(spread))
    mem = memory_stats()
    if on_tpu:
        checks.add("every_device_holds_state",
                   len(mem) == n
                   and all(m["bytes_in_use"] > (1 << 28) for m in mem),
                   bytes_in_use=[m["bytes_in_use"] for m in mem])
    prog = engine.perf.programs.program("train_step")
    checks.add("one_train_step_compile",
               prog.compiles == 1 and prog.recompiles == 0,
               compiles=prog.compiles, recompiles=prog.recompiles)
    del engine, prog, leaves
    free_device_memory()

    one, _, ref_losses, _ = train_steps(
        cfg, sizes, args.seed, mesh=build_mesh(devices=jax.devices()[:1]))
    del one
    free_device_memory()
    gaps = [loss_gap(a, b) for a, b in zip(losses, ref_losses)]
    checks.add("per_step_loss_sharded_vs_one_device",
               max(gaps) <= LOSS_REL_TOL, sharded=losses,
               one_device=ref_losses, max_gap=max(gaps), tol=LOSS_REL_TOL)
    return {"phase": "sharded_train", "ok": checks.ok,
            "depth": sizes["layers"], "cuts": depth_cut(cfg, args.tiny),
            "mesh": {"data": 2, "model": 2}, "zero_stage": 3,
            "attention_impl": cfg.attention_impl,
            "compile_s": round(step_s[0] - min(step_s[1:]), 2),
            "step_s": step_s[1:],
            "memory": mem, "checks": checks}


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy size; never ok, never rc 0")
    args = ap.parse_args()

    from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,
                                                force_cpu_devices)

    if args.tiny:
        force_cpu_devices(args.chips)
    import jax

    dev = device_info()
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: needs a TPU, jax found {dev}", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {dev}",
              file=sys.stderr)
        return 1
    cache_dir = configure_compile_cache()
    sizes = TINY if args.tiny else FULL
    phases = [("train", phase_train), ("serve", phase_serve)] \
        if args.chips == 1 else [("train", phase_sharded_train)]
    where = {**dev, "jax": jax.__version__}
    emit({"phase": "start", **where, "compile_cache": cache_dir,
          "seed": args.seed})
    ok = True
    for key, phase in phases:
        t0 = time.perf_counter()
        try:
            line = phase(args, sizes[key], on_tpu)
        except Exception as e:  # a phase that raises fails the smoke
            import traceback

            traceback.print_exc()
            line = {"phase": phase.__name__.removeprefix("phase_"),
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}"[:2000]}
        line["wall_s"] = round(time.perf_counter() - t0, 1)
        emit({**line, **where})
        ok = ok and line["ok"]
    if not on_tpu:
        emit({"ok": False, "rehearsal": "passed" if ok else "failed",
              "device": dev})
        return 1
    if not ok:
        emit({"ok": False, "device": dev})
        return 1
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
