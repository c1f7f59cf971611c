"""A training cell: ``deepspeed_tpu.initialize`` -> ``train_batch`` on packed
sequences, one optimizer step per call, a fresh seeded batch every step.

Set-up: the engine's own seeded init, the correctness check at the initial
weights, the warm-up steps. Window: steps dispatched back to back, fenced
with ``block_until_ready`` about once a second and at the end. With
``--trace 1`` the window's last seconds run under the profiler and every
host-clock number comes from the part before them.
"""

import math
import time

import numpy as np

from benchmark import common, flops, stats as st, trace_reduce
from benchmark.traffic import generator

#: deliberately wrong computations the check must refuse (``--control``)
CONTROLS = ("window_off", "top1_routing")


def build_engine(ctx, sizes, control=None):
    import deepspeed_tpu as ds

    wl, mix = ctx["workload"], ctx["mix"]
    over = dict(wl.get("model", {}))
    if control == "window_off":
        over["sliding_window"] = None
    if control == "top1_routing":
        over["num_experts_per_tok"] = 1
    cfg, model = common.build_model(ctx["config"], sizes, **over)
    chips = ctx["cell"]["chips"]
    config = {k: v for k, v in wl["engine"].items() if k != "parallel"}
    mesh = common.cell_mesh(chips, **wl["engine"].get("parallel", {}))
    config = {**config,
              "train_batch_size": mix["sequences_per_chip"] * chips,
              "seed": weight_seed(ctx) % (2 ** 31)}
    example = {k: v[:1] for k, v in generator.packed_batch(
        mix, 0, 0, sizes["vocab_size"], chips).items()}
    engine, _, _, _ = ds.initialize(
        model=model, config=config, example_batch=example, mesh=mesh,
        partition_rules=type(model).partition_rules(cfg))
    return engine


def model_logits(engine, ids, rows):
    """Logits of the LAST ``rows`` positions of each sequence through the
    system's own forward pass (its module, its kernels, its compute dtype),
    at the engine's current weights."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    dtype = engine.compute_dtype

    def forward(params, ids):
        half = jax.tree_util.tree_map(
            lambda p: p.astype(dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        out = engine.module.apply({"params": half}, ids)
        return out[:, -rows:].astype(jnp.float32)

    sharding = NamedSharding(engine.mesh, PartitionSpec(engine._batch_axes))
    fn = jax.jit(forward, in_shardings=(engine.param_shardings, sharding),
                 out_shardings=NamedSharding(engine.mesh, PartitionSpec()))
    return np.asarray(fn(engine.state.params,
                         jax.device_put(jnp.asarray(ids), sharding)))


def check(ctx, engine, sizes):
    """The verdict on the arithmetic, from the seed alone: first-step loss
    and late-position logits against the plain reference at the initial
    weights, every loss finite, loss falling on one repeated batch."""
    import jax

    ref = common.load_file_module("reference", ctx["config"]["reference"])
    wl, tol = ctx["workload"], ctx["workload"]["check"]
    chips = ctx["cell"]["chips"]
    batch = generator.packed_batch(ctx["mix"], ctx["seed"], -1,
                                   sizes["vocab_size"], chips)
    ids = batch["input_ids"]
    rows = min(tol["probe_positions"], ids.shape[1])
    params = engine.state.params
    ref_loss = float(ref.loss(params, sizes, ids))
    hidden = ref.hidden_states(params, sizes, jax.numpy.asarray(ids[0]))
    hidden = hidden[0] if isinstance(hidden, tuple) else hidden
    ref_logits = np.asarray(ref.logits(params, hidden[-rows:]))
    got_logits = model_logits(engine, ids, rows)[0]
    losses = [float(jax.block_until_ready(engine.train_batch(batch=batch)))
              for _ in range(1 + wl["warmup_steps"])]
    stats = {
        "loss_gap": abs(losses[0] - ref_loss) / max(abs(ref_loss), 1.0),
        "logit_rel_l2": common.rel_l2(got_logits, ref_logits),
        "loss_first": losses[0], "loss_reference": ref_loss,
        "loss_last": losses[-1],
    }
    verdicts = {
        "loss_gap": stats["loss_gap"] <= tol["loss_gap_tol"],
        "logit_rel_l2": stats["logit_rel_l2"] <= tol["logit_rel_l2_tol"],
        "finite": all(math.isfinite(x) for x in losses)
        and bool(np.isfinite(got_logits).all()),
        "falling": losses[-1] < losses[0],
    }
    return all(verdicts.values()), {**stats, "verdicts": verdicts}


def run(ctx):
    import jax

    sizes = ctx["sizes"]
    mix, chips = ctx["mix"], ctx["cell"]["chips"]
    engine = build_engine(ctx, sizes, ctx.get("control"))
    ctx["emit"]({"phase": "engine", "s": time.perf_counter() - ctx["t_start"]})
    correct, stats = check(ctx, engine, sizes)
    ctx["emit"]({"phase": "check", "correct": correct, **stats})
    if ctx.get("check_only"):
        return {"correct": correct, "stats": stats}

    tokens = mix["sequences_per_chip"] * chips * mix["seq_len"]
    prog = engine.perf.programs.program("train_step")
    compiles0 = prog.compiles + prog.recompiles

    def fenced_steps(n, first):
        t0 = time.perf_counter()
        out = []
        for i in range(n):
            with trace_reduce.annotate("bench.make_batch"):
                batch = generator.packed_batch(
                    mix, ctx["seed"], first + i, sizes["vocab_size"], chips)
            with trace_reduce.annotate("bench.train_batch"):
                out.append(engine.train_batch(batch=batch))
        with trace_reduce.annotate("bench.fence"):
            jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    # two warm windows on fresh batches size the fence interval
    fenced_steps(2, 0)
    _, dt = fenced_steps(2, 2)
    per_fence = max(1, math.ceil(1.0 / (dt / 2)))

    def drive(t0, seconds, first):
        """Fenced groups of steps until ``seconds`` have passed since ``t0``."""
        losses, fence_ms = [], []
        while time.perf_counter() - t0 < seconds:
            out, dt = fenced_steps(per_fence, first + len(losses))
            losses += out
            fence_ms.append(1e3 * dt / per_fence)
        return losses, fence_ms, time.perf_counter()

    # A traced run clocks the window less its last ``trace_seconds`` and
    # runs those under the profiler, so that the profiler's start, its stop
    # and the parse stand in no number taken from the host's clock.
    tail_s = ctx["trace_seconds"] if ctx["trace"] else 0
    w0 = time.perf_counter()
    setup_s = w0 - ctx["t_start"]
    losses, fence_ms, w1 = drive(w0, ctx["seconds"] - tail_s, 4)
    clocked, trace = len(losses), None
    if tail_s:
        with trace_reduce.traced(ctx["trace_dir"]):
            losses += drive(time.perf_counter(), tail_s, 4 + clocked)[0]
        trace = trace_reduce.reduce_dir(ctx["trace_dir"])
    losses = [float(x) for x in losses]
    compiled = prog.compiles + prog.recompiles - compiles0
    if compiled:
        ctx["emit"]({"defect": "compile inside the window",
                     "program": "train_step", "count": compiled})
    rate = clocked * tokens / (w1 - w0)
    # the clocked groups, so that a stalled run can be told from a slow
    # program
    ctx["emit"]({"phase": "window", **st.fence_groups(fence_ms)})
    tol = ctx["workload"]["check"]
    return {
        "correct": correct,
        # each number the check compared, beside its limit (the benchmark's
        # contract: a run that is not correct leaves them in the record)
        "compared": {k[:-4]: {"value": stats[k[:-4]], "limit": limit}
                     for k, limit in tol.items() if k.endswith("_tol")},
        "attempted": len(losses),
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "end_to_end": {"setup_s": setup_s, rate_metric(ctx): rate / chips},
        "observed": {"kind": "train", "fence_ms": fence_ms,
                     "tokens_per_s": rate, "chips": chips,
                     "flops_per_token": flops.train_flops_per_token(
                         sizes, mix["seq_len"]),
                     "steps": clocked, "window_s": w1 - w0,
                     "compiles_in_window": compiled},
        "trace": trace,
    }


def weight_seed(ctx):
    """The seed of the engine's initial weights: the one the cell's file
    states as ``weight_seed`` (a cell whose step time follows what a seeded,
    frozen router sends its held experts is measured on ONE set of weights,
    and ``--seed`` draws the data alone), else the run's own seed. At the
    file's end: the frames above keep their lines."""
    seed = ctx["workload"].get("weight_seed")
    return ctx["seed"] if seed is None else seed


def rate_metric(ctx):
    """The end-to-end name the cell's rate is reported under: the one its
    file states as ``rate_metric`` (``BENCHMARK.json`` gives a bound a
    metric, not a cell, so a cell whose rate follows its own training
    trajectory reports the same quantity under a name with a bound of its
    own), else ``train_tokens_per_s_per_chip``."""
    return ctx["workload"].get("rate_metric", "train_tokens_per_s_per_chip")
