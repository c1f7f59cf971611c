"""Deliberately wrong computations of ``kimi-linear-48b-a3b.train.8k``'s
model, each one thing of the layers as ISSUE 68 wrote them down left out or
replaced, for the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/kimi_linear.py`` and ``deepseek_v3.py`` (every
parameter still exists, so the reference reads the same tree), and the plain
reference computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). Used by the CPU tests at the tiny
size and by the builder's chip script at the published widths (``python3
tests/benchmark/kimi_linear_wrong.py <data seeds>`` through the chip tool;
``--tiny`` rehearses it on the CPU; PERF.md section 6)."""

import contextlib
import dataclasses

import jax.numpy as jnp

import deepspeed_tpu.models.deepseek_v3 as dsv3
import deepspeed_tpu.models.kimi_linear as kimi_linear
from deepspeed_tpu.models.layers import rotary_embedding
from kimi_vl_wrong import (_normalised_over_held,  # noqa: F401
                           reference_from_float8)


def _decay_head_mean(m):
    """The decay taken as its mean over a head's channels: ONE number a
    head and position, qwen3-next's rule."""
    real = m._log_decay

    def log_decay(a_log, f, dt_bias):
        g = real(a_log, f, dt_bias)
        return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    return {"_log_decay": log_decay}


def _correction_left_out(m):
    """``d_t = beta_t v_t``: the state is never read for the key (the
    solve's second right-hand side, what becomes ``w``, zeroed, and the
    strictly lower table with it)."""
    def solve(a, rhs):
        dv = rhs.shape[-1] // 2      # keys and values are as wide here
        return jnp.concatenate(
            [rhs[..., :dv], jnp.zeros_like(rhs[..., dv:])], -1)
    return {"_unit_lower_solve": solve}


def _rotated(m):
    """The latent attention's 64 shared key columns and their query columns
    rotated at theta 10,000, the row's ``rope_theta`` (a key no layer
    reads)."""
    real = m.DeepseekV3Attention

    def attention(cfg, name):
        layer = real(dataclasses.replace(cfg, mla_use_nope=False), name=name)

        def call(h, cos, sin, mask):
            positions = jnp.broadcast_to(jnp.arange(h.shape[1])[None],
                                         h.shape[:2])
            return layer(h, *rotary_embedding(
                positions, cfg.qk_rope_head_dim, 10000.0, dtype=h.dtype),
                mask)
        return call
    return {"DeepseekV3Attention": attention}


#: name -> [(module, patches of it ({attribute: replacement}))]
WRONG = {
    "decay_head_mean": [(kimi_linear, _decay_head_mean)],
    "decay_left_out": [(kimi_linear, lambda m: {
        "_log_decay": lambda a_log, f, dt_bias: jnp.zeros_like(f)})],
    "correction_left_out": [(kimi_linear, _correction_left_out)],
    "beta_one": [(kimi_linear, lambda m: {"_beta": jnp.ones_like})],
    "mla_rotated": [(kimi_linear, _rotated)],
    "output_gate_left_out": [(kimi_linear, lambda m: {
        "_out_gate": jnp.ones_like})],
    "conv_silu_left_out": [(kimi_linear, lambda m: {
        "_conv_act": lambda x: x})],
    "not_unit_length": [(kimi_linear, lambda m: {
        "_unit_length": lambda x: x.astype(jnp.float32)})],
    "normalised_over_held": [(dsv3, _normalised_over_held)],
}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = [(module, k, v) for module, make in WRONG[name]
               for k, v in make(module).items()]
    saved = [(module, k, getattr(module, k)) for module, k, _ in patches]
    try:
        for module, k, v in patches:
            setattr(module, k, v)
        yield
    finally:
        for module, k, v in saved:
            setattr(module, k, v)


def main(argv):
    """The sound model, every wrong computation, the harness's control and
    the float8 e5m2 reference through ``kinds/train.py``'s own comparison of
    logits and first-step loss on ONE engine a data seed (``model_logits``
    traces the model anew at every call; a training step a wrong computation
    would cost a compile each): one JSON line a reading."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    import jax
    import numpy as np

    from benchmark import common, run as bench_run
    from benchmark.traffic import generator
    from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,
                                                force_cpu_devices)

    tiny = "--tiny" in argv
    seeds = [int(a) for a in argv if a.lstrip("-").isdigit()]
    if tiny:
        force_cpu_devices(1)
    configure_compile_cache()
    cell = "kimi-linear-48b-a3b.train.8k"
    bench = common.load_benchmark()
    for seed in seeds:
        ctx = bench_run.context(bench, cell, seed, tiny=tiny)
        ctx["emit"] = lambda obj: None
        kind = common.load_file_module("kinds", ctx["workload"]["kind"])
        sizes = ctx["sizes"]
        engine = kind.build_engine(ctx, sizes)
        ids = generator.packed_batch(ctx["mix"], seed, -1,
                                     sizes["vocab_size"], 1)["input_ids"]
        rows = min(ctx["workload"]["check"]["probe_positions"], ids.shape[1])

        def loss_of(params):
            def fwd(p, ids):
                half = jax.tree_util.tree_map(
                    lambda a: a.astype(engine.compute_dtype), p)
                out = engine.module.apply({"params": half}, ids, labels=ids)
                return out[0] if isinstance(out, tuple) else out
            return float(jax.jit(fwd)(params, jnp.asarray(ids)))

        def reading(name, how, control=None):
            with how:
                ref = common.load_file_module("reference",
                                              ctx["config"]["reference"])
                params = engine.state.params
                if control:      # top-1: a model of its own on these weights
                    _, module = common.build_model(
                        ctx["config"], sizes, num_experts_per_tok=1,
                        **ctx["workload"].get("model", {}))
                    real, engine.module = engine.module, module
                try:
                    got = kind.model_logits(engine, ids, rows)[0]
                    loss = loss_of(params)
                finally:
                    if control:
                        engine.module = real
                hidden = ref.hidden_states(params, sizes,
                                           jnp.asarray(ids[0]))[0]
                want = np.asarray(ref.logits(params, hidden[-rows:]))
                ref_loss = float(ref.loss(params, sizes, ids))
            print(json.dumps({
                "seed": seed, "name": name,
                "logit_rel_l2": common.rel_l2(got, want),
                "loss_gap": abs(loss - ref_loss) / max(abs(ref_loss), 1.0),
                "finite": bool(np.isfinite(got).all())}), flush=True)

        reading("sound", contextlib.nullcontext())
        for name in WRONG:
            reading(name, wrong(name))
        reading("top1_routing", contextlib.nullcontext(), control=True)
        reading("reference_fp8_e5m2", reference_from_float8(5, 2))
        del engine
        bench_run.free_device_memory()


if __name__ == "__main__":
    import sys
    main(sys.argv[1:])
