"""Deliberately wrong computations of ``kimi-vl-a3b.train.8k``'s model, each
one thing of the published forward pass left out or replaced, for the
cell's check to refuse: patches of ``deepspeed_tpu/models/deepseek_v3.py``'s
module-level functions (every parameter still exists, so the reference
reads the same tree), and the plain reference itself computed from weights
one precision below bfloat16. Used by the CPU tests at the tiny size and by
the builder's chip script at the published widths (PERF.md section 6)."""

import contextlib
import dataclasses
import types

import jax
import jax.numpy as jnp

import deepspeed_tpu.models.deepseek_v3 as dsv3
from benchmark import common


def _route_with(**over):
    def patch(m):
        route = m.route
        return {"route": lambda cfg, logits, bias: route(
            dataclasses.replace(cfg, **over), logits, bias)}
    return patch


def _rope_off_shared_key(m):
    rotate = m._rotate
    return {"_rotate": lambda x, cos, sin, interleave: x
            if x.shape[2] == 1 else rotate(x, cos, sin, interleave)}


def _kv_norm_left_out(m):
    norm = m._kv_norm       # called for its parameter, which stays unused
    return {"_kv_norm": lambda cfg, latent: latent + 0 * norm(cfg, latent)}


def _bias_left_out(m):
    route = m.route
    return {"route": lambda cfg, logits, bias: route(cfg, logits, None)}


def _normalised_over_held(m):
    """The weights divided by the sum over the HELD chosen experts, as a
    share that forgot the other chips would."""
    def route(cfg, logits, bias):
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias, cfg.num_experts_per_tok)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        held = (idx >= cfg.first_expert) & \
            (idx < cfg.first_expert + cfg.n_routed_experts)
        w = w / (jnp.sum(w * held, axis=-1, keepdims=True) + 1e-20)
        return w * cfg.routed_scaling_factor, idx
    return {"route": route}


def _shared_zeroed(m):
    shared = m._shared_experts
    return {"_shared_experts": lambda cfg, x: 0 * shared(cfg, x)}


def _held_zeroed(m):
    routed = m._routed_experts

    def zero(*args):
        out, rows = routed(*args)
        return 0 * out, rows
    return {"_routed_experts": zero}


#: name -> patches of models/deepseek_v3.py ({attribute: replacement})
WRONG = {
    "rope_off_shared_key": _rope_off_shared_key,
    "kv_norm_left_out": _kv_norm_left_out,
    "bias_left_out_of_selection": _bias_left_out,
    "softmax_for_sigmoid": _route_with(scoring_func="softmax"),
    "scale_left_out": _route_with(routed_scaling_factor=1.0),
    "normalised_over_held_only": _normalised_over_held,
    "shared_expert_zeroed": _shared_zeroed,
    "held_experts_zeroed": _held_zeroed,
}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = WRONG[name](dsv3)
    saved = {k: getattr(dsv3, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(dsv3, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(dsv3, k, v)


@contextlib.contextmanager
def reference_from_float8(exponent_bits=4, mantissa_bits=3):
    """Inside the block ``common.load_file_module("reference", ...)`` hands
    out the plain reference with every weight rounded to float8 (e4m3 by
    default: the nearest precision below the bfloat16 the configuration
    states) before it computes in float32."""
    load = common.load_file_module
    low = lambda params: jax.tree_util.tree_map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits, mantissa_bits),
        params)

    def load_low(directory, name):
        ref = load(directory, name)
        if directory != "reference":
            return ref
        return types.SimpleNamespace(
            hidden_states=lambda params, *a: ref.hidden_states(low(params),
                                                               *a),
            logits=lambda params, h: ref.logits(low(params), h),
            loss=lambda params, *a: ref.loss(low(params), *a))

    common.load_file_module = load_low
    try:
        yield
    finally:
        common.load_file_module = load
