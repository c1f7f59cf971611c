"""Deliberately wrong computations of ``zaya1-8b.train.8k``'s model, each one
thing of the layer as ISSUE 35 wrote it down left out or replaced, for the
cell's check to refuse: patches of ``deepspeed_tpu/models/zaya.py``'s
module-level functions (every parameter still exists, so the reference reads
the same tree), one configuration override (top-2), and the plain reference
itself computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). Used by the CPU tests at the tiny
size and by the builder's chip script at the published widths (PERF.md
section 6)."""

import contextlib

import jax.numpy as jnp

import deepspeed_tpu.models.zaya as zaya
from deepspeed_tpu.models.layers import apply_rotary, rotary_embedding
from kimi_vl_wrong import reference_from_float8  # noqa: F401


def _conv_left_out(ndim):
    """Convolution A (depthwise, ``weight.ndim`` 2) or B (grouped, 4)
    replaced by the identity."""
    def patch(m):
        conv = m.causal_conv
        return {"causal_conv": lambda x, weight, bias=None: x
                if weight.ndim == ndim else conv(x, weight, bias)}
    return patch


def _qk_mean_left_out(m):
    mean = m._qk_mean
    return {"_qk_mean": lambda q, k: tuple(0 * part for part in mean(q, k))}


def _rotary_on_all_columns(m):
    def rotary(cfg, x, cos, sin):
        B, T, _, D = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        return apply_rotary(x, *rotary_embedding(positions, D, cfg.rope_theta,
                                                 dtype=x.dtype))
    return {"_rotary": rotary}


def _bias_left_out(m):
    route = m.route
    return {"route": lambda cfg, logits, bias: route(cfg, logits, None)}


def _replace(**patches):
    return lambda m: patches


#: name -> patches of models/zaya.py ({attribute: replacement})
WRONG = {
    "conv_a_left_out": _conv_left_out(2),
    "conv_b_left_out": _conv_left_out(4),
    "qk_mean_left_out": _qk_mean_left_out,
    "value_from_current_token": _replace(
        _shifted_value=lambda v1, v2: jnp.concatenate([v1, v2], axis=-1)),
    "temperature_left_out": _replace(_temperature=lambda k, tau: k),
    "unit_norm_left_out": _replace(
        _unit_length=lambda x: x.astype(jnp.float32)),
    "rotary_on_all_columns": _rotary_on_all_columns,
    "router_state_left_out": _replace(
        _carry_state=lambda r, gamma, state: r),
    "skip_expert_returns_zero": _replace(
        _skip_expert=lambda h, weight: jnp.zeros_like(h)),
    "bias_left_out_of_choice": _bias_left_out,
    "residual_scaling_left_out": _replace(
        _residual=lambda x, y, b_r, a_r, b_y, a_y: x + y),
    # hidden / heads, what common.sizes_of calls head_dim
    "softmax_scale_256": _replace(_softmax_scale=lambda cfg: float(
        cfg.hidden_size // cfg.num_attention_heads) ** -0.5),
}

#: name -> overrides of the model's configuration (``workload["model"]``)
WRONG_CONFIG = {"top2": {"num_experts_per_tok": 2}}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = WRONG[name](zaya)
    saved = {k: getattr(zaya, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(zaya, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(zaya, k, v)


def wrong_context(ctx, name):
    """``ctx`` with the model's configuration overridden as ``name`` says."""
    workload = ctx["workload"]
    return {**ctx, "workload": {**workload, "model": {
        **workload.get("model", {}), **WRONG_CONFIG[name]}}}
