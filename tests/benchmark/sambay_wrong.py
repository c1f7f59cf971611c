"""Deliberately wrong computations of ``phi4-mini-flash.train.8k``'s model,
each one thing of the layers as ISSUE 41 wrote them down left out or
replaced, for the cell's check to refuse: patches of
``deepspeed_tpu/models/sambay.py``'s module-level functions (every parameter
still exists, so the reference reads the same tree), and the plain reference
itself computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). The window left off is the
harness's own ``--control window_off``. Used by the CPU tests at the tiny size
and by the builder's chip script at the published widths (PERF.md section
6)."""

import contextlib

import jax.numpy as jnp

import deepspeed_tpu.models.sambay as sambay
from deepspeed_tpu.models.layers import apply_rotary, rotary_embedding
from kimi_vl_wrong import reference_from_float8  # noqa: F401


def _conv_bias_left_out(m):
    conv = m.causal_conv
    return {"causal_conv": lambda x, weight, bias=None: conv(x, weight)}


def _own_keys(h, kv):
    """Cross-attention on its OWN layer's input: the two halves of ``h`` read
    as keys and values (a ``W_kv`` it does not have, the identity)."""
    k, v = kv
    n = k.shape[2] * k.shape[3]
    return h[..., :n].reshape(k.shape), h[..., n:2 * n].reshape(v.shape)


def _rotated(cfg, q, k):
    """Rotate-half RoPE (theta 10,000) on queries and keys."""
    B, T, _, d = q.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    cos, sin = rotary_embedding(positions, d, 10000.0, dtype=q.dtype)
    return apply_rotary(q, cos, sin), \
        None if k is None else apply_rotary(k, cos, sin)


def _replace(**patches):
    return lambda m: patches


#: name -> patches of models/sambay.py ({attribute: replacement})
WRONG = {
    "memory_after_the_gate": _replace(_memory=lambda y, gated: gated),
    "gmu_gate_left_out": _replace(_gmu_gate=lambda memory, gate: memory),
    "cross_attention_on_own_keys": _replace(_cross_kv=_own_keys),
    "lambda_fixed_at_init": _replace(
        _lambda=lambda lq1, lk1, lq2, lk2, init: init),
    "pair_norm_left_out": _replace(_pair_norm=lambda a, scale, eps: a),
    "rescale_left_out": _replace(_rescale=lambda a, init: a),
    "skip_connection_left_out": _replace(
        _skip_weight=lambda d: jnp.zeros_like(d, jnp.float32)),
    "conv_bias_left_out": _conv_bias_left_out,
    "softplus_left_out": _replace(_step_size=lambda dt, kernel, bias: (
        jnp.einsum("btr,rc->btc", dt, kernel.astype(dt.dtype),
                   preferred_element_type=jnp.float32)
        + bias.astype(jnp.float32))),
    "a_log_read_as_a": _replace(
        _decay_rate=lambda a_log: a_log.astype(jnp.float32)),
    "positions_rotated": _replace(_positional=_rotated),
}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = WRONG[name](sambay)
    saved = {k: getattr(sambay, k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(sambay, k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(sambay, k, v)
