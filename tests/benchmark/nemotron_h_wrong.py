"""Deliberately wrong computations of ``nemotron-3-nano-30b-a3b.train.8k``'s
model, each one thing of the layers as ISSUE 66 wrote them down left out or
replaced, for the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/nemotron_h.py``, ``mixtral.py`` and ``llama.py`` (every
parameter still exists, so the reference reads the same tree); the plain
reference itself under ANOTHER PATTERN of the same layers
(``reference_under_pattern``: the system's tree is one pattern's, so the
pattern is moved on the reference's side); and the reference computed from
weights one precision below bfloat16 (``kimi_vl_wrong.reference_from_float8``).
Used by the CPU tests at the tiny size and by the builder's chip script at
the published widths (PERF.md section 6)."""

import contextlib
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

import deepspeed_tpu.models.llama as llama
import deepspeed_tpu.models.mixtral as mixtral
import deepspeed_tpu.models.nemotron_h as nemotron_h
from benchmark import common
from deepspeed_tpu.models.layers import causal_conv, rotary_embedding
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _norm_before_gate(m):
    def gated_norm(y, z, scale, eps, groups):
        g = y.reshape(*y.shape[:-1], groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
        return (g.reshape(y.shape) * scale
                * nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return {"_gated_norm": gated_norm}


def _one_norm_over_all_columns(m):
    real = m._gated_norm
    return {"_gated_norm": lambda y, z, scale, eps, groups: real(
        y, z, scale, eps, 1)}


def _split_xbc_first(m):
    """``[xBC ; z ; dt]`` of ``in_proj``'s columns."""
    def split(zxbcdt, d, gn):
        xbc, z, dt = jnp.split(zxbcdt, (d + 2 * gn, 2 * d + 2 * gn), axis=-1)
        return z, xbc, dt
    return {"_split": split}


def _activation_of(forward):
    """``relu2`` looked up as another ungated activation (the check reads
    the forward pass alone)."""
    def patch(m):
        return {"_ACTIVATIONS": {**m._ACTIVATIONS, "relu2": m.Activation(
            False, lambda h1, _: forward(h1), m.RELU2.rule)}}
    return patch


def _moe_under(**over):
    """The real expert block of a config with ``over`` replaced."""
    def patch(m):
        real = m.MixtralSparseMoeBlock
        return {"MixtralSparseMoeBlock": lambda cfg, name: real(
            dataclasses.replace(cfg, **over), name=name)}
    return patch


def _shared_expert_left_out(m):
    real = m.SharedExpert
    return {"SharedExpert": lambda cfg, name: (
        lambda h: 0 * real(cfg, name=name)(h))}


def _rotated(m):
    """The attention's queries and keys rotated over every column at theta
    10,000, the row's ``rope_theta`` (a key no layer reads)."""
    real = m.LlamaAttention

    def attention(cfg, name):
        layer = real(dataclasses.replace(cfg, rotary_dim=None), name=name)

        def call(h, cos, sin, mask):
            positions = jnp.broadcast_to(jnp.arange(h.shape[1])[None],
                                         h.shape[:2])
            return layer(h, *rotary_embedding(
                positions, cfg.head_dim, 10000.0, dtype=h.dtype), mask)
        return call
    return {"LlamaAttention": attention}


#: name -> [(module, patches of it ({attribute: replacement}))]
WRONG = {
    "norm_before_gate": [(nemotron_h, _norm_before_gate)],
    "one_norm_over_all_columns": [(nemotron_h, _one_norm_over_all_columns)],
    "every_head_reads_group_0": [(nemotron_h, lambda m: {
        "_groups": lambda t: jnp.broadcast_to(t[:, :, :1], t.shape)})],
    "skip_left_out": [(nemotron_h, lambda m: {"_skip": lambda y, x, d: y})],
    "dt_bias_left_out": [(nemotron_h, lambda m: {
        "_step_size": lambda dt, bias: jax.nn.softplus(
            dt.astype(jnp.float32))})],
    "softplus_left_out": [(nemotron_h, lambda m: {
        "_step_size": lambda dt, bias: dt.astype(jnp.float32) + bias})],
    "conv_bias_left_out": [(nemotron_h, lambda m: {
        "_conv_act": lambda xbc, taps, bias: nn.silu(causal_conv(
            xbc, taps.astype(xbc.dtype)))})],
    "conv_silu_left_out": [(nemotron_h, lambda m: {
        "_conv_act": lambda xbc, taps, bias: causal_conv(
            xbc, taps.astype(xbc.dtype), bias.astype(xbc.dtype))})],
    "split_xbc_first": [(nemotron_h, _split_xbc_first)],
    "relu_for_relu2": [(mixtral, _activation_of(nn.relu))],
    "silu_for_relu2": [(mixtral, _activation_of(nn.silu))],
    "softmax_scores": [(nemotron_h, _moe_under(router_scoring="softmax"))],
    "routed_scale_left_out": [(nemotron_h, _moe_under(
        routed_scaling_factor=1.0))],
    "topk_not_normalised": [(nemotron_h, _moe_under(norm_topk_prob=False))],
    "shared_expert_left_out": [(nemotron_h, _shared_expert_left_out)],
    "attention_rotated": [(nemotron_h, _rotated)],
    # query head i reads key-value head i mod Hkv, where its own is
    # i // (Hq / Hkv)
    "kv_head_by_modulo": [(llama, lambda m: {
        "repeat_kv": lambda x, n_rep: jnp.tile(x, (1, 1, n_rep, 1))})],
}

#: the cell's pattern and the tiny preset's, each with its attention layer
#: moved: the same layers in another order
OTHER_PATTERN = {"MEMEM*EME": "MEM*EMEME", "EM*EMEMEM*": "EMEM*EMEM*"}


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


@contextlib.contextmanager
def reference_under_pattern(pattern=None):
    """Inside the block ``common.load_file_module("reference", ...)`` hands
    out the plain reference computing the stack's own layers in the order
    of ``pattern`` (None: ``OTHER_PATTERN`` of the stack's own): position
    ``l`` runs the next layer of kind ``pattern[l]`` the tree holds."""
    load = common.load_file_module

    def load_other(directory, name):
        ref = load(directory, name)         # a module of its own every call
        if directory != "reference":
            return ref
        own = ref.layers_in_order

        def moved(params, sizes):
            left = own(params, sizes)
            return [left.pop(next(i for i, (k, _) in enumerate(left)
                                  if k == kind))
                    for kind in pattern or OTHER_PATTERN[ref.kinds(sizes)]]

        ref.layers_in_order = moved
        return ref

    common.load_file_module = load_other
    try:
        yield
    finally:
        common.load_file_module = load
