"""Deliberately wrong computations of ``qwen3-next-80b-a3b.train.8k``'s model,
each one thing of the layers as ISSUE 52 wrote them down left out or replaced,
for the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/qwen3_next.py`` and ``mixtral.py`` (every parameter
still exists, so the reference reads the same tree), and the plain reference
itself computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). Top-1 routing is the harness's own
``--control top1_routing``. Used by the CPU tests at the tiny size and by the
builder's chip script at the published widths (PERF.md section 6)."""

import contextlib

import jax.numpy as jnp

import deepspeed_tpu.models.mixtral as mixtral
import deepspeed_tpu.models.qwen3_next as qwen3_next
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _correction_left_out(m):
    """``d_t = beta_t v_t``: plain (decayed) linear attention. The solve's
    right-hand side is ``[beta V | beta exp(gamma) K]``: the first half
    passes as it is (no ``(I + A)^-1``), the second, which carries what the
    state already predicts for the key, is zero."""
    assert m.Qwen3NextConfig.linear_key_head_dim == \
        m.Qwen3NextConfig.linear_value_head_dim

    def solve(a, rhs):
        half = rhs.shape[-1] // 2
        return jnp.concatenate(
            [rhs[..., :half], jnp.zeros_like(rhs[..., half:])], axis=-1)
    return {"_unit_lower_solve": solve}


def _weights_over_held_only(m):
    """The chosen experts' weights renormalised over the HELD ones among
    them, where the published sum is over all ten."""
    real = m._expert_mlp

    def expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx):
        held = (topk_idx >= cfg.first_expert) & (
            topk_idx < cfg.first_expert + cfg.num_local_experts)
        over = jnp.sum(jnp.where(held, topk_w, 0.0), -1, keepdims=True)
        return real(cfg, x, w1, w2, w3,
                    topk_w / jnp.where(over > 0, over, 1.0), topk_idx)
    return {"_expert_mlp": expert_mlp}


def _all_columns_rotated(cls):
    return {"rotary_dim": property(lambda cfg: cfg.head_dim)}


#: name -> [(module or class, patches of it ({attribute: replacement}))]
WRONG = {
    "beta_taken_as_one": [(qwen3_next, lambda m: {
        "_beta": lambda b: jnp.ones_like(b)})],
    "decay_left_out": [(qwen3_next, lambda m: {
        "_log_decay": lambda a_log, a, dt_bias: jnp.zeros_like(a)})],
    "correction_left_out": [(qwen3_next, _correction_left_out)],
    "qk_unit_length_left_out": [(qwen3_next, lambda m: {
        "_unit_length": lambda x: x.astype(jnp.float32)})],
    "attention_gate_left_out": [(qwen3_next, lambda m: {
        "_attn_gate": lambda gate: jnp.ones_like(gate)})],
    "shared_gate_left_out": [(qwen3_next, lambda m: {
        "_shared_gate": lambda logit: jnp.ones_like(logit)})],
    "all_columns_rotated": [(qwen3_next.Qwen3NextConfig,
                             _all_columns_rotated)],
    "weights_over_held_only": [(mixtral, _weights_over_held_only)],
    "conv_silu_left_out": [(qwen3_next, lambda m: {
        "_conv_act": lambda x: x})],
}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = [(module, k, v) for module, make in WRONG[name]
               for k, v in make(module).items()]
    saved = [(module, k, module.__dict__[k]) for module, k, _ in patches]
    try:
        for module, k, v in patches:
            setattr(module, k, v)
        yield
    finally:
        for module, k, v in saved:
            setattr(module, k, v)
