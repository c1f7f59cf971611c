"""Fused Adam/AdamW optimizer update (Pallas TPU kernel).

Counterpart of the reference's multi-tensor CUDA Adam
(``csrc/adam/multi_tensor_adam.cu:17`` ``multi_tensor_adam``, fronted by
``deepspeed/ops/adam/fused_adam.py:15``): one kernel pass per flat buffer
that reads (param, grad, m, v) and writes (update, m, v) — the whole Adam
chain (moment updates, bias correction, decoupled weight decay) runs in VMEM
so every HBM byte of optimizer state moves exactly once per step.

The reference needs multi-tensor-apply to amortize kernel-launch overhead
across thousands of small tensors; under jit the whole train step is one
"launch", so this kernel's job is purely memory-locality: a single
grid-of-blocks sweep per leaf instead of whatever loop structure XLA picks
for the optax chain. Exposed as an optax ``GradientTransformation``
(``scale_by_fused_adam``) so it drops into the engine's optimizer registry.

On non-TPU backends the public entry falls back to identical jnp math (tests
compare the kernel in interpret mode against optax.adamw).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import FUSED_ADAM

# Each grid step processes one (8, 1024) fp32 tile per operand: 4 inputs +
# 3 outputs x 32KB = 224KB of VMEM, far under budget, and the last dim is a
# lane multiple (128) so Mosaic tiles it without relayout.
_BLOCK = 8 * 1024


def _adam_kernel(alpha_ref, p_ref, g_ref, m_ref, v_ref, u_ref, mo_ref, vo_ref, *,
                 b1: float, b2: float, eps: float, weight_decay: float,
                 adam_w_mode: bool):
    # alpha = [lr/(1-b1^t), lr, 1/sqrt(1-b2^t)] — eps is added AFTER the
    # bias-corrected sqrt, matching optax.adamw and the reference kernel
    # (multi_tensor_adam.cu: denom = sqrt(v/beta2_correction) + eps)
    step_size, lr_t, inv_bc2 = alpha_ref[0], alpha_ref[1], alpha_ref[2]
    p = p_ref[:]
    g = g_ref[:]
    if not adam_w_mode and weight_decay:
        # classic Adam: L2 folded into the gradient (reference multi_tensor_adam
        # ADAM_MODE 1)
        g = g + weight_decay * p
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * (g * g)
    u = -step_size * (m / (jnp.sqrt(v) * inv_bc2 + eps))
    if adam_w_mode and weight_decay:
        # AdamW: decoupled decay, scaled by the UNcorrected lr
        u = u - lr_t * weight_decay * p
    u_ref[:] = u
    mo_ref[:] = m
    vo_ref[:] = v


def _run_leaf(p, g, m, v, alpha, b1, b2, eps, weight_decay, adam_w_mode, interpret):
    """One leaf: ravel → pad → grid sweep → unravel. Returns (u, m, v)."""
    shape, dtype = p.shape, p.dtype
    n = p.size
    flat = lambda x: x.astype(jnp.float32).ravel()
    p_, g_, m_, v_ = flat(p), flat(g), flat(m), flat(v)
    pad = (-n) % _BLOCK
    if pad:
        pad1 = lambda x: jnp.pad(x, (0, pad))
        p_, g_, m_, v_ = pad1(p_), pad1(g_), pad1(m_), pad1(v_)
    rows = (n + pad) // 1024
    to2d = lambda x: x.reshape(rows, 1024)
    p_, g_, m_, v_ = to2d(p_), to2d(g_), to2d(m_), to2d(v_)
    nb = rows // 8

    spec = pl.BlockSpec((8, 1024), lambda i: (i, 0))
    u, mo, vo = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps,
                          weight_decay=weight_decay, adam_w_mode=adam_w_mode),
        grid=(nb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * 4,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((rows, 1024), jnp.float32)] * 3,
        interpret=interpret,
        name=FUSED_ADAM,
    )(alpha, p_, g_, m_, v_)
    unflat = lambda x: x.ravel()[:n].reshape(shape).astype(dtype)
    return unflat(u), unflat(mo), unflat(vo)


def _reference_leaf(p, g, m, v, alpha, b1, b2, eps, weight_decay, adam_w_mode):
    """jnp fallback with identical math (non-TPU backends)."""
    p32, g32 = p.astype(jnp.float32), g.astype(jnp.float32)
    if not adam_w_mode and weight_decay:
        g32 = g32 + weight_decay * p32
    m = b1 * m + (1.0 - b1) * g32
    v = b2 * v + (1.0 - b2) * (g32 * g32)
    u = -alpha[0] * (m / (jnp.sqrt(v) * alpha[2] + eps))
    if adam_w_mode and weight_decay:
        u = u - alpha[1] * weight_decay * p32
    return u.astype(p.dtype), m, v


class FusedAdamState(NamedTuple):
    count: jnp.ndarray
    mu: optax.Updates
    nu: optax.Updates


def scale_by_fused_adam(lr=1e-3, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, weight_decay: float = 0.0,
                        adam_w_mode: bool = True,
                        interpret: Optional[bool] = None
                        ) -> optax.GradientTransformation:
    """optax transformation backed by the Pallas kernel.

    Produces the COMPLETE update (lr, bias correction, and weight decay
    included) — use it terminally, like ``optax.adamw``. ``lr`` may be a
    schedule (step -> lr).
    """

    def init_fn(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return FusedAdamState(count=jnp.zeros([], jnp.int32),
                              mu=jax.tree_util.tree_map(zeros, params),
                              nu=jax.tree_util.tree_map(zeros, params))

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("fused adam requires params")
        count = state.count + 1
        t = count.astype(jnp.float32)
        # schedules see the PRE-increment count (optax.scale_by_schedule
        # convention); bias correction uses the post-increment step
        lr_t = jnp.asarray(lr(state.count) if callable(lr) else lr, jnp.float32)
        step_size = lr_t / (1.0 - b1 ** t)
        inv_bc2 = 1.0 / jnp.sqrt(1.0 - b2 ** t)
        alpha = jnp.stack([step_size, lr_t, inv_bc2])

        use_interpret = interpret
        if use_interpret is None and jax.default_backend() != "tpu":
            leaf = functools.partial(_reference_leaf, b1=b1, b2=b2, eps=eps,
                                     weight_decay=weight_decay,
                                     adam_w_mode=adam_w_mode)
            out = jax.tree_util.tree_map(
                lambda p, g, m, v: leaf(p, g, m, v, alpha),
                params, updates, state.mu, state.nu)
        else:
            leaf = functools.partial(_run_leaf, b1=b1, b2=b2, eps=eps,
                                     weight_decay=weight_decay,
                                     adam_w_mode=adam_w_mode,
                                     interpret=bool(use_interpret))
            out = jax.tree_util.tree_map(
                lambda p, g, m, v: leaf(p, g, m, v, alpha),
                params, updates, state.mu, state.nu)
        is_triple = lambda x: isinstance(x, tuple) and len(x) == 3
        u = jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=is_triple)
        mu = jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=is_triple)
        nu = jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=is_triple)
        return u, FusedAdamState(count=count, mu=mu, nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def scale_by_fused_lamb(lr=1e-3, b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, weight_decay: float = 0.0,
                        min_coeff: float = 0.01, max_coeff: float = 10.0,
                        interpret: Optional[bool] = None
                        ) -> optax.GradientTransformation:
    """LAMB on the fused kernel (reference
    ``csrc/lamb/fused_lamb_cuda_kernel.cu:474``): the Adam direction comes
    from the single-sweep Pallas kernel; the per-tensor trust ratio (a pair
    of norms) is a cheap XLA reduction on top — the HBM-bound elementwise
    sweep stays fused, which is where the CUDA kernel spent its effort too."""
    inner = scale_by_fused_adam(lr=1.0, b1=b1, b2=b2, eps=eps,
                                weight_decay=0.0, adam_w_mode=True,
                                interpret=interpret)

    def init_fn(params):
        return inner.init(params)

    def update_fn(updates, state, params=None):
        if params is None:
            raise ValueError("fused lamb requires params")
        u, new_state = inner.update(updates, state, params)
        lr_t = jnp.asarray(lr(state.count) if callable(lr) else lr, jnp.float32)

        def leaf(u_, p):
            # inner produced -adam_dir (lr=1); LAMB direction adds decay
            direction = -u_.astype(jnp.float32) + \
                weight_decay * p.astype(jnp.float32)
            p_norm = jnp.linalg.norm(p.astype(jnp.float32))
            d_norm = jnp.linalg.norm(direction)
            ratio = jnp.where((p_norm > 0) & (d_norm > 0),
                              p_norm / jnp.maximum(d_norm, 1e-12), 1.0)
            ratio = jnp.clip(ratio, min_coeff, max_coeff)
            return (-lr_t * ratio * direction).astype(p.dtype)

        out = jax.tree_util.tree_map(leaf, u, params)
        return out, new_state

    return optax.GradientTransformation(init_fn, update_fn)
