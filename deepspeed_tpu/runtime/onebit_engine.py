"""Wire-compressed 1-bit optimizer training steps (Adam / LAMB / 0-1 Adam).

Counterpart of the reference 1-bit optimizers' COMMUNICATION path
(``runtime/fp16/onebit/{adam.py:10, lamb.py:11, zoadam.py:10}`` +
``runtime/comm/nccl.py:51``). The error-compensated 1-bit
``compressed_allreduce`` — the collective that actually cuts wire volume
~32x — is SHARED across the three optimizers; what differs is the per-leaf
update around it:

- **OnebitAdam**: warmup = dense grad allreduce, variance adapts; after
  ``freeze_step`` the variance freezes and each rank's LOCAL momentum is
  exchanged compressed.
- **OnebitLamb**: same phases/collective, plus a per-layer clamped
  trust-ratio scale on the unraveled update (reference ``lamb.py`` lamb
  coefficients; clamped to the same [0.01, 10] window as the in-graph
  optax variant).
- **ZeroOneAdam**: no fixed warmup — the 1-bit collective carries the RAW
  local gradient (matching reference ``zoadam.py:214``); stability comes
  from the dense-refresh interval, which starts at 1 (every step dense) and
  DOUBLES every ``var_update_scaler`` refreshes, so early training is
  effectively dense and the compressed fraction of steps tends to 1. On a
  refresh step the averaged gradient updates both moments; other steps
  advance only the momentum.

Engine activation: ``optimizer.type`` one of ``OnebitAdam | OnebitLamb |
ZeroOneAdam`` with ``params.comm_backend_name: "compressed"``. Unlike the
optax 1-bit variants (``ops/onebit.py``, which keep the reference's
*semantics* inside XLA's implicit grad psum), this path makes the gradient
exchange EXPLICIT: the whole train step runs in a shard_map manual region
over the batch axes, so the compressed arrays are literally what crosses
the interconnect.

Restrictions (reference has the same shape): pure data parallelism —
ZeRO stage 0, no model/seq axes. Gradient accumulation composes (r3):
local grads accumulate over microbatches with no collectives in the scan,
then ONE compressed exchange per optimizer step. fp16 composes (r4): the
local loss is scaled before backward and the scaled grads are unscaled +
overflow-checked globally BEFORE any state (momentum, error feedback)
advances; an overflow step reverts everything and halves the scale.
"""

from typing import Any, NamedTuple

import jax

import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.compressed import (compressed_allreduce, pad_to_compressible,
                               plain_mean_allreduce)


class OneBitWireState(NamedTuple):
    """Flat-buffer optimizer state. ``worker_error``/``server_error`` are
    PER-RANK (sharded over the batch axes); everything else is replicated."""

    mu: jnp.ndarray            # [n_pad] momentum (replicated)
    nu: jnp.ndarray            # [n_pad] variance (replicated, frozen after warmup)
    worker_error: jnp.ndarray  # [world, n_pad] error feedback, sharded axis 0
    server_error: jnp.ndarray  # [world, chunk] error feedback, sharded axis 0
    var_interval: jnp.ndarray  # [] 0/1 Adam: steps between dense refreshes
    var_counter: jnp.ndarray   # [] 0/1 Adam: refreshes since last doubling


def _flatten_spec(params):
    flat, unravel = ravel_pytree(params)
    return flat.size, unravel


def build_onebit_wire(engine, opt_params: dict, kind: str = "onebitadam"):
    """Returns (initial_opt_state, opt_shardings, train_step_fn).

    ``train_step_fn(state, batch, rng) -> (state, loss, overflow)`` matches
    the engine's compiled-step contract. ``kind`` selects the per-leaf
    update: ``onebitadam`` | ``onebitlamb`` | ``zerooneadam``.
    """
    mesh = engine.mesh
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if shape.get("model", 1) != 1 or shape.get("seq", 1) != 1 or \
            shape.get("pipe", 1) != 1:
        raise ValueError("compressed 1-bit training is pure-DP: model/seq/"
                         "pipe mesh axes must be 1 (reference restriction)")
    if engine._config.zero_optimization_stage != 0:
        raise ValueError("compressed 1-bit training requires ZeRO stage 0 "
                         "(params replicated; the compressed quantity is the "
                         "full momentum)")
    # fp16 composes since r4: the local loss is scaled before backward, the
    # scaled local grads are unscaled + overflow-checked GLOBALLY before any
    # state (momentum, error feedback) advances — a skipped step must leave
    # the error-compensation buffers untouched or the compression would
    # absorb inf/nan into every later exchange
    fp16 = engine.fp16_enabled

    axes = tuple(a for a in ("data", "expert") if shape.get(a, 1) > 1) or ("data",)
    world = int(np.prod([shape.get(a, 1) for a in axes]))

    if kind not in ("onebitadam", "onebitlamb", "zerooneadam"):
        raise ValueError(f"unknown 1-bit optimizer kind {kind!r}")
    b1, b2 = map(float, opt_params.get("betas", (0.9, 0.999)))
    eps = float(opt_params.get("eps", 1e-8))
    # engine-built lr schedule wins over the raw config float
    lr = engine.lr_scheduler if engine.lr_scheduler is not None \
        else opt_params.get("lr", 1e-3)
    weight_decay = float(opt_params.get("weight_decay", 0.0))
    freeze_step = int(opt_params.get("freeze_step", 100000))
    var_freeze_step = int(opt_params.get("var_freeze_step") or freeze_step)
    var_update_scaler = int(opt_params.get("var_update_scaler", 16))

    params0 = engine.state.params
    n, unravel = _flatten_spec(params0)
    n_pad = pad_to_compressible(n, world)
    chunk = n_pad // world

    opt_state = OneBitWireState(
        mu=jnp.zeros((n_pad,), jnp.float32),
        nu=jnp.zeros((n_pad,), jnp.float32),
        worker_error=jnp.zeros((world, n_pad), jnp.float32),
        server_error=jnp.zeros((world, chunk), jnp.float32),
        var_interval=jnp.ones([], jnp.int32),
        var_counter=jnp.zeros([], jnp.int32))
    repl = NamedSharding(mesh, P())
    shard0 = NamedSharding(mesh, P(axes))
    opt_shardings = OneBitWireState(mu=repl, nu=repl, worker_error=shard0,
                                    server_error=shard0, var_interval=repl,
                                    var_counter=repl)

    axis_tuple = axes if len(axes) > 1 else axes[0]
    from .step_common import (accumulate_local_grads, make_local_loss,
                              scale_local_loss)

    local_loss = make_local_loss(engine)
    gas = engine.gradient_accumulation_steps

    def spmd(params, mu, nu, werr, serr, vint, vcnt, count, batch, rng,
             lscale):
        # per-rank: lose the leading sharded axis of the error buffers
        werr, serr = werr[0], serr[0]
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_tuple))
        # gas > 1: LOCAL grads accumulate over microbatches (no collectives
        # inside the scan), then ONE compressed exchange per optimizer step.
        # fp16: backward runs on the SCALED loss; grads unscale right here
        scaled_loss = scale_local_loss(local_loss, lscale, fp16)
        loss_local, g = accumulate_local_grads(scaled_loss, params, batch,
                                               rng, gas)
        if fp16:
            loss_local = loss_local / lscale
        loss = jax.lax.pmean(loss_local, axis_tuple)
        flat_g = jnp.pad(ravel_pytree(g)[0], (0, n_pad - n))
        if fp16:
            flat_g = flat_g / lscale
        # GLOBAL overflow verdict before any state advances — fp16 only:
        # bf16/fp32 keep the pre-r4 behavior (overflow never skips; a NaN
        # surfaces in the loss), matching the generic engine path
        if fp16:
            ov_local = (~jnp.isfinite(flat_g).all()).astype(jnp.int32)
            ov = jax.lax.psum(ov_local, axis_tuple) > 0
        else:
            ov = jnp.bool_(False)
        # monitoring: norm of the MEAN gradient (exact in warmup; in the
        # compression phase the mean is never materialized, so this reports
        # the norm of the averaged-by-psum local grads, which equals it)
        g_mean = jax.lax.pmean(flat_g, axis_tuple)
        grad_norm = jnp.sqrt(jnp.sum(g_mean * g_mean))

        t = count.astype(jnp.float32)
        lr_t = jnp.asarray(lr(count) if callable(lr) else lr, jnp.float32)
        flat_p = ravel_pytree(params)[0]
        flat_p_pad = jnp.pad(flat_p, (0, n_pad - n))

        if kind == "zerooneadam":
            # 0/1 Adam (zoadam.py pre-freeze phase): no fixed warmup —
            # instead the DENSE refresh interval starts at 1 (every step)
            # and DOUBLES every ``var_update_scaler`` refreshes, so early
            # training is effectively dense (stable) and the compressed
            # fraction of steps tends to 1. On a refresh step the averaged
            # gradient updates BOTH moments; on other steps the 1-bit
            # collective carries the raw local gradient and only the
            # momentum advances (variance held). The replicated state
            # (mu, nu, params) is only ever advanced by cross-rank-identical
            # values; the per-rank error feedback absorbs the quantization.
            refresh = (count % vint == 0) & (count <= var_freeze_step)

            def dense(_):
                g_avg = plain_mean_allreduce(flat_g, axis_tuple)
                return (b1 * mu + (1 - b1) * g_avg,
                        b2 * nu + (1 - b2) * g_avg * g_avg, werr, serr)

            def one_bit(_):
                g_hat, werr_c, serr_c = compressed_allreduce(
                    flat_g, werr, serr, axis_tuple)
                return b1 * mu + (1 - b1) * g_hat, nu, werr_c, serr_c

            mu2, nu2, werr2, serr2 = jax.lax.cond(refresh, dense, one_bit,
                                                  operand=None)
            upd = mu2 / (jnp.sqrt(nu2) + eps)  # no bias correction (zoadam)
            # exponential interval growth, reference zoadam.py:281-289
            vcnt2 = jnp.where(refresh, vcnt + 1, vcnt)
            double = refresh & (vcnt2 >= var_update_scaler)
            vint2 = jnp.where(double, vint * 2, vint)
            vcnt2 = jnp.where(double, 0, vcnt2)
        else:
            vint2, vcnt2 = vint, vcnt
            in_warmup = count <= freeze_step

            def warmup(_):
                g_avg = plain_mean_allreduce(flat_g, axis_tuple)
                mu_w = b1 * mu + (1 - b1) * g_avg
                nu_w = b2 * nu + (1 - b2) * g_avg * g_avg
                return mu_w, nu_w, werr, serr

            def compressed(_):
                mu_local = b1 * mu + (1 - b1) * flat_g
                mu_global, werr_c, serr_c = compressed_allreduce(
                    mu_local, werr, serr, axis_tuple)
                return mu_global, nu, werr_c, serr_c

            mu2, nu2, werr2, serr2 = jax.lax.cond(
                in_warmup, warmup, compressed, operand=None)
            # bias-corrected Adam step on the flat buffer (variance
            # correction freezes with the variance, reference onebit/adam.py)
            bc1 = 1.0 - b1 ** t
            bc2 = 1.0 - b2 ** jnp.minimum(t, float(freeze_step))
            upd = mu2 / bc1 / (jnp.sqrt(nu2 / bc2) + eps)

        direction = upd + weight_decay * flat_p_pad
        if kind == "onebitlamb":
            # per-leaf clamped trust ratio (reference lamb.py lamb
            # coefficients; same [0.01, 10] clamp as the optax variant)
            d_tree = unravel(direction[:n])
            p_tree = unravel(flat_p)

            def trust(d, p):
                p_norm = jnp.linalg.norm(p.astype(jnp.float32))
                d_norm = jnp.linalg.norm(d.astype(jnp.float32))
                ratio = jnp.where((p_norm > 0) & (d_norm > 0),
                                  p_norm / d_norm, 1.0)
                return d * jnp.clip(ratio, 0.01, 10.0)

            scaled = jax.tree_util.tree_map(trust, d_tree, p_tree)
            direction = jnp.pad(ravel_pytree(scaled)[0], (0, n_pad - n))
        new_flat = flat_p_pad - lr_t * direction
        new_params = unravel(new_flat[:n])
        # overflow: EVERY piece of advanced state reverts (params, both
        # moments, the error-feedback buffers, the 0/1-Adam interval) — a
        # jnp.where select, so the discarded NaN-laden values never land
        old_new = [(params, new_params), (mu, mu2), (nu, nu2),
                   (werr, werr2), (serr, serr2), (vint, vint2),
                   (vcnt, vcnt2)]
        kept = [jax.tree_util.tree_map(
            lambda o, nw: jnp.where(ov, o, nw), o, nw) for o, nw in old_new]
        new_params, mu2, nu2, werr2, serr2, vint2, vcnt2 = kept
        return (new_params, mu2, nu2, werr2[None], serr2[None], vint2, vcnt2,
                loss, grad_norm, ov)

    def train_step(state, batch, rng):
        count = state.step + 1
        mu, nu, werr, serr, vint, vcnt = state.opt_state
        ls = state.loss_scale
        lscale = ls.cur_scale if (fp16 and ls is not None) \
            else jnp.float32(1.0)
        fn = jax.shard_map(
            spmd, mesh=mesh, axis_names=frozenset(axes),
            in_specs=(P(), P(), P(), P(axes), P(axes), P(), P(), P(),
                      P(None, axes), P(), P()),
            out_specs=(P(), P(), P(), P(axes), P(axes), P(), P(), P(), P(),
                       P()),
            check_vma=False)
        (new_params, mu2, nu2, werr2, serr2, vint2, vcnt2, loss,
         grad_norm, ov) = fn(state.params, mu, nu, werr, serr, vint, vcnt,
                             count, batch, rng, lscale)
        new_ls = ls
        if fp16 and ls is not None:
            from .fp16.loss_scaler import update_scale

            new_ls = update_scale(ls, ov)
        new_state = state.replace(
            step=jnp.where(ov, state.step, count), params=new_params,
            opt_state=OneBitWireState(mu2, nu2, werr2, serr2, vint2, vcnt2),
            loss_scale=new_ls,
            skipped_steps=state.skipped_steps + ov.astype(jnp.int32))
        return new_state, (loss, grad_norm, {}), ov

    return opt_state, opt_shardings, train_step
