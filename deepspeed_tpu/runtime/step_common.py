"""Shared pieces of the explicit-collective (shard_map) train steps.

The wire-compressed 1-bit step (``onebit_engine.py``) and the
sparse-gradient step (``sparse_engine.py``) both compute per-rank LOCAL
gradients inside a manual region and exchange them explicitly; the local
loss cast and the gradient-accumulation scan are identical and live here so
the contract cannot drift between them. (The fused dense step in
``engine.py`` keeps its own richer copy: it additionally threads loss
scaling, MoQ, PLD, and compression.)
"""

import jax
import jax.numpy as jnp


def make_local_loss(engine):
    """Per-rank loss closure: cast params to the engine compute dtype and run
    the client loss_fn or the engine default loss."""
    loss_fn = engine.loss_fn
    compute_dtype = engine.compute_dtype

    def local_loss(params, batch, rng):
        with jax.named_scope("ds.param_cast"):
            half = jax.tree_util.tree_map(
                lambda p: p.astype(compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        if loss_fn is not None:
            loss, _ = loss_fn(half, batch, rng)
        else:
            loss, _ = engine._default_loss(half, batch, rng)
        return loss.astype(jnp.float32)

    return local_loss


def scale_local_loss(local_loss, lscale, fp16):
    """fp16 discipline shared by the explicit lanes (onebit / overlap):
    backward runs on the SCALED loss, and the scaled local grads unscale
    only after (or inside) the explicit exchange — the loss-scaler
    contract of ``fp16/loss_scaler.py`` kept identical across lanes."""
    if not fp16:
        return local_loss
    return lambda p, mb, r: local_loss(p, mb, r) * lscale


@jax.named_scope("ds.loss_and_grad")
def accumulate_local_grads(local_loss, params, batch, rng, gas):
    """(mean loss, mean grads) over ``gas`` microbatches of the LOCAL batch
    (leading dim ``gas``), via ``lax.scan`` — the in-jit GAS boundary
    (reference ``engine.py:1729,1889``)."""
    grad_fn = jax.value_and_grad(local_loss)
    if gas > 1:
        rngs = jax.random.split(rng, gas)

        def body(acc, xs):
            mb, r = xs
            loss, g = grad_fn(params, mb, r)
            acc_g, acc_l = acc
            return (jax.tree_util.tree_map(jnp.add, acc_g, g),
                    acc_l + loss), None

        zero_g = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (sum_g, sum_loss), _ = jax.lax.scan(
            body, (zero_g, jnp.float32(0.0)), (batch, rngs))
        return sum_loss / gas, jax.tree_util.tree_map(lambda g: g / gas, sum_g)
    squeezed = jax.tree_util.tree_map(lambda x: x[0], batch)
    return grad_fn(params, squeezed, rng)
