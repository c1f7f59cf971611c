"""Explicit sparse-gradient data-parallel train step.

Counterpart of the reference's sparse-gradient path: the engine registers
``torch.nn.Embedding`` modules when ``sparse_gradients`` is on
(``deepspeed/runtime/engine.py:333-337``, ``sparse_tensor_module_names``)
and routes their gradients through the allgather-based
``sparse_allreduce_no_retain`` (``engine.py:2286``) instead of the dense
allreduce, cutting DP gradient traffic from O(vocab x hidden) to
O(tokens x hidden).

TPU-native form: like the wire-compressed 1-bit path
(``runtime/onebit_engine.py``), the whole train step runs in a ``shard_map``
manual region over the batch axes so the gradient exchange is EXPLICIT:
embedding-table gradients are compressed to row slices
(``SparseTensor.from_dense_bounded``) and allgathered; every other leaf is
``pmean``-ed. The optimizer then updates replicated state exactly as the
fused step does.

Safety contract: a sparse-eligible leaf whose touched-row count exceeds the
token capacity (the classic case: a TIED embedding whose gradient is dense
because the vocab projection also writes it) cannot be represented in the
static-capacity slices. torch fails loudly on that sparse+dense autograd
mix; here the step reports it as an overflow and SKIPS the update
(``engine.skipped_steps`` counts it), never silently truncating gradients.

Restrictions (the reference's sparse path has the same shape): pure data
parallelism — no model/seq/pipe axes, ZeRO stage 0, bf16/fp32 (no fp16 loss
scaling), and none of MoQ / PLD / compression-training.
"""

import jax

import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.compressed import plain_mean_allreduce
from .sparse_tensor import SparseTensor, sparse_all_reduce
from .step_common import accumulate_local_grads, make_local_loss


def find_sparse_leaves(params) -> set:
    """Paths of embedding-table leaves, by the flax ``nn.Embed`` convention
    (param named ``embedding``, 2-D). Reference: ``_configure_distributed_
    model`` registers ``nn.Embedding`` module names (``engine.py:333-337``).
    """
    names = set()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in kp]
        if keys and keys[-1] == "embedding" and getattr(leaf, "ndim", 0) == 2:
            names.add("/".join(keys))
    return names


def probe_dense_sparse_leaves(engine, sparse_names: set) -> set:
    """One real gradient evaluation on the engine's example batch; returns the
    sparse-eligible leaves whose gradient is DENSE (touches more rows than the
    batch has tokens) — the tied-embedding / vocab-projection case.

    Such a leaf can never fit the static token-capacity row slices, so every
    runtime step would overflow and be skipped: training silently stalls. The
    reference's torch path fails loudly on the sparse+dense autograd mix
    (sparse embedding grads cannot be added to the dense matmul grad); this
    probe is the static-shape equivalent — detect at init, exclude the leaf
    from the sparse set (it takes the dense pmean path), and warn.
    """
    if not sparse_names or engine.example_batch is None:
        return set()
    from ..utils.logging import log_dist

    local_loss = make_local_loss(engine)
    batch = {k: jnp.asarray(v) for k, v in engine.example_batch.items()}
    tokens = max([int(np.prod(x.shape))
                  for x in jax.tree_util.tree_leaves(batch)
                  if jnp.issubdtype(x.dtype, jnp.integer)] or [0])
    if tokens == 0:
        return set()
    rng = jax.random.PRNGKey(0)
    grads = jax.grad(lambda p: local_loss(p, batch, rng))(engine.state.params)
    dense = set()
    for kp, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        if path not in sparse_names or tokens >= g.shape[0]:
            continue
        touched = int(jnp.sum(jnp.any(g != 0, axis=tuple(range(1, g.ndim)))))
        if touched > tokens:
            dense.add(path)
    if dense:
        log_dist(f"sparse_gradients: excluding dense-writing embedding leaves "
                 f"{sorted(dense)} (tied embedding / vocab projection — their "
                 f"gradient touches every row; they take the dense allreduce "
                 f"path instead)", ranks=[0])
    return dense


def build_sparse_dp_step(engine):
    """Returns (sparse_leaf_names, train_step_fn) with the engine's compiled
    step contract: ``train_step(state, batch, rng) -> (state, (loss,
    grad_norm, named scalars), overflow)``."""
    mesh = engine.mesh
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if shape.get("model", 1) != 1 or shape.get("seq", 1) != 1 or \
            shape.get("pipe", 1) != 1 or shape.get("expert", 1) != 1:
        raise ValueError("sparse_gradients is a pure-DP path: model/seq/pipe/"
                         "expert mesh axes must be 1 (reference restriction: "
                         "sparse allreduce runs over the dp group only; "
                         "expert-sharded params would break the replicated-"
                         "param pmean exchange)")
    if engine._config.zero_optimization_stage != 0:
        raise ValueError("sparse_gradients requires ZeRO stage 0 (the "
                         "reference's ZeRO optimizers reject sparse grads)")
    if engine.fp16_enabled:
        raise ValueError("sparse_gradients supports bf16/fp32 (fp16 loss "
                         "scaling not composed with the explicit-DP step)")
    if engine._moq is not None or engine._pld is not None or \
            engine._compression is not None:
        raise ValueError("sparse_gradients does not compose with "
                         "quantize_training, progressive_layer_drop, or "
                         "compression_training")

    axes = ("data",)
    axis_tuple = axes[0]

    sparse_names = find_sparse_leaves(engine.state.params)
    sparse_names -= probe_dense_sparse_leaves(engine, sparse_names)
    optimizer = engine.optimizer
    gas = engine.gradient_accumulation_steps
    local_loss = make_local_loss(engine)

    def leaf_path(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)

    def spmd(params, opt_state, batch, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_tuple))
        loss_local, grads = accumulate_local_grads(local_loss, params, batch,
                                                   rng, gas)
        loss = jax.lax.pmean(loss_local, axis_tuple)

        # touched-row bound: the embedding VJP writes at most one row per
        # token, and tokens are the integer fields of the (local) batch
        tokens = max([int(np.prod(x.shape))
                      for x in jax.tree_util.tree_leaves(batch)
                      if jnp.issubdtype(x.dtype, jnp.integer)] or [0])

        overflow = jnp.bool_(False)
        combined = []
        for kp, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            if leaf_path(kp) in sparse_names and 0 < tokens < g.shape[0]:
                st, count = SparseTensor.from_dense_bounded(g, capacity=tokens)
                overflow = jnp.logical_or(overflow, count > tokens)
                combined.append(sparse_all_reduce(st, axis_tuple).to_dense())
            else:
                combined.append(plain_mean_allreduce(g, axis_tuple))
        grads = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(grads), combined)
        # count (hence overflow) is data-dependent per shard: reduce it so
        # every device takes the same keep/skip branch and replicated state
        # cannot physically diverge
        overflow = jax.lax.pmax(overflow.astype(jnp.int32), axis_tuple) > 0

        import optax as _optax

        grad_norm = _optax.global_norm(grads)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), params, updates)

        # capacity overflow => the sparse slices truncated a dense gradient:
        # skip the update rather than apply a wrong one (fp16-overflow-skip
        # contract, reference _take_model_step engine.py:1889)
        keep = lambda new, old: jax.tree_util.tree_map(
            lambda n, o: jnp.where(overflow, o, n), new, old)
        return (keep(new_params, params), keep(new_opt, opt_state), loss,
                grad_norm, overflow)

    batch_spec = P(None, axes)

    def train_step(state, batch, rng):
        fn = jax.shard_map(
            spmd, mesh=mesh, axis_names=frozenset(axes),
            in_specs=(P(), P(), batch_spec, P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)
        new_params, new_opt, loss, grad_norm, overflow = fn(
            state.params, state.opt_state, batch, rng)
        new_state = state.replace(
            step=state.step + jnp.where(overflow, 0, 1),
            params=new_params, opt_state=new_opt,
            skipped_steps=state.skipped_steps + jnp.where(overflow, 1, 0))
        return new_state, (loss, grad_norm, {}), overflow

    return sparse_names, train_step
