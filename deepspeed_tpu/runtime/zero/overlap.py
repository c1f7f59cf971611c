"""Bucketed grad reduce-scatter overlap + data-axis sharded optimizer step.

The explicit backward-communication lane (``zero_optimization.
overlap_grad_sync: true``). Two levers, composed in one compiled
``train_step``:

**Overlap** (T3, arxiv 2401.16677): instead of the fused step's single
post-backward grad all-reduce, grad leaves coalesce into size-bucketed
per-layer reduce-scatters (flush at ``reduce_bucket_size`` bytes) issued
*inside* the backward pass through a ``custom_vjp`` identity wrapper on
the params at the loss root. Each bucket is a ``reduce_scatter_start`` /
``reduce_scatter_done`` pair through the traced verbs in ``comm/comm.py``
— after jaxpr inlining every bucket's start depends only on its own
leaves' cotangents, so XLA's latency-hiding scheduler hoists the
collective under the remaining backward compute. The flight recorder
sees both edges of every pair (span args carry ``tag: grad_bucket<i>``).

**Resharded update** (ZeRO-1, arxiv 2004.13336): with ``stage >= 1`` the
optimizer state and the optax update are sharded over the data axis in
the *flat* param space — rank ``r`` owns row ``r`` of every leaf's
``[world, c_i]`` padded view (``partition.zero1_chunk_sizes``), updates
its ``1/dp`` share, and the updated param chunks all-gather back
(``param_bucket<i>`` start/done pairs) inside the same program. Grad
accumulation scatters once per boundary (the sync moves after the
microbatch scan); fp16 loss scaling and global-norm clipping ride the
scattered shards via ONE tiny all-gather of a ``[3]`` vector (loss,
sum-of-squares, nonfinite count) reduced in a fixed order.

Bucket composition is DATA, not program structure that the outside can
see: the interleaved chunk layout is a pure function of (leaf shapes,
world), so changing ``reduce_bucket_size`` regroups the collectives but
never changes which elements a rank owns, the step's input/output
shardings, or the recompile sentinel's fingerprint — and (reduction
grouping invariance of the tiled reduce-scatter) never changes a single
bit of the result.

Parity contract (the tier-1 bar): for a fixed (zero stage, gas,
precision) config, every lane variant — overlap on/off, any
``reduce_bucket_size`` — is BITWISE identical over N steps. The design
that makes this hold on XLA (which freely re-fuses and re-associates
*compute* per program — FMA contraction, reciprocal rewrites, reduction
tiling all change with fusion context, even for "elementwise" chains):

- the variants differ ONLY in collectives and pure data movement.
  Collectives are bitwise grouping-invariant (a tiled reduce-scatter
  split by columns equals the whole-buffer one — verified on the
  8-device CPU mesh), and slicing/concat/reshape are exact;
- ALL arithmetic — unscale, global norm, clip, the optimizer update —
  lives in one canonical *flat pipeline* over the materialized
  ``[C_total]`` grad row, fenced by ``lax.optimization_barrier`` on
  both sides so its HLO (and therefore XLA's fusion/rewrite choices)
  is identical in every variant;
- cross-rank scalar reductions (loss mean, grad-norm sq-sum, overflow
  count) go through ONE tiny all-gather + fixed left-to-right add
  chain, never ``psum``/``pmean`` (whose emitted reduction order is
  program-dependent).
"""

from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm import comm as dist
from .partition import zero1_chunk_sizes, zero1_state_shardings

#: optimizers whose update is elementwise over the flat param space —
#: the eligibility set for the sharded (chunked) update. FusedLamb's
#: per-leaf trust ratio needs whole leaves; the 1-bit family owns its
#: own explicit lane.
ELEMENTWISE_OPTIMIZERS = ("adam", "adamw", "adagrad")


class GradBucketPlan(NamedTuple):
    """Size-bucketing policy over the param leaves, in treedef order.

    ``buckets`` partitions ``range(n_leaves)`` into runs; leaf ``i``
    contributes a ``[world, chunks[i]]`` padded view to its bucket's
    ``[world, sum(chunks)]`` buffer (row ``k`` = rank ``k``'s chunks,
    concatenated). The per-rank element ownership depends only on
    ``(sizes, world)`` — never on the bucket grouping.
    """

    sizes: Tuple[int, ...]    # true leaf sizes
    padded: Tuple[int, ...]   # ceil(size/world)*world
    chunks: Tuple[int, ...]   # padded/world — the per-rank share
    buckets: Tuple[Tuple[int, ...], ...]
    world: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def bucket_bytes(self, b: int) -> int:
        return sum(self.padded[i] for i in self.buckets[b]) * 4

    def bucket_cols(self, b: int) -> Tuple[int, int]:
        """Column range ``[start, stop)`` of bucket ``b`` in the flat
        per-rank ``[C_total]`` row (buckets are contiguous leaf runs)."""
        start = sum(self.chunks[i] for i in range(self.buckets[b][0]))
        stop = start + sum(self.chunks[i] for i in self.buckets[b])
        return start, stop


def plan_grad_buckets(params_shapes: Any, world: int,
                      bucket_bytes: int) -> GradBucketPlan:
    """Greedy coalescing in leaf order: a bucket flushes once it holds
    ``bucket_bytes`` of fp32 grads (a single oversized leaf gets its own
    bucket; ``bucket_bytes <= 0`` degenerates to one bucket per leaf)."""
    sizes, padded, chunks = zero1_chunk_sizes(params_shapes, world)
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, p in enumerate(padded):
        if cur and cur_bytes >= max(bucket_bytes, 0):
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += p * 4
    if cur:
        buckets.append(tuple(cur))
    return GradBucketPlan(sizes=sizes, padded=padded, chunks=chunks,
                          buckets=tuple(buckets), world=world)


# ---------------------------------------------------------------------------
# flat pack / unpack (layout: [world, C] — row k is rank k's chunks)
# ---------------------------------------------------------------------------


def _pack(plan: GradBucketPlan, leaves, idxs):
    cols = []
    for i in idxs:
        flat = jnp.ravel(leaves[i]).astype(jnp.float32)
        if plan.padded[i] != plan.sizes[i]:
            flat = jnp.pad(flat, (0, plan.padded[i] - plan.sizes[i]))
        cols.append(flat.reshape(plan.world, plan.chunks[i]))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def _unpack(plan: GradBucketPlan, buf, idxs, like):
    """[world, C_b] bucket buffer -> {leaf index: full leaf}."""
    out = {}
    off = 0
    for i in idxs:
        c = plan.chunks[i]
        flat = buf[:, off:off + c].reshape(plan.padded[i])[:plan.sizes[i]]
        out[i] = flat.reshape(like[i].shape).astype(like[i].dtype)
        off += c
    return out


def _row_chunks(plan: GradBucketPlan, row, idxs):
    """[C_b] rank-row -> {leaf index: [c_i] chunk}."""
    out = {}
    off = 0
    for i in idxs:
        out[i] = row[off:off + plan.chunks[i]]
        off += plan.chunks[i]
    return out


def _leaf_chunk(plan: GradBucketPlan, leaf, i, r):
    """Rank ``r``'s [c_i] chunk of a full leaf."""
    flat = jnp.ravel(leaf).astype(jnp.float32)
    if plan.padded[i] != plan.sizes[i]:
        flat = jnp.pad(flat, (0, plan.padded[i] - plan.sizes[i]))
    rows = flat.reshape(plan.world, plan.chunks[i])
    return lax.dynamic_slice_in_dim(rows, r, 1, 0)[0]


def _embed_chunk(plan: GradBucketPlan, chunk, i, r, like):
    """Inverse of ``_leaf_chunk`` into a zeros leaf: the cotangent a
    sharded-update backward hands the autodiff machinery (full leaf
    shape, only the rank's row populated — the update re-slices it)."""
    rows = lax.dynamic_update_slice(
        jnp.zeros((plan.world, plan.chunks[i]), jnp.float32),
        chunk[None, :], (r, 0))
    flat = rows.reshape(plan.padded[i])[:plan.sizes[i]]
    return flat.reshape(like.shape).astype(like.dtype)


# ---------------------------------------------------------------------------
# the grad exchange (bucketed async pairs, or the monolithic kill-switch)
# ---------------------------------------------------------------------------


@jax.named_scope("ds.grad_sync")
def _exchange_flat(plan: GradBucketPlan, g_leaves, axis_tuple,
                   overlap: bool, tag: str = "grad_bucket"):
    """Sum-reduce the local grad leaves across ranks and return the
    rank's RAW (undivided) flat ``[C_total]`` shard row.

    ``overlap=True``: one reduce-scatter start/done pair per bucket.
    ``overlap=False``: the ``overlap_comm: false`` kill-switch — ONE
    monolithic synchronous reduce-scatter (the scatter phase of an
    all-reduce), no async pairs. The tiled reduce-scatter is invariant
    under column grouping, so the two are bitwise interchangeable;
    lowering through ``psum`` instead is NOT (XLA's all-reduce emitter
    associates the reduction differently per program at 1 ulp).
    """
    if overlap:
        handles = []
        for b, idxs in enumerate(plan.buckets):
            buf = _pack(plan, g_leaves, idxs)
            handles.append(dist.reduce_scatter_start(
                buf, group=axis_tuple, tag=f"{tag}{b}"))
        rows = [dist.reduce_scatter_done(h)[0] for h in handles]  # [C_b]
        return jnp.concatenate(rows) if len(rows) > 1 else rows[0]
    buf = _pack(plan, g_leaves, tuple(range(len(g_leaves))))
    return dist.reduce_scatter(buf, group=axis_tuple)[0]  # [C_total]


def _gather_flat(plan: GradBucketPlan, flat_row, axis_tuple,
                 overlap: bool, like_leaves, tag: str):
    """All-gather a flat per-rank ``[C_total]`` row back into full
    leaves (bucketed start/done pairs, or one monolithic gather)."""
    n = len(like_leaves)
    out: List[Any] = [None] * n
    # trace scope: gathering grads back is the second half of the grad
    # sync; gathering updated shards is the ZeRO param all-gather
    scope = "ds.param_gather" if tag == "param_bucket" else "ds.grad_sync"
    with jax.named_scope(scope):
        if overlap:
            handles = []
            for b in range(plan.num_buckets):
                a, z = plan.bucket_cols(b)
                handles.append(dist.all_gather_start(
                    flat_row[a:z][None], group=axis_tuple, axis=0, tiled=True,
                    tag=f"{tag}{b}"))
            for b, idxs in enumerate(plan.buckets):
                buf = dist.all_gather_done(handles[b])  # [world, C_b]
                for i, leaf in _unpack(plan, buf, idxs, like_leaves).items():
                    out[i] = leaf
        else:
            buf = dist.all_gather(flat_row[None], group=axis_tuple, axis=0,
                                  tiled=True)
            for i, leaf in _unpack(plan, buf, tuple(range(n)),
                                   like_leaves).items():
                out[i] = leaf
    return out


def make_overlap_grad_sync(plan: GradBucketPlan, axis_tuple,
                           overlap: bool, want_full: bool):
    """The ``custom_vjp`` identity wrapper on the params at the loss root.

    Forward is the identity; backward intercepts the raw per-rank
    cotangents and runs the bucketed exchange IN the backward pass, so
    each bucket's reduce-scatter can overlap the rest of the backward
    compute. ``want_full=True`` (unsharded update) returns the fully
    synced mean grads; otherwise the cotangent carries the rank's RAW
    sum-reduced chunks embedded at their flat offsets
    (``_embed_chunk``) — the canonical flat pipeline in the step body
    re-slices them and owns every arithmetic op (unscale/norm/clip).
    """

    @jax.custom_vjp
    def overlap_grad_sync(params, lscale):
        return params

    def _fwd(params, lscale):
        return params, lscale

    def _bwd(lscale, ct):
        leaves, treedef = jax.tree_util.tree_flatten(ct)
        flat_row = _exchange_flat(plan, leaves, axis_tuple, overlap)
        if want_full:
            flat_row = flat_row / plan.world / lscale
            out = _gather_flat(plan, flat_row, axis_tuple, overlap,
                               leaves, tag="grad_bucket")
        else:
            r = lax.axis_index(axis_tuple)
            chunks = _row_chunks(plan, flat_row, tuple(range(len(leaves))))
            out = [_embed_chunk(plan, chunks[i], i, r, leaves[i])
                   for i in range(len(leaves))]
        return jax.tree_util.tree_unflatten(treedef, out), \
            jnp.zeros_like(lscale)

    overlap_grad_sync.defvjp(_fwd, _bwd)
    return overlap_grad_sync


# ---------------------------------------------------------------------------
# the lane builder (the engine's dispatch target)
# ---------------------------------------------------------------------------


def _build_raw_tx(engine):
    """The lane's optax transform WITHOUT the engine's clip chain — the
    lane clips manually from the scattered shards (one psum), so the tx
    must see already-clipped grads."""
    if engine.client_optimizer is not None:
        return engine.client_optimizer, "client"
    opt_cfg = engine._config.optimizer
    if opt_cfg is None:
        from ...ops.optimizers import FusedAdam

        return FusedAdam(engine.lr_scheduler or 1e-3), "adam"
    from ...ops.optimizers import get_optimizer

    return get_optimizer(opt_cfg.type, opt_cfg.params, engine.lr_scheduler,
                         engine.mesh), opt_cfg.type.lower()


def build_overlap_step(engine):
    """Returns ``(opt_state, opt_shardings, train_step_fn)`` — the
    ``build_onebit_wire`` contract, for the bucketed-overlap lane."""
    mesh = engine.mesh
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if shape.get("model", 1) != 1 or shape.get("seq", 1) != 1 or \
            shape.get("pipe", 1) != 1:
        raise ValueError("overlap_grad_sync is pure-DP: model/seq/pipe mesh "
                         "axes must be 1 (the explicit lane exchanges the "
                         "full flat grad over the batch axes)")
    zcfg = engine._config.zero_config
    stage = int(zcfg.stage)
    if stage >= 3:
        raise ValueError("overlap_grad_sync supports ZeRO stages 0-2 "
                         "(stage 3 shards the params themselves; its "
                         "gather/release schedule is compiler-owned)")
    if engine._moq is not None or engine._pld is not None or \
            engine._compression is not None:
        raise ValueError("overlap_grad_sync does not compose with "
                         "quantize_training (MoQ), progressive_layer_drop, "
                         "or compression_training — those ride the fused "
                         "dense step")

    axes = tuple(a for a in ("data", "expert")
                 if shape.get(a, 1) > 1) or ("data",)
    axis_tuple = axes if len(axes) > 1 else axes[0]
    world = int(np.prod([shape.get(a, 1) for a in axes]))

    tx, kind = _build_raw_tx(engine)
    sharded_update = stage >= 1
    if sharded_update and kind not in ELEMENTWISE_OPTIMIZERS:
        raise ValueError(
            f"overlap_grad_sync with ZeRO stage>=1 shards the optimizer "
            f"update over the flat param space, which requires an "
            f"elementwise optimizer ({'/'.join(ELEMENTWISE_OPTIMIZERS)}); "
            f"got {kind!r}. Use stage 0 (overlap only), or an eligible "
            f"optimizer.")

    fp16 = engine.fp16_enabled
    gas = engine.gradient_accumulation_steps
    overlap = bool(zcfg.overlap_comm)
    clip = float(engine._config.gradient_clipping or 0.0)

    params0 = engine.state.params
    p_leaves0, p_def = jax.tree_util.tree_flatten(params0)
    n_leaves = len(p_leaves0)
    plan = plan_grad_buckets(params0, world, int(zcfg.reduce_bucket_size))

    from ..step_common import (accumulate_local_grads, make_local_loss,
                               scale_local_loss)

    local_loss = make_local_loss(engine)
    repl_spec = P()
    axes_spec = P(axes)

    # ---- optimizer state: flat [world, C_total] rows (stage>=1) or full
    C_total = sum(plan.chunks)
    if sharded_update:
        opt_template = jax.eval_shape(
            tx.init, jax.ShapeDtypeStruct((C_total,), jnp.float32))
        opt_specs = jax.tree_util.tree_map(
            lambda l: axes_spec if getattr(l, "ndim", 0) >= 1 else repl_spec,
            opt_template)
        expanded = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((world,) + tuple(l.shape), l.dtype)
            if getattr(l, "ndim", 0) >= 1 else l, opt_template)
        opt_shardings = zero1_state_shardings(expanded, mesh, axes)

        def init_spmd(params):
            r = lax.axis_index(axis_tuple)
            leaves = jax.tree_util.tree_leaves(params)
            st = tx.init(jnp.concatenate(
                [_leaf_chunk(plan, leaves[i], i, r)
                 for i in range(n_leaves)]))
            return jax.tree_util.tree_map(
                lambda x: x[None] if getattr(x, "ndim", 0) >= 1 else x, st)

        init_fn = jax.shard_map(
            init_spmd, mesh=mesh, axis_names=frozenset(axes),
            in_specs=(repl_spec,), out_specs=opt_specs, check_vma=False)
        opt_state = jax.jit(init_fn)(params0)
    else:
        opt_template = jax.eval_shape(tx.init, params0)
        opt_specs = jax.tree_util.tree_map(lambda _: repl_spec, opt_template)
        opt_shardings = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, repl_spec), opt_template)
        opt_state = jax.jit(tx.init)(params0)

    grad_sync = make_overlap_grad_sync(plan, axis_tuple, overlap,
                                       want_full=not sharded_update)

    def spmd(params, opt_state, batch, rng, lscale):
        r = lax.axis_index(axis_tuple)
        rng = jax.random.fold_in(rng, r)
        scaled_loss = scale_local_loss(local_loss, lscale, fp16)
        p_leaves = jax.tree_util.tree_leaves(params)

        if gas == 1:
            # in-backward sync: the custom_vjp bwd runs the bucketed
            # exchange while the rest of backward is still in flight
            def loss_with_sync(p, mb, rr):
                return scaled_loss(grad_sync(p, lscale), mb, rr)

            loss_local, g = accumulate_local_grads(loss_with_sync, params,
                                                   batch, rng, 1)
            g_leaves = jax.tree_util.tree_leaves(g)
            # sharded: g carries the RAW chunk sums (embedded); stage 0:
            # g is the fully synced mean grad. Either way the flat row
            # re-slices out of the leaves as pure data movement.
            flat_g = jnp.concatenate([_leaf_chunk(plan, g_leaves[i], i, r)
                                      for i in range(n_leaves)])
            full_g = g_leaves if not sharded_update else None
        else:
            # grad accumulation: local grads accumulate over the
            # microbatch scan with NO collectives, then ONE exchange per
            # optimizer-step boundary; the barrier fences the scan so
            # its compiled form cannot vary with the exchange structure
            loss_local, g = accumulate_local_grads(scaled_loss, params,
                                                   batch, rng, gas)
            loss_local, g = lax.optimization_barrier((loss_local, g))
            g_leaves = jax.tree_util.tree_leaves(g)
            flat_g = _exchange_flat(plan, g_leaves, axis_tuple, overlap)
            if sharded_update:
                full_g = None
            else:
                flat_g = flat_g / world / lscale
                full_g = _gather_flat(plan, flat_g, axis_tuple, overlap,
                                      g_leaves, tag="grad_bucket")

        # ---- canonical flat pipeline -------------------------------
        # ALL arithmetic below runs on barrier-materialized flat rows,
        # so its HLO — and XLA's fusion/FMA/reciprocal rewrites — is
        # identical across overlap/kill-switch/bucket-size variants.
        if sharded_update:
            p_flat = lax.dynamic_slice_in_dim(
                _pack(plan, p_leaves, tuple(range(n_leaves))), r, 1, 0)[0]
            flat_g, p_flat = lax.optimization_barrier((flat_g, p_flat))
            flat_g = flat_g / world / lscale
        else:
            flat_g = lax.optimization_barrier(flat_g)
        if fp16:
            loss_local = loss_local / lscale

        # global loss mean + grad norm + overflow verdict: ONE tiny
        # all-gather of a [3] vector (loss, sum of squares, nonfinite
        # count) reduced in a fixed left-to-right chain — deterministic
        # association across program variants (``psum``/``pmean`` is
        # NOT: XLA's all-reduce emitter associates per program)
        sq = jnp.sum(flat_g * flat_g)
        nf = jnp.sum((~jnp.isfinite(flat_g)).astype(jnp.float32))
        vec = jnp.stack([loss_local, sq, nf])[None]          # [1, 3]
        rows = dist.all_gather(vec, group=axis_tuple, axis=0, tiled=True)
        tot = rows[0]
        for k in range(1, world):
            tot = tot + rows[k]
        loss = tot[0] / world
        grad_norm = jnp.sqrt(tot[1])
        ov = (tot[2] > 0) if fp16 else jnp.bool_(False)

        if clip > 0:
            clip_v = jnp.float32(clip)
            factor = clip_v / jnp.maximum(grad_norm, clip_v)
            flat_g = flat_g * factor
            if full_g is not None:
                full_g = [f * factor for f in full_g]

        if sharded_update:
            opt_local = jax.tree_util.tree_map(
                lambda x: x[0] if getattr(x, "ndim", 0) >= 1 else x,
                opt_state)
            updates, new_opt_local = tx.update(flat_g, opt_local, p_flat)
            new_flat = p_flat + updates
            # overflow: the advanced flat shard (and moments) revert
            # BEFORE the gather, so replicated params stay coherent
            # with the shard (jnp.where select)
            new_flat = jnp.where(ov, p_flat, new_flat)
            new_opt_local = jax.tree_util.tree_map(
                lambda o, nw: jnp.where(ov, o, nw), opt_local,
                new_opt_local)
            new_flat = lax.optimization_barrier(new_flat)
            # fused param all-gather: the updated 1/dp shards rejoin
            new_leaves = _gather_flat(plan, new_flat, axis_tuple, overlap,
                                      p_leaves, tag="param_bucket")
            new_params = jax.tree_util.tree_unflatten(p_def, new_leaves)
            new_opt = jax.tree_util.tree_map(
                lambda x: x[None] if getattr(x, "ndim", 0) >= 1 else x,
                new_opt_local)
        else:
            g_tree = jax.tree_util.tree_unflatten(p_def, full_g)
            updates, new_opt = tx.update(g_tree, opt_state, params)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates)
            new_params = jax.tree_util.tree_map(
                lambda o, nw: jnp.where(ov, o, nw), params, new_params)
            new_opt = jax.tree_util.tree_map(
                lambda o, nw: jnp.where(ov, o, nw), opt_state, new_opt)
        return new_params, new_opt, loss, grad_norm, ov

    def ds_train_step(state, batch, rng):
        # trace-time side effect: the compiled-program registry's
        # compile counter (one resident program is the acceptance bar)
        engine.perf.note_compile("train_step")
        count = state.step + 1
        ls = state.loss_scale
        lscale = ls.cur_scale if (fp16 and ls is not None) \
            else jnp.float32(1.0)
        fn = jax.shard_map(
            spmd, mesh=mesh, axis_names=frozenset(axes),
            in_specs=(repl_spec, opt_specs, P(None, axes), repl_spec,
                      repl_spec),
            out_specs=(repl_spec, opt_specs, repl_spec, repl_spec,
                       repl_spec),
            check_vma=False)
        new_params, new_opt, loss, grad_norm, ov = fn(
            state.params, state.opt_state, batch, rng, lscale)
        new_ls = ls
        if fp16 and ls is not None:
            from ..fp16.loss_scaler import update_scale

            new_ls = update_scale(ls, ov)
        new_state = state.replace(
            step=jnp.where(ov, state.step, count), params=new_params,
            opt_state=new_opt, loss_scale=new_ls,
            skipped_steps=state.skipped_steps + ov.astype(jnp.int32))
        return new_state, (loss, grad_norm, {}), ov

    return opt_state, opt_shardings, ds_train_step
