"""ZeRO stages as sharding policies over the data-parallel mesh axes.

This is the central TPU-first design departure from the reference. DeepSpeed
implements ZeRO imperatively: flatten + scatter buffers (stage 1/2,
``stage_1_and_2.py:270``), autograd-hook-driven bucketed reduce-scatter
(:789, :1216), and per-submodule allgather/release choreography for stage 3
(``partition_parameters.py:537``, ``partitioned_param_coordinator.py:237``).
Under XLA SPMD the same *placement contract* is declarative:

- **stage 1** — optimizer state carries a ``NamedSharding`` over the ZeRO
  axes; XLA reduce-scatters grads into the shard that owns each slice and
  runs the optimizer update shard-locally.
- **stage 2** — identical placement contract; the reference's grad
  partitioning is about *transient* grad memory, which XLA already handles
  (grads are consumed by the fused update, never materialized replicated
  when the consumer is sharded).
- **stage 3** — parameters themselves carry the ZeRO sharding; XLA inserts
  the forward all-gather per layer and frees gathered copies after use —
  exactly the fetch/release protocol of
  ``partitioned_param_coordinator.py:237/:356``, but scheduled by the
  compiler (prefetch = XLA latency-hiding scheduler).

``param_persistence_threshold`` maps directly: params smaller than the
threshold stay replicated (reference ``partition_parameters.py`` persistent
params).
"""

import contextlib
import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ...parallel.topology import ZERO_AXES
from ...utils.logging import logger
from .config import DeepSpeedZeroConfig, ZeroStageEnum


def _axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= shape.get(a, 1)
    return n


def _used_axes(spec: Optional[PartitionSpec]) -> set:
    used = set()
    if spec is None:
        return used
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def _canon(entries) -> PartitionSpec:
    """Strip trailing Nones so specs compare equal to their canonical form."""
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def partition_spec_for_param(
    shape: Tuple[int, ...],
    mesh: Mesh,
    *,
    zero_shard: bool,
    base_spec: Optional[PartitionSpec] = None,
    persistence_threshold: int = 0,
    zero_axes: Sequence[str] = ZERO_AXES,
) -> PartitionSpec:
    """Overlay ZeRO partitioning on top of a (possibly TP-sharded) base spec.

    Picks the largest dimension not already sharded whose size divides by the
    ZeRO world, and shards it over the composite ZeRO axes. Small params
    (<= persistence_threshold elements) stay as-is — the TPU analog of
    persistent parameters (``partition_parameters.py:310``).
    """
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    if not zero_shard:
        return _canon(base)

    n = _axis_size(mesh, zero_axes)
    if n <= 1:
        return _canon(base)
    if int(np.prod(shape or (1,))) <= persistence_threshold:
        return _canon(base)

    used = _used_axes(PartitionSpec(*base))
    usable_zero_axes = tuple(a for a in zero_axes if a not in used)
    n = _axis_size(mesh, usable_zero_axes)
    if n <= 1:
        return _canon(base)

    # largest unsharded, divisible dim
    candidates = [(dim_size, i) for i, dim_size in enumerate(shape)
                  if base[i] is None and dim_size % n == 0]
    if not candidates:
        return _canon(base)
    _, dim = max(candidates)
    new = list(base)
    new[dim] = usable_zero_axes if len(usable_zero_axes) > 1 else usable_zero_axes[0]
    return _canon(new)


def _resolve_base_spec(path: str, shape, rules, mesh) -> Optional[PartitionSpec]:
    if rules is None:
        return None
    if callable(rules):
        return rules(path, shape)
    for pattern, spec in rules:
        if re.search(pattern, path):
            # a layout that follows the mesh's sizes is a function of the mesh
            return spec(mesh) if callable(spec) else spec
    return None


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def state_shardings(
    params_shapes: Any,
    mesh: Mesh,
    zero_config: Optional[DeepSpeedZeroConfig] = None,
    partition_rules: Optional[Any] = None,
) -> Tuple[Any, Any]:
    """Build (param_shardings, optstate_sharding_fn) for a train state.

    - ``params_shapes``: pytree of ``jax.ShapeDtypeStruct`` (or arrays).
    - ``partition_rules``: tensor-parallel rules — list of
      ``(path_regex, PartitionSpec)`` or callable ``(path, shape) -> spec``;
      a list entry's spec may be a callable ``mesh -> PartitionSpec``.

    Returns the params sharding pytree and a function that shards any
    param-shaped pytree (optimizer moments) with stage>=1 policy.
    """
    cfg = zero_config or DeepSpeedZeroConfig()
    stage = int(cfg.stage)

    def spec_of(path, leaf, zero_shard, threshold):
        path_s = _path_str(path)
        base = _resolve_base_spec(path_s, leaf.shape, partition_rules, mesh)
        return partition_spec_for_param(
            tuple(leaf.shape), mesh, zero_shard=zero_shard, base_spec=base,
            persistence_threshold=threshold)

    param_specs = jax.tree_util.tree_map_with_path(
        lambda p, l: spec_of(p, l, stage >= 3, cfg.param_persistence_threshold),
        params_shapes)
    param_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))

    # Optimizer state: sharded from stage>=1. Moments mirror param shapes;
    # scalar state (step counts) stays replicated.
    opt_specs = jax.tree_util.tree_map_with_path(
        lambda p, l: spec_of(p, l, stage >= 1, 0), params_shapes)

    def shard_opt_state(opt_state_shapes):
        """Shard param-shaped *subtrees* (optimizer moments mirror the params
        treedef, e.g. Adam mu/nu) with the ZeRO policy; everything else
        (step counters, scalars) stays replicated."""
        pdef = jax.tree_util.tree_structure(params_shapes)
        moment_shardings = jax.tree_util.tree_unflatten(
            pdef, [NamedSharding(mesh, s) for s in jax.tree_util.tree_leaves(
                opt_specs, is_leaf=lambda x: isinstance(x, PartitionSpec))])

        def handle(node):
            if node is None:
                return None
            ndef = jax.tree_util.tree_structure(node)
            if ndef == pdef and not jax.tree_util.treedef_is_leaf(ndef):
                return moment_shardings
            # recurse through containers (incl. zero-leaf NamedTuples like
            # optax.EmptyState, which must keep their structure, not become
            # a sharding leaf)
            if isinstance(node, tuple):
                children = [handle(c) for c in node]
                return type(node)(*children) if hasattr(node, "_fields") \
                    else tuple(children)
            if isinstance(node, list):
                return [handle(c) for c in node]
            if isinstance(node, dict):
                return {k: handle(v) for k, v in node.items()}
            return NamedSharding(mesh, PartitionSpec())

        return handle(opt_state_shapes)

    return param_shardings, shard_opt_state


def shard_params(params: Any, shardings: Any) -> Any:
    """Place a params pytree onto its shardings (device_put is a no-op for
    already-correct placement)."""
    return jax.tree_util.tree_map(lambda p, s: jax.device_put(p, s), params, shardings)


# ---------------------------------------------------------------------------
# ZeRO-1 flat partition (the data-axis sharded optimizer update)
# ---------------------------------------------------------------------------
#
# The explicit lane (``zero/overlap.py``) shards the *flattened* param
# space: each leaf pads to a multiple of the ZeRO world ``w`` and is viewed
# as ``[w, c_i]`` rows — rank ``r`` owns row ``r`` of EVERY leaf (the
# interleaved layout of reference ``stage_1_and_2.py`` flat partitions).
# The layout is a pure function of (leaf shapes, w): bucket composition —
# which leaves share one reduce-scatter — never changes which elements a
# rank owns, which is what keeps the compiled step's interface (and the
# recompile sentinel) invariant under ``reduce_bucket_size`` changes.


def zero1_chunk_sizes(params_shapes: Any, world: int
                      ) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                                 Tuple[int, ...]]:
    """Per-leaf ``(sizes, padded, chunks)`` of the flat partition:
    ``padded[i] = ceil(sizes[i]/world)*world`` and ``chunks[i] =
    padded[i]//world`` — the per-rank share of leaf ``i``."""
    leaves = jax.tree_util.tree_leaves(params_shapes)
    sizes = tuple(int(np.prod(l.shape or (1,))) for l in leaves)
    padded = tuple(-(-n // world) * world for n in sizes)
    chunks = tuple(p // world for p in padded)
    return sizes, padded, chunks


def zero1_state_shardings(opt_state_shapes: Any, mesh: Mesh,
                          axes: Sequence[str]) -> Any:
    """Shardings for a flat-chunked optimizer state (the
    ``state_shardings`` policy applied to the flat partition): leaves
    carrying a leading ZeRO-world dim — the single ``[world, C_total]``
    moment per optax leaf, C_total the concatenation of every param
    leaf's per-rank chunk — shard dim 0 over ``axes``; scalar state
    (step counts) replicates. One flat row per rank keeps the update a
    single fused elementwise pass and the canonical arithmetic pipeline
    identical across collective groupings (``zero/overlap.py``)."""
    axes = tuple(axes)
    row = NamedSharding(mesh, PartitionSpec(axes))
    repl = NamedSharding(mesh, PartitionSpec())

    def place(leaf):
        return row if getattr(leaf, "ndim", 0) >= 1 else repl

    return jax.tree_util.tree_map(place, opt_state_shapes)


# ---------------------------------------------------------------------------
# zero.Init + GatheredParameters parity API
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def Init(mesh: Optional[Mesh] = None, config_dict_or_path=None, dtype=None, enabled=True,
         **_ignored):
    """Parity shim for ``deepspeed.zero.Init`` (``partition_parameters.py:537``).

    The reference must metaclass-patch ``nn.Module.__init__`` so params are
    scattered *at construction* (a 175B model never fits on one GPU). In JAX,
    model construction is shape-only: ``engine.initialize`` derives shardings
    from ``jax.eval_shape`` of the init function and then materializes under
    ``jax.jit(init_fn, out_shardings=param_shardings)`` — every leaf is born
    directly into its shards with no replicated copy and no hook machinery
    (``engine.params_born_sharded`` records this; see
    ``test_params_born_sharded_no_replicated_birth``). This context manager
    therefore only marks a region (and validates a mesh exists);
    creation-time sharding is the default behavior of ``engine.initialize``.
    """
    if enabled and mesh is None:
        from ...parallel.topology import get_mesh

        if get_mesh() is None:
            logger.info("zero.Init: no mesh set yet; engine.initialize will create one")
    yield


@contextlib.contextmanager
def GatheredParameters(params, modifier_rank: Optional[int] = 0, fwd_module=None, enabled=True):
    """Parity shim for ``zero.GatheredParameters`` (``partition_parameters.py:1512``).

    In the reference this allgathers partitioned params so host code can read/
    modify them. JAX arrays are already globally addressable views; reading a
    sharded array (``np.asarray``) performs the gather. Yields the params
    unchanged; modifications are value-level (functional), so re-sharding is
    a ``device_put`` by the caller.
    """
    yield params
