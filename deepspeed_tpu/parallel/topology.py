"""Device mesh topology with named parallelism axes.

TPU-native replacement for the reference's process-group bookkeeping
(``deepspeed/runtime/pipe/topology.py:9`` ``ProcessTopology`` and
``deepspeed/utils/groups.py``). Instead of building torch process groups for
every (pipe, data, model, expert) combination, we build ONE
``jax.sharding.Mesh`` with named axes and let the XLA SPMD partitioner insert
collectives. Axis conventions:

- ``pipe``    : pipeline stages (reference: ``topology.py:232`` axis "pipe")
- ``data``    : pure data parallelism / ZeRO partitioning (axis "data")
- ``expert``  : expert parallelism; subdivides the data-parallel set the same
  way ``ep_size`` divides ``dp_world_size`` in the reference
  (``deepspeed/utils/groups.py:109``). Dense layers treat ``expert`` as part
  of the batch sharding; MoE layers all_to_all over it.
- ``seq``     : sequence/context parallelism (Ulysses/ring attention) — a
  capability the 2022 reference lacks but that we deliver first-class.
- ``model``   : tensor (model) parallelism (axis "model", ``groups.py:59``).

The full data-parallel world (what the reference calls ``dp_world_size``) is
``data * expert * seq`` — ZeRO shards over this composite.
"""

import math
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence, Tuple

import numpy as np

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"

#: Canonical mesh axis order. ``model`` is innermost so tensor-parallel
#: collectives ride the fastest ICI links; ``pipe`` is outermost so stages can
#: span slices/hosts over DCN (cheapest traffic: microbatch activations).
MESH_AXES: Tuple[str, ...] = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)

#: The composite set of axes ZeRO partitions over (== reference dp group).
ZERO_AXES: Tuple[str, ...] = (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS)

#: Axes over which the global batch is sharded for dense compute.
BATCH_AXES: Tuple[str, ...] = (DATA_AXIS, EXPERT_AXIS)


@dataclass(frozen=True)
class MeshTopology:
    """Sizes of each parallelism axis. ``data=-1`` means "absorb remaining
    devices" (like the reference inferring dp from world/mp/pp,
    ``deepspeed/utils/groups.py:59``)."""

    pipe: int = 1
    data: int = -1
    expert: int = 1
    seq: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> "MeshTopology":
        fixed = self.pipe * self.expert * self.seq * self.model
        if self.data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"world size {n_devices} not divisible by pipe*expert*seq*model={fixed}")
            return replace(self, data=n_devices // fixed)
        total = fixed * self.data
        if total != n_devices:
            raise ValueError(
                f"topology {self.axis_sizes()} needs {total} devices, have {n_devices}")
        return self

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.pipe, self.data, self.expert, self.seq, self.model)

    @property
    def world_size(self) -> int:
        return int(np.prod([max(s, 1) for s in self.axis_sizes()]))

    @property
    def dp_world_size(self) -> int:
        """Reference semantics: world / (mp * pp) — includes expert & seq axes."""
        return self.data * self.expert * self.seq

    @property
    def batch_world_size(self) -> int:
        """Number of distinct global-batch shards. Sequence-parallel group
        members share the same samples (they split the sequence dim), so
        ``seq`` is excluded here while it still counts toward the ZeRO
        sharding world."""
        return self.data * self.expert


def build_mesh(topology: Optional[MeshTopology] = None,
               devices: Optional[Sequence] = None,
               **axis_sizes) -> "jax.sharding.Mesh":
    """Create a named-axis Mesh. ``build_mesh(model=4)`` etc.

    Uses ``jax.make_mesh`` so the device assignment respects physical ICI
    topology (nearest-neighbor axes get contiguous device blocks).
    """
    import jax
    from jax.sharding import Mesh

    if topology is None:
        topology = MeshTopology(**axis_sizes)
    elif axis_sizes:
        topology = replace(topology, **axis_sizes)

    default_devices = devices is None
    if default_devices:
        devices = jax.devices()
    topology = topology.resolve(len(devices))

    sizes = topology.axis_sizes()
    # Auto axis types: the XLA SPMD partitioner owns resharding decisions
    # (our design premise — collectives are inserted by the compiler, not
    # spelled per-op as jax 0.9's Explicit mode would require).
    axis_types = (jax.sharding.AxisType.Auto,) * len(MESH_AXES)
    if default_devices:
        # jax.make_mesh lays axes onto the physical ICI topology; a layout
        # it cannot make is an error, never a silent reshape of
        # jax.devices() in enumeration order
        return jax.make_mesh(sizes, MESH_AXES, axis_types=axis_types)
    mesh_devices = np.asarray(devices).reshape(sizes)
    return Mesh(mesh_devices, MESH_AXES, axis_types=axis_types)


# ---------------------------------------------------------------------------
# Global mesh registry (counterpart of deepspeed/utils/groups.py module state)
# ---------------------------------------------------------------------------

_CURRENT_MESH = None
_CURRENT_TOPOLOGY: Optional[MeshTopology] = None
#: active token layout for dense stacked-expert MoE (engine sets this from
#: ``{"moe": {"replicate_tokens": true}}``): True = tokens shard over
#: ``data`` only, so MoE-internal expert-axis batch pins must not apply
_REPLICATE_TOKENS = False


def set_token_replication(flag: bool) -> None:
    global _REPLICATE_TOKENS
    _REPLICATE_TOKENS = bool(flag)


def tokens_replicated() -> bool:
    return _REPLICATE_TOKENS


def set_mesh(mesh, topology: Optional[MeshTopology] = None) -> None:
    global _CURRENT_MESH, _CURRENT_TOPOLOGY
    _CURRENT_MESH = mesh
    if mesh is None:
        set_token_replication(False)
    if topology is None and mesh is not None:
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        topology = MeshTopology(pipe=shape.get(PIPE_AXIS, 1), data=shape.get(DATA_AXIS, 1),
                                expert=shape.get(EXPERT_AXIS, 1), seq=shape.get(SEQ_AXIS, 1),
                                model=shape.get(MODEL_AXIS, 1))
    _CURRENT_TOPOLOGY = topology


def get_mesh():
    return _CURRENT_MESH


def get_topology() -> Optional[MeshTopology]:
    return _CURRENT_TOPOLOGY


def ensure_mesh(**axis_sizes):
    """Return the current mesh, building a default one if none is set."""
    if _CURRENT_MESH is None:
        set_mesh(build_mesh(**axis_sizes))
    return _CURRENT_MESH
