"""Compressed sparse (IndexedSlices-style) tensors for embedding gradients.

Counterpart of ``deepspeed/runtime/sparse_tensor.py`` (``SparseTensor``: a
row-sparse view of a dense 2-D gradient — flat row ``indices`` + the
corresponding ``values`` rows) and the engine's allgather-based sparse
"allreduce" (``deepspeed/runtime/engine.py:2301`` ``sparse_allreduce``:
scale values by 1/world, allgather indices and values, concatenate — the
combined slices scatter-add to the mean dense gradient).

TPU-native differences:

- ``from_dense`` must be jit-compatible, so the sparse extraction uses
  ``jnp.nonzero(..., size=capacity)`` with a STATIC row capacity (XLA has no
  dynamic shapes). The natural capacity for an embedding gradient is the
  number of tokens fed that step — the gather's VJP touches at most one row
  per token. Padding rows carry index 0 with all-zero values, so they are
  harmless under scatter-add.
- The cross-replica combine is ``jax.lax.all_gather`` inside a ``shard_map``
  manual region over the data axis: wire volume is ``world * capacity *
  (row + 1)`` elements instead of the dense ``[rows, cols]`` psum — the win
  whenever tokens-per-step << vocab, exactly the regime the reference's
  sparse path targets.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..comm.comm import comms_logger


@jax.tree_util.register_pytree_node_class
class SparseTensor:
    """Row-sparse tensor: ``dense[indices[i]] == values[i]`` (other rows 0).

    Reference ``SparseTensor`` (``sparse_tensor.py:11``) keeps the same
    (indices, values, dense_size) triple.
    """

    def __init__(self, indices: jnp.ndarray, values: jnp.ndarray,
                 dense_shape: Tuple[int, ...]):
        self.indices = indices
        self.values = values
        self.dense_shape = tuple(int(s) for s in dense_shape)

    # -- pytree protocol (so SparseTensor flows through jit/shard_map) ----
    def tree_flatten(self):
        return (self.indices, self.values), self.dense_shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_dense(cls, dense: jnp.ndarray,
                   capacity: Optional[int] = None) -> "SparseTensor":
        """Extract nonzero rows (reference ``SparseTensor.__init__`` dense
        branch: ``result = sum(dense, dim=1); indices = result.nonzero()``).

        Without ``capacity`` this is eager-only (dynamic output shape). With
        ``capacity`` the extraction is jit-compatible; rows beyond capacity
        are silently dropped, so callers must bound capacity by the true
        touched-row count (see ``from_dense_bounded`` for an overflow flag).
        """
        st, _ = cls.from_dense_bounded(dense, capacity)
        return st

    @classmethod
    def from_dense_bounded(cls, dense: jnp.ndarray,
                           capacity: Optional[int] = None):
        """As ``from_dense`` but also returns the true nonzero-row count so
        callers can detect capacity overflow (e.g. a tied embedding whose
        gradient is dense — torch fails loudly on the sparse+dense autograd
        mix; we surface the same condition as ``count > capacity``)."""
        # |row| sums, not plain sums: symmetric rows must not cancel to zero
        mag = jnp.sum(jnp.abs(dense), axis=tuple(range(1, dense.ndim)))
        if capacity is None:
            idx = jnp.nonzero(mag)[0]
            return cls(idx, dense[idx], dense.shape), idx.shape[0]
        capacity = min(int(capacity), dense.shape[0])
        idx = jnp.nonzero(mag, size=capacity, fill_value=0)[0]
        count = jnp.sum((mag != 0).astype(jnp.int32))
        mask = jnp.arange(capacity) < count  # nonzero pads at the tail
        vals = jnp.where(mask.reshape((-1,) + (1,) * (dense.ndim - 1)),
                         dense[idx], 0)
        return cls(idx, vals, dense.shape), count

    # -- reference API parity --------------------------------------------
    def to_dense(self) -> jnp.ndarray:
        """Scatter-add back to dense (reference ``to_dense`` :40 — duplicate
        indices accumulate, which makes concatenated allgather results
        correct without a dedup pass)."""
        zeros = jnp.zeros(self.dense_shape, self.values.dtype)
        return zeros.at[self.indices].add(self.values)

    def add(self, other: "SparseTensor") -> "SparseTensor":
        """Concatenate slices (reference ``add`` :56)."""
        assert self.dense_shape == other.dense_shape
        return SparseTensor(jnp.concatenate([self.indices, other.indices]),
                            jnp.concatenate([self.values, other.values]),
                            self.dense_shape)

    def sparse_size(self) -> Tuple[int, int]:
        """(elements stored sparse, elements if dense) — reference
        ``sparse_size`` :48."""
        sparse = self.indices.size + self.values.size
        dense = 1
        for s in self.dense_shape:
            dense *= s
        return sparse, dense

    @staticmethod
    def type() -> str:
        return "deepspeed.SparseTensor"

    def __repr__(self):
        sparse, dense = self.sparse_size()
        return (f"SparseTensor(indices={tuple(self.indices.shape)}, "
                f"values={tuple(self.values.shape)}, "
                f"dense_shape={self.dense_shape}, "
                f"reduction_factor={dense / max(sparse, 1):.1f})")


def sparse_all_reduce(st: SparseTensor, axis_name="data") -> SparseTensor:
    """MEAN-allreduce of a row-sparse gradient over ``axis_name``.

    Must run inside a shard_map manual region. Matches the reference's
    ``sparse_allreduce`` (``engine.py:2302``): values pre-scaled by
    1/world, indices and values allgathered and concatenated (the reference
    pads ranks to a common row count before its allgather — here the static
    capacity already makes every rank's slice the same shape).
    """
    world = jax.lax.axis_size(axis_name)
    # log the PRE-gather per-rank payload — the same convention as the dense
    # helpers (compressed.py:97 logs x.size before pmean), so dense-vs-sparse
    # comms_dict comparisons are apples-to-apples
    comms_logger.append(
        "sparse_allreduce",
        int(st.indices.size * st.indices.dtype.itemsize
            + st.values.size * st.values.dtype.itemsize),
        axis_name)
    idx = jax.lax.all_gather(st.indices, axis_name, tiled=True)
    vals = jax.lax.all_gather(st.values / world, axis_name, tiled=True)
    return SparseTensor(idx, vals, st.dense_shape)
