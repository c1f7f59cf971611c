"""A grouped matmul for SMALL groups: the expert layer's products on one chip.

``gmm`` is ``jax.lax.ragged_dot``'s ``lhs [M, A] x rhs [G, A, B] -> [M, B]``
(rows of group ``g``, runs of ``group_sizes[g]`` sorted rows, times
``rhs[g]``) and ``tgmm`` its weight gradient ``lhs [M, A], rhs [M, B] ->
[G, A, B]``. XLA:TPU's own kernel reads 61-68% of the matmul peak at eight
groups of ~8k rows and 15-36% at 8-64 groups of ~500-1,000 rows x
~900-2,000 columns (PERF.md section 6): the shapes a held share of a
fine-grained router leaves on one chip. Two Pallas kernels take those:

* ``ds_moe_gmm``: a group's weight block is held in VMEM while the group's
  row tiles stream past it, so no weight is read twice however small the
  tile; a tile is computed in blocks of 128 rows, and the tile two groups
  share, visited once a group, costs each group its own blocks.
* ``ds_moe_gmm_t``: a group's ``[A, B]`` float32 accumulator is held while
  its row tiles stream; written once, rounded to the operands' dtype.

``group_sizes`` is DATA. The (group, row tile) visits are a table built on
the device and read by scalar prefetch, and their count is the grid's last,
TRACED dimension: the work follows the real rows, not the buffer. Rows of no
group come back exactly zero (their tiles are visited to be zeroed: a store,
no product). The arithmetic is ``ragged_dot``'s: operands as they come,
float32 accumulation, one rounding to the operands' dtype.

``plan`` is the rule that says where the kernels run and with which tiles (a
width of no whole lanes WHOLE, counted as Mosaic holds it), a pure function
of what the call site sees: backend, mesh, shape, device kind. No option.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.logging import log_dist
from . import MOE_GMM as GMM, MOE_GMM_T as GMM_T

_FIRST, _LAST, _DEAD = 1, 2, 4


class Tiles(NamedTuple):
    """Rows of a visit's tile, and the column block (whole, or the half that
    fits) of ``ds_moe_gmm``'s weight and of ``ds_moe_gmm_t``'s accumulator."""
    rows: int
    cols: int
    cols_t: int


#: VMEM of the chips the kernels were timed on. A group's resident block may
#: take 5/16 of it, whole or as two column halves: ``ds_moe_gmm`` holds the
#: bf16 weight block twice (the next group's arrives while this one's is
#: read), ``ds_moe_gmm_t`` a float32 accumulator and the block it is written
#: to twice. The widest timed, 2048 x 2048 (ZAYA), fits both whole; its
#: accumulator in halves read 7% slower. The kernels ask Mosaic for what
#: they hold and half as much again, at most 100 MiB.
_VMEM_BYTES = {"TPU v5 lite": 128 << 20}
_VMEM_CAP = 100 << 20

#: Rows of a visit and of the blocks it is computed in. On a v5e, per call
#: at the five cells' shapes and loads (PERF.md section 6, PR 50): 512 / 128
#: is the fastest or within 6% of it for both kernels (256-row tiles lose
#: 4-12% where a group has ~500-1,000 rows: twice the grid steps; whole
#: 256-row blocks lose 2-14%: more of the tile two groups share is paid
#: twice).
_ROWS, _BLOCK = 512, 128


def backend() -> str:
    """The platform the step is traced for (a test that compiles for a
    described chip on a CPU steers this function and the next)."""
    return jax.default_backend()


def device_kind() -> str:
    return jax.devices()[0].device_kind


def _lanes(n):      # n as Mosaic holds it along the lanes: 1856 -> 1920
    return -(-n // 128) * 128


def _cols(A, B, bytes_per, budget):
    """``B`` whole, or its halves of whole lanes (a ``B`` of no whole lanes
    has none), where the resident block of ``A`` rows fits ``budget`` as
    Mosaic holds it: either width may lie along the lanes."""
    for parts in (1, 2):
        bn = B // parts
        if B % parts == 0 and (bn % 128 == 0 or parts == 1) \
                and _lanes(A) * _lanes(bn) * bytes_per <= budget:
            return bn
    return None


def plan(platform: str, mesh_devices: int, M: int, A: int, B: int, G: int,
         itemsize: int = 2,
         device_kind: str = "TPU v5 lite") -> Optional[Tiles]:
    """The tiles the kernels take ``lhs [M, A] x rhs [G, A, B]`` and its
    weight gradient with, or None where ``jax.lax.ragged_dot`` stays: off a
    TPU or on one whose VMEM is not in the table, under a mesh of several
    devices (a Mosaic call is not partitioned; ep4's products run inside a
    ``shard_map`` and read 61-67% of the peak on XLA's kernel), operands
    that are not two bytes wide, rows that are no whole tiles or fewer than
    a block a group (``T == 1``), a group's weight that does not fit the
    weight-stationary budget (ep4's 4096 x 3584 a chip, Mixtral's whole 4096
    x 14336), and widths never timed: no whole 16-row sublane tiles (900
    columns, the tiny test sizes), or two of no whole lanes. ONE may be no
    whole lanes (Nemotron-3's 2688 x 1856, 14.5; 1808 and 1872 timed beside
    it) and is taken WHOLE, as columns and as contraction: a block may be the
    dimension itself; Mosaic holds it at the next whole lane, which the budget
    and the kernels count (``ds_moe_gmm_t``: 2688 x 1920 x 8 B of 41.9 MB)."""
    if platform != "tpu" or mesh_devices > 1 or itemsize != 2 \
            or device_kind not in _VMEM_BYTES:
        return None
    if M % _ROWS or M // G < _BLOCK or A % 16 or B % 16 \
            or (A % 128 and B % 128):
        return None
    budget = _VMEM_BYTES[device_kind] * 5 // 16
    cols = _cols(A, B, 2 * itemsize, budget)
    cols_t = _cols(A, B, 4 + 2 * itemsize, budget)
    if cols is None or cols_t is None:
        return None
    return Tiles(_ROWS, cols, cols_t)


# -- the visits --------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(group_sizes, M, tm, every_group):
    """The kernels' table ``(group_of, tile_of, read_of, flags, offsets,
    count)`` -- a jitted entry of its own: a layer's products share two
    tables, and a module holds one lowering of each.

    Group ``g`` owns the rows ``offsets[g] .. offsets[g + 1]`` and visits
    the row tiles of ``tm`` they touch, groups in order; ``every_group``
    gives an empty group one visit too (its accumulator is written, as
    zeros), otherwise it has none. After them, without ``every_group``, come
    the tiles past the last group's rows, flagged ``_DEAD`` (zeroed, not
    computed: ``read_of``, the tile whose ``lhs`` block a visit reads, stays
    at the last computed tile there, so a zeroed tile fetches nothing).
    ``_FIRST``: a tile's (with ``every_group`` a group's) first visit;
    ``_LAST``: a group's last. The arrays have the static worst-case length
    ``M / tm + G``; ``count`` of them are live."""
    G = group_sizes.shape[0]
    n = M // tm + G
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if every_group else 0)
    first = jnp.minimum(first, M // tm - 1)
    stop = jnp.cumsum(tiles)                    # visits through group g
    live = stop[-1]
    t = jnp.arange(n, dtype=jnp.int32)
    # the group whose visits hold t: how many groups' visits end at or
    # before it (a compare and a sum: cheaper to trace than a search)
    g = jnp.minimum(jnp.sum(stop[None, :] <= t[:, None], axis=1,
                            dtype=jnp.int32), G - 1)
    tile = first[g] + t - (stop[g] - tiles[g])
    if every_group:
        count = live
        new = jnp.pad(g[1:] != g[:-1], (1, 0), constant_values=True)
        flags = new * _FIRST
    else:
        done = -(-ends[-1] // tm)               # tiles with a real row
        dead = t >= live
        last_g = jnp.max(jnp.where(sizes > 0, jnp.arange(G), 0))
        g = jnp.where(dead, last_g, g).astype(jnp.int32)  # its weight stays
        tile = jnp.where(dead, done + t - live, tile)
        count = live + M // tm - done
        new = jnp.pad(tile[1:] != tile[:-1], (1, 0), constant_values=True)
        flags = new * _FIRST + dead * _DEAD
    tile = jnp.clip(tile, 0, M // tm - 1).astype(jnp.int32)
    read = tile if every_group else jnp.where(
        dead, jnp.maximum(done - 1, 0), tile).astype(jnp.int32)
    nxt = jnp.pad(g[1:] != g[:-1], (0, 1), constant_values=True) \
        | (t == count - 1)
    flags = (flags + nxt * _LAST).astype(jnp.int32)
    offsets = jnp.pad(ends, (1, 0))
    return g, tile, read, flags, offsets, count.astype(jnp.int32)


def _interpret(interpret):
    return backend() != "tpu" if interpret is None else interpret


def _vmem_limit(resident):
    return int(min(_VMEM_CAP, max(32 << 20, resident * 3 // 2)))


# -- rows x a group's weight --------------------------------------------------

def _gmm_kernel(group_of, tile_of, read_of, flags, offsets, lhs_ref, rhs_ref,
                out_ref, *, tm, sub, transpose_rhs):
    t = pl.program_id(1)
    word = flags[t]
    first = word & _FIRST != 0
    g = group_of[t]
    start, end = offsets[g], offsets[g + 1]
    dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    # the tile in blocks of `sub` rows: only a block that holds a row of
    # this group is computed, so the tile two groups share costs each its
    # own blocks (and the one block they split), not the whole tile twice.
    # A loop, not an unrolled one: the kernel's text is what every program
    # that holds it lowers again, and set-up pays for it
    def block(s, _):
        at = pl.ds(pl.multiple_of(s * sub, sub), sub)
        row0 = tile_of[t] * tm + s * sub
        hit = (word & _DEAD == 0) & (row0 < end) & (row0 + sub > start)

        @pl.when(hit)
        def _product():
            acc = jax.lax.dot_general(lhs_ref[at, :], rhs_ref[0], dims,
                                      preferred_element_type=jnp.float32)
            acc = acc.astype(out_ref.dtype)
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            mask = (rows >= start) & (rows < end)
            # rows of an earlier group are written; later ones will be
            keep = jnp.where(first, jnp.zeros_like(acc), out_ref[at, :])
            out_ref[at, :] = jnp.where(mask, acc, keep)

        @pl.when(jnp.logical_not(hit) & first)
        def _zero():
            out_ref[at, :] = jnp.zeros((sub, out_ref.shape[1]),
                                       out_ref.dtype)

    jax.lax.fori_loop(0, tm // sub, block, None)


def gmm(lhs, rhs, group_sizes, *, rows, cols, sub=_BLOCK, transpose_rhs=False,
        interpret=None):
    """``lhs [M, A] x rhs [G, A, B] -> [M, B]`` (``rhs [G, B, A]`` with
    ``transpose_rhs``: the kernel contracts its last dimension), in row
    tiles of ``rows`` computed in blocks of ``sub`` rows, and column blocks
    of ``cols``; rows of no group zero. The table and the kernel are jitted
    entries: a module holds one lowering of each a shape, whatever the
    number of call sites. ``interpret=None``: Pallas' interpreter off a TPU
    (where ``plan`` gives no tiles, so only a test gets there)."""
    M = lhs.shape[0]
    B = rhs.shape[1 if transpose_rhs else 2]
    sub = min(sub, rows)
    if M % rows or B % cols or rows % sub:
        raise ValueError(f"[{M}, {B}] is no whole number of "
                         f"{rows} x {cols} tiles of {sub}-row blocks")
    return _gmm(*_visits(group_sizes, M, rows, False), lhs, rhs, rows=rows,
                cols=cols, sub=sub, transpose_rhs=transpose_rhs,
                interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "rows", "cols", "sub", "transpose_rhs", "interpret"))
def _gmm(group_of, tile_of, read_of, flags, offsets, count, lhs, rhs, *, rows,
         cols, sub, transpose_rhs, interpret):
    M, A = lhs.shape
    B = rhs.shape[1 if transpose_rhs else 2]
    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, cols, A), lambda j, t, g, *_: (g[t], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, A, cols), lambda j, t, g, *_: (g[t], 0, j))
    size = jnp.dtype(lhs.dtype).itemsize
    a, c = _lanes(A), _lanes(cols)              # as Mosaic holds them
    resident = 2 * size * (a * c + rows * a + rows * c) + 4 * rows * c
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=rows, sub=sub,
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B // cols, count),
            in_specs=[
                pl.BlockSpec((rows, A), lambda j, t, g, tile, read, *_:
                             (read[t], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((rows, cols), lambda j, t, g, tile, *_:
                                   (tile[t], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, B), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(resident)),
        interpret=interpret,
        name=GMM,
    )(group_of, tile_of, read_of, flags, offsets, lhs, rhs)


# -- a group's rows, transposed, x the same rows: a weight's gradient ---------

def _tgmm_kernel(group_of, tile_of, read_of, flags, offsets, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tm, sub):
    t = pl.program_id(1)
    word = flags[t]
    g = group_of[t]
    start, end = offsets[g], offsets[g + 1]

    @pl.when(word & _FIRST != 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def block(s, _):                # as in _gmm_kernel: this group's blocks
        at = pl.ds(pl.multiple_of(s * sub, sub), sub)
        row0 = tile_of[t] * tm + s * sub

        @pl.when((row0 < end) & (row0 + sub > start))
        def _product():
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            mask = (rows >= start) & (rows < end)
            # both operands: a row of no group may hold anything
            lhs = jnp.where(mask, lhs_ref[at, :], 0)
            rhs = jnp.where(mask, rhs_ref[at, :], 0)
            acc_ref[...] += jax.lax.dot_general(
                lhs, rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, tm // sub, block, None)

    @pl.when(word & _LAST != 0)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, group_sizes, *, rows, cols, sub=_BLOCK, interpret=None):
    """``lhs [M, A], rhs [M, B] -> [G, A, B]``: ``lhs[rows of g]^T @
    rhs[rows of g]``, accumulated in float32 over row tiles of ``rows`` in
    column blocks of ``cols``, an empty group's block zero. Jitted entries,
    as ``gmm``'s."""
    M, B = rhs.shape
    sub = min(sub, rows)
    if M % rows or B % cols or rows % sub:
        raise ValueError(f"[{M}] x [{B}] is no whole number of {rows}-row "
                         f"tiles of {sub}-row blocks and {cols}-column blocks")
    return _tgmm(*_visits(group_sizes, M, rows, True), lhs, rhs, rows=rows,
                 cols=cols, sub=sub, interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "rows", "cols", "sub", "interpret"))
def _tgmm(group_of, tile_of, read_of, flags, offsets, count, lhs, rhs, *,
          rows, cols, sub, interpret):
    M, A = lhs.shape
    B = rhs.shape[1]
    groups = offsets.shape[0] - 1
    size = jnp.dtype(lhs.dtype).itemsize
    a, c = _lanes(A), _lanes(cols)              # as Mosaic holds them
    resident = (8 + 2 * size) * a * c + 2 * size * rows * (a + c)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=rows, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B // cols, count),
            in_specs=[
                pl.BlockSpec((rows, A), lambda j, t, g, tile, *_:
                             (tile[t], 0)),
                pl.BlockSpec((rows, cols), lambda j, t, g, tile, *_:
                             (tile[t], j)),
            ],
            out_specs=pl.BlockSpec((1, A, cols), lambda j, t, g, *_:
                                   (g[t], 0, j)),
            scratch_shapes=[pltpu.VMEM((A, cols), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, A, B), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(resident)),
        interpret=interpret,
        name=GMM_T,
    )(group_of, tile_of, read_of, flags, offsets, lhs, rhs)


@functools.lru_cache(maxsize=None)
def log_plan(M, A, B, G, tiles) -> None:
    """Once per shape, at trace time, beside ``moe expert layout: ...``."""
    log_dist(
        f"moe grouped products: [{M}, {A}] x [{G}, {A}, {B}] -> "
        + ("jax.lax.ragged_dot" if tiles is None else
           f"{GMM} (rows {tiles.rows}, cols {tiles.cols}), "
           f"{GMM_T} (rows {tiles.rows}, cols {tiles.cols_t})"),
        ranks=[0])
