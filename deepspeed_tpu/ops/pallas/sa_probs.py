"""The indexer's loss of a learned sparse attention (Pallas TPU kernels),
forward and backward.

The indexer (``models/indexed_attention.py``) is trained towards ``p^[t, s] =
(1/H) sum_h P[t, h, s]`` over the selected pairs, which no flash kernel
materialises: ``mean_t KL(p^_t || softmax_{S_t} I[t, .])`` against its scores
``I``. Both kernels here recompute ``P`` tile by tile from the queries, the
keys and the flash forward's SAVED log-sum-exp — as
``flash_attention._bwd_dq_kernel`` does — and sum it over the heads. The grid
is ``(B, tiles)`` over the table the flash kernels walk under this mask, by q
row (the causal entries whose tile holds a selected pair,
``flash_attention.mask_tiles``, their traced count the grid's last
dimension), and a grid step holds ALL the heads' queries and keys of its
tile: the heads are a loop unrolled inside the kernel, so a tile of ``p^`` is
a value in VMEM while its ``H`` terms are added and is never written, the
queries are fetched once a q row and the keys in one 4 MB block a tile (with
the heads a grid dimension a step fetched 256 KB and cost 0.82 us whatever
its body did, PERF.md section 6, PR 44). The MXU takes the operands as they
come (a product of two bf16 values is exact in float32); the mask is applied
once a tile, to the sum, with a ``where`` (an unselected pair's ``exp(s -
lse)`` may be ``inf``).

For row ``t`` with selection ``S_t``, ``m_t = max_{S_t} I`` and ``l_t =
sum_{S_t} exp(I - m_t)``:

    KL_t = a_t - b_t + z_t log l_t
      a_t = sum p^ log p^    b_t = sum p^ (I - m_t)    z_t = sum p^
    d(mean KL)/dI[t, s] = (z_t exp(I[t, s] - m_t) / l_t - p^[t, s]) / (B T)

over ``s`` in ``S_t`` (a ``p^`` of exactly zero adds zero), which is
``index_loss`` of ``indexed_attention.py`` and its ``jax.grad``; ``b`` is kept
relative to the running max, so scores of tens cost a KL of tenths no digits.

``ds_sa_probs`` (forward): behind the heads' sum it reads the tile of ``I``
and adds to five running columns of the q row — ``m``, ``l`` (an online
log-sum-exp, as the flash forward keeps its own), ``a``, ``b``, ``z`` — set at
the row's first table entry and written at its last as rows of ``[B, 8, T]``
float32. The loss is their ``mean(a - b + z log l)``, in XLA over ``[B, T]``.
``ds_sa_probs_bwd``: the same walk; behind the sum it writes the tile of
``dI`` from the saved rows. Its output is aliased onto a zeroed buffer, so a
causal tile the table drops holds zeros (``sa_index.py``'s backward reads
every causal tile); tiles above the diagonal are zeros too. ``p^``, and with
it the queries, the keys and the log-sum-exp, are detached: the scores alone
take a gradient.

VMEM at keye 16k's shapes (32 heads of 128 in bf16, tiles of 512 x 512; blocks
double-buffered): 8 MB of queries, 8 of keys, 0.5 of mask, 2 of scores (and 2
of ``dI``) and the float32 temporaries of the heads' sum and the epilogue, a
megabyte each: over the 16 MB a kernel may use by default, so the calls ask
for ``_VMEM_LIMIT`` of the chip's 128.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import SA_KL_ROWS, SA_PROBS, SA_PROBS_BWD
from .flash_attention import (_FIRST, _LAST, NEG_INF, _pad_mask, _pad_seq,
                              _table, mask_tiles)

_NT = (((1,), (1,)), ((), ()))      # a . b^T: both contract their columns
_ROWS = 8                           # m, l, a, b, z and three of padding
_VMEM_LIMIT = 64 * 2 ** 20


def _tile(q_ref, k_ref, lse_ref, mask_ref, i_ref, sm_scale):
    """``(keep, p^, I)`` of the step's tile: ``p^`` zero and ``I`` ``NEG_INF``
    outside the selection (an unwritten score never reaches a product). The
    heads' loop is unrolled: the MXU runs a head's product under the
    exponentials of the one before (as a ``fori_loop`` a 512 x 512 tile of
    32 heads took 20.5 us on the v5e, unrolled 15.6)."""
    acc = None
    for h in range(q_ref.shape[1]):
        s = jax.lax.dot_general(q_ref[0, h], k_ref[0, h], _NT,
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s * sm_scale - lse_ref[0, h, 0][:, None])
        acc = p if acc is None else acc + p
    keep = mask_ref[0].astype(jnp.int32) != 0
    return (keep, jnp.where(keep, acc * (1.0 / q_ref.shape[1]), 0.0),
            jnp.where(keep, i_ref[0], NEG_INF))


def _fwd_kernel(iq_of, ik_of, flags_of, q_ref, k_ref, lse_ref, mask_ref,
                i_ref, rows_ref, m_scr, l_scr, a_scr, b_scr, z_scr, *,
                sm_scale: float):
    flags = flags_of[pl.program_id(1)]

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        for scr in (l_scr, a_scr, b_scr, z_scr):
            scr[...] = jnp.zeros_like(scr)

    keep, p, i = _tile(q_ref, k_ref, lse_ref, mask_ref, i_ref, sm_scale)
    row = lambda x: jnp.sum(x, axis=1, keepdims=True)
    m_old, z_old = m_scr[...], z_scr[...]
    m = jnp.maximum(m_old, jnp.max(i, axis=1, keepdims=True))
    d = i - m                                       # <= 0 on the selection
    l_scr[...] = l_scr[...] * jnp.exp(m_old - m) \
        + row(jnp.where(keep, jnp.exp(d), 0.0))
    a_scr[...] += row(p * jnp.log(jnp.where(p > 0, p, 1.0)))
    # sum p^ (I - m) under the new max: the old terms move by z (m' - m)
    b_scr[...] += z_old * (m_old - m) + row(p * d)
    z_scr[...] = z_old + row(p)
    m_scr[...] = m

    @pl.when(flags & _LAST != 0)
    def _write():
        # a row without a key (padding) has z = 0: any l but 0 gives it a
        # loss and a gradient of zero
        l = jnp.where(l_scr[...] == 0, 1.0, l_scr[...])
        for j, col in enumerate((m, l, a_scr[...], b_scr[...], z_scr[...])):
            rows_ref[0, j] = col[:, 0]
        rows_ref[0, 5:] = jnp.zeros_like(rows_ref[0, 5:])


def _bwd_kernel(iq_of, ik_of, flags_of, q_ref, k_ref, lse_ref, mask_ref,
                i_ref, rows_ref, zeros_ref, di_ref, *, sm_scale: float):
    del zeros_ref                   # aliased to di: the tiles not walked
    keep, p, i = _tile(q_ref, k_ref, lse_ref, mask_ref, i_ref, sm_scale)
    m, zg, g = (rows_ref[0, j][:, None] for j in range(3))
    di_ref[0] = jnp.where(keep, zg * jnp.exp(i - m) - g * p, 0.0)


def _reference(q, k, lse, mask, sm_scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    p = jnp.exp(jnp.where((mask != 0)[:, None], s, NEG_INF)
                - lse[..., None])
    return jnp.mean(p, axis=1)


def _call(kernel, name, q, k, lse, mask, scores, tiles, sm_scale, block_q,
          block_k, interpret, rows=None):
    """One walk of the table: the forward's rows ``[B, 8, Tq']`` without
    ``rows``, ``dI [B, Tq', Tk']`` from the backward's three with them."""
    B, H, T, D = q.shape
    bq, bk = min(block_q, T), min(block_k, T)
    q, k = _pad_seq(q, bq), _pad_seq(k, bk)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    pad = ((0, 0), (0, Tq_p - T), (0, Tk_p - T))
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, Tq_p - T)))[:, :, None]
    # a row's body-less placeholder (a q row without a key) runs like any
    # tile here: its empty mask block adds, and writes, zeros
    table, steps = _table(tiles, T, T, bq, bk, True, None)
    q_rows = lambda b, t, iq_of, ik_of, flags_of: (b, 0, iq_of[t])
    tile = pl.BlockSpec((1, bq, bk), lambda b, t, iq_of, ik_of, flags_of:
                        (b, iq_of[t], ik_of[t]))
    in_specs = [                    # every head's rows of the tile
        pl.BlockSpec((1, H, bq, D), lambda b, t, iq_of, ik_of, flags_of:
                     (b, 0, iq_of[t], 0)),
        pl.BlockSpec((1, H, bk, D), lambda b, t, iq_of, ik_of, flags_of:
                     (b, 0, ik_of[t], 0)),
        pl.BlockSpec((1, H, 1, bq), lambda b, t, iq_of, ik_of, flags_of:
                     (b, 0, 0, iq_of[t])),
        tile, tile]
    operands = [q, k, lse, _pad_mask(mask, Tq_p, Tk_p),
                jnp.pad(scores, pad)]
    if rows is None:
        extra = dict(out_specs=pl.BlockSpec((1, _ROWS, bq), q_rows),
                     scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)] * 5)
        out_shape, aliases = (B, _ROWS, Tq_p), {}
    else:
        in_specs += [pl.BlockSpec((1, 3, bq), q_rows),
                     pl.BlockSpec(memory_space=pl.ANY)]
        operands += [jnp.pad(rows, ((0, 0), (0, 0), (0, Tq_p - T))),
                     jnp.zeros((B, Tq_p, Tk_p), jnp.float32)]
        extra = dict(out_specs=tile)
        out_shape, aliases = (B, Tq_p, Tk_p), {3 + len(operands) - 1: 0}
    return pl.pallas_call(
        functools.partial(kernel, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, steps), in_specs=in_specs,
            **extra),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        input_output_aliases=aliases, interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
    )(*table, *operands)


def _kl_of(rows):
    l, a, b, z = (rows[:, j] for j in range(1, 5))
    return jnp.mean(a - b + z * jnp.log(l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _index_kl(q, k, lse, scores, mask, tiles, sm_scale, block_q, block_k,
              interpret):
    return _vjp_fwd(q, k, lse, scores, mask, tiles, sm_scale, block_q,
                    block_k, interpret)[0]


def _vjp_fwd(q, k, lse, scores, mask, tiles, *static):
    rows = _call(_fwd_kernel, SA_PROBS, q, k, lse, mask, scores, tiles,
                 *static)[:, :, :q.shape[2]]
    # named so that every remat policy keeps them (resolve_remat_policy): the
    # kernel's one output, so a jax.checkpoint replay holds no forward call
    rows = checkpoint_name(rows, SA_KL_ROWS)
    return _kl_of(rows), (q, k, lse, scores, mask, tiles, rows)


def _vjp_bwd(sm_scale, block_q, block_k, interpret, res, g):
    q, k, lse, scores, mask, tiles, rows = res
    B, T = scores.shape[:2]
    m, l, z = rows[:, 0], rows[:, 1], rows[:, 4]
    g = g.astype(jnp.float32) / (B * T)
    rows = jnp.stack([m, g * z / l, jnp.broadcast_to(g, m.shape)], axis=1)
    di = _call(_bwd_kernel, SA_PROBS_BWD, q, k, lse, mask, scores, tiles,
               sm_scale, block_q, block_k, interpret, rows=rows)
    return (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
            di[:, :T, :T], None, None)


_index_kl.defvjp(_vjp_fwd, _vjp_bwd)


def index_kl(q, k, lse, scores, mask, sm_scale: Optional[float] = None,
             block_q: int = 512, block_k: int = 512,
             interpret: Optional[bool] = None, tiles=None):
    """``mean_t KL(p^_t || softmax_{S_t} I[t, .])``, a float32 scalar that
    differentiates in ``scores`` alone, from ``q``, ``k`` ``[B, T, H, D]``
    (keys repeated to the query heads, as the flash training path takes
    them), the flash forward's ``lse [B, H, T]``, the index scores ``scores
    [B, T, T]`` float32 and the selection ``mask [B, T, T]`` (``tiles``: its
    ``mask_tiles``, where the caller holds them). Scores outside the
    selection are never read into a sum and take a zero gradient.
    ``interpret=None``: the kernels on a TPU, einsum math and
    ``indexed_attention.index_loss`` elsewhere."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    # detached here: the kernels have no derivative in them
    q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))
    if interpret is None:
        if jax.default_backend() != "tpu":
            from ...models.indexed_attention import index_loss

            return index_loss(_reference(q, k, lse, mask, sm_scale), scores,
                              mask)
        interpret = False
    if tiles is None:
        tiles = mask_tiles(mask, block_q, block_k)
    return _index_kl(jnp.transpose(q, (0, 2, 1, 3)),
                     jnp.transpose(k, (0, 2, 1, 3)), lse,
                     scores.astype(jnp.float32), mask, tiles, sm_scale,
                     block_q, block_k, interpret)
