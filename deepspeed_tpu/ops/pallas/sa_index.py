"""The index scores of a learned sparse attention (Pallas TPU kernels),
forward and backward.

``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` for the ``J`` indexer
heads of ``models/indexed_attention.py``, over the flash kernels' causal tile
table: a tile above the diagonal is neither a grid step nor a fetch, and
nothing of shape ``[J, bq, bk]`` leaves VMEM. The products run on the MXU on
the operands as they come (bf16 in training) into a float32 accumulator; the
weighted sum over the heads is element-wise float32 in a fixed head order.

The heads' ``d`` columns are narrower than a lane tile (64 of 128), so the
heads are taken ``g = 128 // d`` at a time: the queries stay ``[B, T, J d]``
as the projection wrote them and a slab of ``g d`` columns is one aligned
block; the shared key is handed in ``g`` times, ``kk[r]`` holding it in
columns ``r d .. (r + 1) d`` of ``g d`` and zeros beside it. ``slab . kk[r]^T``
is then head ``p g + r``'s product at the full contraction depth (the zeros
cost what the idle half of the MXU's rows would), and ``m . kk[r]`` lands a
head's ``[bq, d]`` result in its own columns of the slab, with no lane shift.

Backward, two kernels, as the flash backward has, each accumulating into a
block that stays resident. Both recompute ``pre_j`` in VMEM from the three
small inputs (the only residuals); no ReLU mask is stored or packed.
``ds_sa_index_bwd_dq`` walks a query row's key tiles. The head weight is one
number a query row, so it is taken OUT of the tile: the kernel accumulates
``u_j = m_j . kI`` with ``m_j = dI (pre_j > 0)`` cast to the operands' type
for the MXU, and ``dqI_j = w_j u_j`` and ``dw_j = qI_j . u_j`` (``= sum_s dI
max(pre_j, 0)``, with ``dqI``'s operand precision) follow once a row, outside
the kernel. ``ds_sa_index_bwd_dk`` walks a key column's query tiles and holds
the tile keys first (one transpose of ``dI`` a tile, every product as it
comes); ``w_j`` is then a row along the lanes that broadcasts along the
sublanes, and the kernel accumulates ``dkI = sum_j g_j^T . qI_j`` with ``g_j =
dI w_j (pre_j > 0)`` formed in float32 and cast for the MXU.

VMEM at keye 16k's shapes (``[1, 16384, 16, 64]`` bf16, tiles of 512 x 512;
blocks double-buffered): forward 1 MB of queries + 0.25 of keys + 1 of
scores, twice, + the tile's float32 products = about 8 MB; ``_bwd_dq`` 1 + 0.25
+ 1 (``dI``) + 2 (``u``), twice, + products = about 12 MB; ``_bwd_dk`` 1 +
0.25 + 1 + 0.5, twice, + the transposed ``dI`` and products = about 9 MB; all
under the 16 MB a kernel may use on a v5e by default.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import SA_INDEX_BWD_DK, SA_INDEX_BWD_DQ, SA_INDEX_FWD
from .flash_attention import _FIRST, _LAST, _tile_table

_NT = (((1,), (1,)), ((), ()))      # a . b^T: both contract their columns


def _pre(q_ref, kk_ref, p, r, keys_first=False):
    """Head ``p g + r``'s products of the tile, float32: ``[bq, bk]``, or
    ``[bk, bq]`` for a tile held keys first."""
    gd = kk_ref.shape[3]
    slab, k = q_ref[0, :, p * gd:(p + 1) * gd], kk_ref[0, r]
    return jax.lax.dot_general(*((k, slab) if keys_first else (slab, k)), _NT,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(iq_of, ik_of, flags_of, q_ref, kk_ref, w_ref, o_ref, *,
                heads: int):
    group = kk_ref.shape[1]
    w = w_ref[0]                                    # [bq, J] float32
    acc = None
    for j in range(heads):
        term = w[:, j:j + 1] * jnp.maximum(
            _pre(q_ref, kk_ref, *divmod(j, group)), 0.0)
        acc = term if acc is None else acc + term
    o_ref[0] = acc


def _bwd_dq_kernel(iq_of, ik_of, flags_of, q_ref, kk_ref, di_ref, w_ref,
                   dq_ref, dw_ref, u_scr, *, heads: int):
    group, gd = kk_ref.shape[1], kk_ref.shape[3]
    d, slabs = gd // group, heads // group
    flags = flags_of[pl.program_id(1)]

    @pl.when(flags & _FIRST != 0)
    def _init():
        u_scr[...] = jnp.zeros_like(u_scr)

    di = di_ref[0]                                  # [bq, bk] float32
    for p in range(slabs):
        u = None
        for r in range(group):
            k = kk_ref[0, r]
            m = jnp.where(_pre(q_ref, kk_ref, p, r) > 0, di, 0.0)
            part = jnp.dot(m.astype(k.dtype), k,
                           preferred_element_type=jnp.float32)
            u = part if u is None else u + part     # each in its own columns
        u_scr[:, p * gd:(p + 1) * gd] += u

    @pl.when(flags & _LAST != 0)
    def _finalize():                                # once a row of tiles
        w = w_ref[0]                                # [bq, J] float32
        column = jax.lax.broadcasted_iota(jnp.int32, (w.shape[0], gd), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw = jnp.zeros_like(w)
        for p in range(slabs):
            u = u_scr[:, p * gd:(p + 1) * gd]
            qu = u * q_ref[0, :, p * gd:(p + 1) * gd].astype(jnp.float32)
            spread = None                           # w_j over head j's columns
            for r in range(group):
                j = p * group + r
                own = column // d == r
                spread = w[:, j:j + 1] if spread is None else \
                    jnp.where(own, w[:, j:j + 1], spread)
                dw = jnp.where(head == j, jnp.sum(
                    jnp.where(own, qu, 0.0), axis=1, keepdims=True), dw)
            dq_ref[0, :, p * gd:(p + 1) * gd] = (u * spread).astype(
                dq_ref.dtype)
        dw_ref[0] = dw


def _bwd_dk_kernel(iq_of, ik_of, flags_of, q_ref, kk_ref, di_ref, wt_ref,
                   dk_ref, *, heads: int):
    group, gd = kk_ref.shape[1], kk_ref.shape[3]

    @pl.when(flags_of[pl.program_id(1)] & _FIRST != 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)

    di_t = di_ref[0].T                              # [bk, bq] float32
    wt = wt_ref[0]                                  # [J, bq]: rows broadcast
    for r in range(group):                          # along the sublanes
        acc = None
        for p in range(heads // group):
            j = p * group + r
            slab = q_ref[0, :, p * gd:(p + 1) * gd]
            g = jnp.where(_pre(q_ref, kk_ref, p, r, keys_first=True) > 0,
                          di_t * wt[j:j + 1, :], 0.0)
            part = jnp.dot(g.astype(slab.dtype), slab,
                           preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
        # columns r d .. (r + 1) d are head r's of every slab; the rest pair
        # a head's mask with another head's queries and are dropped outside
        dk_ref[0, r] += acc


def _group(heads, d):
    """Heads a slab holds: as many as fill a lane tile and divide ``J``."""
    g = max(1, 128 // d)
    while heads % g:
        g -= 1
    return g


def _spread_keys(ki, group):
    """``kk [B, g, T, g d]``: the key in columns ``r d .. (r + 1) d``."""
    d = ki.shape[-1]
    return jnp.stack([jnp.pad(ki, ((0, 0), (0, 0), (r * d, (group - 1 - r) * d)))
                      for r in range(group)], axis=1)


def _pad_rows(x, block):
    pad = (-x.shape[1]) % block
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def _layout(qi, ki, w, block_q, block_k, interpret):
    """``(q [B, T', J d], kk [B, g, T', g d], w [B, T', J])``, rows padded to
    the tiles, and ``call(kernel, name, by_kv, ins, outs, shapes, *operands)``
    over the grid ``(b, tiles)`` with the tile table prefetched: ``ins`` /
    ``outs`` / ``scratch`` name blocks (float32 results and scratch)."""
    B, T, J, d = qi.shape
    bq, bk = min(block_q, T), min(block_k, T)
    group = _group(J, d)
    q = _pad_rows(qi.reshape(B, T, J * d), bq)
    kk = _spread_keys(_pad_rows(ki, bk), group)
    q_rows = lambda b, t, iq_of, ik_of, flags_of: (b, iq_of[t], 0)
    specs = {
        "q": pl.BlockSpec((1, bq, J * d), q_rows),
        "w": pl.BlockSpec((1, bq, J), q_rows),
        "wt": pl.BlockSpec((1, J, bq), lambda b, t, iq_of, ik_of, flags_of:
                           (b, 0, iq_of[t])),
        "kk": pl.BlockSpec((1, group, bk, group * d),
                           lambda b, t, iq_of, ik_of, flags_of:
                           (b, 0, ik_of[t], 0)),
        "tile": pl.BlockSpec((1, bq, bk), lambda b, t, iq_of, ik_of, flags_of:
                             (b, iq_of[t], ik_of[t]))}

    def call(kernel, name, by_kv, ins, outs, shapes, *operands, scratch=()):
        table = _tile_table(T, T, bq, bk, True, None, by_kv=by_kv)
        return pl.pallas_call(
            functools.partial(kernel, heads=J),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(B, table.shape[1]),
                in_specs=[specs[k] for k in ins],
                out_specs=[specs[k] for k in outs],
                scratch_shapes=[
                    pltpu.VMEM(specs[k].block_shape[1:], jnp.float32)
                    for k in scratch]),
            out_shape=[jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes],
            interpret=interpret, name=name)(*table, *operands)

    return q, kk, _pad_rows(w, bq), call


def _scores_fwd(qi, ki, w, block_q, block_k, interpret):
    T = qi.shape[1]
    q, kk, w, call = _layout(qi, ki, w, block_q, block_k, interpret)
    out, = call(_fwd_kernel, SA_INDEX_FWD, False, ("q", "kk", "w"),
                ("tile",), [(q.shape[0], q.shape[1], kk.shape[2])], q, kk, w)
    return out[:, :T, :T]


def _scores_bwd(qi, ki, w, di, block_q, block_k, interpret):
    B, T, J, d = qi.shape
    q, kk, w, call = _layout(qi, ki, w, block_q, block_k, interpret)
    di = jnp.pad(di.astype(jnp.float32),
                 ((0, 0), (0, q.shape[1] - T), (0, kk.shape[2] - T)))
    dq, dw = call(_bwd_dq_kernel, SA_INDEX_BWD_DQ, False,
                  ("q", "kk", "tile", "w"), ("q", "w"), [q.shape, w.shape],
                  q, kk, di, w, scratch=("q",))
    # dqI leaves the kernel in float32 and is rounded here: XLA folds the
    # layout its consumer wants into this convert, where a bf16 result gets a
    # copy named after the kernel, which a trace reader counts as a call
    dq, dw = dq[:, :T].reshape(B, T, J, d).astype(qi.dtype), dw[:, :T]
    dk, = call(_bwd_dk_kernel, SA_INDEX_BWD_DK, True,
               ("q", "kk", "tile", "wt"), ("kk",), [kk.shape],
               q, kk, di, w.swapaxes(1, 2))
    dk = sum(dk[:, r, :T, r * d:(r + 1) * d] for r in range(kk.shape[1]))
    return dq, dk.astype(ki.dtype), dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _index_scores(qi, ki, w, block_q, block_k, interpret):
    return _scores_fwd(qi, ki, w, block_q, block_k, interpret)


def _vjp_fwd(qi, ki, w, block_q, block_k, interpret):
    return _scores_fwd(qi, ki, w, block_q, block_k, interpret), (qi, ki, w)


def _vjp_bwd(block_q, block_k, interpret, res, di):
    return _scores_bwd(*res, di, block_q, block_k, interpret)


_index_scores.defvjp(_vjp_fwd, _vjp_bwd)


def _reference(qi, ki, w):
    pre = jnp.einsum("bqjd,bkd->bjqk", qi, ki,
                     preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(pre) * jnp.swapaxes(w, 1, 2)[..., None],
                   axis=1)


def index_scores(qi, ki, w, block_q: int = 512, block_k: int = 512,
                 interpret: Optional[bool] = None):
    """``I [B, T, T]`` float32 from ``qi [B, T, J, d]``, ``ki [B, T, d]`` and
    ``w [B, T, J]`` float32; it differentiates with respect to all three.
    Entries in tiles above the diagonal are undefined: the readers apply the
    causal rule (``indexed_attention._select_rows``, ``index_loss`` under
    the selection) and hand back a zero cotangent there, which is never read
    either. ``interpret=None``: the kernels on a TPU, einsum math
    elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _reference(qi, ki, w)
        interpret = False
    return _index_scores(qi, ki, w.astype(jnp.float32), block_q, block_k,
                         interpret)
