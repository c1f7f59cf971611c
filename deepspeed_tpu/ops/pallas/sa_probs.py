"""The head-mean attention probabilities of a selection (Pallas TPU kernel).

The indexer of a learned sparse attention (``models/indexed_attention.py``)
is trained towards ``p^[t, s] = (1/H) sum_h P[t, h, s]`` over the selected
pairs, which no flash kernel materialises. This kernel recomputes ``P`` tile
by tile from the queries, the keys and the forward kernel's SAVED log-sum-exp
— as ``flash_attention._bwd_dq_kernel`` does — and sums it over the heads: no
second softmax, no ``[H, T, T]`` tensor. The grid is ``(B, tiles, H)`` over
the flash kernels' tile table with the heads innermost, so a tile of
``p^`` stays in VMEM while its ``H`` terms are added and is written once.
That table is the one the flash kernels walk under this mask, by q row: the
causal entries whose tile holds a selected pair
(``flash_attention.mask_tiles``), compacted on the device, their traced count
the grid's middle dimension.

Forward only: ``p^`` is detached from its inputs. Tiles the causal rule drops
and tiles the selection leaves empty are never written; the caller reads
``p^`` under the mask.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import SA_PROBS
from .flash_attention import NEG_INF, _pad_mask, _pad_seq, _table, mask_tiles


def _probs_kernel(iq_of, ik_of, q_ref, k_ref, lse_ref, mask_ref, p_ref, *,
                  sm_scale: float, heads: int):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _init():
        p_ref[...] = jnp.zeros_like(p_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
    lse = lse_ref[0, 0, 0][:, None]                # [bq, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
    p_ref[0] += jnp.exp(s - lse)

    @pl.when(h == heads - 1)
    def _finalize():
        p_ref[...] = p_ref[...] * (1.0 / heads)


def _reference(q, k, lse, mask, sm_scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    p = jnp.exp(jnp.where((mask != 0)[:, None], s, NEG_INF)
                - lse[..., None])
    return jnp.mean(p, axis=1)


def head_mean_probs(q, k, lse, mask, sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    force_pallas: bool = False, tiles=None):
    """``p^ [B, T, T]`` float32 from ``q``, ``k`` ``[B, T, H, D]`` (keys
    repeated to the query heads, as the flash training path takes them),
    the flash forward's ``lse [B, H, T]`` and the selection ``mask
    [B, T, T]`` (``tiles``: its ``mask_tiles``, where the caller holds
    them). Entries outside the tiles the selection keeps are undefined.
    ``interpret=None``: the kernel on a TPU, einsum math elsewhere."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    # detached here: the kernel has no derivative and its readers want none
    q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            return _reference(q, k, lse, mask, sm_scale)
        interpret = not on_tpu
    B, T, H, D = q.shape
    bq, bk = min(block_q, T), min(block_k, T)
    qt = _pad_seq(jnp.transpose(q, (0, 2, 1, 3)), bq)
    kt = _pad_seq(jnp.transpose(k, (0, 2, 1, 3)), bk)
    Tq_p, Tk_p = qt.shape[2], kt.shape[2]
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, Tq_p - T)))[:, :, None]
    if tiles is None:
        tiles = mask_tiles(mask, block_q, block_k)
    # a row's body-less placeholder (a q row without a key) runs like any
    # tile here and writes the zeros its empty mask block gives
    (iq_of, ik_of, _), steps = _table(tiles, T, T, bq, bk, True, None)
    out = pl.pallas_call(
        functools.partial(_probs_kernel, sm_scale=sm_scale, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, steps, H),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, t, h, iq_of, ik_of:
                             (b, h, iq_of[t], 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, t, h, iq_of, ik_of:
                             (b, h, ik_of[t], 0)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda b, t, h, iq_of, ik_of:
                             (b, h, 0, iq_of[t])),
                pl.BlockSpec((1, bq, bk),
                             lambda b, t, h, iq_of, ik_of:
                             (b, iq_of[t], ik_of[t])),
            ],
            out_specs=pl.BlockSpec((1, bq, bk),
                                   lambda b, t, h, iq_of, ik_of:
                                   (b, iq_of[t], ik_of[t])),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Tq_p, Tk_p), jnp.float32),
        interpret=interpret,
        name=SA_PROBS,
    )(iq_of, ik_of, qt, kt, lse, _pad_mask(mask, Tq_p, Tk_p))
    return out[:, :T, :T]
