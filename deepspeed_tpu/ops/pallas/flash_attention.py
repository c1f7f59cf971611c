"""Flash attention (Pallas TPU kernel), forward + backward.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, strided-batch attention GEMMs in
``csrc/transformer/ds_transformer_cuda.cpp``): an online-softmax tiled
attention that never materializes the [T, T] score matrix in HBM.

Layout: inputs are [B, T, H, D] (model convention); kernels operate on
[B, H, T, D]. Values (and with them the output, its cotangent and dV) may
have a width ``Dv`` of their own: queries and keys share ``D``, and where
``Dv == D`` the calls are what they were before the widths were told apart. The grid is ``(B, H, tiles)``: its last dimension walks a
STATIC TILE TABLE (``_tile_table``) that holds only the (q-tile, kv-tile)
pairs the causal rule and the window (or a ``BlockDiffusion``) keep, in numpy
from the shapes and handed to the kernel by scalar prefetch; the index maps
read it, so a tile the mask rules out is neither a grid step nor a fetch.
The table is ordered by q row with keys ascending (forward, dQ) or by kv
row with queries ascending (dK/dV), so the per-row running max / sum /
accumulator live in VMEM scratch across consecutive grid steps (standard
TPU flash pattern) and are initialised / written on the row's first / last
entry. The forward holds a tile keys-by-queries, so those statistics are
lane-dense rows (``_fwd_kernel``). A tile wholly inside the mask skips the
iota / compare / where.

A mask can also be DATA: ``flash_attention(..., mask=[B, Tq, Tk] int8)``, one
set of visible keys a query shared by the heads (a learned selection,
``models/indexed_attention.py``). The table is then DATA too: the rule's
entries that hold a selected pair (``mask_tiles``), compacted on the device
(``_mask_tile_table``) and prefetched like the constant,
the grid's last dimension their traced count; a kept tile reads its block of
the mask in place of the iota rule. That path differentiates (its own
``custom_vjp``, the mask one more operand) and hands out the log-sum-exp too.
Without a mask no operand, branch or table entry differs from what they were.
Backward uses the saved logsumexp and recomputes P per tile, in ONE kernel
(``ds_flash_bwd``) that walks the table by kv row: a tile's ``s``, ``p``,
``dp`` and ``ds`` are formed once and feed all three gradients, five
products a tile. dK and dV accumulate in a kv row's scratch; dQ adds into
the rows of a float32 buffer that holds a head's WHOLE dQ in VMEM for the
walk (for one query row the kv tiles still arrive ascending), written back
once a head. Where that buffer does not fit the chip's VMEM, or the chip is
not in the table (``fused_backward``: a rule of shape, item size and
``device_kind``, no option), two kernels run, seven products a tile: one for
dQ (by q row, loop over kv), one for dK/dV (by kv row, loop over q).

On non-TPU backends the public entry falls back to reference einsum math so
the same model code runs everywhere (tests use the fallback + interpret
mode for kernel parity).
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (FLASH_BWD, FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD, FLASH_LSE,
               FLASH_OUT, grouped_matmul)

NEG_INF = -1e30


def _ceil_div(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# the tile table
# ---------------------------------------------------------------------------

# flag word of a table entry. A tile runs the body without the in-tile mask
# (_INSIDE: every entry visible), with it (_CUT: the diagonal, the window's
# edge or a ragged tail crosses it), or not at all (neither: a placeholder)
_FIRST, _LAST, _INSIDE, _CUT = 1, 2, 4, 8


def _tile_table(tq, tk, block_q, block_k, causal, window, by_kv=False,
                dense_mask=False):
    """The tiles the mask keeps, as int32 rows ``[q tile, kv tile, flags]``.

    Causality is bottom-right aligned (offset = tk - tq), matching the decode
    convention and the einsum fallback's tril(k=Tk-Tq). Inside one tile
    ``d = row + (tk - tq) - col`` takes every integer of ``[dmin, dmax]``
    over its REAL rows and columns; the mask keeps ``d >= 0`` (causal) and
    ``d < window``, so a tile holds a visible entry iff the two ranges meet
    and is wholly visible iff one lies in the other (and it has no padded
    tail). A ``window`` that is a ``BlockDiffusion`` is a RULE
    that stands for both: it says which tiles it keeps and which whole.
    Entries are ordered by q row, keys ascending (``by_kv``: by kv row,
    queries ascending); ``_FIRST`` / ``_LAST`` mark a row's ends. Every row
    of output tiles owns at least one entry, or its block would be neither
    initialised nor written: a row the mask empties keeps one placeholder
    that runs no body -- the in-tile mask could not empty it (``exp(NEG_INF
    - NEG_INF)`` is 1) -- so its outputs are zeros. ``dense_mask``: a mask
    that is data rules inside every kept tile, so none is ``_INSIDE``."""
    off = tk - tq
    r0 = (np.arange(_ceil_div(tq, block_q)) * block_q)[:, None]
    c0 = (np.arange(_ceil_div(tk, block_k)) * block_k)[None, :]
    r1 = np.minimum(r0 + block_q, tq) - 1
    c1 = np.minimum(c0 + block_k, tk) - 1
    dmin, dmax = r0 + off - c1, r1 + off - c0
    keep = np.ones(dmin.shape, bool)
    inside = (r0 + block_q <= tq) & (c0 + block_k <= tk)
    if isinstance(window, BlockDiffusion):
        some, whole = window.tiles(r0, r1, c0, c1)
        keep, inside, causal, window = keep & some, inside & whole, False, None
    if causal:
        keep &= dmax >= 0
        inside &= dmin >= 0
    if window is not None:
        keep &= dmin < window
        inside &= dmax < window
    flags = np.where(inside & (not dense_mask), _INSIDE, _CUT) * keep
    if by_kv:
        keep, flags = keep.T, flags.T
    keep[~keep.any(axis=1), 0] = True           # placeholders: flags 0
    outer, inner = np.nonzero(keep)             # row-major: the order above
    first = np.r_[True, outer[1:] != outer[:-1]]
    last = np.r_[first[1:], True]
    word = flags[outer, inner] | first * _FIRST | last * _LAST
    iq, ik = (inner, outer) if by_kv else (outer, inner)
    return np.stack([iq, ik, word]).astype(np.int32)


# index maps over the grid (b, h, t) and the prefetched table
def _q_tile(b, h, t, iq_of, ik_of, flags_of):
    return (b, h, iq_of[t], 0)


def _q_row(b, h, t, iq_of, ik_of, flags_of):
    return (b, h, 0, iq_of[t])


def _kv_tile(rep=1):
    """GQA: q head h reads kv head h // rep."""
    def index_map(b, h, t, iq_of, ik_of, flags_of):
        return (b, h // rep, ik_of[t], 0)
    return index_map


def _on_tile(flags, body):
    """Run ``body(cut)`` as the table entry's flag word says."""
    pl.when(flags & _INSIDE != 0)(functools.partial(body, False))
    pl.when(flags & _CUT != 0)(functools.partial(body, True))


def _tile_valid(iq, ik, block_q, block_k, tq, tk, causal, window,
                keys_first=False):
    """In-tile mask of a cut tile, and its global row numbers: ``[bq, bk]``,
    or ``[bk, bq]`` for a tile held ``keys_first`` (the forward's)."""
    shape, q_dim = ((block_k, block_q), 1) if keys_first else \
        ((block_q, block_k), 0)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, q_dim) + iq * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim) + ik * block_k
    ruled = _ruled(window, rows, cols)  # None under causal / window alone
    valid = (cols < tk) & (rows < tq)   # ragged tails contribute nothing
    if causal and ruled is None:
        valid = valid & (rows + (tk - tq) >= cols)
    if window is not None and ruled is None:
        valid = valid & (rows + (tk - tq) - cols < window)
    return (valid if ruled is None else valid & ruled), rows


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(iq_of, ik_of, flags_of, q_ref, k_ref, v_ref, *rest,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                tq: int, tk: int, window, has_mask: bool = False,
                dense_mask: bool = False):
    """The tile is held TRANSPOSED, ``sT [bk, bq]``: keys on the sublanes,
    queries on the lanes. The statistics are per query, so the running max,
    the running sum and the rescale factor are lane-dense ``[1, bq]`` rows,
    ``max`` / ``sum`` over the keys reduce across sublanes (element-wise over
    the tile's registers), the rows broadcast along sublanes, and the
    accumulator is ``accT [Dv, bq]``, turned once a row of tiles in
    ``_finalize``. With queries on the sublanes (``[bq, 1]`` columns, lane
    reductions, one live lane in 128) a kept 512 x 512 tile took 2.0 us on
    the v5e where this takes 1.45 (PERF.md section 6, PR 38)."""
    if has_mask or dense_mask:  # one or the other: [bk, 1] or [bk, bq]
        kmask_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    t = pl.program_id(2)
    iq, ik, flags = iq_of[t], ik_of[t], flags_of[t]

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _body(cut):
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [bk, Dv]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * sm_scale
        valid = None
        if dense_mask:  # the tile's block of the [B, Tk, Tq] mask
            valid = kmask_ref[0].astype(jnp.int32) != 0
        elif cut:
            valid, _ = _tile_valid(iq, ik, block_q, block_k, tq, tk, causal,
                                   window, keys_first=True)
        if has_mask:  # [B, Tk] key-padding mask (left-padded prompts),
            # here a [bk, 1] column over the sublanes
            real = kmask_ref[0] > 0
            valid = real if valid is None else valid & real
        if valid is not None:
            st = jnp.where(valid, st, NEG_INF)

        m_prev = m_scr[:]                       # [1, bq]
        m_cur = jnp.max(st, axis=0, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(st - m_new)                 # [bk, bq]
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
        # vT . pT as a transposed-left product on v as it comes (the kind
        # dK/dV runs): no second layout of the values outside the kernel
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            v, p, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [Dv, bq]
        m_scr[:] = m_new

    _on_tile(flags, _body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).T.astype(o_ref.dtype)
        # compact residual, one fp32 per q row, written from the rows as
        # they are kept. It is stored as a [1, bq] ROW of [B, H, 1, Tq]: a
        # (1, bq) block of a bare [B, H, Tq] array breaks Mosaic's
        # block-shape rule
        lse_ref[0, 0] = m_scr[:] + jnp.log(l_safe)


def _pad_seq(x, block):
    t = x.shape[2]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _pad_mask(mask, rows, cols):
    return jnp.pad(mask.astype(jnp.int8),
                   ((0, 0), (0, rows - mask.shape[1]),
                    (0, cols - mask.shape[2])))


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               window=None, key_mask=None, mask=None, tiles=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    Hkv = k.shape[1]
    Dv = v.shape[3]     # values, the output and its accumulator: their own
    # width (latent attention: 192-wide queries and keys, 128-wide values)
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    rep = H // Hkv  # GQA: q head h reads kv head h // rep — no
    # repeat_kv materialization (the index map does the mapping)
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    # pad to block multiples; kernels mask with the ORIGINAL lengths
    q, k, v = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    # numpy constants, or built on the device from the mask's tiles
    table, steps = _table(tiles, Tq, Tk, bq, bk, causal, window)

    mask_args = []
    mask_specs = []
    if mask is not None:
        # keys first, as the kernel holds a tile; padding sees nothing
        mask_args = [_pad_mask(jnp.swapaxes(mask, 1, 2), Tk_p, Tq_p)]
        mask_specs = [pl.BlockSpec(
            (1, bk, bq), lambda b, h, t, iq_of, ik_of, flags_of:
            (b, ik_of[t], iq_of[t]))]
    elif key_mask is not None:
        km = jnp.pad(key_mask.astype(jnp.int32),
                     ((0, 0), (0, Tk_p - key_mask.shape[1])))
        mask_args = [km[:, :, None]]
        mask_specs = [pl.BlockSpec(
            (1, bk, 1), lambda b, h, t, iq_of, ik_of, flags_of:
            (b, ik_of[t], 0))]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, tq=Tq, tk=Tk,
                          window=window, has_mask=key_mask is not None
                          and mask is None, dense_mask=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H, steps),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), _q_tile),
                pl.BlockSpec((1, 1, bk, D), _kv_tile(rep)),
                pl.BlockSpec((1, 1, bk, Dv), _kv_tile(rep)),
            ] + mask_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, Dv), _q_tile),
                pl.BlockSpec((1, 1, 1, bq), _q_row),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, bq), jnp.float32),
                pltpu.VMEM((1, bq), jnp.float32),
                pltpu.VMEM((Dv, bq), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, Tq_p), jnp.float32),
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(*table, q, k, v, *mask_args)
    return out[:, :, :Tq], lse[:, :, 0, :Tq]  # lse: compact [B,H,Tq] fp32


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(iq_of, ik_of, flags_of, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, *rest,
                   sm_scale: float, causal: bool, block_q: int, block_k: int,
                   tq: int, tk: int, window, dense_mask: bool = False):
    mask_ref, dq_ref, dq_scr = rest if dense_mask else (None, *rest)
    t = pl.program_id(2)
    iq, ik, flags = iq_of[t], ik_of[t], flags_of[t]

    @pl.when(flags & _FIRST != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body(cut):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]            # compact [bq] residual
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if dense_mask:
            s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
        elif cut:
            valid, _ = _tile_valid(iq, ik, block_q, block_k, tq, tk, causal,
                                   window)
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[:] += sm_scale * jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    _on_tile(flags, _body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(iq_of, ik_of, flags_of, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, *rest,
                    sm_scale: float, causal: bool, block_q: int,
                    block_k: int, tq: int, tk: int, window,
                    dense_mask: bool = False, fused: bool = False):
    """dK and dV of a kv row from its query tiles and, ``fused``, dQ too
    (``ds_flash_bwd``): ``dq_scr [Tq_p, D]`` float32 is a head's whole dQ,
    zeroed at the walk's first step, added into a q tile's rows at a time
    and written to ``dq_ref``'s ``(1, 1, Tq_p, D)`` block at the last (its
    index moves with ``(b, h)`` alone: one write-back a head). Rows no kept
    tile touches stay zero."""
    mask_ref, dk_ref, dv_ref, *rest = rest if dense_mask else (None, *rest)
    dq_ref, dk_scr, dv_scr, dq_scr = rest if fused else (None, *rest, None)
    t = pl.program_id(2)
    iq, ik, flags = iq_of[t], ik_of[t], flags_of[t]

    def q_rows(i):
        return pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    def each_q_tile(fn):  # a loop, not 1,024 unrolled registers of text
        jax.lax.fori_loop(0, dq_scr.shape[0] // block_q,
                          lambda i, _: fn(q_rows(i)), None)

    if fused:
        @pl.when(t == 0)
        def _zero_dq():
            def zero(rows):
                dq_scr[rows, :] = jnp.zeros((block_q, dq_scr.shape[1]),
                                            jnp.float32)
            each_q_tile(zero)

    @pl.when(flags & _FIRST != 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body(cut):
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0][:, None]            # compact [bq] residual
        delta = delta_ref[0, 0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if dense_mask:  # its padding empties the padded q rows too
            s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
        elif cut:
            valid, rows = _tile_valid(iq, ik, block_q, block_k, tq, tk,
                                      causal, window)
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)                    # [bq, bk]
        if cut and not dense_mask:  # padded q rows (lse 0) must add zero
            p = jnp.where(rows < tq, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                   # [bq, bk]
        dk_scr[:] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if fused:  # the fifth product, on the ds the fourth just read
            dq_scr[q_rows(iq), :] += sm_scale * jax.lax.dot(
                ds, k, preferred_element_type=jnp.float32)

    _on_tile(flags, _body)

    @pl.when(flags & _LAST != 0)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)

    if fused:
        # the table's count is traced under a mask that is data
        @pl.when(t == pl.num_programs(2) - 1)
        def _write_dq():
            def cast(rows):
                dq_ref[0, 0, rows, :] = dq_scr[rows, :].astype(dq_ref.dtype)
            each_q_tile(cast)


#: the resident dQ -- the float32 buffer and the output block it is written
#: to, which the pipeline holds twice -- may take this share of a chip's
#: VMEM (``grouped_matmul._VMEM_BYTES``: the chips the kernels were timed
#: on). On a v5e a quarter is 32 MiB: 8,192 x 128 in bf16 holds 8, kimi's
#: 192-wide keys (256 lanes) and keye's 16,384 rows 16 each, and 32,768 x 128
#: is the longest that fits; 131,072 x 128 would hold the whole VMEM.
_DQ_VMEM_SHARE = 4


def fused_backward(tq_p: int, d: int, itemsize: int,
                   device_kind: str) -> Optional[int]:
    """The rule that says which backward runs, a pure function of what
    ``_flash_bwd`` sees: the bytes of VMEM a head's resident dQ takes
    (``[tq_p, d]`` in float32 and twice in the output's item size, its lanes
    padded to whole registers) where ``ds_flash_bwd`` runs, or None where
    the two kernels stay -- a dQ over the share above, and a chip whose VMEM
    is not in the table (every platform but a TPU among them: interpret
    mode on a CPU walks the two kernels unless a test answers for a chip)."""
    vmem = grouped_matmul._VMEM_BYTES.get(device_kind)
    resident = tq_p * _ceil_div(d, 128) * 128 * (4 + 2 * itemsize)
    if vmem is None or resident * _DQ_VMEM_SHARE > vmem:
        return None
    return resident


def _fused_vmem(resident, bq, bk, d, dv, itemsize, dense_mask):
    """What a ``ds_flash_bwd`` call holds: the resident dQ; the tile's
    operand and dK/dV blocks, each twice; dK/dV's float32 scratch and the
    operands' float32 copies; the four ``[bq, bk]`` float32 products (s, p,
    dp, ds); the two statistics' rows (eight sublanes each, twice) and the
    mask's block twice."""
    tile = (bq + bk) * (d + dv)
    return resident + tile * (4 * itemsize + 8) + 4 * bq * bk * 4 \
        + 4 * 8 * bq * 4 + 2 * bq * bk * dense_mask


def _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
               window=None, mask=None, tiles=None):
    q, k, v, out, lse = res
    do = g
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[2], v.shape[3]
    bq, bk = min(block_q, Tq), min(block_k, Tk)

    # compact [B,H,Tq] residuals (see _fwd_kernel finalize note)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    # pad to block multiples (kernels mask with the original lengths)
    q, do = _pad_seq(q, bq), _pad_seq(do, bq)
    k, v = _pad_seq(k, bk), _pad_seq(v, bk)
    pad_q = q.shape[2] - Tq
    if pad_q:
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)))
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    lse, delta = lse[:, :, None], delta[:, :, None]

    # q, k and their gradients are D wide; v, the output's cotangent and dv
    # Dv wide
    q_spec = pl.BlockSpec((1, 1, bq, D), _q_tile)
    k_spec = pl.BlockSpec((1, 1, bk, D), _kv_tile())
    v_spec = pl.BlockSpec((1, 1, bk, Dv), _kv_tile())
    do_spec = pl.BlockSpec((1, 1, bq, Dv), _q_tile)
    row_spec = pl.BlockSpec((1, 1, 1, bq), _q_row)
    in_specs = [q_spec, k_spec, v_spec, do_spec, row_spec, row_spec]
    kernel_kw = dict(sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
                     tq=Tq, tk=Tk, window=window)
    mask_args = []
    if mask is not None:
        mask_args = [_pad_mask(mask, Tq_p, Tk_p)]
        in_specs = in_specs + [pl.BlockSpec(
            (1, bq, bk), lambda b, h, t, iq_of, ik_of, flags_of:
            (b, iq_of[t], ik_of[t]))]
        kernel_kw["dense_mask"] = True

    def call(kernel, name, by_kv, out_specs, out_shape, scratch, **kw):
        table, steps = _table(tiles, Tq, Tk, bq, bk, causal, window, by_kv)
        return pl.pallas_call(
            functools.partial(kernel, **kernel_kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B, H, steps),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                                for shape in scratch],
            ),
            out_shape=out_shape,
            interpret=interpret,
            name=name,
            **kw,
        )(*table, q, k, v, do, lse, delta, *mask_args)

    dq_shape = jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype)
    dkv_shapes = [jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
                  jax.ShapeDtypeStruct((B, H, Tk_p, Dv), v.dtype)]
    resident = fused_backward(Tq_p, D, q.dtype.itemsize,
                              grouped_matmul.device_kind())
    if resident is not None:
        held = _fused_vmem(resident, bq, bk, D, Dv, q.dtype.itemsize,
                           mask is not None)
        dk, dv, dq = call(
            functools.partial(_bwd_dkv_kernel, fused=True), FLASH_BWD, True,
            [k_spec, v_spec, pl.BlockSpec(
                (1, 1, Tq_p, D),
                lambda b, h, t, iq_of, ik_of, flags_of: (b, h, 0, 0))],
            dkv_shapes + [dq_shape], [(bk, D), (bk, Dv), (Tq_p, D)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=grouped_matmul._vmem_limit(held)))
    else:
        # the same entries twice: by q row here, by kv row for dK/dV below
        dq = call(_bwd_dq_kernel, FLASH_BWD_DQ, False, q_spec, dq_shape,
                  [(bq, D)])
        dk, dv = call(_bwd_dkv_kernel, FLASH_BWD_DKV, True, [k_spec, v_spec],
                      dkv_shapes, [(bk, D), (bk, Dv)])
    return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention_bhtd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                          window=None):
    out, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                        window)
    return out


def _vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
             window=None):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret, window)
    # named so that a remat policy can keep them (resolve_remat_policy does,
    # under every policy): q, k, v are cheap to rebuild in jax.checkpoint's
    # replay, these two cost a whole forward kernel call. Outside a
    # jax.checkpoint the names are the identity.
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _vjp_bwd(sm_scale, causal, block_q, block_k, interpret, window, res, g):
    return _flash_bwd(res, g, sm_scale, causal, block_q, block_k, interpret,
                      window)


_flash_attention_bhtd.defvjp(_vjp_fwd, _vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_masked_bhtd(q, k, v, mask, tiles, sm_scale, causal, block_q,
                       block_k, interpret, window=None):
    """``(out, lse [B, H, Tq])`` under a mask that is data. The log-sum-exp
    is handed out for readers that detach it (the head-mean probabilities of
    ``sa_probs.py``): its cotangent is dropped."""
    return _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                      window, mask=mask, tiles=tiles)


def _masked_vjp_fwd(q, k, v, mask, tiles, sm_scale, causal, block_q, block_k,
                    interpret, window=None):
    out, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret, window, mask=mask, tiles=tiles)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return (out, lse), (q, k, v, mask, tiles, out, lse)


def _masked_vjp_bwd(sm_scale, causal, block_q, block_k, interpret, window,
                    res, g):
    *res, mask, tiles, out, lse = res
    dq, dk, dv = _flash_bwd((*res, out, lse), g[0], sm_scale, causal, block_q,
                            block_k, interpret, window, mask, tiles)
    return dq, dk, dv, None, None


_flash_masked_bhtd.defvjp(_masked_vjp_fwd, _masked_vjp_bwd)


def _reference_attention(q, k, v, causal, sm_scale, window=None,
                         key_mask=None, mask=None):
    """[B,T,H,D] einsum reference (used on non-TPU backends); with ``mask``
    ``(out, lse [B, H, Tq])`` as the kernels give them."""
    if k.shape[2] != q.shape[2]:
        # GQA (masked fwd-only path accepts un-repeated kv heads): expand
        # consecutively, matching the kernel's h // rep index map
        rep = q.shape[2] // k.shape[2]
        b, t, hk, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None], (b, t, hk, rep, d)).reshape(
            b, t, hk * rep, d)
        v = jnp.broadcast_to(v[:, :, :, None], (b, t, hk, rep, d)).reshape(
            b, t, hk * rep, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    Tq, Tk = q.shape[1], k.shape[1]
    if causal and not isinstance(window, BlockDiffusion):
        tril = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        logits = jnp.where(tril[None, None], logits, NEG_INF)
    if window is not None:
        # a sliding window's width, or a rule that stands for causality
        # too (``BlockDiffusion``)
        wmask = window_mask(window, Tq, Tk)
        logits = jnp.where(wmask[None, None], logits, NEG_INF)
    if key_mask is not None:
        logits = jnp.where((key_mask > 0)[:, None, None, :], logits,
                           NEG_INF)
    if mask is not None:
        logits = jnp.where((mask != 0)[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if mask is None:
        return out
    return out, jax.nn.logsumexp(logits, axis=-1)


def flash_attention(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None, force_pallas: bool = False,
                    window: Optional[int] = None, key_mask=None, mask=None,
                    tiles=None):
    """Flash attention over [B, T, H, D] tensors.

    ``interpret=None`` auto-selects: real kernel on TPU, reference math
    elsewhere (interpret mode is available for kernel-parity tests).

    ``key_mask`` ``[B, Tk]`` (1 = real key) masks padded keys in-kernel
    (left-padded prefill). FORWARD-ONLY: the masked path skips the
    custom-vjp wrapper (serving prefill never differentiates); taking a
    gradient through it falls to JAX's default AD over the kernel,
    which pallas_call does not support — use the unmasked path (drop
    padding via the loss mask) for training.

    ``mask`` ``[B, Tq, Tk]`` (nonzero = query sees key) is a selection that
    is data, shared by the heads, inside the causal rule and the window given
    here, with a key for every query (``tiles``: its ``mask_tiles``, if held).
    Differentiates; pre-repeated kv heads; ``(out, lse [B, H, Tq] float32)``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu and not force_pallas:
            return _reference_attention(q, k, v, causal, sm_scale, window=window,
                                        key_mask=key_mask, mask=mask)
        interpret = not on_tpu

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if mask is not None:
        if key_mask is not None or k.shape[2] != q.shape[2]:
            raise ValueError("mask= takes pre-repeated kv heads, no key_mask")
        if tiles is None:
            tiles = mask_tiles(mask, block_q, block_k)
        out, lse = _flash_masked_bhtd(qt, kt, vt, mask, tiles, sm_scale, causal,
                                      block_q, block_k, interpret, window)
        return jnp.transpose(out, (0, 2, 1, 3)), lse
    if key_mask is not None:
        # fwd-only masked path; GQA rides the kv-head index map (no
        # repeat_kv materialization)
        out, _ = _flash_fwd(qt, kt, vt, sm_scale, causal, block_q,
                            block_k, interpret, window, key_mask)
    else:
        if k.shape[2] != q.shape[2]:
            raise ValueError(
                "flash_attention training path needs pre-repeated kv "
                "heads (repeat_kv) — the dK/dV grid accumulates per "
                "head; GQA-native reads are forward-only (key_mask "
                "path)")
        out = _flash_attention_bhtd(qt, kt, vt, sm_scale, causal,
                                    block_q, block_k, interpret, window)
    return jnp.transpose(out, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# the tile table under a mask that is data
# ---------------------------------------------------------------------------


def mask_tiles(mask, block_q: int = 512, block_k: int = 512):
    """bool ``[nq, nk]``: the ``block_q x block_k`` tiles of ``mask
    [B, Tq, Tk]`` in which some sequence of the batch selects a pair. One
    pass over the mask; the tile tables of all the kernels under it
    (``flash_attention(tiles=)``, ``sa_probs.index_kl(tiles=)``) and
    the counter ``indexed_attention.kept_tile_share`` read this one array.

    The rows of a tile are folded first (an element-wise max and min of int8
    rows: any nonzero byte counts) and only the ``[nq, Tk]`` that leaves is
    reduced along the lanes; the barrier keeps XLA from fusing the mask's
    producer into that fold. At ``[1, 16384, 16384]`` on a v5e: 0.36 ms a
    call; 2.2 ms where the fold took ``unpackbits`` in with it, 2.0 as one
    ``any`` over both axes of a tile (PERF.md section 6, PR 42)."""
    B, Tq, Tk = mask.shape
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    nq, nk = _ceil_div(Tq, bq), _ceil_div(Tk, bk)
    mask = jax.lax.optimization_barrier(_pad_mask(mask, nq * bq, nk * bk))
    rows = mask.reshape(B, nq, bq, nk * bk)
    rows = (jnp.max(rows, axis=(0, 2)) != 0) | (jnp.min(rows, axis=(0, 2)) != 0)
    return jnp.any(rows.reshape(nq, nk, bk), axis=2)


def _mask_tile_table(tiles, static, by_kv=False):
    """``_tile_table``'s rows with the entries ``tiles`` empties taken out,
    built on the device: ``(int32 [3, n], count)``, the static table's
    length ``n`` with the ``count`` live entries in front. The kernels' grid
    walks ``count`` steps (a traced grid dimension): a tile the mask empties
    is neither a grid step nor a fetch, as one the rule empties.

    The live entries keep the static order (stable), every one ``_CUT`` (the
    mask's block rules inside it), ``_FIRST`` / ``_LAST`` from its new
    neighbours. A row of output tiles none of whose entries is kept holds
    its first one (by kv row: the diagonal) as a placeholder that runs no
    body, as ``_tile_table`` does for a row the rule empties: its block is
    still initialised and written, as zeros. Past ``count`` lie the dropped
    entries, which no grid step reads."""
    iq, ik, word = static
    first = word & _FIRST != 0
    row = np.cumsum(first) - 1                  # of output tiles, per entry
    start = np.flatnonzero(first)[row]
    end = np.flatnonzero(word & _LAST != 0)[row]
    body = jnp.asarray(word & _CUT != 0) & tiles[iq, ik]
    seen = jnp.cumsum(body, dtype=jnp.int32)
    empty_row = seen[end] - seen[start] + body[start] == 0
    keep = body | (jnp.asarray(first) & empty_row)
    count = jnp.sum(keep, dtype=jnp.int32)
    at = jnp.argsort(~keep, stable=True)
    outer = jnp.asarray(ik if by_kv else iq)[at]
    edge = outer[1:] != outer[:-1]
    new = body[at] * _CUT \
        | jnp.pad(edge, (1, 0), constant_values=True) * _FIRST \
        | (jnp.pad(edge, (0, 1)) | (jnp.arange(at.size) == count - 1)) * _LAST
    return jnp.stack([jnp.asarray(iq)[at], jnp.asarray(ik)[at],
                      new]).astype(jnp.int32), count


def _table(tiles, tq, tk, block_q, block_k, causal, window, by_kv=False):
    """The kernels' tile table and the steps their grid walks it:
    ``_tile_table``'s numpy constant and its length under a rule alone, a
    device array of the same form and a traced count under a mask with
    ``tiles``."""
    static = _tile_table(tq, tk, block_q, block_k, causal, window, by_kv,
                         dense_mask=tiles is not None)
    if tiles is None:
        return static, static.shape[1]
    return _mask_tile_table(tiles, static, by_kv)


# ---------------------------------------------------------------------------
# a rule in the window's place. HERE, below the kernels, and not beside
# ``_tile_table``, for the reason above: a Mosaic payload holds the line AND
# column of every frame that calls it, so one line more above a kernel (or in
# a caller, ``llama.LlamaAttention``) re-keys the compiled step of every
# cell that runs these kernels. ``_tile_table`` and ``_tile_valid`` took the
# rule within the lines they had; ``tests/unit/test_flash_attention.py``
# holds the standing cells' tables to their bytes
# ---------------------------------------------------------------------------


class BlockDiffusion(NamedTuple):
    """Block diffusion's training mask (Arriola et al., ICLR 2025) as a
    static RULE over ``[x_t ; x_0]``: positions ``0 .. half - 1`` are a noised
    copy of the sequence, ``half ..`` the clean one, both in blocks of
    ``block`` tokens. With ``clean(p) = p >= half`` and ``blk(p) = (p - half *
    clean(p)) // block``, query ``q`` sees key ``j`` iff

    - ``clean(q) == clean(j)`` and ``blk(q) == blk(j)``: a block sees itself,
      both ways; or
    - ``j`` is clean and ``blk(q) + clean(q) > blk(j)``: a noised block sees
      the clean blocks BEFORE it, a clean block those up to its own.

    A clean query never sees a noised key. ``half = 0`` is block-causal
    attention alone (a prefill over clean ids). Handed to ``flash_attention``
    (and ``layers.dot_product_attention``) as ``window=``: the slot of the
    static rule that narrows what a query sees. It stands for causality too:
    under it ``causal`` is NOT READ, at any of the sites that take the rule
    (``_tile_table``, ``_tile_valid``, ``_reference_attention``,
    ``layers.dot_product_attention``), whatever the caller passed. Hashable: a
    static argument of the kernels' ``custom_vjp`` and of their tile table,
    never an array -- at 2 x 8,192 positions and blocks of 4 the table keeps
    288 of 1,024 tiles where a ``[16384, 16384]`` mask would be 268 MB."""

    half: int
    block: int

    def _split(self, p):
        clean = p >= self.half
        return clean, (p - self.half * clean) // self.block

    def sees(self, rows, cols):
        """The rule on broadcastable position arrays (numpy or traced)."""
        (cq, bq), (cj, bj) = self._split(rows), self._split(cols)
        return ((cq == cj) & (bq == bj)) | (cj & (bq + cq > bj))

    def tiles(self, r0, r1, c0, c1):
        """``(some, whole)``: does the rule keep a pair, and every pair, of
        the tile of rows ``r0 .. r1`` and columns ``c0 .. c1`` (inclusive;
        arrays that broadcast)? Exact for any tile: a range of positions
        splits into its noised and its clean part, each a range of blocks
        ``[a0, a1]`` against ``[b0, b1]``, under one clause each."""
        some, whole = False, True
        for cq in (False, True):
            (qa, qb), q_has = self._part(r0, r1, cq)
            for cj in (False, True):
                (ka, kb), k_has = self._part(c0, c1, cj)
                if cq == cj:    # same block; and among the clean, earlier
                    any_ = (qb >= ka) & ((kb >= qa) | cq)
                    all_ = (qa >= kb) & ((qb <= ka) | cq)
                else:           # noised sees clean before it; never back
                    any_, all_ = cj & (qb > ka), cj & (qa > kb)
                has = q_has & k_has
                some, whole = some | (has & any_), whole & (~has | all_)
        return some, whole

    def _part(self, p0, p1, clean):
        """Block range of the noised (clean) part of ``p0 .. p1``, and
        whether it holds a position."""
        lo = np.maximum(p0, self.half) if clean else p0
        hi = p1 if clean else np.minimum(p1, self.half - 1)
        return self._split(np.stack([lo, hi]))[1], lo <= hi


def _ruled(window, rows, cols):
    """A cut tile's mask under a rule; None where ``window`` is a width."""
    if isinstance(window, BlockDiffusion):
        return window.sees(rows, cols)
    return None


def window_mask(window, tq: int, tk: int):
    """bool ``[tq, tk]``: the pairs a sliding window's width (inside a causal
    mask the caller applies) or a ``BlockDiffusion`` keeps -- the einsum paths'
    dense form of what the kernels read off the tile table."""
    i, j = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
    if isinstance(window, BlockDiffusion):
        return window.sees(i, j)
    return i + (tk - tq) - j < window


def rule_tile_share(rule, t: int, block_q: int = 512,
                    block_k: int = 512) -> float:
    """The tiles the kernels walk under ``rule`` over ``t`` positions (the
    forward's table; the backward's holds the same entries) over all
    ``ceil(t / block)**2``: a constant of the shapes, 288 / 1,024 at 2 x 8,192
    positions and blocks of 4."""
    bq, bk = min(block_q, t), min(block_k, t)
    word = _tile_table(t, t, bq, bk, True, rule)[2]
    return float(np.sum(word & (_INSIDE | _CUT) != 0)) \
        / (_ceil_div(t, bq) * _ceil_div(t, bk))
