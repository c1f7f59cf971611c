"""What stands around the gated delta rule in a linear-attention layer
(``models/qwen3_next.py GatedDeltaNet``): between ``in_proj_qkvz`` and the
rule one kernel with its backward (``premix``), between the rule and
``out_proj`` another with its backward (``gate``). All of it is element-wise
or a sum along a head's lanes; as XLA fusions it moved three times the bytes
it needs (PERF.md section 5).

``qkvz [B, T, Hk W]`` holds, a KEY head, ``W = 2 dk + 2 r dv`` columns as
published: ``dk`` of q, ``dk`` of k, ``r dv`` of v, ``r dv`` of z (``r``
value heads a key head). The kernels read them IN PLACE through block specs
-- with ``2 dk == r dv`` a key head is three blocks of one width, ``[q k]``,
``v`` and ``z`` -- and nothing regroups, splits or concatenates the array.

*``premix``* (``ds_gdn_premix_fwd``): grid ``(batch, key head, time tile)``;
a step convolves the tile's ``[q k]`` and ``v`` columns causally over
``taps`` rows (the rows before the tile come through a second spec on the
same array, zeros before position 0), applies ``conv_act``, rounds to the
operands' type, brings q and k to ``unit_length`` a head in float32, scales
q by ``dk ** -0.5``, rounds again, and writes ``q, k [B, T, Hv dk]`` (a key
head's block once to each of its ``r`` value heads) and ``v [B, T, Hv dv]``:
the rows-of-time layout ``gdn_rule.chunk_rule`` takes. ``conv_act`` and
``unit_length`` are the MODEL's functions, handed over at trace time: the
tile body calls them, and the backward is their ``jax.vjp`` in the kernel.

*``gate``* (``ds_gdn_gate_fwd``): reads the rule's ``o [B, T, Hv dv]`` and z
in place, ``scale * o rsqrt(mean(o^2) + eps) * silu(z)`` a head in float32,
written in the operands' type.

*The backwards*: ``ds_gdn_gate_bwd`` reads ``do``, ``o``, z and writes ``d
o``, ``dz [B, T, Hv dv]`` and the partial sums of ``d scale``;
``ds_gdn_premix_bwd`` walks the time tiles from the LAST (the convolution's
transpose needs the gradient at the convolution's output for the rows that
follow: the following tile's first rows wait in VMEM), recomputes the tile's
convolution, sums dq and dk over a key head's value heads, accumulates ``d
taps`` over time in VMEM, and writes the gradient of ``qkvz`` WHOLE, ``dz``
copied into its columns -- no concatenate follows.

*How ``dz`` gets there*: ``premix`` also returns a HANDLE for z, ``[B, T, Hv
dv]`` zeros that no kernel reads (XLA never builds them); ``gate`` takes the
handle beside ``qkvz``, declares ``dz`` the handle's cotangent and none for
``qkvz``, and ``premix``'s backward receives it as the cotangent of its
fourth output. The pair differentiates as the mixer does; neither half alone
is the derivative of what it computes, so the two are used together.

``plan`` says where the kernels run, a pure function of what the call site
sees; no option selects any of it. Everywhere else the XLA form of
``models/qwen3_next.py`` (``_premix_xla``, ``_gate_xla``) stands, which is
also what the tests hold the kernels to.
"""

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import (GDN_GATE_BWD, GDN_GATE_FWD, GDN_PREMIX_BWD, GDN_PREMIX_FWD,
               grouped_matmul)

_F32 = jnp.float32
#: rows of the spec that brings the rows BEFORE a tile (a packed two-byte
#: tile is 16 sublanes); the last ``_HALO`` of them reach the float32
#: scratch -- a float32 tile's 8 sublanes, which is also the rows of the
#: small accumulators' blocks (``d taps``: a row a tap; ``d scale``: row 0)
_BEFORE, _HALO = 16, 8


class Tiling(NamedTuple):
    """Positions a grid step (a multiple of ``_BEFORE``)."""
    rows: int


class Heads(NamedTuple):
    """The mixer's widths: key heads, a key head's q (and k) columns, value
    heads a key head, a value head's columns, the convolution's taps."""
    key_heads: int
    dk: int
    r: int
    dv: int
    taps: int

    @property
    def qk(self):
        return 2 * self.dk

    @property
    def v(self):
        return self.r * self.dv


#: positions a grid step on a v5e at 16 key heads of 128 + 128 + 256 + 256
#: columns (PERF.md section 6, PR 55)
_TILING = Tiling(1024)


def plan(platform: str, mesh_devices: int, heads: Heads, itemsize: int = 2,
         device_kind: str = "TPU v5 lite") -> Optional[Tiling]:
    """The tiling the kernels take the mixer of ``heads`` with, or None
    where the XLA form stays: off a TPU or on one that is not in
    ``grouped_matmul._VMEM_BYTES``, under a mesh of several devices (a
    Mosaic call is not partitioned), operands that are not two bytes wide,
    heads that are no whole lanes (the tiny test sizes), a key head whose
    ``[q k]``, ``v`` and ``z`` columns are not three blocks of one width,
    more taps than the halo holds."""
    if platform != "tpu" or mesh_devices > 1 or itemsize != 2 \
            or device_kind not in grouped_matmul._VMEM_BYTES:
        return None
    if heads.dk % 128 or heads.dv % 128 or not _fits(heads):
        return None
    return _TILING


def _fits(heads: Heads) -> bool:
    """What the index maps and the accumulators' blocks take for granted,
    whatever the lanes: ``[q k]``, ``v`` and ``z`` are three blocks of one
    width, and a tap has a row of ``_HALO``."""
    return heads.qk == heads.v and heads.taps <= _HALO


class _Static(NamedTuple):
    """What a trace is keyed on: the widths, rows a step, interpret mode,
    the model's two functions, the norm's epsilon."""
    heads: Heads
    rows: int
    interpret: bool
    conv_act: Optional[Callable] = None
    unit_length: Optional[Callable] = None
    eps: float = 0.0


def _rows(T, rows):
    """Rows a step: ``rows``, or ``T`` rounded up to whole packed tiles
    where that is less."""
    return min(rows, -(-T // _BEFORE) * _BEFORE)


def _valid(tile, T, tt, width):
    """``[tt, width]``: the row is a position of the sequence (None where
    every tile is whole)."""
    if T % tt == 0:
        return None
    row = jax.lax.broadcasted_iota(jnp.int32, (tt, width), 0)
    return row + tile * tt < T


def _conv(x_scr, cur, before, first, taps, valid=None):
    """The causal convolution of a tile: ``cur [tt, L]``, ``before [16, L]``
    the rows ahead of it (``first``: there are none, zeros), ``taps [K, L]``
    float32 -> ``(y [tt, L] float32, the K windows of x it summed: window s
    is x shifted s rows back)``. Tap ``K - 1`` meets the current row, as
    ``layers.causal_conv`` has it, and the terms add in its order."""
    tt, K = cur.shape[0], taps.shape[0]
    cur = cur.astype(_F32)
    if valid is not None:
        cur = jnp.where(valid, cur, 0.0)
    x_scr[0:_HALO] = jnp.where(first, 0.0, before.astype(_F32)[_HALO:])
    x_scr[_HALO:_HALO + tt] = cur
    windows = [cur] + [x_scr[pl.ds(_HALO - s, tt), :] for s in range(1, K)]
    y = windows[0] * taps[K - 1:K]
    for s in range(1, K):
        y = y + windows[s] * taps[K - 1 - s:K - s]
    return y, windows


def _rounded(x, dtype):
    """``x`` float32 at ``dtype``'s precision."""
    return x.astype(dtype).astype(_F32)


# -- premix -------------------------------------------------------------------

def _premix_fwd_kernel(qk_ref, v_ref, qk_before, v_before, taps_ref, q_out,
                       k_out, v_out, x_scr, *, static: _Static):
    hd, dtype = static.heads, q_out.dtype
    first = pl.program_id(2) == 0
    taps = _rounded(taps_ref[...], dtype)
    y, _ = _conv(x_scr, qk_ref[0], qk_before[0], first, taps[:, :hd.qk])
    a = _rounded(static.conv_act(y), dtype)
    q = (static.unit_length(a[:, :hd.dk]) * hd.dk ** -0.5).astype(dtype)
    k = static.unit_length(a[:, hd.dk:]).astype(dtype)
    for i in range(hd.r):       # a key head's block to each of its values
        q_out[0, :, i * hd.dk:(i + 1) * hd.dk] = q
        k_out[0, :, i * hd.dk:(i + 1) * hd.dk] = k
    y, _ = _conv(x_scr, v_ref[0], v_before[0], first, taps[:, hd.qk:])
    v_out[0] = static.conv_act(y).astype(dtype)


def _premix_bwd_kernel(qk_ref, v_ref, qk_before, v_before, taps_ref, dq_ref,
                       dk_ref, dv_ref, dz_ref, dx_out, dtaps_out, x_scr,
                       dy_scr, *, static: _Static, T: int):
    hd, dtype = static.heads, dx_out.dtype
    step, tiles = pl.program_id(2), pl.num_programs(2)
    tile = tiles - 1 - step                 # from the last tile to the first
    tt, K = qk_ref.shape[1], hd.taps
    valid = _valid(tile, T, tt, hd.qk)
    taps = _rounded(taps_ref[...], dtype)

    @pl.when(step == 0)
    def _init():
        dtaps_out[...] = jnp.zeros_like(dtaps_out)
        dy_scr[...] = jnp.zeros_like(dy_scr)

    def through_conv(cur, before, taps, da_of, piece, lanes):
        """One of the two blocks of columns: the convolution recomputed,
        ``da_of(a)`` the gradient at the rounded activation, ``d x`` of the
        tile; ``d taps`` accumulated; the rows of ``dy`` the tile before
        this one needs left in ``dy_scr[piece]``."""
        y, windows = _conv(x_scr, cur, before, tile == 0, taps, valid)
        a, vjp_act = jax.vjp(static.conv_act, y)
        (dy,) = vjp_act(da_of(_rounded(a, dtype)))
        if valid is not None:
            dy = jnp.where(valid, dy, 0.0)
        scr = dy_scr.at[piece]
        scr[0:tt] = dy
        dx = dy * taps[K - 1:K]
        for s in range(1, K):               # dy of the s-th row that follows
            dx = dx + scr[pl.ds(s, tt), :] * taps[K - 1 - s:K - s]
        scr[tt:tt + _HALO] = dy[0:_HALO]
        for s in range(K):
            dtaps_out[0, K - 1 - s:K - s, lanes] += jnp.sum(
                dy * windows[s], axis=0, keepdims=True)
        return dx

    def da_qk(a):
        cot = lambda ref: sum(                   # over the key head's values
            ref[0, :, i * hd.dk:(i + 1) * hd.dk].astype(_F32)
            for i in range(hd.r))
        _, vjp_q = jax.vjp(static.unit_length, a[:, :hd.dk])
        _, vjp_k = jax.vjp(static.unit_length, a[:, hd.dk:])
        (da_q,) = vjp_q(cot(dq_ref) * hd.dk ** -0.5)
        (da_k,) = vjp_k(cot(dk_ref))
        return jnp.concatenate([da_q, da_k], axis=1)

    dx = through_conv(qk_ref[0], qk_before[0], taps[:, :hd.qk], da_qk, 0,
                      slice(0, hd.qk))
    dx_out[0, :, 0:hd.qk] = dx.astype(dtype)
    dx = through_conv(v_ref[0], v_before[0], taps[:, hd.qk:],
                      lambda a: dv_ref[0].astype(_F32), 1,
                      slice(hd.qk, hd.qk + hd.v))
    dx_out[0, :, hd.qk:hd.qk + hd.v] = dx.astype(dtype)
    dx_out[0, :, hd.qk + hd.v:] = dz_ref[0]


def _premix_specs(hd: Heads, tt, at):
    """Block specs by name over the grid ``(batch, key head, step)``; ``at``
    gives a step's time tile. ``qk``, ``v``: a key head's columns of
    ``qkvz`` in place; ``*_before``: the 16 rows ahead of the tile (the
    first tile's are its own first rows: the kernel takes zeros); ``head``:
    a key head's columns of ``[B, T, Hv d]``; ``whole``: its columns of
    ``qkvz``."""
    ahead = lambda j: jnp.maximum(at(j) * (tt // _BEFORE) - 1, 0)
    return {
        "qk": pl.BlockSpec((1, tt, hd.qk), lambda b, h, j: (b, at(j), 3 * h)),
        "v": pl.BlockSpec((1, tt, hd.v),
                          lambda b, h, j: (b, at(j), 3 * h + 1)),
        "qk_before": pl.BlockSpec((1, _BEFORE, hd.qk),
                                  lambda b, h, j: (b, ahead(j), 3 * h)),
        "v_before": pl.BlockSpec((1, _BEFORE, hd.v),
                                 lambda b, h, j: (b, ahead(j), 3 * h + 1)),
        "taps": pl.BlockSpec((hd.taps, hd.qk + hd.v),
                             lambda b, h, j: (0, h)),
        "head": pl.BlockSpec((1, tt, hd.v), lambda b, h, j: (b, at(j), h)),
        "whole": pl.BlockSpec((1, tt, hd.qk + 2 * hd.v),
                              lambda b, h, j: (b, at(j), h)),
        "dtaps": pl.BlockSpec((1, _HALO, hd.qk + hd.v),
                              lambda b, h, j: (b, 0, h)),
    }


def _params(static, held):
    if static.interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=grouped_matmul._vmem_limit(held))}


# jitted entries: a step's three delta-rule layers trace and lower each
# kernel once, not once a call site
@functools.partial(jax.jit, static_argnums=(2,))
def _premix_fwd(qkvz, taps, static: _Static):
    hd = static.heads
    B, T, _ = qkvz.shape
    tt = _rows(T, static.rows)
    specs = _premix_specs(hd, tt, lambda j: j)
    out = jax.ShapeDtypeStruct((B, T, hd.key_heads * hd.v), qkvz.dtype)
    return pl.pallas_call(
        functools.partial(_premix_fwd_kernel, static=static),
        grid=(B, hd.key_heads, pl.cdiv(T, tt)),
        in_specs=[specs[s] for s in ("qk", "v", "qk_before", "v_before",
                                     "taps")],
        out_specs=[specs["head"]] * 3, out_shape=[out] * 3,
        scratch_shapes=[pltpu.VMEM((_HALO + tt, hd.qk), _F32)],
        name=GDN_PREMIX_FWD, interpret=static.interpret,
        # five blocks of a step held twice, the float32 tile a dozen times
        **_params(static, tt * hd.qk * (20 + 48)))(
            qkvz, qkvz, qkvz, qkvz, taps)


@functools.partial(jax.jit, static_argnums=(6,))
def _premix_bwd(qkvz, taps, dq, dk, dv, dz, static: _Static):
    hd = static.heads
    B, T, _ = qkvz.shape
    tt = _rows(T, static.rows)
    tiles = pl.cdiv(T, tt)
    specs = _premix_specs(hd, tt, lambda j: tiles - 1 - j)
    return pl.pallas_call(
        functools.partial(_premix_bwd_kernel, static=static, T=T),
        grid=(B, hd.key_heads, tiles),
        in_specs=[specs[s] for s in ("qk", "v", "qk_before", "v_before",
                                     "taps", "head", "head", "head", "head")],
        out_specs=[specs["whole"], specs["dtaps"]],
        out_shape=[jax.ShapeDtypeStruct(qkvz.shape, qkvz.dtype),
                   jax.ShapeDtypeStruct(
                       (B, _HALO, hd.key_heads * (hd.qk + hd.v)), _F32)],
        scratch_shapes=[pltpu.VMEM((_HALO + tt, hd.qk), _F32),
                        pltpu.VMEM((2, tt + _HALO, hd.qk), _F32)],
        name=GDN_PREMIX_BWD, interpret=static.interpret,
        **_params(static, tt * hd.qk * (36 + 80)))(
            qkvz, qkvz, qkvz, qkvz, taps, dq, dk, dv, dz)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _premix(qkvz, taps, static):
    return _premix_vjp_fwd(qkvz, taps, static)[0]


def _premix_vjp_fwd(qkvz, taps, static):
    q, k, v = _premix_fwd(qkvz, taps, static)
    return (q, k, v, jnp.zeros_like(v)), (qkvz, taps)


def _premix_vjp_bwd(static, res, cts):
    qkvz, taps = res
    dx, dtaps = _premix_bwd(qkvz, taps, *cts, static)
    return dx, jnp.sum(dtaps, axis=0)[:static.heads.taps]


_premix.defvjp(_premix_vjp_fwd, _premix_vjp_bwd)


def _by_head(taps, hd: Heads):
    """``[K, 2 Hk dk + Hv dv]`` in the convolution's ``[q ; k ; v]`` channel
    order -> ``[K, Hk (2 dk + r dv)]``, a key head's ``[q k v]`` taps
    together (32 K numbers a tap at the published widths)."""
    K, Hk = taps.shape[0], hd.key_heads
    q, k, v = jnp.split(taps, (Hk * hd.dk, 2 * Hk * hd.dk), axis=1)
    return jnp.concatenate(
        [q.reshape(K, Hk, hd.dk), k.reshape(K, Hk, hd.dk),
         v.reshape(K, Hk, hd.v)], axis=-1).reshape(K, -1)


def premix(qkvz, taps, heads: Heads, conv_act, unit_length, tiling: Tiling,
           interpret: Optional[bool] = None):
    """``(q, k [B, T, Hv dk], v [B, T, Hv dv], z's handle)`` from ``qkvz [B,
    T, Hk W]`` as published and ``taps [K, 2 Hk dk + Hv dv]`` (float32, the
    convolution's ``[q ; k ; v]`` channel order; rounded to qkvz's type as
    the XLA form rounds them): q, k, v convolved, through ``conv_act``, q
    and k to ``unit_length`` and q scaled, each key head's q and k at each
    of its ``r`` value heads. It differentiates with respect to ``qkvz`` and
    ``taps``; the handle goes to ``gate`` (the module's docstring has why).
    ``interpret=None``: the kernels on a TPU, interpret mode elsewhere."""
    if not _fits(heads):
        raise ValueError(f"{heads}: the kernels take 2 dk == r dv and at "
                         f"most {_HALO} taps (plan leaves the others to XLA)")
    static = _Static(heads, tiling.rows, grouped_matmul._interpret(interpret),
                     conv_act, unit_length)
    return _premix(qkvz, _by_head(taps.astype(_F32), heads), static)


# -- gate -----------------------------------------------------------------------

def _gate_tile(o, z, scale, eps):
    """A value head's rows: ``o, z [tt, dv]`` float32, ``scale [1, dv]``."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale
    return o * jax.nn.silu(z)


def _gate_fwd_kernel(o_ref, z_ref, scale_ref, out, *, static: _Static):
    hd = static.heads
    for i in range(hd.r):
        lanes = slice(i * hd.dv, (i + 1) * hd.dv)
        out[0, :, lanes] = _gate_tile(
            o_ref[0, :, lanes].astype(_F32), z_ref[0, :, lanes].astype(_F32),
            scale_ref[...], static.eps).astype(out.dtype)


def _gate_bwd_kernel(g_ref, o_ref, z_ref, scale_ref, do_out, dz_out,
                     dscale_out, *, static: _Static, T: int):
    hd = static.heads
    valid = _valid(pl.program_id(2), T, g_ref.shape[1], hd.dv)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dscale_out[...] = jnp.zeros_like(dscale_out)

    for i in range(hd.r):
        lanes = slice(i * hd.dv, (i + 1) * hd.dv)
        g, o, z = (r[0, :, lanes].astype(_F32) for r in (g_ref, o_ref, z_ref))
        if valid is not None:
            g, o, z = (jnp.where(valid, x, 0.0) for x in (g, o, z))
        _, vjp = jax.vjp(functools.partial(_gate_tile, eps=static.eps), o, z,
                         scale_ref[...])
        do, dz, dscale = vjp(g)
        do_out[0, :, lanes] = do.astype(do_out.dtype)
        dz_out[0, :, lanes] = dz.astype(dz_out.dtype)
        dscale_out[0, 0, 0:1] += dscale


def _gate_specs(hd: Heads, tt):
    return {
        "head": pl.BlockSpec((1, tt, hd.v), lambda b, h, j: (b, j, h)),
        "z": pl.BlockSpec((1, tt, hd.v), lambda b, h, j: (b, j, 3 * h + 2)),
        "scale": pl.BlockSpec((1, hd.dv), lambda b, h, j: (0, 0)),
        "dscale": pl.BlockSpec((1, 1, _HALO, hd.dv),
                               lambda b, h, j: (b, h, 0, 0)),
    }


@functools.partial(jax.jit, static_argnums=(3,))
def _gate_fwd(o, qkvz, scale, static: _Static):
    hd = static.heads
    B, T, _ = o.shape
    tt = _rows(T, static.rows)
    specs = _gate_specs(hd, tt)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, static=static),
        grid=(B, hd.key_heads, pl.cdiv(T, tt)),
        in_specs=[specs[s] for s in ("head", "z", "scale")],
        out_specs=specs["head"],
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        name=GDN_GATE_FWD, interpret=static.interpret,
        **_params(static, tt * hd.v * (12 + 24)))(o, qkvz, scale)


@functools.partial(jax.jit, static_argnums=(4,))
def _gate_bwd(g, o, qkvz, scale, static: _Static):
    hd = static.heads
    B, T, _ = o.shape
    tt = _rows(T, static.rows)
    specs = _gate_specs(hd, tt)
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, static=static, T=T),
        grid=(B, hd.key_heads, pl.cdiv(T, tt)),
        in_specs=[specs[s] for s in ("head", "head", "z", "scale")],
        out_specs=[specs["head"], specs["head"], specs["dscale"]],
        out_shape=[like, like, jax.ShapeDtypeStruct(
            (B, hd.key_heads, _HALO, hd.dv), _F32)],
        name=GDN_GATE_BWD, interpret=static.interpret,
        **_params(static, tt * hd.v * (20 + 48)))(g, o, qkvz, scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gate(o, z_handle, qkvz, scale, static):
    return _gate_fwd(o, qkvz, scale, static)


def _gate_vjp_fwd(o, z_handle, qkvz, scale, static):
    return _gate_fwd(o, qkvz, scale, static), (o, qkvz, scale)


def _gate_vjp_bwd(static, res, g):
    o, qkvz, scale = res
    do, dz, dscale = _gate_bwd(g, o, qkvz, scale, static)
    # dz is the HANDLE's cotangent and qkvz has none here: premix's backward
    # writes it into the gradient of qkvz
    return do, dz, None, jnp.sum(dscale, axis=(0, 1, 2))[None]


_gate.defvjp(_gate_vjp_fwd, _gate_vjp_bwd)


def gate(o, z_handle, qkvz, scale, eps, heads: Heads, tiling: Tiling,
         interpret: Optional[bool] = None):
    """``scale * rms_norm(o) * silu(z)`` a value head, ``[B, T, Hv dv]`` in
    o's type, from the rule's ``o [B, T, Hv dv]``, ``premix``'s handle, the
    ``qkvz`` it was given (z is read in place) and ``scale [dv]`` float32.
    It differentiates with respect to ``o``, ``scale`` and, through the
    handle and ``premix``'s backward, ``qkvz``."""
    static = _Static(heads, tiling.rows, grouped_matmul._interpret(interpret),
                     eps=float(eps))
    return _gate(o, z_handle, qkvz, scale.astype(_F32)[None], static)
