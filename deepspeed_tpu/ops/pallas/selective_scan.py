"""The selective scan of a state-space (Mamba) layer, forward and backward.

``h_t = exp(delta_t A) * h_{t-1} + (delta_t u_t) (x) B_t``, ``h_{-1} = 0``;
``y_t = h_t C_t + D u_t`` -- ``u, delta [B, T, C]`` over ``C`` channels, each
with ``N`` states (``A [C, N]``, negative), ``B_t, C_t [N]`` shared by the
channels, everything in float32 whatever the operands' type. Nothing of shape
``[T, C, N]`` exists on either path: the sequence is walked a CHUNK at a time
and only the state at each chunk's start is kept for the backward
(``T / chunk x C x N`` float32).

*The kernels* (``ds_ssm_scan_fwd``, ``ds_ssm_scan_bwd``): grid ``(batch,
channel tiles, chunks)``, the chunk axis last and sequential; a block is a
chunk of time on the sublanes by a tile of channels on the lanes, and the
state ``[N, channel tile]`` (states on the sublanes) lives in VMEM scratch
from chunk to chunk. A step is element-wise on the state's vregs: ``delta_t``
and ``u_t`` are rows that broadcast along the sublanes, ``B_t`` and ``C_t``
columns that broadcast along the lanes -- they come transposed, ``[N, T]``, and
a step's column is selected out of a 128-lane slab -- and ``y_t`` is one
reduction over the sublanes. Steps run in groups of 16 (one packed bf16 tile
of ``u``), unrolled, under a ``fori_loop`` over the groups.

The backward walks the chunks in reverse. For a chunk it first recomputes the
state BEFORE each step from the chunk's saved boundary into VMEM
(``chunk x N x tile`` float32: 4 MB at 128 x 16 x 512), then runs the steps
backwards carrying ``dL/dh``; ``dA`` and ``dD`` accumulate in blocks that stay
resident over the chunk axis (one a batch row, summed outside), ``dB`` and
``dC`` leave as one partial a channel tile (summed outside).

*The XLA path* (``impl="xla"``; what a CPU and a multi-device mesh run: no
Pallas under a mesh) is the same chunking as a ``lax.scan`` over chunks, an
associative scan inside a chunk, the chunk body rematerialised so that its
backward too keeps boundary states only.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import SSM_SCAN_BWD, SSM_SCAN_FWD

_F32 = jnp.float32


def _column(slab, lane, idx):
    """Column ``idx`` of ``slab [N, W]`` as ``[N, 1]``."""
    return jnp.sum(jnp.where(lane == idx, slab, 0.0), axis=1, keepdims=True)


def _rows(ref, t0, group):
    return ref[0, pl.ds(t0, group), :].astype(_F32)


def _slab(ref, s0, width):
    return ref[0, :, pl.ds(s0, width)]


def _fwd_kernel(u_ref, d_ref, at_ref, bt_ref, ct_ref, dd_ref, y_ref, hs_ref,
                h_scr, *, group: int, width: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[0, 0] = h_scr[...]                   # the chunk's boundary
    at, dd = at_ref[...], dd_ref[...]           # [N, Ct], [1, Ct]
    lane = jax.lax.broadcasted_iota(jnp.int32, (at.shape[0], width), 1)

    def steps(g, h):
        t0 = pl.multiple_of(g * group, group)
        s0 = pl.multiple_of((t0 // width) * width, width)
        u, d = _rows(u_ref, t0, group), _rows(d_ref, t0, group)
        bs, cs = _slab(bt_ref, s0, width), _slab(ct_ref, s0, width)
        out = []
        for j in range(group):
            dj, uj = d[j:j + 1], u[j:j + 1]
            h = jnp.exp(dj * at) * h \
                + (dj * uj) * _column(bs, lane, t0 - s0 + j)
            out.append(jnp.sum(h * _column(cs, lane, t0 - s0 + j), axis=0,
                               keepdims=True) + dd * uj)
        y_ref[0, pl.ds(t0, group), :] = jnp.concatenate(out, axis=0)
        return h

    h_scr[...] = jax.lax.fori_loop(0, u_ref.shape[1] // group, steps,
                                   h_scr[...])


def _bwd_kernel(u_ref, d_ref, at_ref, bt_ref, ct_ref, dd_ref, hs_ref, dy_ref,
                du_ref, ddelta_ref, dat_ref, dbt_ref, dct_ref, ddd_ref,
                hist_scr, g_scr, *, group: int, width: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        g_scr[...] = jnp.zeros_like(g_scr)
        dat_ref[...] = jnp.zeros_like(dat_ref)
        ddd_ref[...] = jnp.zeros_like(ddd_ref)

    dbt_ref[...] = jnp.zeros_like(dbt_ref)
    dct_ref[...] = jnp.zeros_like(dct_ref)
    at, dd = at_ref[...], dd_ref[...]
    groups = u_ref.shape[1] // group
    lane = jax.lax.broadcasted_iota(jnp.int32, (at.shape[0], width), 1)

    def recompute(g, h):                        # the state BEFORE each step
        t0 = pl.multiple_of(g * group, group)
        s0 = pl.multiple_of((t0 // width) * width, width)
        u, d = _rows(u_ref, t0, group), _rows(d_ref, t0, group)
        bs = _slab(bt_ref, s0, width)
        for j in range(group):
            hist_scr[t0 + j] = h
            dj = d[j:j + 1]
            h = jnp.exp(dj * at) * h \
                + (dj * u[j:j + 1]) * _column(bs, lane, t0 - s0 + j)
        return h

    jax.lax.fori_loop(0, groups, recompute, hs_ref[0, 0])

    def steps(i, carry):
        gh, da, ddd = carry                     # dL/dh_t from the steps after
        t0 = pl.multiple_of((groups - 1 - i) * group, group)
        s0 = pl.multiple_of((t0 // width) * width, width)
        u, d = _rows(u_ref, t0, group), _rows(d_ref, t0, group)
        dy = _rows(dy_ref, t0, group)
        bs, cs = _slab(bt_ref, s0, width), _slab(ct_ref, s0, width)
        dbs, dcs = _slab(dbt_ref.at[0], s0, width), \
            _slab(dct_ref.at[0], s0, width)
        du, ddelta = [None] * group, [None] * group
        for j in reversed(range(group)):
            at_t = t0 - s0 + j
            dj, uj, dyj = d[j:j + 1], u[j:j + 1], dy[j:j + 1]
            b, c = _column(bs, lane, at_t), _column(cs, lane, at_t)
            before = hist_scr[t0 + j]
            decay = jnp.exp(dj * at)
            h = decay * before + (dj * uj) * b
            gh = gh + c * dyj
            dcs = jnp.where(lane == at_t, jnp.sum(
                h * dyj, axis=1, keepdims=True), dcs)
            dbs = jnp.where(lane == at_t, jnp.sum(
                gh * (dj * uj), axis=1, keepdims=True), dbs)
            s = jnp.sum(gh * b, axis=0, keepdims=True)
            gh = gh * decay                     # dL/dh_{t-1} through the decay
            q = gh * before
            ddelta[j] = jnp.sum(q * at, axis=0, keepdims=True) + uj * s
            du[j] = dd * dyj + dj * s
            da = da + q * dj
            ddd = ddd + dyj * uj
        du_ref[0, pl.ds(t0, group), :] = jnp.concatenate(du, axis=0)
        ddelta_ref[0, pl.ds(t0, group), :] = jnp.concatenate(ddelta, axis=0)
        dbt_ref[0, 0, :, pl.ds(s0, width)] = dbs
        dct_ref[0, 0, :, pl.ds(s0, width)] = dcs
        return gh, da, ddd

    gh, da, ddd = jax.lax.fori_loop(
        0, groups, steps,
        (g_scr[...], jnp.zeros_like(at), jnp.zeros_like(dd)))
    g_scr[...] = gh
    dat_ref[0] += da
    ddd_ref[0] += ddd


def _tile_of(channels, tile):
    """The largest of ``tile`` and its halves down to 128 that divides the
    channels, else all of them in one tile."""
    while tile >= 128:
        if channels % tile == 0:
            return tile
        tile //= 2
    return channels


def _plan(u, delta, A, B, C, D, chunk, tile, interpret, reverse):
    """``(operands, block specs, pallas_call keywords, the kernel's static
    sizes)``. The operands as the kernels take them: time padded to whole
    chunks (a padded step has ``delta = 0``: the state passes through it),
    ``A``, ``B`` and ``C`` with the states first. ``reverse`` walks the
    chunks from the last."""
    T, N = u.shape[1], A.shape[1]
    chunk = min(chunk, -(-T // 8) * 8)
    pad = (-T) % chunk
    seq = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
    states_first = lambda x: jnp.swapaxes(seq(x).astype(_F32), 1, 2)
    ops = (seq(u), seq(delta), A.astype(_F32).T, states_first(B),
           states_first(C), D.astype(_F32)[None, :])
    Bt, Tp, Cd = ops[0].shape
    tile, K = _tile_of(Cd, tile), Tp // chunk
    at = (lambda k: K - 1 - k) if reverse else (lambda k: k)
    specs = {
        "seq": pl.BlockSpec((1, chunk, tile), lambda b, c, k: (b, at(k), c)),
        "a": pl.BlockSpec((N, tile), lambda b, c, k: (0, c)),
        "bc": pl.BlockSpec((1, N, chunk), lambda b, c, k: (b, 0, at(k))),
        "d": pl.BlockSpec((1, tile), lambda b, c, k: (0, c)),
        "hs": pl.BlockSpec((1, 1, N, tile), lambda b, c, k: (b, at(k), 0, c)),
        "da": pl.BlockSpec((1, N, tile), lambda b, c, k: (b, 0, c)),
        "dbc": pl.BlockSpec((1, 1, N, chunk),
                            lambda b, c, k: (b, c, 0, at(k))),
        "dd": pl.BlockSpec((1, 1, tile), lambda b, c, k: (b, 0, c)),
    }
    call = dict(grid=(Bt, Cd // tile, K), interpret=interpret)
    if not interpret:
        call["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    # steps run in groups of one packed tile of rows; B_t and C_t are
    # selected out of a slab of at most 128 lanes
    sizes = dict(group=16 if chunk % 16 == 0 else 8, width=min(128, chunk))
    return ops, specs, call, sizes


def _scan_fwd(u, delta, A, B, C, D, chunk, tile, interpret):
    T, N = u.shape[1], A.shape[1]
    ops, specs, call, sizes = _plan(u, delta, A, B, C, D, chunk, tile,
                                    interpret, False)
    (Bt, Tp, Cd), (_, tiles, K) = ops[0].shape, call["grid"]
    y, hs = pl.pallas_call(
        functools.partial(_fwd_kernel, **sizes),
        in_specs=[specs[k] for k in ("seq", "seq", "a", "bc", "bc", "d")],
        out_specs=[specs["seq"], specs["hs"]],
        out_shape=[jax.ShapeDtypeStruct((Bt, Tp, Cd), _F32),
                   jax.ShapeDtypeStruct((Bt, K, N, Cd), _F32)],
        scratch_shapes=[pltpu.VMEM((N, Cd // tiles), _F32)],
        name=SSM_SCAN_FWD, **call)(*ops)
    return y[:, :T], hs


def _scan_bwd(u, delta, A, B, C, D, hs, dy, chunk, tile, interpret):
    T, N = u.shape[1], A.shape[1]
    ops, specs, call, sizes = _plan(u, delta, A, B, C, D, chunk, tile,
                                    interpret, True)
    (Bt, Tp, Cd), (_, tiles, K) = ops[0].shape, call["grid"]
    dy = jnp.pad(dy.astype(_F32), ((0, 0), (0, Tp - T), (0, 0)))
    seq = jax.ShapeDtypeStruct((Bt, Tp, Cd), _F32)
    part = jax.ShapeDtypeStruct((Bt, tiles, N, Tp), _F32)
    du, ddelta, dat, dbt, dct, ddd = pl.pallas_call(
        functools.partial(_bwd_kernel, **sizes),
        in_specs=[specs[k] for k in ("seq", "seq", "a", "bc", "bc", "d",
                                     "hs", "seq")],
        out_specs=[specs[k] for k in ("seq", "seq", "da", "dbc", "dbc",
                                      "dd")],
        out_shape=[seq, seq, jax.ShapeDtypeStruct((Bt, N, Cd), _F32), part,
                   part, jax.ShapeDtypeStruct((Bt, 1, Cd), _F32)],
        scratch_shapes=[pltpu.VMEM((Tp // K, N, Cd // tiles), _F32),
                        pltpu.VMEM((N, Cd // tiles), _F32)],
        name=SSM_SCAN_BWD, **call)(*ops, hs, dy)
    states_last = lambda x, like: jnp.swapaxes(
        jnp.sum(x, axis=1)[:, :, :T], 1, 2).astype(like.dtype)
    return (du[:, :T].astype(u.dtype), ddelta[:, :T].astype(delta.dtype),
            jnp.sum(dat, axis=0).T.astype(A.dtype), states_last(dbt, B),
            states_last(dct, C), jnp.sum(ddd, axis=(0, 1)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(u, delta, A, B, C, D, chunk, tile, interpret):
    return _scan_fwd(u, delta, A, B, C, D, chunk, tile, interpret)[0]


def _vjp_fwd(u, delta, A, B, C, D, chunk, tile, interpret):
    y, hs = _scan_fwd(u, delta, A, B, C, D, chunk, tile, interpret)
    return y, (u, delta, A, B, C, D, hs)


def _vjp_bwd(chunk, tile, interpret, res, dy):
    return _scan_bwd(*res, dy, chunk, tile, interpret)


_scan.defvjp(_vjp_fwd, _vjp_bwd)


def _combine(left, right):
    """Two stretches of the recurrence ``h -> a h + b`` as one."""
    return left[0] * right[0], right[0] * left[1] + right[1]


def _scan_xla(u, delta, A, B, C, D, chunk):
    Bt, T, Cd = u.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    # [chunks, B, chunk, ...]: lax.scan walks the leading axis
    split = lambda x: jnp.swapaxes(
        jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0))).reshape(
            Bt, -1, chunk, x.shape[-1]), 0, 1)
    A = A.astype(_F32)

    @jax.checkpoint
    def body(h, xs):
        u, d, b, c = xs
        decay = jnp.exp(d[..., None] * A)                     # [B, Tc, C, N]
        add = (d * u)[..., None] * b[:, :, None, :]
        a, s = jax.lax.associative_scan(_combine, (decay, add), axis=1)
        states = a * h[:, None] + s
        return states[:, -1], jnp.einsum("btcn,btn->btc", states, c)

    h0 = jnp.zeros((Bt, Cd, A.shape[1]), _F32)
    _, y = jax.lax.scan(body, h0, (split(u), split(delta), split(B),
                                   split(C)))
    y = jnp.swapaxes(y, 0, 1).reshape(Bt, -1, Cd)[:, :T]
    return y + D.astype(_F32) * u.astype(_F32)


def selective_scan(u, delta, A, B, C, D, impl: str = "pallas",
                   chunk: int = 128, channel_tile: int = 512,
                   interpret: Optional[bool] = None):
    """``y [B, T, C]`` float32 from ``u, delta [B, T, C]``, ``A [C, N]``,
    ``B, C [B, T, N]`` and ``D [C]``; it differentiates with respect to all
    six. ``impl="pallas"`` with ``interpret=None``: the kernels on a TPU, the
    XLA path of the same chunking elsewhere."""
    if impl not in ("pallas", "xla"):
        raise ValueError(f"ssm_impl is 'pallas' or 'xla', not {impl!r}")
    if interpret is None:
        interpret = False
        if jax.default_backend() != "tpu":
            impl = "xla"
    if impl == "xla":
        return _scan_xla(u, delta, A, B, C, D, chunk)
    return _scan(u, delta, A, B, C, D, chunk, channel_tile, interpret)
