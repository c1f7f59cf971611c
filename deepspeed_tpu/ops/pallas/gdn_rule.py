"""The chunked gated delta rule of a linear-attention layer, forward and
backward, a chunk's tables and a head's state in VMEM.

A value head keeps ``S [dk, dv]`` in float32 from ``S = 0``; a chunk of ``C``
positions with ``gamma_i`` the running sum of the log decay inside it,
``total = gamma_C`` and ``T = (I + A)^-1`` the inverse of the chunk's unit
lower-triangular table (``models/qwen3_next.py gated_delta_rule`` has the
algebra and builds ``gamma``, ``A`` and ``T`` in XLA) does

    u = T (beta v)          w = T (beta e^gamma k)
    m = (q k^T) * exp(gamma_i - gamma_j), j <= i
    d = u - w S             o = (e^gamma q) S + m d
    S <- e^total S + (e^(total - gamma) k)^T d

Every exponent is a difference that is ``<= 0``. XLA's form of this writes
``u``, ``w``, ``m``, ``e^gamma q`` and ``e^(total - gamma) k`` for all chunks
to HBM (float32, twice q's bytes each) and then scans the chunk boundaries;
here a chunk's q, k, v (48 KB in bf16 at 64 x 128) and ``T`` (16 KB) are read
once and everything between them and ``o`` lives in VMEM beside the state.

*The kernels* (``ds_gdn_rule_fwd``, ``ds_gdn_rule_bwd``): grid ``(batch, head
groups, chunk blocks)``, the last axis sequential; a grid step holds
``Tiling.heads`` heads (their chains are independent, so one head's products
fill the other's latencies) by ``Tiling.chunks`` chunks under an inner loop.
q, k, v and o stay ``[B, T, H * d]`` as the model has them: a block is rows
of time by a head's lanes, no transpose on either side. The forward writes
``o`` and the state AT EACH CHUNK'S START (float32, ``[B, H, n, dk, dv]``:
what a checkpointed scan keeps). The backward walks the chunks in reverse
carrying ``dL/dS``; for a chunk it rebuilds ``u, w, m, d`` from the same
inputs and the saved boundary, and writes dq, dk, dv (the operands' type),
``dgamma``, ``dbeta`` and ``dT`` (float32), which XLA carries on through the
inverse, the table and the running sum.

*Rounding*: the state, ``u``, ``d`` and every accumulation are float32. The
products with ``T`` (``u``, ``w`` and their four transposes) are at full
float32 precision, as XLA's solve is (``_full``: ``Precision.HIGHEST``, or
the float32 operand split in three against an operand that is bf16 as it
comes). The products with the state and the chunk's pair tables round their
operands to q's type ONCE, as XLA:TPU's default precision does with the same
float32 operands.

``plan`` is the rule that says where the kernels run, a pure function of
what the call site can see: backend, devices under the mesh, the head's
widths, the chunk, the item size, the device kind. No option selects any of
it.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import GDN_RULE_BWD, GDN_RULE_FWD, REMAT_GDN_RULE, grouped_matmul

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


class Tiling(NamedTuple):
    """Chunks and heads a grid step: the call takes the largest divisors of
    its chunk count and head count that are no larger."""
    chunks: int
    heads: int


#: chunks x heads a grid step on a v5e at 64 x 128 x 128 (PERF.md section 6,
#: PR 53)
_TILING = Tiling(4, 4)


def plan(platform: str, mesh_devices: int, dk: int, dv: int, chunk: int,
         itemsize: int = 2,
         device_kind: str = "TPU v5 lite") -> Optional[Tiling]:
    """The tiling the kernels take a head of ``dk x dv`` in chunks of
    ``chunk`` with, or None where the XLA form stays: off a TPU or on one
    whose VMEM is not in ``grouped_matmul._VMEM_BYTES``, under a mesh of
    several devices (a Mosaic call is not partitioned), operands that are
    not two bytes wide, a head that is no whole lanes (the tiny test sizes),
    a chunk that is no whole sublanes."""
    if platform != "tpu" or mesh_devices > 1 or itemsize != 2 \
            or device_kind not in grouped_matmul._VMEM_BYTES:
        return None
    if dk % 128 or dv % 128 or chunk % 8:
        return None
    return _TILING


def _fit(n, want):
    """The largest divisor of ``n`` that is at most ``want``."""
    return max(d for d in range(1, min(n, want) + 1) if n % d == 0)


def _dot(a, b, contract, precision=None):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=_F32,
                               precision=precision)


_nn = functools.partial(_dot, contract=(1, 0))      # a b
_nt = functools.partial(_dot, contract=(1, 1))      # a b^T
_tn = functools.partial(_dot, contract=(0, 0))      # a^T b


def _full(a, b, contract):
    """``a`` float32 times ``b`` at full float32 precision. A bf16 ``b`` is
    exact in its type, so ``a`` alone is split, into the three bf16 parts
    that hold its 24 bits, stacked by rows against the one ``b``: three
    passes of the matrix unit where ``Precision.HIGHEST`` takes six."""
    if b.dtype != jnp.bfloat16:
        return _dot(a, b.astype(_F32), contract, precision=_HIGHEST)
    hi = a.astype(jnp.bfloat16)
    rest = a - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    n = a.shape[0]
    out = _dot(jnp.concatenate([hi, mid, lo], axis=0), b, contract)
    return out[2 * n:] + out[n:2 * n] + out[:n]


def _to_row(col, eye):
    """``[C, 1] -> [1, C]``."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _chunk(q, k, v, g_row, b_row, t, S, backward=False):
    """What both directions form of a chunk from its inputs and the state at
    its start, by name: the table ``E`` (``exp(gamma_i - gamma_j)``, ``j <=
    i``), ``A = T beta_j`` and ``Bm = A e^gamma_j`` (``u = A v``, ``w = Bm
    k``), ``m``, ``qg`` and ``kd`` in float32, and ``d``; for the forward
    ``qs = qg S``, for the backward ``Et`` and ``mt``, the transposes of
    ``E`` and ``m`` (from ``k q^T``: no transpose is taken). Products with
    ``S`` and the pair tables take their operands in q's type."""
    C, cdt = q.shape[0], q.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = row == col
    g_col = jnp.sum(jnp.where(eye, g_row, 0.0), axis=1, keepdims=True)
    last = col[:1] == C - 1
    total = jnp.sum(jnp.where(last, g_row, 0.0), axis=1, keepdims=True)
    E = jnp.exp(jnp.where(row >= col, g_col - g_row, -jnp.inf))
    into_row = jnp.exp(g_row)
    A = t * b_row
    Bm = A * into_row
    k32 = k.astype(_F32)
    u = _full(A, v, (1, 0))
    w = _full(Bm, k, (1, 0))
    m = _nt(q, k) * E
    into_col, out_col = jnp.exp(g_col), jnp.exp(total - g_col)
    qg, kd = into_col * q.astype(_F32), out_col * k32
    x = dict(eye=eye, last=last, total=total, E=E, into_row=into_row,
             into_col=into_col, out_col=out_col, A=A, Bm=Bm, w=w, m=m, qg=qg,
             kd=kd)
    if backward:
        x["Et"] = jnp.exp(jnp.where(col >= row, g_row - g_col, -jnp.inf))
        x["mt"] = _nt(k, q) * x["Et"]
        x["d"] = u - _nn(w.astype(cdt), S.astype(cdt))
    else:       # w S and qg S as one product: the state is loaded once
        ws = _nn(jnp.concatenate([w.astype(cdt), qg.astype(cdt)], axis=0),
                 S.astype(cdt))
        x["d"], x["qs"] = u - ws[:C], ws[C:]
    return x


def _blocks(ref, c, C, h, d):
    """Chunk ``c``, head ``h`` of a ``[1, K C, G d]`` block."""
    return ref.at[0, pl.ds(pl.multiple_of(c * C, C), C), h * d:(h + 1) * d]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, o_ref, hs_ref,
                s_scr, *, chunk: int, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    heads, chunks = t_ref.shape[1], t_ref.shape[2]

    def step(c, _):
        for h in range(heads):
            q, k = (_blocks(r, c, chunk, h, dk)[...] for r in (q_ref, k_ref))
            v = _blocks(v_ref, c, chunk, h, dv)[...]
            S = s_scr[h]
            hs_ref[0, h, c] = S                 # the chunk's boundary
            x = _chunk(q, k, v, g_ref[0, h, c], b_ref[0, h, c],
                       t_ref[0, h, c], S)
            cdt, d = q.dtype, x["d"].astype(q.dtype)
            o = x["qs"] + _nn(x["m"].astype(cdt), d)
            _blocks(o_ref, c, chunk, h, dv)[...] = o.astype(o_ref.dtype)
            s_scr[h] = jnp.exp(x["total"]) * S \
                + _tn(x["kd"].astype(cdt), d)

    jax.lax.fori_loop(0, chunks, step, None, unroll=True)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, hs_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dt_ref, ds_scr, *,
                chunk: int, dk: int, dv: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    heads, chunks = t_ref.shape[1], t_ref.shape[2]

    def step(i, _):
        c = chunks - 1 - i
        for h in range(heads):
            q, k = (_blocks(r, c, chunk, h, dk)[...] for r in (q_ref, k_ref))
            v = _blocks(v_ref, c, chunk, h, dv)[...]
            t, S, dS = t_ref[0, h, c], hs_ref[0, h, c], ds_scr[h]
            x = _chunk(q, k, v, g_ref[0, h, c], b_ref[0, h, c], t, S, True)
            cdt = q.dtype
            do = _blocks(do_ref, c, chunk, h, dv)[...].astype(cdt)
            Sc, dSc, d = S.astype(cdt), dS.astype(cdt), x["d"].astype(cdt)
            qg, kd, decay = x["qg"], x["kd"], jnp.exp(x["total"])
            # o = qg S + m d;  S' = decay S + kd^T d;  d = u - w S
            dd = _nn(x["mt"].astype(cdt), do) + _nn(kd.astype(cdt), dSc)
            ddc = dd.astype(cdt)
            dm, dmt = _nt(do, d), _nt(d, do)
            dqg, dkd, dw = _nt(do, Sc), _nt(d, dSc), -_nt(ddc, Sc)
            ds_scr[h] = decay * dS + _tn(qg.astype(cdt), do) \
                - _tn(x["w"].astype(cdt), ddc)
            # u = A v, w = Bm k;  A = T beta_j, Bm = A e^gamma_j
            dA = _full(dd, v, (1, 1))
            dB = _full(dw, k, (1, 1))
            dAB = dA + dB * x["into_row"]
            dt_ref[0, h, c] = dAB * b_ref[0, h, c]
            db_ref[0, h, c] = jnp.sum(dAB * t, axis=0, keepdims=True)
            dv_h = _tn(x["A"], dd, precision=_HIGHEST)
            dk_w = _tn(x["Bm"], dw, precision=_HIGHEST)
            # m = (q k^T) E;  qg = e^gamma q;  kd = e^(total - gamma) k
            dq_h = _nn((dm * x["E"]).astype(cdt), k) + x["into_col"] * dqg
            dk_h = _nn((dmt * x["Et"]).astype(cdt), q) \
                + x["out_col"] * dkd + dk_w
            M = dm * x["m"]
            out = jnp.sum(dkd * kd, axis=1, keepdims=True)
            rows = jnp.sum(M, axis=1, keepdims=True) \
                + jnp.sum(dqg * qg, axis=1, keepdims=True) - out
            dtotal = jnp.sum(out, axis=0, keepdims=True) \
                + decay * jnp.sum(jnp.sum(dS * S, axis=1, keepdims=True),
                                  axis=0, keepdims=True)
            dg_ref[0, h, c] = jnp.sum(dB * x["Bm"] - M, axis=0,
                                      keepdims=True) \
                + _to_row(rows, x["eye"]) + jnp.where(x["last"], dtotal, 0.0)
            _blocks(dq_ref, c, chunk, h, dk)[...] = dq_h.astype(dq_ref.dtype)
            _blocks(dk_ref, c, chunk, h, dk)[...] = dk_h.astype(dk_ref.dtype)
            _blocks(dv_ref, c, chunk, h, dv)[...] = dv_h.astype(dv_ref.dtype)

    jax.lax.fori_loop(0, chunks, step, None, unroll=True)


def _call(q, k, v, gamma, tiling, interpret, reverse):
    """``(the block specs by name, pallas_call keywords, the kernels' static
    sizes, the state scratch's shape)`` of a call over ``q, k [B, T, H dk]``,
    ``v [B, T, H dv]`` and ``gamma [B, H, n, 1, C]``. ``reverse`` walks the
    chunk blocks from the last."""
    B, H, n, _, C = gamma.shape
    dk, dv = q.shape[2] // H, v.shape[2] // H
    K, G = _fit(n, tiling.chunks), _fit(H, tiling.heads)
    at = (lambda j: n // K - 1 - j) if reverse else (lambda j: j)
    seq = lambda d: pl.BlockSpec((1, K * C, G * d),
                                 lambda b, h, j: (b, at(j), h))
    per_chunk = lambda *dims: pl.BlockSpec(
        (1, G, K) + dims, lambda b, h, j: (b, h, at(j)) + (0,) * len(dims))
    specs = {"k": seq(dk), "v": seq(dv), "row": per_chunk(1, C),
             "pairs": per_chunk(C, C), "state": per_chunk(dk, dv)}
    call = dict(grid=(B, H // G, n // K), interpret=interpret)
    if not interpret:
        item = q.dtype.itemsize
        # the blocks of a step, each held twice: q, k, v, o and their
        # gradients; the chunks' tables and boundary states and their
        # gradients
        held = 2 * (K * C * G * (4 * dk + 4 * dv) * item
                    + G * K * (2 * C * C + dk * dv + 4 * 8 * C) * 4)
        call["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=grouped_matmul._vmem_limit(held))
    return specs, call, dict(chunk=C, dk=dk, dv=dv), (G, dk, dv)


# jitted entries: a step's three delta-rule layers trace and lower each
# kernel once, not once a call site (a fully unrolled grid step is long)
@functools.partial(jax.jit, static_argnums=(6, 7))
def _rule_fwd(q, k, v, gamma, beta, t, tiling, interpret):
    B, H, n = gamma.shape[:3]
    specs, call, sizes, state = _call(q, k, v, gamma, tiling, interpret,
                                      False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **sizes),
        in_specs=[specs[s] for s in ("k", "k", "v", "row", "row", "pairs")],
        out_specs=[specs["v"], specs["state"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, n) + state[1:], _F32)],
        scratch_shapes=[pltpu.VMEM(state, _F32)],
        name=GDN_RULE_FWD, **call)(q, k, v, gamma, beta, t)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _rule_bwd(q, k, v, gamma, beta, t, hs, do, tiling, interpret):
    specs, call, sizes, state = _call(q, k, v, gamma, tiling, interpret, True)
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape,
                                                      dtype or x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **sizes),
        in_specs=[specs[s] for s in ("k", "k", "v", "row", "row", "pairs",
                                     "state", "v")],
        out_specs=[specs[s] for s in ("k", "k", "v", "row", "row", "pairs")],
        out_shape=[like(q), like(k), like(v), like(gamma, _F32),
                   like(beta, _F32), like(t, _F32)],
        scratch_shapes=[pltpu.VMEM(state, _F32)],
        name=GDN_RULE_BWD, **call)(q, k, v, gamma, beta, t, hs, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rule(q, k, v, gamma, beta, t, tiling, interpret):
    return _rule_fwd(q, k, v, gamma, beta, t, tiling, interpret)[0]


def _vjp_fwd(q, k, v, gamma, beta, t, tiling, interpret):
    o, hs = _rule_fwd(q, k, v, gamma, beta, t, tiling, interpret)
    # named, as the flash forward names its pair, so that a remat policy can
    # keep them (models/layers.resolve_remat_policy does where the engine
    # finds room; the caller names t, the chunk inverse, the same): the
    # replay then calls no forward kernel. Outside a policy: the identity
    o = checkpoint_name(o, REMAT_GDN_RULE)
    hs = checkpoint_name(hs, REMAT_GDN_RULE)
    return o, (q, k, v, gamma, beta, t, hs)


def _vjp_bwd(tiling, interpret, res, do):
    return tuple(_rule_bwd(*res, do, tiling, interpret))


_rule.defvjp(_vjp_fwd, _vjp_bwd)


def chunk_rule(q, k, v, gamma, beta, inverse, tiling: Tiling,
               interpret: Optional[bool] = None):
    """``o [B, T, H, dv]`` in v's type from ``q, k [B, T, H, dk]``, ``v [B,
    T, H, dv]`` over ``n`` whole chunks of ``C`` (``T = n C``), ``gamma,
    beta [B, H, n, C]`` float32 and ``inverse [B, H, n, C, C]`` float32 (the
    module's ``T``); it differentiates with respect to all six.
    ``interpret=None``: the kernels on a TPU, interpret mode elsewhere."""
    B, T, H, dv = v.shape
    flat = lambda x: x.reshape(B, T, -1)
    rows = lambda x: x.astype(_F32)[:, :, :, None, :]
    # resolved here: the jitted entries below key their traces on it
    o = _rule(flat(q), flat(k), flat(v), rows(gamma), rows(beta),
              inverse.astype(_F32), tiling,
              grouped_matmul._interpret(interpret))
    return o.reshape(B, T, H, dv)
