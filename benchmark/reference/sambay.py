"""Phi-4-mini-flash-reasoning (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type`` ``phi4flash``): the SambaY decoder-hybrid-decoder with
differential attention, forward pass and training loss. No modelling code of
this family is installed, so this follows the published description (Ren et
al., "Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", 2025) as ISSUE 41 wrote the layers down; what the catalog row
does not fix is listed under ``assumed`` in ``configs/phi4-mini-flash.json``.

Every layer ``i``: ``x <- x + Mixer_i(LN(x))``, then ``x <- x + MLP(LN(x))``;
``LN`` is LayerNorm with scale and bias, eps 1e-5; ``MLP(h) = W_down(up *
SiLU(gate))`` with ``[gate ; up] = W_gate_up h`` (no bias; the first half is
the gate). Input: the tied table's row; output: ``LN_final``, then the same
table transposed, no bias. NO positional encoding anywhere. With ``S =
self_decoder_layers`` (16 of 32 as published):

*Mamba* (``i < S``, ``i`` even; and ``i = S``): ``[u ; z] = W_in h`` (no
bias); ``u <- SiLU(causal_conv(u))``, depthwise, 4 taps, bias, zeros before
position 0; ``[dt ; B_t ; C_t] = W_x u`` (160 + 16 + 16, no bias); ``delta =
softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)`` ``[5120, 16]``; ``h_t =
exp(delta_t A) * h_{t-1} + (delta_t u_t) (x) B_t``, ``h_{-1} = 0``; ``y_t = h_t
C_t + D * u_t``; output ``W_out(y * SiLU(z))`` (no bias). Layer ``S`` also
hands on ``m = y`` (before the gate): the memory.

*Differential attention* (``i < S``, ``i`` odd: window 512; ``i = S + 1``: full
causal): ``[q ; k ; v] = W_qkv h + b`` (40 query, 20 key, 20 value heads of
64); heads in pairs (even, odd): ``q1, q2`` 20 heads each, ``k1, k2`` and ``v1,
v2`` 10 each, query pair ``p`` on key/value pair ``p // 2``; ``a1 = softmax(q1
k1^T / 8 + mask) [v1 ; v2]``, ``a2 = softmax(q2 k2^T / 8 + mask) [v1 ; v2]``
(values 128 wide); ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
``lambda_init = 0.8 - 0.6 exp(-0.3 d)`` with ``d`` the layer's PUBLISHED
index; ``a = (1 - lambda_init) RMSNorm_128(a1 - lambda a2)`` (learned scale,
eps 1e-5); the 128 columns go back to the pair's two heads; output ``W_o a +
b_o``. Layer ``S + 1`` also hands on its ``k`` and ``v``.

*Gated memory unit* (``i > S + 1``, ``i - S`` even): ``W_out(m * SiLU(W_in
h))``, no bias, ``m`` from layer ``S``, position by position.

*Cross-attention* (``i > S + 1``, ``i - S`` odd): ``q = W_q h + b`` only;
differential attention as above, full causal, over layer ``S + 1``'s ``k`` and
``v``, with its own lambda vectors, RMSNorm and ``W_o``.

``params`` is the system's own tree (``model/self_decoder/{mamba, window}``
stacked over periods, ``memory_layer``, ``kv_layer``, ``cross_decoder/{gmu,
cross}`` stacked). Float32, matmuls at the highest precision, the recurrence
a plain ``lax.scan`` over positions, attention in blocks of queries, one
sequence at a time.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import dense
from benchmark.reference.keye_vl2 import layer_norm
from benchmark.reference.zaya import conv_depthwise

QUERY_BLOCK = 1024


def mlp(h, p):
    gate_up = h @ p["gate_up_proj"]["kernel"]
    half = gate_up.shape[-1] // 2
    return (gate_up[:, half:] * jax.nn.silu(gate_up[:, :half])) \
        @ p["down_proj"]["kernel"]


def recurrence(u, delta, A, B, C, D):
    """``y [T, C]`` of the selective scan, position by position."""
    def step(h, x):
        u_t, d_t, b_t, c_t = x
        h = jnp.exp(d_t[:, None] * A) * h + (d_t * u_t)[:, None] * b_t[None]
        return h, h @ c_t + D * u_t

    return jax.lax.scan(step, jnp.zeros_like(A), (u, delta, B, C))[1]


def mamba(h, p, sizes):
    """(the layer's output [T, H], the scan's output before the gate)."""
    N = sizes["mamba_d_state"]
    R = sizes.get("mamba_dt_rank") or math.ceil(sizes["hidden_size"] / 16)
    uz = h @ p["in_proj"]["kernel"]
    C = uz.shape[-1] // 2
    u, z = uz[:, :C], uz[:, C:]
    u = jax.nn.silu(conv_depthwise(u, p["conv_weight"], p["conv_bias"]))
    dbc = u @ p["x_proj"]["kernel"]
    delta = jax.nn.softplus(dbc[:, :R] @ p["dt_kernel"] + p["dt_bias"])
    y = recurrence(u, delta, -jnp.exp(p["A_log"]), dbc[:, R:R + N],
                   dbc[:, R + N:], p["D"])
    return (y * jax.nn.silu(z)) @ p["out_proj"]["kernel"], y


def softmax_values(q, k, v, window):
    """q: [T, P, d], k: [T, P, d], v: [T, P, dv] -> [T, P, dv]; causal, a
    query sees ``window`` keys at most."""
    T, d = q.shape[0], q.shape[-1]
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, QUERY_BLOCK):
        i = jnp.arange(s, min(s + QUERY_BLOCK, T))[:, None]
        seen = j <= i if window is None else (j <= i) & (i - j < window)
        scores = jnp.einsum("qpd,kpd->pqk", q[s:s + QUERY_BLOCK], k) \
            / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("pqk,kpd->qpd", probs, v))
    return jnp.concatenate(out)


def diff_attention(q, k, v, p, sizes, index, window):
    """q: [T, Hq, d], k, v: [T, Hkv, d] -> the layer's output [T, H]."""
    T, Hq, d = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    q1, q2 = q[:, 0::2], q[:, 1::2]                       # [T, Hq/2, d]
    # query pair p on key/value pair p // rep
    k1, k2 = (jnp.repeat(x, rep, axis=1) for x in (k[:, 0::2], k[:, 1::2]))
    vv = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1), rep,
                    axis=1)                               # [T, Hq/2, 2d]
    a1 = softmax_values(q1, k1, vv, window)
    a2 = softmax_values(q2, k2, vv, window)
    init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
    a = dense.rms_norm(a1 - lam * a2, p["subln_scale"],
                       sizes["layer_norm_eps"]) * (1.0 - init)
    # the pair's 2d columns are its even head's d, then its odd head's
    return a.reshape(T, Hq * d) @ p["out_proj"]["kernel"] \
        + p["out_proj"]["bias"]


def heads(sizes):
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return Hq, Hkv, sizes["hidden_size"] // Hq


def self_attention(h, p, sizes, index, window):
    """(the layer's output, its keys, its values)."""
    T = h.shape[0]
    Hq, Hkv, d = heads(sizes)
    qkv = h @ p["Wqkv"]["kernel"] + p["Wqkv"]["bias"]
    q = qkv[:, :Hq * d].reshape(T, Hq, d)
    k = qkv[:, Hq * d:(Hq + Hkv) * d].reshape(T, Hkv, d)
    v = qkv[:, (Hq + Hkv) * d:].reshape(T, Hkv, d)
    return diff_attention(q, k, v, p, sizes, index, window), k, v


def cross_attention(h, k, v, p, sizes, index):
    Hq, _, d = heads(sizes)
    q = (h @ p["Wq"]["kernel"] + p["Wq"]["bias"]).reshape(-1, Hq, d)
    return diff_attention(q, k, v, p, sizes, index, None)


def gated_memory(h, memory, p):
    return (memory * jax.nn.silu(h @ p["in_proj"]["kernel"])) \
        @ p["out_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "kind", "index",
                                             "window"))
def _layer(x, p, handed, sizes_t, kind, index, window):
    """One layer of ``kind`` at published index ``index``: ``(x, what it
    hands on)``; ``handed`` is what it reads of an earlier layer."""
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["layer_norm_eps"]
    h = layer_norm(x, p["input_layernorm"], eps)
    out = None
    if kind == "mamba":
        y, out = mamba(h, p["mixer"], sizes)
    elif kind == "attention":
        y, k, v = self_attention(h, p["mixer"], sizes, index, window)
        out = (k, v)
    elif kind == "gmu":
        y = gated_memory(h, handed, p["mixer"])
    else:
        y = cross_attention(h, *handed, p["mixer"], sizes, index)
    x = x + y
    return x + mlp(layer_norm(x, p["post_attention_layernorm"], eps),
                   p["mlp"]), out


def hidden_states(params, sizes, ids, window="published"):
    """Final-normed hidden states [T, H] of one sequence ``ids`` [T]."""
    if window == "published":
        window = sizes.get("sliding_window")
    static = dense._static(sizes)
    L = sizes["num_hidden_layers"]
    S = sizes.get("self_decoder_layers")
    S = L // 2 if S is None else S
    first = sizes.get("cross_decoder_first_index")
    first = S if first is None else first         # layer S's published index
    at = lambda stack, l: jax.tree_util.tree_map(lambda a: a[l], stack)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        for i in range(0, S, 2):
            period = at(model["self_decoder"], i // 2)
            x, _ = _layer(x, period["mamba"], None, static, "mamba", i, None)
            x, _ = _layer(x, period["window"], None, static, "attention",
                          i + 1, window)
        x, memory = _layer(x, model["memory_layer"], None, static, "mamba",
                           first, None)
        x, kv = _layer(x, model["kv_layer"], None, static, "attention",
                       first + 1, None)
        for i in range(S + 2, L, 2):
            period = at(model["cross_decoder"], (i - S - 2) // 2)
            x, _ = _layer(x, period["gmu"], memory, static, "gmu",
                          first + i - S, None)
            x, _ = _layer(x, period["cross"], kv, static, "cross",
                          first + i + 1 - S, None)
        return layer_norm(x, dense.f32(model["final_layernorm"]),
                          sizes["layer_norm_eps"])


def logits(params, hidden):
    """[rows, H] -> [rows, V] float32, through the embedding table."""
    with jax.default_matmul_precision("highest"):
        return dense._logits(
            hidden, params["model"]["embed_tokens"]["embedding"].T)


def loss(params, sizes, batch_ids, window="published"):
    """The training loss of a batch [B, T] with labels = inputs."""
    head = {"lm_head": {
        "kernel": params["model"]["embed_tokens"]["embedding"].T}}
    total, count = jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        total = total + dense.nll_sum(
            head, hidden_states(params, sizes, ids, window), ids)
        count += ids.shape[0] - 1
    return total / count
