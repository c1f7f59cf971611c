"""Qwen3-Next-80B-A3B's decoder (``Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json``, ``model_type`` ``qwen3_next``; catalog row
``Qwen3-Next-80B-A3B-Instruct``), forward pass and training loss, at ONE
CHIP'S SHARE of each expert layer. Written from the row's ``config`` and the
published class's equations; what the row does not fix is listed under
``assumed`` in ``configs/qwen3-next-80b-a3b.json``.

Layer ``l`` (from 0) is a gated FULL-attention layer where ``(l + 1) mod
full_attention_interval = 0`` and a gated DELTA-RULE layer otherwise; every
layer's feed-forward is the sparse one. A block: ``h = x + Mixer(Norm(x))``,
``y = h + MoE(Norm(h))``; ``Norm(x) = x rsqrt(mean(x^2) + eps) (1 + w)``
(zero-centred scale) everywhere but the delta rule's output norm.

*Full layer*, for the normed input ``u [T, 2048]``: ``W_q u`` as 16 heads of
``2 x 256`` columns, the first 256 the query and the next 256 the gate;
``k``, ``v`` as 2 heads of 256; a zero-centred norm over each head's 256
columns of ``q`` and ``k``; rotate-half RoPE over the FIRST 64 columns
(``partial_rotary_factor`` 0.25) at ``rope_theta``, the other 192 pass;
scores ``q . k / sqrt(256)``, causal, softmax; ``W_o (attn *
sigmoid(gate))``.

*Delta-rule layer*: ``W_qkvz u`` grouped by key head (128 of q, 128 of k,
2 x 128 of v, 2 x 128 of z a group of the 16), ``W_ba u`` the same (2 of b,
2 of a a group); a depthwise causal convolution of 4 taps over ``[q ; k ;
v]`` (zeros before position 0, no bias) and SiLU; ``beta = sigmoid(b)``,
``g = -exp(A_log) softplus(a + dt_bias)`` a value head; ``q <- q / sqrt(sum
q^2 + 1e-6) / sqrt(128)``, ``k <- k / sqrt(sum k^2 + 1e-6)``, a key head's
serving its two value heads; then TOKEN BY TOKEN, a value head's state ``S
[128, 128]`` from zero:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

-- a ``lax.scan`` over positions, not the chunked form the system runs --
then ``o_t <- w * (o_t rsqrt(mean(o_t^2) + eps)) * SiLU(z_t)`` a head (plain
scale, the norm before the gate) and ``W_out``.

*MoE*: softmax over ALL ``router_experts`` experts, top-10, the chosen
probabilities renormalised over the ten, the weighted sum of the chosen
experts' SwiGLU outputs, plus ``sigmoid(w_g . x) SwiGLU_shared(x)``. *The
share* (``keye_vl2.held_experts``, the same router family):
``num_local_experts`` experts are held, the router's ``first_expert ..``; the
held experts add their part, what the absent ones would add is left out, the
shared expert is whole. Final norm (zero-centred), untied head, mean
next-token cross entropy; no router loss (the row has no coefficient).

Departures from the published description: no multi-token-prediction head
(``described_as`` names one, ``config`` has no key for it); no auxiliary
loss; the vocabulary is the chip's slice; the experts are the chip's share.

``params`` is the system's own tree: ``model/periods/block_<i>``, position
``i`` of every period stacked on a leading axis. Float32, matmuls at the
highest precision, attention in blocks of 512 queries, one sequence at a
time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense
from benchmark.reference.keye_vl2 import held_experts
from benchmark.reference.zaya import conv_depthwise, partial_rope


def is_full(sizes, layer):
    return (layer + 1) % sizes["full_attention_interval"] == 0


def norm(x, weight, eps):
    """The zero-centred RMSNorm: ``(1 + weight)``."""
    return dense.rms_norm(x, 1.0 + weight, eps)


def gated_attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    Hq, Hkv, D = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim_override"])
    eps = sizes["rms_norm_eps"]
    qg = (h @ p["q_proj"]["kernel"]).reshape(T, Hq, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    # rotate-half RoPE over the first partial_rotary_factor of each head
    q = partial_rope(norm(q, p["q_norm"]["weight"], eps), sizes)
    k = partial_rope(norm(k, p["k_norm"]["weight"], eps), sizes)
    q = q.reshape(T, Hkv, Hq // Hkv, D)
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, dense.QUERY_BLOCK):
        i = jnp.arange(s, min(s + dense.QUERY_BLOCK, T))[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", q[s:s + dense.QUERY_BLOCK],
                        k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where((j <= i)[None, None], sc, -jnp.inf), -1)
        out.append(jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, Hq, D))
    out = jnp.concatenate(out) * jax.nn.sigmoid(gate)
    return out.reshape(T, Hq * D) @ p["o_proj"]["kernel"]


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q, k [T, H, dk]``, ``v [T, H,
    dv]``, ``g, beta [T, H]`` -> ``o [T, H, dv]``."""
    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        d = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))[1]


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def gated_delta_net(h, p, sizes):
    """h: [T, H] normed input; returns the out_proj output [T, H]."""
    T = h.shape[0]
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    r = Hv // Hk
    qkvz = (h @ p["in_proj_qkvz"]["kernel"]).reshape(T, Hk, -1)
    ba = (h @ p["in_proj_ba"]["kernel"]).reshape(T, Hk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, Hv, dv)
    b, a = ba[..., :r].reshape(T, Hv), ba[..., r:].reshape(T, Hv)
    # y[t] = sum_j taps[j] x[t - 3 + j], zeros before 0, no bias
    mixed = jax.nn.silu(conv_depthwise(jnp.concatenate(
        [q.reshape(T, -1), k.reshape(T, -1), v.reshape(T, -1)], -1),
        p["conv1d"], 0.0))
    q = mixed[:, :Hk * dk].reshape(T, Hk, dk)
    k = mixed[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk)
    v = mixed[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    q = jnp.repeat(unit(q) / dk ** 0.5, r, axis=1)
    k = jnp.repeat(unit(k), r, axis=1)
    o = delta_rule(q, k, v, g, beta)
    o = dense.rms_norm(o, p["norm_scale"], sizes["rms_norm_eps"]) \
        * jax.nn.silu(z)
    return o.reshape(T, Hv * dv) @ p["out_proj"]["kernel"]


def shared_expert(h, p):
    return jax.nn.sigmoid(h @ p["shared_expert_gate"]["kernel"]) \
        * dense.mlp(h, p)


@functools.partial(jax.jit, static_argnames=("sizes_t", "full"))
def _layer(x, p, sizes_t, full):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    h = norm(x, p["input_layernorm"]["weight"], eps)
    x = x + (gated_attention(h, p["self_attn"], sizes) if full
             else gated_delta_net(h, p["linear_attn"], sizes))
    h = norm(x, p["post_attention_layernorm"]["weight"], eps)
    out, rows = held_experts(h, p["block_sparse_moe"], sizes)
    return x + out + shared_expert(h, p["shared_expert"]), rows


def layer_params(params, sizes, layer):
    period, i = divmod(layer, sizes["full_attention_interval"])
    return jax.tree_util.tree_map(
        lambda a: a[period], params["model"]["periods"][f"block_{i}"])


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, H], pairs each held expert computed [G]
    summed over layers) of one sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        rows = jnp.zeros(sizes["num_local_experts"])
        for l in range(sizes["num_hidden_layers"]):
            x, r = _layer(x, layer_params(params, sizes, l), static,
                          is_full(sizes, l))
            rows = rows + r
        return norm(x, model["norm"]["weight"].astype(jnp.float32),
                    sizes["rms_norm_eps"]), rows


logits = dense.logits


def loss(params, sizes, batch_ids):
    """The training loss of a batch [B, T] with labels = inputs."""
    total, count = jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        total = total + dense.nll_sum(
            params, hidden_states(params, sizes, ids)[0], ids)
        count += ids.shape[0] - 1
    return total / count
