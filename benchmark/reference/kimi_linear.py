"""Kimi-Linear-48B-A3B's decoder (``moonshotai/Kimi-Linear-48B-A3B-Instruct``
``config.json``, ``model_type`` ``kimi_linear``; catalog row
``Kimi-Linear-48B-A3B-Instruct``; the mixer is "Kimi Delta Attention" of the
Kimi Linear report, arXiv:2510.26692), forward pass and training loss, at ONE
CHIP'S SHARE of each expert layer. Written from the row's ``config`` and the
report's equations; what the row does not fix is listed under ``assumed`` in
``configs/kimi-linear-48b-a3b.json``.

Published layer ``l`` (1-indexed) is a KDA layer where ``l`` is in
``linear_attn_config.kda_layers`` and a latent-attention layer where it is in
``full_attn_layers`` (the two lists are written out below: a configuration's
sizes are numbers); its feed-forward is a dense SwiGLU of ``intermediate_size``
where ``l <= first_k_dense_replace`` and the sparse layer otherwise. A block:
``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

*KDA layer*, for the normed input ``u [T, 2304]``, 32 heads of 128: ``q, k, v
= SiLU(conv4(W_q u)), SiLU(conv4(W_k u)), SiLU(conv4(W_v u))`` -- a depthwise
causal convolution of 4 taps each (zeros before position 0, no bias); ``q <- q
/ sqrt(sum q^2 + 1e-6) / sqrt(128)``, ``k <- k / sqrt(sum k^2 + 1e-6)`` a head;
``beta = sigmoid(W_b u)`` a head; the log decay a CHANNEL of the key, ``g =
-exp(A_log_h) softplus(W_fb (W_fa u) + dt_bias)``; then TOKEN BY TOKEN, a
head's state ``S [128, 128]`` from zero:

    S <- Diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

-- a ``lax.scan`` over positions, not the chunked form the system runs --
then ``o_t <- w * (o_t rsqrt(mean(o_t^2) + eps)) * sigmoid(W_gb (W_ga u))`` a
head (the norm before the gate) and ``W_o``.

*Latent-attention layer* (32 heads): ``q = W_q u`` (192 a head), ``[c ; k_r] =
W_kva u`` (512 + 64), ``[k_n ; v] = W_kvb RMSNorm(c)`` (128 + 128 a head), keys
``[k_n ; k_r]`` with the ONE ``k_r`` for every head, NO rotation of any column
(``mla_use_nope``), causal softmax at ``192 ** -0.5``, ``W_o``: three einsums.

*Sparse layer*: ``deepseek_v3.moe_parts`` -- sigmoid scores over ALL
``router_experts``, top-8 of score + bias, the chosen scores over their sum,
x 2.446; the HELD experts ``first_expert .. + n_routed_experts`` add their
part, what the absent ones would add is left out; the shared expert whole.
Final RMSNorm, untied head, mean next-token cross entropy.

Departures from the published description: the vocabulary is the chip's slice
(the head's loss is over the sliced rows); the experts are the chip's share;
the stack is published layers ``first_layer ..`` at the depth given; no
auxiliary loss (the row has no coefficient).

``params`` is the system's own tree: ``model/leading/block_<i>`` the dense
layers, ``model/periods/block_<i>`` position ``i`` of every period stacked on
a leading axis. Float32, matmuls at the highest precision, attention in blocks
of 512 queries, one sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense
from benchmark.reference.deepseek_v3 import moe_parts
from benchmark.reference.qwen3_next import unit
from benchmark.reference.zaya import conv_depthwise

KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22,
              23, 25, 26)
FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


def published_layer(sizes, layer):
    """The published (1-indexed) layer that stack layer ``layer`` (from 0)
    is."""
    return (sizes.get("first_layer") or 1) + layer


def is_kda(sizes, layer):
    return published_layer(sizes, layer) in KDA_LAYERS


def is_dense(sizes, layer):
    return published_layer(sizes, layer) <= sizes.get(
        "first_k_dense_replace", 1)


def latent_attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]. No column
    rotates (departure from ``deepseek_v3.attention``: ``mla_use_nope``)."""
    T = h.shape[0]
    H, r = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    q = (h @ p["q_proj"]["kernel"]).reshape(T, H, dn + dr)
    kv_a = h @ p["kv_a_proj_with_mqa"]["kernel"]
    latent = dense.rms_norm(kv_a[:, :r], p["kv_a_layernorm"]["scale"],
                            sizes["rms_norm_eps"])
    kv = (latent @ p["kv_b_proj"]["kernel"]).reshape(T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kv_a[:, None, r:], (T, H, dr))], -1)
    v = kv[..., dn:]
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, dense.QUERY_BLOCK):
        i = jnp.arange(s, min(s + dense.QUERY_BLOCK, T))[:, None]
        sc = jnp.einsum("qhd,khd->hqk", q[s:s + dense.QUERY_BLOCK],
                        k) * (dn + dr) ** -0.5
        pr = jax.nn.softmax(jnp.where((j <= i)[None], sc, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", pr, v).reshape(-1, H * dv))
    return jnp.concatenate(out) @ p["o_proj"]["kernel"]


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q, k, g [T, H, dk]``, ``v [T, H,
    dv]``, ``beta [T, H]`` -> ``o [T, H, dv]``; the decay a channel."""
    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S
        d = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
        S = S + k[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))[1]


def kimi_delta_attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    H, D = sizes["kda_num_heads"], sizes["kda_head_dim"]
    # y[t] = sum_j taps[j] x[t - 3 + j], zeros before 0, no bias
    mix = lambda n: jax.nn.silu(conv_depthwise(
        h @ p[f"{n}_proj"]["kernel"], p[f"{n}_conv1d"], 0.0)).reshape(T, H, D)
    q, k, v = mix("q"), mix("k"), mix("v")
    q, k = unit(q) / D ** 0.5, unit(k)
    beta = jax.nn.sigmoid(h @ p["b_proj"]["kernel"])
    f = (h @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (f + p["dt_bias"]).reshape(T, H, D))
    o = delta_rule(q, k, v, g, beta)
    gate = (h @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
    o = dense.rms_norm(o, p["o_norm"], sizes["rms_norm_eps"]) \
        * jax.nn.sigmoid(gate.reshape(T, H, D))
    return o.reshape(T, H * D) @ p["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "kda", "is_dense"))
def _layer(x, p, sizes_t, kda, is_dense):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    h = dense.rms_norm(x, p["input_layernorm"]["scale"], eps)
    x = x + (kimi_delta_attention(h, p["linear_attn"], sizes) if kda
             else latent_attention(h, p["self_attn"], sizes))
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if is_dense:
        return x + dense.mlp(h, p["mlp"]), None
    routed, shared, rows = moe_parts(h, p["mlp"], sizes)
    return x + routed + shared, rows


def period_length(sizes):
    """Layers a period: as far as the first two full-attention layers lie
    apart."""
    return FULL_ATTN_LAYERS[1] - FULL_ATTN_LAYERS[0]


def layers_in_order(params, sizes):
    """``[((kda, dense), the layer's own parameters)]`` of the stack, in
    order: the dense layers unrolled under ``leading``, then position ``i``
    of period ``p`` under ``periods/block_<i>`` at index ``p``."""
    model, out = params["model"], []
    lead = sum(is_dense(sizes, l) for l in range(sizes["num_hidden_layers"]))
    n = period_length(sizes)
    for l in range(sizes["num_hidden_layers"]):
        kind = (is_kda(sizes, l), is_dense(sizes, l))
        if l < lead:
            out.append((kind, model["leading"][f"block_{l}"]))
            continue
        period, i = divmod(l - lead, n)
        out.append((kind, jax.tree_util.tree_map(
            lambda a: a[period], model["periods"][f"block_{i}"])))
    return out


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, H], pairs each held expert computed [G]
    summed over the expert layers) of one sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        rows = jnp.zeros(sizes["n_routed_experts"])
        for (kda, is_dense_layer), p in layers_in_order(params, sizes):
            x, r = _layer(x, p, static, kda, is_dense_layer)
            rows = rows if r is None else rows + r
        return dense.rms_norm(x, model["norm"]["scale"].astype(jnp.float32),
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
