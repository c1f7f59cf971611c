"""OLMoE-1B-7B's forward pass as published (``modeling_olmoe.py``):
RMSNorm -> attention whose WHOLE projected query and key (widths
``num_attention_heads * head_dim`` and ``num_key_value_heads * head_dim``)
pass through an RMSNorm of their own before the split into heads and before
rotate-half RoPE, causal, no window, no bias, ``clip_qkv`` null -> residual
-> RMSNorm -> sparse mixture: router logits -> float32 softmax over ALL 64
experts -> top-8 per token -> the selected probabilities used AS THEY ARE
(``norm_topk_prob`` false: they sum to about 8/64 under a near-uniform
router, not to 1) -> the weighted sum of the selected experts' SwiGLU
outputs -> residual; final RMSNorm; untied head; token-mean cross entropy of
the shifted labels plus ``router_aux_loss_coef`` (0.01) times Mixtral's
``load_balancing_loss_func``.

Every expert runs over every token, one expert at a time, and the combine
weight is zero outside a token's top-k: no dispatch, no capacity, nothing
dropped. Departures from the published code: none.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


def route(h, gate_kernel, top_k):
    """[T, H] -> (combine [T, E], probs [T, E], routed [T, E] in {0, 1});
    the combine weights are the raw softmax values of the top-k."""
    probs = jax.nn.softmax(h @ gate_kernel, -1)
    w, idx = jax.lax.top_k(probs, top_k)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", w, onehot), probs, jnp.max(onehot, 1)


def attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]. As
    ``dense.attention`` without a window, the whole projected query and key
    normed before the heads are split (blocks of queries likewise)."""
    T = h.shape[0]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes.get("head_dim") or sizes["hidden_size"] // Hq
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    q = dense.rms_norm(h @ p["q_proj"]["kernel"], p["q_norm"]["scale"], eps)
    k = dense.rms_norm(h @ p["k_proj"]["kernel"], p["k_norm"]["scale"], eps)
    q = dense.rope(q.reshape(T, Hq, D), theta).reshape(T, Hkv, Hq // Hkv, D)
    k = dense.rope(k.reshape(T, Hkv, D), theta)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, dense.QUERY_BLOCK):
        i = jnp.arange(s, min(s + dense.QUERY_BLOCK, T))[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", q[s:s + dense.QUERY_BLOCK],
                        k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where((j <= i)[None, None], sc, -jnp.inf),
                            -1)
        out.append(jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, Hq * D))
    return jnp.concatenate(out) @ p["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "top_k"))
def _layer(x, p, sizes_t, top_k):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes)
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    experts = p["block_sparse_moe"]
    combine, probs, routed = route(h, experts["gate"]["kernel"], top_k)

    def one_expert(out, e):
        w1, w3, w2, c = e
        y = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2
        return out + c[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (experts["w1"], experts["w3"], experts["w2"],
                           combine.T))
    return x + out, jnp.sum(routed, 0), jnp.sum(probs, 0)


def hidden_states(params, sizes, ids, top_k="published"):
    """(final-normed hidden [T, H], routed-token counts [E], summed router
    probabilities [E]) of one sequence, the sums over layers and tokens."""
    if top_k == "published":
        top_k = sizes["num_experts_per_tok"]
    E = sizes["num_local_experts"]
    with jax.default_matmul_precision("highest"):
        x = params["model"]["embed_tokens"]["embedding"][ids].astype(
            jnp.float32)
        stack = params["model"]["layers"]["block"]
        routed, probs = jnp.zeros(E), jnp.zeros(E)
        for l in range(sizes["num_hidden_layers"]):
            x, r, p = _layer(x, jax.tree_util.tree_map(lambda a: a[l], stack),
                             dense._static(sizes), top_k)
            routed, probs = routed + r, probs + p
        return dense.rms_norm(
            x, params["model"]["norm"]["scale"].astype(jnp.float32),
            sizes["rms_norm_eps"]), routed, probs


logits = dense.logits


def loss(params, sizes, batch_ids, top_k="published"):
    """Cross entropy + router_aux_loss_coef * load-balancing loss of a batch
    [B, T] with labels = inputs (the sums of ``moe.loss``)."""
    total, count = jnp.float32(0.0), 0
    E = sizes["num_local_experts"]
    routed, probs, rows = jnp.zeros(E), jnp.zeros(E), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        hidden, r, p = hidden_states(params, sizes, ids, top_k)
        total = total + dense.nll_sum(params, hidden, ids)
        count += ids.shape[0] - 1
        routed, probs = routed + r, probs + p
        rows += ids.shape[0] * sizes["num_hidden_layers"]
    aux = E * jnp.sum((routed / rows) * (probs / rows))
    return total / count + sizes["router_aux_loss_coef"] * aux
