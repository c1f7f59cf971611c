"""Mistral-7B-v0.1's forward pass as published (``modeling_mistral.py``):
RMSNorm -> GQA attention with rotate-half RoPE and a causal sliding-window
mask (query i sees keys j with 0 <= i - j < window) -> residual -> RMSNorm
-> SwiGLU -> residual; final RMSNorm; untied head; token-mean cross entropy
of the shifted labels.

``params`` is the system's own tree (layers stacked on a leading axis);
each layer is upcast to float32 as it is used, and attention runs in blocks
of queries, so the reference fits beside the engine. One sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
HEAD_BLOCK = 2048


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: [T, heads, D], positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, sizes, window):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes.get("head_dim") or sizes["hidden_size"] // Hq
    q = rope((h @ p["q_proj"]["kernel"]).reshape(T, Hq, D),
             sizes["rope_theta"]).reshape(T, Hkv, Hq // Hkv, D)
    k = rope((h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D),
             sizes["rope_theta"])
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, QUERY_BLOCK):
        i = jnp.arange(s, min(s + QUERY_BLOCK, T))[:, None]
        seen = (j <= i) if not window else (j <= i) & (i - j < window)
        sc = jnp.einsum("qhgd,khd->hgqk", q[s:s + QUERY_BLOCK], k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        out.append(jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, Hq * D))
    return jnp.concatenate(out) @ p["o_proj"]["kernel"]


def mlp(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "window"))
def _layer(x, p, sizes_t, window):
    sizes = dict(sizes_t)
    p = f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes, window)
    return x + mlp(rms_norm(x, p["post_attention_layernorm"]["scale"], eps),
                   p["mlp"])


def _static(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float)) and v is not None))


def hidden_states(params, sizes, ids, window="published"):
    """Final-normed hidden states [T, H] of one sequence ``ids`` [T]."""
    if window == "published":
        window = sizes.get("sliding_window")
    with jax.default_matmul_precision("highest"):
        x = params["model"]["embed_tokens"]["embedding"][ids].astype(
            jnp.float32)
        stack = params["model"]["layers"]["block"]
        for l in range(sizes["num_hidden_layers"]):
            x = _layer(x, jax.tree_util.tree_map(lambda a: a[l], stack),
                       _static(sizes), window)
        return rms_norm(x, params["model"]["norm"]["scale"].astype(
            jnp.float32), sizes["rms_norm_eps"])


@jax.jit
def _logits(hidden, kernel):
    return hidden @ kernel.astype(jnp.float32)


def logits(params, hidden):
    """[rows, H] -> [rows, V] float32."""
    with jax.default_matmul_precision("highest"):
        return _logits(hidden, params["lm_head"]["kernel"])


def nll_sum(params, hidden, ids):
    """Sum over positions 0..T-2 of -log p(ids[t+1] | ids[:t+1]), in blocks
    of positions so [T, V] never exists."""
    total = jnp.float32(0.0)
    T = ids.shape[0]
    for s in range(0, T - 1, HEAD_BLOCK):
        e = min(s + HEAD_BLOCK, T - 1)
        lg = logits(params, hidden[s:e])
        gold = jnp.take_along_axis(lg, ids[s + 1:e + 1, None], -1)[:, 0]
        total = total + jnp.sum(jax.nn.logsumexp(lg, -1) - gold)
    return total


def loss(params, sizes, batch_ids, window="published"):
    """The training loss of a batch [B, T] with labels = inputs."""
    total, count = jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        total = total + nll_sum(params, hidden_states(params, sizes, ids,
                                                      window), ids)
        count += ids.shape[0] - 1
    return total / count
