"""Mellum2-12B-A2.5B's decoder (``JetBrains/Mellum2-12B-A2.5B-Instruct``
``config.json``, ``model_type`` ``mellum``; catalog row
``Mellum2-12B-A2.5B-Instruct``), forward pass and training loss, at ONE
CHIP'S SHARE of each expert layer. Written from the row's ``config``; what
the row does not fix is listed under ``assumed`` in
``configs/mellum2-12b-a2.5b.json``.

Layer ``l`` (from 0) is a FULL-attention layer where ``(l + 1) mod
full_attention_period = 0`` and a SLIDING-window layer otherwise
(``layer_types``); every layer's feed-forward is the sparse one
(``mlp_layer_types``). A block: ``h = x + Attn_kind(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``.

*Attention*, for the normed input ``u [T, 2304]``: ``q = W_q u`` as 32 heads
of 128, ``k = W_k u`` and ``v = W_v u`` as 4 heads of 128; an RMSNorm with a
learned scale over each head's 128 columns of ``q`` and of ``k`` (assumed:
the Qwen3-MoE lineage's); rotate-half RoPE with the KIND's table; scores
``q . k / sqrt(128)``, causal, and on a sliding layer key ``j`` is seen from
query ``i`` where ``0 <= i - j < sliding_window``; softmax; ``W_o``.

*The tables* (``rope_parameters``). Sliding layers: ``inv_freq_i =
theta^(-2i/128)``. Full layers, YaRN: ``extrap_i = theta^(-2i/d)``,
``interp_i = extrap_i / factor``, ``corr(n) = d ln(L / (2 pi n)) / (2 ln
theta)`` with ``L`` the original length, ``low = max(floor(corr(beta_fast)),
0)``, ``high = min(ceil(corr(beta_slow)), d - 1)``, ``ramp_i = clip((i -
low) / (high - low), 0, 1)``, ``inv_freq_i = interp_i ramp_i + extrap_i (1 -
ramp_i)``, and cos AND sin times ``attention_factor`` (the scores carry its
square). The same table at every length: no switch at ``L``.

*Experts*: softmax over ALL ``router_experts`` experts, top-8, the chosen
probabilities renormalised to sum to 1 (``norm_topk_prob``), the weighted sum
of the chosen experts' SwiGLU outputs; no shared expert. *The share*
(``keye_vl2.held_experts``, the same router family): ``num_local_experts``
experts are held, the router's ``first_expert ..``; router, top-k and
renormalisation are over all of them, the held experts add their part and
what the absent ones would add is left out. Final RMSNorm, untied head, mean
next-token cross entropy; no router loss (the row has no coefficient).

Departures from the published description: the per-head q/k norm is assumed
(above); no multi-token-prediction head is built (``described_as`` names
one, ``config`` has no key for it); the vocabulary is the chip's slice.

``params`` is the system's own tree: ``model/periods/block_<i>``, position
``i`` of every period stacked on a leading axis. Float32, matmuls at the
highest precision, attention in blocks of 512 queries, one sequence at a
time.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import dense
from benchmark.reference.keye_vl2 import held_experts


def is_full(sizes, layer):
    return (layer + 1) % sizes["full_attention_period"] == 0


def inv_freq(sizes, full):
    """(rotary frequencies [D / 2], the factor on cos and sin)."""
    d, theta = sizes["head_dim_override"], sizes["rope_theta"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extrap = theta ** (-2 * i / d)
    if not full or not sizes.get("yarn_factor"):
        return extrap, 1.0
    L = sizes["yarn_original_max_position_embeddings"]
    corr = lambda n: d * math.log(L / (2 * math.pi * n)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(sizes["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(sizes["yarn_beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0, 1)
    factor = sizes.get("yarn_attention_factor") or \
        0.1 * math.log(sizes["yarn_factor"]) + 1
    return extrap / sizes["yarn_factor"] * ramp + extrap * (1 - ramp), factor


def rotate(x, freq, factor):
    """x: [T, heads, D], positions 0..T-1, rotate-half convention."""
    T, _, D = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = factor * jnp.cos(ang)[:, None], factor * jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, p, sizes, full):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    Hq, Hkv, D = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], sizes["head_dim_override"])
    eps, window = sizes["rms_norm_eps"], sizes["sliding_window"]
    table = inv_freq(sizes, full)
    q = (h @ p["q_proj"]["kernel"]).reshape(T, Hq, D)
    k = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    q = rotate(dense.rms_norm(q, p["q_norm"]["scale"], eps), *table)
    k = rotate(dense.rms_norm(k, p["k_norm"]["scale"], eps), *table)
    q = q.reshape(T, Hkv, Hq // Hkv, D)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, dense.QUERY_BLOCK):
        i = jnp.arange(s, min(s + dense.QUERY_BLOCK, T))[:, None]
        seen = (j <= i) if full else (j <= i) & (i - j < window)
        sc = jnp.einsum("qhgd,khd->hgqk", q[s:s + dense.QUERY_BLOCK],
                        k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        out.append(jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, Hq * D))
    return jnp.concatenate(out) @ p["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "full"))
def _layer(x, p, sizes_t, full):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes, full)
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    out, rows = held_experts(h, p["block_sparse_moe"], sizes)
    return x + out, rows


def layer_params(params, sizes, layer):
    period, i = divmod(layer, sizes["full_attention_period"])
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
