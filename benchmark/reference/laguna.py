"""Laguna-XS.2's decoder (``poolside/Laguna-XS.2`` ``config.json``,
``model_type`` ``laguna``; catalog row ``Laguna-XS.2``), forward pass and
training loss, at ONE CHIP'S SHARE of each expert layer. Written from the
row's ``config``; what the row does not fix is listed under ``assumed`` in
``configs/laguna-xs.2.json``.

Layer ``l`` (from 0) of the stream ``x [T, 2048]``; every norm an RMSNorm at
1e-6 with a learned scale. ``l`` is a FULL-attention layer of 48 query heads
where ``l mod 4 = 0`` and a SLIDING layer of 64 (window 512) otherwise
(``layer_types``, ``num_attention_heads_per_layer``), over 8 key-value heads
of 128 in both.

*Attention*, ``h = norm(x)``: ``q = h W_q [T, H_l, 128]``, ``k = h W_k``,
``v = h W_v [T, 8, 128]``, no bias, no q/k norm; ``g = sigmoid(h W_g) [T,
H_l]``, one scalar a head (``gating``). Rotate-half rotation. Sliding layer:
``inv_freq_i = 10000^(-2i/128)`` over all 128 columns. Full layer: the FIRST
64 columns (``partial_rotary_factor`` 0.5) rotate, by YaRN computed over
those ``d = 64`` columns -- ``extrap_i = theta^(-2i/d)``, ``corr(n) = d ln(L
/ (2 pi n)) / (2 ln theta)`` with ``theta`` 500,000 and ``L`` 4,096, ``low =
max(floor(corr(64)), 0)``, ``high = min(ceil(corr(1)), d - 1)``, ``ramp_i =
clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = extrap_i / 64 *
ramp_i + extrap_i (1 - ramp_i)``, cos AND sin times 1.41589 -- and the other
64 pass unrotated and unscaled. ``o_h[i] = sum_j softmax_j(q_h[i] .
k_{floor(h / (H_l / 8))}[j] / sqrt(128)) v[j]`` over ``j <= i`` and, on a
sliding layer, ``i - j < 512``; ``x <- x + concat_h(g_h o_h) W_o``.

*Feed-forward*, ``h2 = norm(x)``. Layer 0 (``mlp_layer_types`` dense): ``x
<- x + (silu(h2 W_1) * h2 W_3) W_2``, 8192 wide. Every later layer: ``s =
sigmoid(h2 W_r)`` over ALL 256 routed experts, the 8 largest, ``w_e = 2.5
s_e / sum_chosen s``, ``x <- x + sum_e w_e SwiGLU_e(h2) +
SwiGLU_shared(h2)``, each 512 wide, the weight on the expert's output. *The
share*: ``num_local_experts`` experts are held, the router's ``first_expert
..``; scores, top-8 and normalisation are over all 256, the held experts add
their part (a loop over them, every token through each), what the absent
ones would add is left out, the shared expert is whole. Final norm, untied
head, mean next-token cross entropy; no router loss.

Departures from the published description: the vocabulary is the chip's
slice, the experts the chip's share, the depth the leading layers.

``params`` is the system's own tree: ``model/leading/block_<l>`` for the
dense layers, ``model/periods/block_<i>`` with position ``i`` of every period
stacked on a leading axis behind them. Float32, matmuls at the highest
precision, attention in blocks of 256 queries against a dense boolean of the
keys each sees, one sequence at a time.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import dense

QUERY_BLOCK = 256


def is_full(sizes, layer):
    return layer % sizes["full_attention_period"] == 0


def query_heads(sizes, full):
    return sizes["num_attention_heads"] if full \
        else sizes["sliding_num_attention_heads"]


def rotary_table(sizes, full):
    """(frequencies of the rotated pairs, the factor on cos and sin): a
    sliding layer's plain table over every column, a full layer's YaRN
    table over its rotated columns alone."""
    D = sizes["head_dim_override"]
    if not full:
        i = jnp.arange(D // 2, dtype=jnp.float32)
        return sizes["sliding_rope_theta"] ** (-2 * i / D), 1.0
    d, theta = int(D * sizes["partial_rotary_factor"]), sizes["rope_theta"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    extrap = theta ** (-2 * i / d)
    if not sizes.get("yarn_factor"):
        return extrap, 1.0
    L = sizes["yarn_original_max_position_embeddings"]
    corr = lambda n: d * math.log(L / (2 * math.pi * n)) \
        / (2 * math.log(theta))
    low = max(math.floor(corr(sizes["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(sizes["yarn_beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0, 1)
    return (extrap / sizes["yarn_factor"] * ramp + extrap * (1 - ramp),
            sizes["yarn_attention_factor"])


def rotate(x, freq, factor):
    """x: [T, heads, D] at positions 0..T-1: the first ``2 len(freq)``
    columns rotate (rotate-half over them), the others pass as they are."""
    T, d = x.shape[0], 2 * freq.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = factor * jnp.cos(ang)[:, None], factor * jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(h, p, sizes, full):
    """h: [T, hidden] normed input; returns the o_proj output."""
    T = h.shape[0]
    Hq, Hkv, D = query_heads(sizes, full), sizes["num_key_value_heads"], \
        sizes["head_dim_override"]
    table = rotary_table(sizes, full)
    q = rotate((h @ p["q_proj"]["kernel"]).reshape(T, Hq, D), *table)
    k = rotate((h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D), *table)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    gate = jax.nn.sigmoid(h @ p["g_proj"]["kernel"])         # [T, Hq]
    q = q.reshape(T, Hkv, Hq // Hkv, D)      # head h reads k[h // (Hq/Hkv)]
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    def one_block(s):
        """Queries s .. s + block against every key, under a dense boolean
        of the keys each sees."""
        i = s + jnp.arange(block)[:, None]
        seen = (j <= i) if full else (j <= i) & (i - j < sizes[
            "sliding_window"])
        qs = jax.lax.dynamic_slice_in_dim(q, s, block)
        sc = jnp.einsum("qhgd,khd->hgqk", qs, k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(block, Hq, D)

    out = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, Hq, D)
    out = out * gate[:, :, None]
    return out.reshape(T, Hq * D) @ p["o_proj"]["kernel"]


def swiglu(h, p):
    return (jax.nn.silu(h @ p["gate_proj"]["kernel"])
            * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def held_experts(h, p, sizes):
    """(what the HELD experts add [T, hidden], pairs routed to each [G])."""
    T, K = h.shape[0], sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ p["gate"]["kernel"])              # [T, 256]
    top, idx = jax.lax.top_k(s, K)
    w = sizes["routed_scaling_factor"] * top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(w)
    chosen = jnp.zeros(s.shape, bool).at[jnp.arange(T)[:, None], idx].set(
        True)
    first, G = sizes.get("first_expert") or 0, sizes["num_local_experts"]

    def one_expert(out, e):
        """Every token through one held expert, weighed where it chose it."""
        w1, w3, w2, w = e
        return out + w[:, None] * ((jax.nn.silu(h @ w1) * (h @ w3)) @ w2), \
            None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["w1"], p["w3"], p["w2"], weight[:, first:first + G].T))
    return out, jnp.sum(chosen[:, first:first + G], 0)


@functools.partial(jax.jit, static_argnames=("sizes_t", "full", "is_dense"))
def _layer(x, p, sizes_t, full, is_dense):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes, full)
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if is_dense:
        return x + swiglu(h, p["mlp"]), jnp.zeros(sizes["num_local_experts"])
    out, rows = held_experts(h, p["block_sparse_moe"], sizes)
    return x + out + swiglu(h, p["shared_expert"]), rows


def layer_params(params, sizes, layer):
    k = sizes["first_k_dense"]
    if layer < k:
        return params["model"]["leading"][f"block_{layer}"]
    period, i = divmod(layer - k, sizes["full_attention_period"])
    return jax.tree_util.tree_map(
        lambda a: a[period], params["model"]["periods"][f"block_{i}"])


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, hidden], pairs each held expert computed [G]
    summed over layers) of one sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        rows = jnp.zeros(sizes["num_local_experts"])
        for l in range(sizes["num_hidden_layers"]):
            x, r = _layer(x, layer_params(params, sizes, l), static,
                          is_full(sizes, l), l < sizes["first_k_dense"])
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
