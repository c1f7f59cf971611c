"""ZAYA1-8B's language model (``Zyphra/ZAYA1-8B``, ``model_type`` ``zaya``),
forward pass and training loss, at ONE CHIP'S SHARE of each expert sublayer.
No modelling code of this family is installed, so this follows the published
description (the CCA paper, arXiv 2510.04476; the ZAYA1 report, arXiv
2511.17127) as ISSUE 35 wrote it down; what the catalog row does not fix is
listed under ``assumed`` in ``configs/zaya1-8b.json``.

A layer is two sublayers on the RMS-normed stream ``h`` (``h_{-1} = 0``):

*CCA*: ``q~ = W_q h`` (8 heads of 128), ``k~ = W_k h`` (2 heads), no bias.
``c = [q~ ; k~]`` passes two causal convolutions along the sequence, zeros
before position 0: A depthwise, ``a_t = wA[0] * c_{t-1} + wA[1] * c_t + bA``;
B grouped by head (10 groups of 128), ``b_t^g = a_{t-1}^g WB[0]^g + a_t^g
WB[1]^g + bB^g``. Query-key mean, 4 query heads to a key head:
``m^q_i = (q~_i + k~_{i // 4}) / 2``, ``m^k_j = (mean_{i in j} q~_i + k~_j) /
2``; ``q = b[:1024] + m^q``, ``k = b[1024:] + m^k``. Per head ``q <- sqrt(128)
q / |q|``, ``k <- tau_j sqrt(128) k / |k|``. Value ``v_t = [W_v1 h_t ; W_v2
h_{t-1}]``, read as 2 heads of 128. Rotate-half RoPE (theta 5e6) on the first
64 columns of each head of q and k; causal grouped-query softmax at scale
``128 ** -0.5``; ``W_o``.

*Expert sublayer*: ``r = W_d h + b_d``, ``s_l = r + gamma_l * s_{l-1}`` (the
router's state, carried from layer to layer, ``s_{-1} = 0``); ``z =
RMSNorm(s_l)``; ``logits = gelu(gelu(z W_1 + b_1) W_2 + b_2) W_3`` over the 16
experts and the skip expert (erf GELU); ``p = softmax(logits)``; the choice is
the top-1 of ``p + b`` (``b`` the balancing bias), its weight ``p``. An expert
adds ``p * down(silu(gate h) * up h)``, the skip expert ``p * h``.

*Residual* of a sublayer with output ``y``: ``x <- (x + b_r) * a_r + (y +
b_y) * a_y``. Final RMSNorm; logits through the embedding table (tied);
token-mean cross entropy of the shifted labels.

*The share*: ``n_routed_experts`` experts are held, the router's
``first_expert ..``; router, choice and weights are over all ``router_experts``
and the skip column; a loop over the HELD experts adds their part, the skip
expert's part is added whole, what the absent experts would add is left out.

``params`` is the system's own tree (layers stacked under ``layers/block``).
Float32, matmuls at the highest precision, attention in blocks of queries,
one sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


def head_dim(sizes):
    """``sizes["head_dim"]`` is hidden / heads wherever ``common.sizes_of``
    made it; this model's own is ``head_dim_override``."""
    return sizes["head_dim_override"]


def conv_depthwise(x, w, b):
    """x: [T, C]; w: [taps, C]; tap ``taps - 1`` meets the current row."""
    T, taps = x.shape[0], w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(w[j] * padded[j:j + T] for j in range(taps)) + b


def conv_grouped(x, w, b):
    """x: [T, C]; w: [taps, G, C/G, C/G], one in x out matrix a group."""
    T, (taps, G, D, _) = x.shape[0], w.shape
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(jnp.einsum("tgi,gio->tgo", padded[j:j + T].reshape(T, G, D),
                          w[j]).reshape(T, G * D) for j in range(taps)) + b


def partial_rope(x, sizes):
    rot = int(x.shape[-1] * sizes["partial_rotary_factor"])
    return jnp.concatenate([dense.rope(x[..., :rot], sizes["rope_theta"]),
                            x[..., rot:]], -1)


def unit(x):
    return x.shape[-1] ** 0.5 * x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D, r = head_dim(sizes), Hq // Hkv
    q0 = (h @ p["q_proj"]["kernel"]).reshape(T, Hq, D)
    k0 = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    c = jnp.concatenate([q0.reshape(T, -1), k0.reshape(T, -1)], -1)
    c = conv_depthwise(c, p["conv_a_weight"], p["conv_a_bias"])
    c = conv_grouped(c, p["conv_b_weight"], p["conv_b_bias"])
    mq = 0.5 * (q0 + jnp.repeat(k0, r, axis=1))
    mk = 0.5 * (q0.reshape(T, Hkv, r, D).mean(2) + k0)
    q = unit(c[:, :Hq * D].reshape(T, Hq, D) + mq)
    k = p["temperature"][:, None] * unit(c[:, Hq * D:].reshape(T, Hkv, D)
                                         + mk)
    before = jnp.concatenate([jnp.zeros_like(h[:1]), h[:-1]])
    v = jnp.concatenate([h @ p["v1_proj"]["kernel"],
                         before @ p["v2_proj"]["kernel"]], -1).reshape(
                             T, Hkv, D)
    q = partial_rope(q, sizes).reshape(T, Hkv, r, D)
    k = partial_rope(k, sizes)
    j = jnp.arange(T)[None, :]
    out = []
    for s in range(0, T, dense.QUERY_BLOCK):
        i = jnp.arange(s, min(s + dense.QUERY_BLOCK, T))[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk", q[s:s + dense.QUERY_BLOCK],
                        k) * D ** -0.5
        pr = jax.nn.softmax(jnp.where((j <= i)[None, None], sc, -jnp.inf), -1)
        out.append(jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, Hq * D))
    return jnp.concatenate(out) @ p["o_proj"]["kernel"]


def route(h, state, p, sizes):
    """[T, H], the previous layer's state [T, R] -> (combine weights
    [T, E + 1] over ALL the router's columns, zero outside a token's choice;
    this layer's state)."""
    state = h @ p["down_kernel"] + p["down_bias"] + p["state_scale"] * state
    gelu = lambda a: jax.nn.gelu(a, approximate=False)
    z = dense.rms_norm(state, p["norm_scale"], sizes["rms_norm_eps"])
    z = gelu(z @ p["fc1_kernel"] + p["fc1_bias"])
    z = gelu(z @ p["fc2_kernel"] + p["fc2_bias"])
    prob = jax.nn.softmax(z @ p["fc3_kernel"], -1)
    _, idx = jax.lax.top_k(prob + p["balancing_bias"],
                           sizes["num_experts_per_tok"])
    chosen = jax.nn.one_hot(idx, prob.shape[-1], dtype=jnp.float32).sum(1)
    return prob * chosen, state


def moe_parts(h, state, p, sizes):
    """(what the HELD experts add [T, H], what the skip expert adds
    [T, H], the router's state [T, R], tokens routed to each held expert
    [G], tokens that chose the skip expert)."""
    combine, state = route(h, state, p["router"], sizes)
    first, G = sizes.get("first_expert") or 0, sizes["n_routed_experts"]
    held = combine[:, first:first + G]

    def one_expert(out, e):
        w1, w3, w2, c = e
        return out + c[:, None] * ((jax.nn.silu(h @ w1) * (h @ w3)) @ w2), \
            None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             (p["w1"], p["w3"], p["w2"], held.T))
    skip = combine[:, -1]
    return routed, skip[:, None] * h, state, jnp.sum(held > 0, 0), \
        jnp.sum(skip > 0)


def residual(x, y, p):
    return (x + p["residual_bias"]) * p["residual_scale"] \
        + (y + p["output_bias"]) * p["output_scale"]


@functools.partial(jax.jit, static_argnames=("sizes_t",))
def _layer(x, state, p, sizes_t):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = residual(x, attention(
        dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
        p["self_attn"], sizes), p["attn_residual"])
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    routed, skip, state, rows, skipped = moe_parts(h, state, p["mlp"], sizes)
    return residual(x, routed + skip, p["mlp_residual"]), state, rows, skipped


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, H], tokens each held expert computed [G],
    tokens that chose the skip expert; both summed over the layers) of one
    sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        state = jnp.zeros((ids.shape[0], sizes["router_hidden_size"]))
        rows, skipped = jnp.zeros(sizes["n_routed_experts"]), 0
        for l in range(sizes["num_hidden_layers"]):
            x, state, r, s = _layer(x, state, jax.tree_util.tree_map(
                lambda a: a[l], model["layers"]["block"]), static)
            rows, skipped = rows + r, skipped + s
        return dense.rms_norm(x, model["norm"]["scale"].astype(jnp.float32),
                              sizes["rms_norm_eps"]), rows, skipped


def logits(params, hidden):
    """[rows, H] -> [rows, V] float32, through the embedding table."""
    with jax.default_matmul_precision("highest"):
        return dense._logits(
            hidden, params["model"]["embed_tokens"]["embedding"].T)


def loss(params, sizes, batch_ids):
    """The training loss of a batch [B, T] with labels = inputs."""
    head = {"lm_head": {
        "kernel": params["model"]["embed_tokens"]["embedding"].T}}
    total, count = jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        total = total + dense.nll_sum(
            head, hidden_states(params, sizes, ids)[0], ids)
        count += ids.shape[0] - 1
    return total / count
