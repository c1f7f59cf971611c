"""The language model of Kimi-VL-A3B (``text_config``; the published
modelling code of that config is ``transformers``' ``modeling_deepseek_v3.py``:
``DeepseekV3Attention``, ``DeepseekV3TopkRouter``, ``DeepseekV3MoE``), forward
pass and training loss, at ONE CHIP'S SHARE of each expert layer.

Every layer: RMSNorm -> latent attention -> residual -> RMSNorm -> feed-forward
-> residual. *Attention*: ``q_proj`` to 16 heads of 128 + 64; ``kv_a_proj_with_mqa``
to a 512-wide latent and ONE 64-wide rotary key; the latent is RMS-normed
(``kv_a_layernorm``) and ``kv_b_proj`` gives each head a 128-wide key and a
128-wide value; RoPE (theta 800000, no scaling) turns the 64 rotary columns of
each query and of the shared key, after the published class's default
de-interleave of those columns (``rope_interleave``: a fixed permutation, the
same on both sides of the product); keys are ``[k_nope, k_rope of all heads]``;
causal softmax in float32 at scale 192 ** -0.5; ``o_proj``. *Feed-forward*:
the first ``first_k_dense_replace`` layers a SwiGLU of ``intermediate_size``;
the others ``s = sigmoid(W_g x)`` over ALL ``router_experts`` experts, choice =
top-k of ``s + e_score_correction_bias``, weights = ``s`` at the chosen experts
over their sum + 1e-20, times ``routed_scaling_factor``; output = sum of weight
x expert SwiGLU + the shared experts' one SwiGLU. Final RMSNorm, untied head,
token-mean cross entropy of the shifted labels. No auxiliary loss (the
published code computes none).

*The share*: ``n_routed_experts`` experts are held, the router's
``first_expert .. first_expert + n_routed_experts``. The router, its top-k and
the weights' normalisation are over all ``router_experts``; a loop over the
HELD experts adds their part, what the absent experts would add is left out,
and that partial result goes on. With ``router_experts`` absent all experts
are held and this is the published forward pass; departures: none.

``params`` is the system's own tree (leading dense layers ``layers_<i>``, the
expert layers stacked under ``layers/block``). Float32, matmuls at the highest
precision, attention in blocks of queries, one sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


def rotary(x, sizes):
    """x: [T, heads, 64] -> RoPE'd, positions 0..T-1."""
    if sizes.get("rope_interleave", True):
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    return dense.rope(x, sizes["rope_theta"])


def attention(h, p, sizes):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    H, r = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    q = (h @ p["q_proj"]["kernel"]).reshape(T, H, dn + dr)
    kv_a = h @ p["kv_a_proj_with_mqa"]["kernel"]
    latent = dense.rms_norm(kv_a[:, :r], p["kv_a_layernorm"]["scale"],
                            sizes["rms_norm_eps"])
    kv = (latent @ p["kv_b_proj"]["kernel"]).reshape(T, H, dn + dv)
    k_rot = rotary(kv_a[:, None, r:], sizes)                   # [T, 1, dr]
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], sizes)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rot, (T, H, dr))], -1)
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


def route(h, p, sizes):
    """[T, H] -> combine weights [T, E] over ALL the router's experts: zero
    outside a token's top-k."""
    scores = jax.nn.sigmoid(h @ p["gate"])
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"],
                           sizes["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if sizes.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * sizes["routed_scaling_factor"]
    return jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        idx, scores.shape[-1], dtype=jnp.float32))


def moe_parts(h, p, sizes):
    """(what the HELD experts add [T, H], the shared experts' output
    [T, H], pairs routed to each held expert [G])."""
    combine = route(h, p, sizes)
    first, G = sizes.get("first_expert") or 0, sizes["n_routed_experts"]
    held = combine[:, first:first + G]

    def one_expert(out, e):
        w1, w3, w2, c = e
        return out + c[:, None] * ((jax.nn.silu(h @ w1) * (h @ w3)) @ w2), \
            None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                             (p["w1"], p["w3"], p["w2"], held.T))
    return routed, dense.mlp(h, p["shared_experts"]), jnp.sum(held > 0, 0)


@functools.partial(jax.jit, static_argnames=("sizes_t", "is_dense"))
def _layer(x, p, sizes_t, is_dense):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes)
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if is_dense:
        return x + dense.mlp(h, p["mlp"]), None
    routed, shared, rows = moe_parts(h, p["mlp"], sizes)
    return x + routed + shared, rows


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, H], pairs each held expert computed [G],
    summed over the expert layers) of one sequence ``ids`` [T]."""
    first = min(sizes.get("first_k_dense_replace", 1),
                sizes["num_hidden_layers"])
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        for l in range(first):
            x, _ = _layer(x, model[f"layers_{l}"], static, True)
        rows = jnp.zeros(sizes["n_routed_experts"])
        for l in range(sizes["num_hidden_layers"] - first):
            x, r = _layer(x, jax.tree_util.tree_map(
                lambda a: a[l], model["layers"]["block"]), static, False)
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
