"""Mixtral-8x7B-v0.1's forward pass as published (``modeling_mixtral.py``):
the dense block of ``dense.py`` with the MLP replaced by a sparse mixture —
router logits -> softmax over ALL experts -> top-k per token -> the selected
weights renormalised to sum to 1 -> the weighted sum of the selected
experts' SwiGLU outputs — plus the load-balancing loss of
``load_balancing_loss_func``: E * sum_e(mean routed share_e * mean router
probability_e), the means over every token of every layer, scaled by
``router_aux_loss_coef``. Mixtral-8x7B has no sliding window.

Every expert runs over every token and the combine weight is zero outside a
token's top-k: no dispatch, no capacity, nothing dropped.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


def route(h, gate_kernel, top_k):
    """[T, H] -> (combine [T, E], probs [T, E], routed [T, E] in {0, 1})."""
    probs = jax.nn.softmax(h @ gate_kernel, -1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.sum(w, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
    return jnp.einsum("tk,tke->te", w, onehot), probs, jnp.max(onehot, 1)


@functools.partial(jax.jit, static_argnames=("sizes_t", "top_k"))
def _layer(x, p, sizes_t, top_k):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + dense.attention(
        dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
        p["self_attn"], sizes, sizes.get("sliding_window"))
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    moe = p["block_sparse_moe"]
    combine, probs, routed = route(h, moe["gate"]["kernel"], top_k)
    # every expert over every token, the stacked weights kept on their
    # leading (expert) axis so that sharded experts stay where they live
    hidden = jax.nn.silu(jnp.einsum("th,ehi->tei", h, moe["w1"])) \
        * jnp.einsum("th,ehi->tei", h, moe["w3"])
    out = jnp.einsum("te,teh->th", combine,
                     jnp.einsum("tei,eih->teh", hidden, moe["w2"]))
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
    [B, T] with labels = inputs."""
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
