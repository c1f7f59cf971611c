"""The language model of Keye-VL-2.0-30B-A3B (``text`` part of the
``KeyeVL2`` config; catalog row ``Keye-VL-2.0-30B-A3B``), forward pass and
training loss, at ONE CHIP'S SHARE of each expert layer. Written from the
row and from the published description of DeepSeek-V3.2-Exp's sparse
attention, which the row's ``described_as`` names; every detail the row does
not fix is listed under ``assumed`` in ``configs/keye-vl2-30b-a3b.json``.

Every layer: RMSNorm -> attention under a learned selection -> residual ->
RMSNorm -> sparse experts -> residual. *Attention*, for the normed input
``h [T, 2048]``: ``q = RoPE(norm(W_q h))`` (32 heads of 128), ``k =
RoPE(norm(W_k h))``, ``v = W_v h`` (4 heads of 128), the norm an RMSNorm over
each head's 128 columns, rotate-half RoPE at ``rope_theta`` (``mrope_section``
is ordinary RoPE on text, whose three position ids are equal). *The
indexer*: ``qI = RoPE(W_qI h) [T, 16, 64]``, ``kI = RoPE(LayerNorm(W_kI h))
[T, 64]`` (one key for the 16 heads, rotary on all 64 columns), ``w = (W_w h)
(16 * 64)^-1/2 [T, 16]``, ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])``.
*The selection*: ``S_t`` = the ``min(topk, t + 1)`` keys ``s <= t`` of largest
``I[t, s]`` — ``lax.top_k`` over the causal part of the row, whose ties go to
the lower index — scattered into a dense mask, one set for all heads. ``o[t,
h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, h // 8] / sqrt(128)) v[s,
h // 8]``, then ``W_o``. *Experts*: softmax over ALL ``router_experts``
experts, top-8, the chosen probabilities renormalised to sum to 1
(``norm_topk_prob``), the weighted sum of the chosen experts' SwiGLU outputs;
no shared expert. Final RMSNorm, untied head.

*The loss* is what the published sparse stage trains: the token-mean cross
entropy of the shifted labels plus, for every layer, ``mean_t KL(p^_t ||
softmax_{S_t} I[t, .])`` with ``p^_t`` the mean over the 32 heads of the
attention probabilities over ``S_t`` (coefficient 1; in training ``p^`` and
the indexer's input are detached, which changes gradients and no value). No
router auxiliary loss (its coefficient is not in the row).

*The share*: ``num_local_experts`` experts are held, the router's
``first_expert .. first_expert + num_local_experts``; router, top-k and
renormalisation are over all ``router_experts``; a loop over the HELD experts
adds their part, what the absent ones would add is left out and that partial
result goes on. With ``router_experts`` absent all experts are held.

``params`` is the system's own tree (layers stacked under ``layers/block``).
Float32, matmuls at the highest precision, indexer, selection and attention
in blocks of 512 queries (a block holds ``[32, 512, T]`` scores), one
sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


def _head_dim(sizes):
    return sizes.get("head_dim_override") or sizes["head_dim"]


def layer_norm(x, p, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def indexer(h, p, sizes):
    """(qI [T, J, d], kI [T, d], w [T, J])."""
    T = h.shape[0]
    J, d = sizes["sa_indexer_num_heads"], sizes["sa_indexer_head_dim"]
    theta = sizes["rope_theta"]
    qi = dense.rope((h @ p["wq"]["kernel"]).reshape(T, J, d), theta)
    ki = dense.rope(layer_norm(h @ p["wk"]["kernel"],
                               p["k_norm"])[:, None], theta)[:, 0]
    return qi, ki, (h @ p["weights_proj"]["kernel"]) * (J * d) ** -0.5


def attention(h, p, sizes):
    """h: [T, H] normed input; returns (the o_proj output [T, H], the sum
    over queries of KL(p^_t || softmax_{S_t} I[t, .]))."""
    T = h.shape[0]
    Hq, Hkv, D = (sizes["num_attention_heads"],
                  sizes["num_key_value_heads"], _head_dim(sizes))
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    topk = min(sizes["sa_topk"], T)
    q = (h @ p["q_proj"]["kernel"]).reshape(T, Hq, D)
    k = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    q = dense.rope(dense.rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = dense.rope(dense.rms_norm(k, p["k_norm"]["scale"], eps), theta)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    qi, ki, w = indexer(h, p["indexer"], sizes)
    j = jnp.arange(T)[None, :]
    block = min(dense.QUERY_BLOCK, T)

    def rows(carry, xs):
        i, q, qi, w = xs                       # i: the block's positions
        i = i[:, None]
        index = jnp.sum(jax.nn.relu(jnp.einsum("qjd,kd->jqk", qi, ki))
                        * w.T[:, :, None], 0)                  # [block, T]
        _, chosen = jax.lax.top_k(jnp.where(j <= i, index, -jnp.inf), topk)
        real = jnp.arange(topk)[None, :] <= i   # the row's first t + 1
        seen = jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], chosen].set(real)
        sc = jnp.einsum("qhgd,khd->hgqk", q.reshape(block, Hkv, -1, D),
                        k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        out = jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(block, Hq * D)
        p_hat = jnp.mean(pr, (0, 1))                           # [block, T]
        log_q = jax.nn.log_softmax(jnp.where(seen, index, -jnp.inf), -1)
        kl = jnp.where(p_hat > 0, p_hat * (
            jnp.log(jnp.where(p_hat > 0, p_hat, 1.0))
            - jnp.where(seen, log_q, 0.0)), 0.0)
        return carry + jnp.sum(kl), out

    fold = lambda a: a.reshape(T // block, block, *a.shape[1:])
    kl, out = jax.lax.scan(rows, jnp.float32(0.0),
                           (fold(jnp.arange(T)), fold(q), fold(qi), fold(w)))
    return out.reshape(T, Hq * D) @ p["o_proj"]["kernel"], kl


def route(h, p, sizes):
    """[T, H] -> combine weights [T, E] over ALL the router's experts: zero
    outside a token's top-k."""
    probs = jax.nn.softmax(h @ p["gate"]["kernel"], -1)
    w, idx = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    if sizes.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
        idx, probs.shape[-1], dtype=jnp.float32))


def held_experts(h, p, sizes):
    """(what the HELD experts add [T, H], pairs routed to each [G])."""
    combine = route(h, p, sizes)
    first, G = sizes.get("first_expert") or 0, sizes["num_local_experts"]
    held = combine[:, first:first + G]

    def one_expert(out, e):
        w1, w3, w2, c = e
        return out + c[:, None] * ((jax.nn.silu(h @ w1) * (h @ w3)) @ w2), \
            None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (p["w1"], p["w3"], p["w2"], held.T))
    return out, jnp.sum(held > 0, 0)


@functools.partial(jax.jit, static_argnames=("sizes_t",))
def _layer(x, p, sizes_t):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    attn, kl = attention(
        dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
        p["self_attn"], sizes)
    x = x + attn
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    out, rows = held_experts(h, p["block_sparse_moe"], sizes)
    return x + out, kl, rows


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, H], the indexer's loss of the sequence —
    the sum over layers of the token-mean KL —, pairs each held expert
    computed [G] summed over layers) of one sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        kl, rows = jnp.float32(0.0), jnp.zeros(sizes["num_local_experts"])
        for l in range(sizes["num_hidden_layers"]):
            x, k, r = _layer(x, jax.tree_util.tree_map(
                lambda a: a[l], model["layers"]["block"]), static)
            kl, rows = kl + k / ids.shape[0], rows + r
        return dense.rms_norm(x, model["norm"]["scale"].astype(jnp.float32),
                              sizes["rms_norm_eps"]), kl, rows


logits = dense.logits


def loss_terms(params, sizes, batch_ids):
    """(token-mean cross entropy, the indexer's loss) of a batch [B, T]
    with labels = inputs, each a mean over the batch's tokens."""
    nll, kl, count = jnp.float32(0.0), jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        hidden, k, _ = hidden_states(params, sizes, ids)
        nll = nll + dense.nll_sum(params, hidden, ids)
        kl = kl + k
        count += ids.shape[0] - 1
    return nll / count, kl / len(batch_ids)


def loss(params, sizes, batch_ids):
    """The training loss: what ``train_batch`` returns."""
    return sum(loss_terms(params, sizes, batch_ids))
