"""SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat`` ``config.json``,
``model_type`` ``sdar_moe``; catalog row ``SDAR-30B-A3B-Chat``): the forward
pass and the block-diffusion training loss, at ONE CHIP'S SHARE of each expert
layer. Written from the row's ``config`` and from the training pass "Block
Diffusion: Interpolating Between Autoregressive and Diffusion Language Models"
(Arriola et al., ICLR 2025) defines -- one forward over ``[x_t ; x_0]`` under
a mask of three parts; what the row leaves open is listed under ``assumed`` in
``configs/sdar-30b-a3b.json``.

*The stack*, every layer: RMSNorm -> attention -> residual -> RMSNorm ->
sparse experts -> residual; final RMSNorm; untied head. Attention: ``q =
RoPE(norm(W_q h))`` (32 heads of 128), ``k = RoPE(norm(W_k h))``, ``v = W_v
h`` (4 heads of 128), the norm an RMSNorm over each head's 128 columns,
rotate-half RoPE at ``rope_theta`` at the position the CALLER gives each row.
Experts: softmax over ALL ``router_experts``, top-8, renormalised, the
weighted sum of the chosen experts' SwiGLU outputs; ``keye_vl2.held_experts``
(the same router family) adds the HELD experts' part, what the absent ones
would add is left out and that partial result goes on.

*The loss* of a sequence ``x0`` of ``L`` tokens in blocks of ``B =
block_length``: block ``k`` draws ``t_k = eps + (1 - eps) u_k`` and token
``i`` draws ``v_i``, uniform on ``[0, 1)`` (on a grid of 65,536: ``noise``;
``eps`` = ``EPS``) from the key ``fold_in(key(0), checksum(x0))``; ``m_i =
[v_i < t_k]``; ``xt_i = MASK if m_i else x0_i``, MASK the table's last row
(configs/sdar-30b-a3b.json ``assumed``). One pass over ``[xt ; x0]`` at
positions ``[0 .. L-1 ; 0 .. L-1]``; with ``half(p) = [p >= L]`` and ``blk(p)
= (p mod L) // B`` query ``q`` sees key ``j`` iff ``half(q) = half(j)`` and ``blk(q) = blk(j)``,
or ``q`` is noised, ``j`` clean and ``blk(q) > blk(j)``, or both are clean
and ``blk(q) >= blk(j)``. Then ``loss = (1 / (batch L)) sum_i m_i (1 / t_k(i))
(-log softmax(W h_i)[x0_i])`` over the noised rows, no shift, no router term.

*Without labels* (``hidden_states``): the same pass's final-normed noised
rows, which the loss reads through the head.

``params`` is the system's own tree (layers stacked under ``layers/block``).
Float32, matmuls at the highest precision, no kernel; the rule is a dense
boolean of one block of ``QUERY_BLOCK`` queries at a time (a block holds
``[32, 256, 2L]`` scores), the head in ``dense.HEAD_BLOCK`` positions, one
sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense
from benchmark.reference.keye_vl2 import held_experts

QUERY_BLOCK = 256
#: ``t`` is drawn from ``[EPS, 1)``
EPS = 1e-3


def checksum(ids):
    """``sum_i (ids_i + 1)(2i + 1)`` modulo ``2**32``, less its top bit."""
    ids = np.asarray(ids, np.int64)
    odd = 2 * np.arange(ids.shape[0], dtype=np.int64) + 1
    return int(np.sum((ids + 1) * odd) % 2 ** 32) & 0x7FFFFFFF


def noise(sizes, ids):
    """``(m [L] bool, t [L] float32)`` of one sequence: its masked tokens,
    and each token's block's ``t``. The draws are 16-bit integers, so that
    ``m`` is the same in every program: ``U_k`` and ``V_i`` uniform on ``0 ..
    65535``, ``T_k = E + floor((65536 - E) U_k / 65536)`` with ``E =
    round(65536 eps)``, ``t_k = T_k / 65536``, ``m_i = [V_i < T_k]``."""
    length, block = len(ids), sizes["block_length"]
    key = jax.random.fold_in(jax.random.key(0, impl="threefry2x32"),
                             checksum(ids))
    key_t, key_v = jax.random.split(key)
    u = np.asarray(jax.random.bits(key_t, (length // block,), jnp.uint32),
                   np.int64) // 65536
    v = np.asarray(jax.random.bits(key_v, (length,), jnp.uint32),
                   np.int64) // 65536
    e = round(65536 * EPS)
    level = np.repeat(e + (65536 - e) * u // 65536, block)
    return jnp.asarray(v < level), jnp.asarray(level / 65536.0, jnp.float32)


def rope_at(x, positions, theta):
    """x: [T, heads, D] rotated by its row's position, rotate-half."""
    D = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(q, j, half, block):
    """The rule on position arrays that broadcast."""
    clean_q, clean_j = q >= half, j >= half
    blk_q, blk_j = (q % half) // block, (j % half) // block
    return ((clean_q == clean_j) & (blk_q == blk_j)) \
        | (~clean_q & clean_j & (blk_q > blk_j)) \
        | (clean_q & clean_j & (blk_q >= blk_j))


def attention(h, p, sizes, positions, half):
    """h: [T, H] normed input; returns the o_proj output [T, H]."""
    T = h.shape[0]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes.get("head_dim_override") or sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    q = (h @ p["q_proj"]["kernel"]).reshape(T, Hq, D)
    k = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    q = rope_at(dense.rms_norm(q, p["q_norm"]["scale"], eps), positions,
                theta)
    k = rope_at(dense.rms_norm(k, p["k_norm"]["scale"], eps), positions,
                theta)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    j = jnp.arange(T)[None, :]
    block = min(QUERY_BLOCK, T)

    def rows(_, xs):
        i, q = xs                               # i: the block's row numbers
        seen = sees(i[:, None], j, half, sizes["block_length"])
        sc = jnp.einsum("qhgd,khd->hgqk", q.reshape(block, Hkv, -1, D),
                        k) / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return None, jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(block,
                                                                 Hq * D)

    fold = lambda a: a.reshape(T // block, block, *a.shape[1:])
    _, out = jax.lax.scan(rows, None, (fold(jnp.arange(T)), fold(q)))
    return out.reshape(T, Hq * D) @ p["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("sizes_t", "half"))
def _layer(x, p, positions, sizes_t, half):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    x = x + attention(dense.rms_norm(x, p["input_layernorm"]["scale"], eps),
                      p["self_attn"], sizes, positions, half)
    h = dense.rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    out, rows = held_experts(h, p["block_sparse_moe"], sizes)
    return x + out, rows


def _stack(params, sizes, ids, positions, half):
    """(final-normed hidden states ``[T, H]`` of the rows ``ids`` at
    ``positions`` under the rule of ``half``, pairs each held expert computed
    ``[G]`` summed over the layers)."""
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        rows = jnp.zeros(sizes["num_local_experts"])
        for l in range(sizes["num_hidden_layers"]):
            x, r = _layer(x, jax.tree_util.tree_map(
                lambda a: a[l], model["layers"]["block"]), positions,
                dense._static(sizes), half)
            rows = rows + r
        return dense.rms_norm(x, model["norm"]["scale"].astype(jnp.float32),
                              sizes["rms_norm_eps"]), rows


def noised_pass(params, sizes, ids):
    """``(hidden [L, H], m [L], t [L])``: the final-normed noised rows of one
    pass over ``[xt ; x0]``, the sequence's masked tokens and their ``t``."""
    ids = jnp.asarray(ids)
    L = ids.shape[0]
    m, t = noise(sizes, np.asarray(ids))
    both = jnp.concatenate([jnp.where(m, sizes["vocab_size"] - 1, ids), ids])
    return _stack(params, sizes, both, jnp.tile(jnp.arange(L), 2),
                  L)[0][:L], m, t


def hidden_states(params, sizes, ids):
    """What the model's label-free call puts under its head, ``[L, H]``."""
    return noised_pass(params, sizes, ids)[0]


logits = dense.logits


def token_losses(params, sizes, ids):
    """``m_i (1 / t_k(i)) (-log softmax(W h_i)[x0_i])`` of one sequence,
    ``[L]``."""
    ids = jnp.asarray(ids)
    hidden, m, t = noised_pass(params, sizes, ids)
    nll = []
    for s in range(0, ids.shape[0], dense.HEAD_BLOCK):
        lg = logits(params, hidden[s:s + dense.HEAD_BLOCK])
        gold = jnp.take_along_axis(
            lg, ids[s:s + dense.HEAD_BLOCK, None], -1)[:, 0]
        nll.append(jax.nn.logsumexp(lg, -1) - gold)
    return jnp.where(m, jnp.concatenate(nll) / t, 0.0)


def loss(params, sizes, batch_ids):
    """The training loss of a batch [B, L]: what ``train_batch`` returns."""
    total = sum(jnp.sum(token_losses(params, sizes, ids))
                for ids in batch_ids)
    return total / (len(batch_ids) * len(batch_ids[0]))


def grads(params, sizes, batch_ids):
    """``jax.grad`` of this file's own ``loss`` at float32 weights."""
    return jax.grad(loss)(dense.f32(params), sizes, batch_ids)
