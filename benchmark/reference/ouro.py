"""Ouro-2.6B's looped decoder (``ByteDance/Ouro-2.6B`` ``config.json``,
``model_type`` ``ouro``; catalog row ``Ouro-2.6B``), forward pass and
training loss. Written from the row's ``config`` and, for what it has no key
for, the published modelling class and the paper ("Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741) as ``configs/ouro-2.6b.json``
lists under ``assumed``.

With ``R = total_ut_steps``, ``L`` layers, ``N(x) = w x rsqrt(mean(x^2) +
eps)``: ``x <- E[ids]``; for pass ``t = 1 .. R`` and layer ``l = 1 .. L``, the
same weights in every pass,

    a = x + N2_l(Attn_l(N1_l(x)));   x = a + N4_l(MLP_l(N3_l(a)))

(16 causal heads of 128, no bias, rotate-half RoPE over all 128 columns at
``rope_theta``, positions 0 .. T-1 in EVERY pass; ``MLP = down(silu(gate) *
up)``); after the pass ``h_t = N_f(x)`` and ``x <- h_t``; ``logits_t = h_t
W_head``; ``lambda_t = sigmoid(w_g . h_t + b_g)``. A token's exit
distribution is ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < R`` and
``p_R = prod_{j<R} (1 - lambda_j)``; its training loss ``sum_t p_t CE_t - beta
H(p)`` with ``H(p) = - sum_t p_t ln p_t`` and ``beta = exit_entropy_coef``;
the loss of a batch the mean over its shifted tokens.

``params`` is the system's own tree (``loop/layers/block`` stacked on a
leading layer axis). Float32, matmuls at the highest precision, no scan, no
remat, no kernel; attention in blocks of 512 queries and the head in blocks
of 2,048 positions (``dense.py``'s), so that 8,192 x 49,152 fits beside the
engine. One sequence at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense


@functools.partial(jax.jit, static_argnames=("sizes_t",))
def _layer(x, p, sizes_t):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    eps = sizes["rms_norm_eps"]
    norm = lambda t, name: dense.rms_norm(t, p[name]["scale"], eps)
    a = x + norm(dense.attention(norm(x, "input_layernorm"), p["self_attn"],
                                 sizes, None), "input_layernorm_2")
    return a + norm(dense.mlp(norm(a, "post_attention_layernorm"), p["mlp"]),
                    "post_attention_layernorm_2")


def pass_states(params, sizes, ids):
    """``[h_1 .. h_R]``, each ``[T, H]``: the normed state after every
    pass."""
    loop = params["loop"]
    scale = loop["norm"]["scale"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        states = []
        for _ in range(sizes["total_ut_steps"]):
            for l in range(sizes["num_hidden_layers"]):
                x = _layer(x, jax.tree_util.tree_map(
                    lambda a: a[l], loop["layers"]["block"]),
                    dense._static(sizes))
            x = dense.rms_norm(x, scale, sizes["rms_norm_eps"])
            states.append(x)
        return states


def hidden_states(params, sizes, ids):
    """The state of the LAST pass ``[T, H]``: what ``logits`` reads without
    labels."""
    return pass_states(params, sizes, ids)[-1]


def logits(params, hidden):
    """[rows, H] -> [rows, V] float32."""
    return dense.logits(params["loop"], hidden)


def _token_nll(params, hidden, ids):
    """``-log p(ids[t+1] | ids[:t+1])`` for positions 0 .. T-2, under the
    head read off ``hidden``, in blocks of positions."""
    out, T = [], ids.shape[0]
    for s in range(0, T - 1, dense.HEAD_BLOCK):
        e = min(s + dense.HEAD_BLOCK, T - 1)
        lg = logits(params, hidden[s:e])
        gold = jnp.take_along_axis(lg, ids[s + 1:e + 1, None], -1)[:, 0]
        out.append(jax.nn.logsumexp(lg, -1) - gold)
    return jnp.concatenate(out)


def step_losses(params, sizes, ids, states=None):
    """``CE_t`` of every shifted token under every pass's logits,
    ``[R, T - 1]``."""
    states = pass_states(params, sizes, ids) if states is None else states
    return jnp.stack([_token_nll(params, h, ids) for h in states])


def exit_distribution(params, sizes, ids, states=None):
    """``p [R, T]``: each token's distribution over the pass it exits at."""
    states = pass_states(params, sizes, ids) if states is None else states
    gate = dense.f32(params["loop"]["early_exit_gate"])
    with jax.default_matmul_precision("highest"):
        lam = [jax.nn.sigmoid((h @ gate["kernel"])[:, 0] + gate["bias"][0])
               for h in states]
    p, stay = [], jnp.ones_like(lam[0])
    for l in lam[:-1]:
        p.append(l * stay)
        stay = stay * (1.0 - l)
    return jnp.stack(p + [stay])


def token_losses(params, sizes, ids):
    """``sum_t p_t CE_t - beta H(p)`` of the shifted tokens, ``[T - 1]``."""
    states = pass_states(params, sizes, ids)
    ce = step_losses(params, sizes, ids, states)
    p = exit_distribution(params, sizes, ids, states)[:, :-1]
    entropy = -jnp.sum(jax.scipy.special.xlogy(p, p), axis=0)
    return jnp.sum(p * ce, axis=0) - sizes["exit_entropy_coef"] * entropy


def loss(params, sizes, batch_ids):
    """The training loss of a batch [B, T] with labels = inputs."""
    total, count = jnp.float32(0.0), 0
    for ids in batch_ids:
        ids = jnp.asarray(ids)
        total = total + jnp.sum(token_losses(params, sizes, ids))
        count += ids.shape[0] - 1
    return total / count


def grads(params, sizes, batch_ids):
    """``jax.grad`` of this file's own ``loss`` (float32 weights): a shared
    layer's gradient is the sum over the passes that used it, the head's and
    the gate's over ``R`` readings."""
    return jax.grad(loss)(dense.f32(params), sizes, batch_ids)
