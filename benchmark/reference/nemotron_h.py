"""NVIDIA-Nemotron-3-Nano-30B-A3B's forward pass, written from the published
``config.json`` (``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``model_type``
``nemotron_h``) and from nothing of ``deepspeed_tpu/models/nemotron_h.py``
but its parameter tree.

Stream ``x [T, hidden]``; layer ``l`` is of kind ``PATTERN[first_layer + l]``
and computes ``x <- x + Mixer(rms_norm(x))``, ONE branch a layer; every norm
``scale * x / sqrt(mean(x^2) + eps)``.

``M`` (Mamba-2; ``H`` heads of ``P``, state ``N``, ``G`` groups, ``d = H P``):
``[z ; xBC ; dt] = h W_in`` of widths ``d``, ``d + 2 G N``, ``H``; ``xBC <-
silu(conv(xBC) + b)``, the depthwise causal convolution as shifted adds, zeros
before position 0; ``[x ; B ; C] = xBC``; ``dt <- softplus(dt + dt_bias)``;
``A = -exp(A_log)``; TOKEN BY TOKEN on ``S [H, P, N]`` from zero: ``S <-
exp(dt A) S + (dt x) B^T`` (head ``i`` reads group ``i // (H / G)``), ``y = S C
+ D x``; ``g = y * silu(z)``; group by group over ``d / G`` columns ``g <-
scale * g / rms(g)``; ``g W_out``. It knows no chunk.

``*``: ``q [T, Hq, D]``, ``k, v [T, Hkv, D]``, no bias, no rotation; causal
softmax at ``1 / sqrt(D)``, query head ``i`` reading key-value head ``i //
(Hq / Hkv)``; ``W_o``.

``E``: ``s = sigmoid(h W_r)`` over all the router's experts, the
``num_experts_per_tok`` largest, ``w = factor * s / (sum of the chosen +
1e-20)``; ``sum_e w_e W_down,e relu(W_up,e h)^2`` over the HELD experts (the
router's ``first_expert ..``: a loop over them, every token through each;
what the absent ones would add is left out) plus the shared expert
``W_down relu(W_up h)^2``.

Then the final norm, the untied head, the mean next-token cross entropy.
Departures from the published description: the vocabulary is the chip's
slice, the experts the chip's share, the depth a slice of the pattern.

``params`` is the system's own tree: ``model/run_<i>/periods/block_<j>``, the
stack's layers in order -- run by run, repeat by repeat along each leaf's
leading axis, block by block. Float32, matmuls at the highest precision,
attention a block of queries at a time against a dense boolean, one sequence
at a time.
"""

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import dense

QUERY_BLOCK = 256
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def kinds(sizes):
    first = sizes.get("first_layer") or 0
    return PATTERN[first:first + sizes["num_hidden_layers"]]


def conv(x, taps, bias):
    """Depthwise causal convolution as shifted adds: ``y[t] = sum_j taps[j]
    x[t - (K - 1) + j] + bias``, zeros before position 0."""
    K, T = taps.shape[0], x.shape[0]
    y = bias
    for j in range(K):
        back = K - 1 - j
        y = y + taps[j] * jnp.pad(x, ((back, 0), (0, 0)))[:T]
    return y


def recurrence(x, dt, a, b, c):
    """Token by token: ``x [T, H, P]``, ``dt [T, H]``, ``a [H]``, ``b, c [T,
    H, N]`` (each head's own group's) -> ``y [T, H, P]``."""
    H, P, N = x.shape[1], x.shape[2], b.shape[2]

    def step(S, t):
        x_t, dt_t, b_t, c_t = t
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.sum(S * c_t[:, None, :], -1)

    return jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, b, c))[1]


def mamba(h, p, sizes):
    T = h.shape[0]
    H, P, N, G = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                  sizes["ssm_state_size"], sizes["n_groups"])
    d = H * P
    zxbcdt = h @ p["in_proj"]["kernel"]
    z, xbc, dt = (zxbcdt[:, :d], zxbcdt[:, d:2 * d + 2 * G * N],
                  zxbcdt[:, 2 * d + 2 * G * N:])
    xbc = jax.nn.silu(conv(xbc, p["conv_weight"], p["conv_bias"]))
    x = xbc[:, :d].reshape(T, H, P)
    b = jnp.repeat(xbc[:, d:d + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(xbc[:, d + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["A_log"]), b, c) \
        + p["D"][None, :, None] * x
    g = (y.reshape(T, d) * jax.nn.silu(z)).reshape(T, G, d // G)
    groups = [g[:, i] * jax.lax.rsqrt(
        jnp.mean(g[:, i] ** 2, -1, keepdims=True) + sizes["rms_norm_eps"])
        for i in range(G)]
    return (jnp.concatenate(groups, -1) * p["norm_scale"]) \
        @ p["out_proj"]["kernel"]


def attention(h, p, sizes):
    T = h.shape[0]
    Hq, Hkv, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim_override"])
    q = (h @ p["q_proj"]["kernel"]).reshape(T, Hkv, Hq // Hkv, D)
    k = (h @ p["k_proj"]["kernel"]).reshape(T, Hkv, D)
    v = (h @ p["v_proj"]["kernel"]).reshape(T, Hkv, D)
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    j = jnp.arange(T)[None, :]

    def one_block(s):
        seen = j <= s + jnp.arange(block)[:, None]
        sc = jnp.einsum("qhgd,khd->hgqk",
                        jax.lax.dynamic_slice_in_dim(q, s, block), k) \
            / D ** 0.5
        pr = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(block, Hq * D)

    out = jax.lax.map(one_block, jnp.arange(0, T, block))
    return out.reshape(T, Hq * D) @ p["o_proj"]["kernel"]


def relu2(h, up, down):
    return jnp.maximum(h @ up, 0) ** 2 @ down


def experts(h, p, shared, sizes):
    """(what the HELD experts and the shared one add [T, hidden], pairs
    routed to each held expert [G])."""
    T, K = h.shape[0], sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ p["gate"]["kernel"])
    top, idx = jax.lax.top_k(s, K)
    w = sizes["routed_scaling_factor"] * top \
        / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    rows = jnp.arange(T)[:, None]
    weight = jnp.zeros_like(s).at[rows, idx].set(w)
    chosen = jnp.zeros(s.shape, bool).at[rows, idx].set(True)
    first, G = sizes.get("first_expert") or 0, sizes["num_local_experts"]

    def one_expert(out, e):
        up, down, w = e
        return out + w[:, None] * relu2(h, up, down), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        p["w1"], p["w2"], weight[:, first:first + G].T))
    out = out + relu2(h, shared["up_proj"]["kernel"],
                      shared["down_proj"]["kernel"])
    return out, jnp.sum(chosen[:, first:first + G], 0)


@functools.partial(jax.jit, static_argnames=("sizes_t", "kind"))
def _layer(x, p, sizes_t, kind):
    sizes = dict(sizes_t)
    p = dense.f32(p)
    h = dense.rms_norm(x, p["norm"]["scale"], sizes["rms_norm_eps"])
    rows = jnp.zeros(sizes["num_local_experts"])
    if kind == "M":
        out = mamba(h, p["mixer"], sizes)
    elif kind == "*":
        out = attention(h, p["self_attn"], sizes)
    else:
        out, rows = experts(h, p["block_sparse_moe"], p["shared_expert"],
                            sizes)
    return x + out, rows


def layer_params(params):
    """The stack's layers' parameters, in order."""
    model = params["model"]
    for run in sorted((k for k in model if k.startswith("run_")),
                      key=lambda k: int(k[4:])):
        blocks = model[run]["periods"]
        names = sorted(blocks, key=lambda k: int(k[6:]))
        repeats = jax.tree_util.tree_leaves(blocks)[0].shape[0]
        for r in range(repeats):
            for name in names:
                yield jax.tree_util.tree_map(lambda a: a[r], blocks[name])


def layers_in_order(params, sizes):
    """[(kind, its parameters)] of the stack's layers, in order."""
    layers = list(layer_params(params))
    assert len(layers) == len(kinds(sizes)), (len(layers), kinds(sizes))
    return list(zip(kinds(sizes), layers))


def hidden_states(params, sizes, ids):
    """(final-normed hidden [T, hidden], pairs each held expert computed [G]
    summed over layers) of one sequence ``ids`` [T]."""
    static = dense._static(sizes)
    with jax.default_matmul_precision("highest"):
        model = params["model"]
        x = model["embed_tokens"]["embedding"][ids].astype(jnp.float32)
        rows = jnp.zeros(sizes["num_local_experts"])
        for kind, p in layers_in_order(params, sizes):
            x, r = _layer(x, p, static, kind)
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
