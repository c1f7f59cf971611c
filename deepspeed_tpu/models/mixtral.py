"""Mixtral-family sparse-MoE decoder (BASELINE north star: Mixtral-8x7B
expert parallel).

The Llama block with the MLP replaced by a top-k sparse mixture of experts,
HF-``MixtralForCausalLM``-exact routing semantics: router logits → softmax
over ALL experts → top-k → renormalize the selected weights → weighted sum
of the selected experts' SwiGLU outputs (plus the Switch load-balancing aux
loss scaled by ``router_aux_loss_coef`` during training).

TPU-native dispatch: expert weights live STACKED ``[E, ...]`` and shard over
the ``expert`` mesh axis (which of their dims it shards: ``expert_layout``,
the one place that says). For ``T > 1`` the layer is a dropless, token-sorted
grouped matmul (``_routed_experts``): the ``N*K`` (token, expert) pairs are
stably sorted by expert, each expert's run of rows multiplies its own
weights in a grouped matmul (``_grouped_dot``: on one TPU device the
small-group kernels of ``ops/pallas/grouped_matmul.py`` where their rule
has tiles for the shape, ``jax.lax.ragged_dot`` — XLA:TPU's own kernel —
everywhere else), and each token's K rows are weighted and summed back in
float32. Group
sizes are DATA, so no routing recompiles, nothing is dropped (decisive for
HF logits parity) or padded to a capacity, and the arithmetic follows the
rows routed: K/E of what computing every expert for every token costs. Under
an ``expert`` mesh axis the same function runs inside a ``shard_map`` that
moves TOKENS and never weights: tokens all-gather over ``expert`` on entry,
each chip computes its share of the layer (``expert_layout``: every routed
pair over its columns of all experts, or the pairs routed to its ``E/ep``
whole experts), and the partial outputs ``psum_scatter`` back onto the batch
layout. ``T == 1`` with replicated experts keeps the weight-gather decode
path. For capacity-based all_to_all dispatch use ``deepspeed_tpu.moe.MoE``
(GShard gating, reference ``sharded_moe.py``).

Attention/rotary/cache machinery is shared with ``models/llama.py``.
"""

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import (REMAT_ATTN_OUT, REMAT_MOE_ROWS, REMAT_MOE_UP,
                          grouped_matmul)
from ..parallel.topology import get_mesh
from ..utils.logging import log_dist
from .layers import (RMSNorm, batch_axes, cross_entropy_loss, device_part,
                     head_scope, init_kv_cache, lm_head_output, name_if_kept,
                     remat_room, resolve_remat_policy, rotary_embedding,
                     shift_labels)
from .indexed_attention import SparseAttentionConfig
from .llama import LlamaAttention, LlamaConfig
from .llama import remat_offers as _llama_offers


def _expert_axis_active() -> bool:
    """True when the active mesh shards the ``expert`` axis (>1): the
    gather decode path would pull sharded expert rows cross-device, so it
    only engages with replicated experts."""
    mesh = get_mesh()
    if mesh is None:
        return False
    return dict(zip(mesh.axis_names,
                    mesh.devices.shape)).get("expert", 1) > 1


def _expert_axis_size(mesh) -> int:
    return 1 if mesh is None else dict(
        zip(mesh.axis_names, mesh.devices.shape)).get("expert", 1)


def expert_layout(E: int, I: int, ep: int) -> str:
    """What an ``expert`` mesh axis of size ``ep`` shards of the stacked
    expert weights ``w1``/``w3 [E, H, I]`` and ``w2 [E, I, H]``: the one
    place that decides (``partition_rules`` and ``_expert_mlp``'s
    ``shard_map`` both ask here, so the parameters' sharding and the
    layer's ``in_specs`` cannot disagree).

    ``"columns"``: every chip holds ``I/ep`` of the intermediate columns of
    ALL ``E`` experts and computes every routed pair at that width, so the
    chips do identical work whatever the router does. Taken when the slice
    keeps XLA's grouped-matmul kernel wide — ``I // ep`` at least 1024 and a
    multiple of the 128 lanes (Mixtral over 4: 3584).

    ``"experts"``: every chip holds ``E/ep`` whole experts and computes the
    pairs routed to them; the step then follows the busiest chip. Taken
    otherwise: fine-grained experts (OLMoE over 4: 64 experts of 1024 would
    leave 256 columns, and 16 experts a chip average the router out by
    themselves), and the tiny test configurations.

    Same parameters and optimizer state a chip and the same two token-sized
    collectives either way; no weight moves in either. (``E`` names the
    case and enters no condition today.)"""
    cols = I // ep
    if ep > 1 and I % ep == 0 and cols >= 1024 and cols % 128 == 0:
        return "columns"
    return "experts"


def _expert_weight_specs(layout: str):
    """``(spec of w1 and w3 [E, H, I], spec of w2 [E, I, H])``."""
    if layout == "columns":
        return P(None, None, "expert"), P(None, "expert", None)
    return P("expert", None, None), P("expert", None, None)


@functools.lru_cache(maxsize=None)
def _log_expert_layout(layout: str, E: int, I: int, ep: int) -> None:
    """Once per layout and shape, at trace time."""
    log_dist(f"moe expert layout: {layout} (E={E}, I/ep={I // ep})"
             if layout == "columns" else
             f"moe expert layout: {layout} (E/ep={E // ep}, I={I})", ranks=[0])


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    #: renormalise each token's top-k router probabilities to sum to 1
    #: (Mixtral); False combines with the raw softmax values (OLMoE)
    norm_topk_prob: bool = True
    #: lecun-normal initial scale of the stacked expert kernels over each
    #: expert's own fan-in (what an ``nn.Dense`` of one expert's shape
    #: gets). False counts the expert axis into the fan, as Mixtral's trees
    #: have always been seeded: every kernel sqrt(E) smaller and the layer's
    #: output E**1.5 smaller, 512x at 64 experts — too small for any logit
    #: to tell a right expert layer from a wrong one
    per_expert_init: bool = False
    #: the training call returns ``(loss, {"moe_rows_max_over_mean",
    #: "moe_rows_min_over_mean"})``: the busiest and the idlest expert's
    #: (token, expert) pairs over the mean, pairs summed over layers, which
    #: the train engine publishes as registry gauges; under an ``expert``
    #: mesh axis also ``"moe_chip_rows_max_over_mean"``, the busiest chip's
    #: pairs over the chips' mean (what the step follows under
    #: ``expert_layout``'s whole experts, 1 by construction under columns)
    report_expert_load: bool = False
    #: each expert's width where it is not ``intermediate_size`` (configs
    #: that also publish a dense width under that key)
    moe_intermediate_size: Optional[int] = None
    #: ONE CHIP'S SHARE of a layer's experts, as ``deepseek_v3.py`` and
    #: ``zaya.py`` hold it: ``num_local_experts`` experts are held, the
    #: router's ``first_expert ..`` of its ``router_experts`` (None: all are
    #: held). Router, top-k and the weights' normalisation are over all of
    #: them; the held experts add their part and the rest is left out
    router_experts: Optional[int] = None
    first_expert: int = 0
    #: False: the optimizer never moves ``block_sparse_moe/gate`` (a share
    #: trained alone teaches its router to starve it, PERF.md section 6)
    router_trainable: bool = True
    #: RMSNorm over each head's ``head_dim`` columns of the query and the
    #: key (scales ``[head_dim]``), before RoPE; ``qk_norm`` is OLMoE's norm
    #: over the whole projection
    qk_norm_per_head: bool = False
    #: a learned indexer chooses each query's keys
    #: (``models/indexed_attention.py``; a dict of the published keys is
    #: taken too). The training call adds the indexer's loss and returns
    #: ``(loss, {"sa_index_loss", ...})``; None: plain causal attention
    sa_config: Optional[SparseAttentionConfig] = None

    def __post_init__(self):
        if isinstance(self.sa_config, dict):
            object.__setattr__(self, "sa_config",
                               SparseAttentionConfig(**self.sa_config))

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_local_experts

    @staticmethod
    def keye_vl2_30b_a3b(**over):
        """The language model of Keye-VL-2.0-30B-A3B (``Kwai-Keye/
        Keye-VL-2.0-30B-A3B`` ``config.json``, ``model_type`` ``KeyeVL2``):
        GQA 32 / 4 heads of 128 with a per-head q/k norm, 128 experts of 768
        with top-8 of a softmax renormalised, no shared expert, and the
        indexer of ``sa_config`` choosing 2,048 keys a query."""
        return MixtralConfig(**{**dict(
            vocab_size=151936, hidden_size=2048, intermediate_size=6144,
            moe_intermediate_size=768, num_hidden_layers=48,
            num_attention_heads=32, num_key_value_heads=4,
            head_dim_override=128, max_position_embeddings=262144,
            rms_norm_eps=1e-6, rope_theta=1e7, num_local_experts=128,
            num_experts_per_tok=8, norm_topk_prob=True,
            router_aux_loss_coef=0.0, qk_norm_per_head=True,
            per_expert_init=True, sa_config=SparseAttentionConfig()),
            **over})

    @staticmethod
    def mixtral_8x7b(**over):
        return MixtralConfig(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=32768,
            rope_theta=1e6, num_local_experts=8, num_experts_per_tok=2),
            **over})

    @staticmethod
    def olmoe_1b_7b(**over):
        """OLMoE-1B-7B (``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``
        and ``modeling_olmoe.py``): 64 experts of 1024, top-8 combined with
        the un-normalised softmax values, MHA with RMSNorm on the whole
        projected query and key."""
        return MixtralConfig(**{**dict(
            vocab_size=50304, hidden_size=2048, intermediate_size=1024,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=4096,
            rope_theta=1e4, num_local_experts=64, num_experts_per_tok=8,
            router_aux_loss_coef=0.01, norm_topk_prob=False, qk_norm=True,
            per_expert_init=True),
            **over})

    @staticmethod
    def tiny(**over):
        return MixtralConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            num_local_experts=4, num_experts_per_tok=2, remat=False), **over})


@jax.named_scope("ds.moe_router")
def _router_stats(onehot, probs, token_mask, tokens):
    """Per-expert token fraction and mean router probability ``[E]`` of one
    layer (HF excludes pad tokens via ``attention_mask``)."""
    routed = jnp.max(onehot, axis=2).astype(jnp.float32)
    if token_mask is None:
        denom = float(tokens)
        return (jnp.sum(routed, axis=(0, 1)) / denom,
                jnp.sum(probs, axis=(0, 1)) / denom)
    m = token_mask.astype(jnp.float32)[..., None]        # [B, T, 1]
    denom = jnp.maximum(jnp.sum(m), 1.0)
    return (jnp.sum(routed * m, axis=(0, 1)) / denom,
            jnp.sum(probs * m, axis=(0, 1)) / denom)


class MixtralSparseMoeBlock(nn.Module):
    """HF ``MixtralSparseMoeBlock`` semantics. Returns ``(out, frac, prob,
    rows)`` where ``frac``/``prob`` are this layer's per-expert token-fraction
    and mean-router-probability vectors ``[E]`` (token-masked) and ``rows``
    the pairs each held expert computed (None on the decode path), accumulated
    across layers by the caller — HF's ``load_balancing_loss_func``
    concatenates all layers' tokens BEFORE taking the means, so the product
    must happen at the top, not per layer."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.config
        B, T, H = x.shape
        # G of the router's E experts are held (all of them but for a share)
        G, E, K = cfg.num_local_experts, cfg.router_width, \
            cfg.num_experts_per_tok
        I = cfg.expert_width
        if cfg.router_experts is not None:
            _check_held_share(cfg.first_expert, G, E)

        with jax.named_scope("ds.moe_router"):
            router_logits = nn.Dense(E, use_bias=False, name="gate",
                                     param_dtype=jnp.float32)(x)  # [B, T, E]
            probs = _router_scores(cfg, router_logits.astype(jnp.float32))
            topk_w, topk_idx = jax.lax.top_k(probs, K)
            if cfg.norm_topk_prob:
                topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
            topk_w = _routed_scale(cfg, topk_w)
            # one-hot routing (also feeds the aux-loss stats below)
            onehot = jax.nn.one_hot(topk_idx, E,
                                    dtype=topk_w.dtype)  # [B,T,K,E]

        # stacked expert weights: [E, H, I] / [E, I, H], sharded over "expert"
        # (expert_layout); no w3 where the experts' activation has no gate
        init = nn.initializers.lecun_normal(
            batch_axis=(0,) if cfg.per_expert_init else ())
        w1 = self.param("w1", init, (G, H, I), jnp.float32)  # gate
        w3 = self.param("w3", init, (G, H, I), jnp.float32) \
            if _activation(cfg).gated else None              # up
        w2 = self.param("w2", init, (G, I, H), jnp.float32)  # down
        out, rows = _expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx)
        if rows is not None:
            # (token, expert) pairs each expert computed this call, [E]:
            # free unless the caller asks for the collection
            self.sow("intermediates", "expert_rows", rows)
        frac, prob = _router_stats(onehot, probs, token_mask, B * T)
        return out, frac, prob, rows


def _grouped_tiles(M, A, B, G, dtype):
    """``grouped_matmul.plan`` of what this call site can see: the tiles of
    the small-group kernels, or None where ``jax.lax.ragged_dot`` stays the
    path (off a TPU, under a mesh of several devices, shapes the rule
    leaves). Logged once a shape at trace time."""
    mesh = get_mesh()
    tiles = grouped_matmul.plan(
        grouped_matmul.backend(), 1 if mesh is None else mesh.devices.size,
        M, A, B, G, jnp.dtype(dtype).itemsize, grouped_matmul.device_kind())
    grouped_matmul.log_plan(M, A, B, G, tiles)
    return tiles


def _grouped_dot(lhs, rhs, group_sizes, transposed=False):
    """``lhs [M, A] x rhs [G, A, B] -> [M, B]``: rows of group ``g`` (runs
    of ``group_sizes[g]`` sorted rows) times ``rhs[g]``, float32
    accumulation; with ``transposed`` ``rhs`` comes as ``[G, B, A]``. Rows
    past the last group belong to no group and come back zero.

    Where ``_grouped_tiles`` has tiles: ``ds_moe_gmm``
    (``ops/pallas/grouped_matmul.py``), which contracts a transposed weight
    as it lies and zeroes the rows of no group itself. Elsewhere
    ``jax.lax.ragged_dot`` — XLA:TPU's own grouped-matmul kernel. Only
    ``[M, A] x [G, A, B]`` reaches that kernel: asked to contract ``rhs``'s
    last dim instead, the compiler falls back to a masked dense product that
    costs 12-14 ms where the kernel takes 8 (PERF.md), so a transposed
    weight is transposed first. And the TPU leaves the rows of no group as
    it finds them (NaN at Mixtral's sizes), so they are SELECTED to zero —
    a multiply would keep a NaN."""
    M, A = lhs.shape
    G, B = rhs.shape[0], rhs.shape[1 if transposed else 2]
    tiles = _grouped_tiles(M, A, B, G, lhs.dtype)
    if tiles is not None:
        return grouped_matmul.gmm(
            lhs, rhs, group_sizes, rows=tiles.rows, cols=tiles.cols,
            transpose_rhs=transposed)
    if transposed:
        rhs = jnp.swapaxes(rhs, 1, 2)
    valid = jnp.arange(M) < jnp.sum(group_sizes)
    return jnp.where(valid[:, None],
                     jax.lax.ragged_dot(lhs, rhs, group_sizes), 0)


def _grouped_outer(lhs, rhs, group_sizes):
    """``lhs [M, A], rhs [M, B] -> [G, A, B]``: ``lhs[rows of g]^T @
    rhs[rows of g]`` for every group — a stacked weight's gradient, in the
    operands' dtype (``ds_moe_gmm_t`` or ``ragged_dot_general``, as
    ``_grouped_dot`` chooses)."""
    (M, A), B, G = lhs.shape, rhs.shape[1], group_sizes.shape[0]
    tiles = _grouped_tiles(M, A, B, G, lhs.dtype)
    if tiles is not None:
        return grouped_matmul.tgmm(lhs, rhs, group_sizes, rows=tiles.rows,
                                   cols=tiles.cols_t)
    dims = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    return jax.lax.ragged_dot_general(lhs, rhs, group_sizes, dims)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sorted_experts(act, x, w1, w3, w2, topk_w, order, inv, group_sizes):
    """The grouped expert MLP over sorted rows and the weighted combine.

    ``act`` is the experts' ``Activation`` (below: ``SWIGLU``, or ``RELU2``
    with no ``w3``); ``x [N, H]`` tokens; ``w1``/``w3 [G, H, I]``, ``w2 [G,
    I, H]`` in the compute dtype; ``topk_w [K, N]`` float32. Sorted row ``r``
    holds pair ``order[r]`` (pair ``k * N + n`` is token ``n``'s ``k``-th
    choice), the ``sum(group_sizes)`` pairs of these experts first; ``inv [K
    * N]`` is each pair's row. Returns ``[N, H]``: each token's K rows times
    their routing weights, summed in float32; pairs of no group add zero.

    The backward pass is written out. Both permutations stay gathers (by
    ``order`` and by ``inv``) where autodiff would scatter-add ``[M, H]``
    rows. The routing weights' gradient ``<y_row, g_row>`` is taken as
    ``<h_row, (g @ w2^T)_row>``, a by-product of ``dh``: the down projection
    is no residual, so ``jax.checkpoint`` replays the first products alone."""
    return _sorted_experts_fwd(act, x, w1, w3, w2, topk_w, order, inv,
                               group_sizes)[0]


def _sorted_experts_fwd(act, x, w1, w3, w2, topk_w, order, inv, group_sizes):
    K, N = topk_w.shape
    with jax.named_scope("moe_dispatch"):
        xs = x[order % N]
    with jax.named_scope("moe_gmm"):
        h1 = _grouped_dot(xs, w1, group_sizes)
        h3 = None if w3 is None else _grouped_dot(xs, w3, group_sizes)
        y = _grouped_dot(act.forward(h1, h3), w2, group_sizes)
    with jax.named_scope("moe_combine"):
        y = y[inv].reshape(K, N, -1).astype(jnp.float32)
        out = jnp.sum(y * topk_w[:, :, None], axis=0).astype(x.dtype)
    return out, _named(xs, w1, w3, w2, topk_w, order, inv, group_sizes, h1, h3)


def _sorted_experts_bwd(act, res, g):
    xs, w1, w3, w2, topk_w, order, inv, group_sizes, h1, h3 = res
    K, N = topk_w.shape
    dt, f32 = xs.dtype, jnp.float32
    with jax.named_scope("moe_combine"):
        g_row = g[order % N]                 # each row's cotangent, before
        w_row = topk_w.reshape(K * N)[order][:, None]  # its routing weight
    with jax.named_scope("moe_gmm"):
        t = _grouped_dot(g_row, w2, group_sizes,
                         transposed=True).astype(f32)    # g @ w2^T
        h, pull = act.rule(h1.astype(f32),
                           None if h3 is None else h3.astype(f32))
        d_w_row = jnp.sum(h * t, axis=-1)
        dh1, dh3 = pull(t * w_row, dt)
        dxs = _grouped_dot(dh1, w1, group_sizes, transposed=True)
        if w3 is not None:
            dxs = dxs + _grouped_dot(dh3, w3, group_sizes, transposed=True)
        dw1 = _grouped_outer(xs, dh1, group_sizes)
        dw3 = None if w3 is None else _grouped_outer(xs, dh3, group_sizes)
        dw2 = _grouped_outer((h * w_row).astype(dt), g_row, group_sizes)
    with jax.named_scope("moe_dispatch"):
        dx = dxs[inv].reshape(K, N, -1).sum(axis=0)
        d_topk_w = d_w_row[inv].reshape(K, N)
    return dx, dw1, dw3, dw2, d_topk_w, None, None, None


_sorted_experts.defvjp(_sorted_experts_fwd, _sorted_experts_bwd)


def _routed_experts(x, w1, w2, w3, topk_w, topk_idx, first, experts=None,
                    act=None):
    """Dropless grouped MLP (``act``; None: ``SWIGLU``) of tokens ``x [N,
    H]`` through the experts ``first .. first + G`` of weights ``w1``/``w3
    [G, H, I]`` and ``w2 [G, I, H]``: ``(out [N, H], group_sizes [G])``.

    The sorted row buffer is the static worst case, ``N*K`` rows (every
    pair here), or what ``_sorted_experts_for`` makes of ``G`` of a router's
    ``experts``; ``group_sizes`` is data: no routing recompiles or drops."""
    N, K = topk_idx.shape
    G, M, dt = w1.shape[0], N * K, x.dtype
    with jax.named_scope("moe_dispatch"):
        # choice-major pair ids (k * N + n): [M, H] folds to [K, N, H]
        # without the relayout a [N, K, H] fold costs on the TPU
        local = topk_idx.T.reshape(M) - first
        key = jnp.where((local >= 0) & (local < G), local, G)  # others last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)  # row -> pair
        inv = jnp.zeros((M,), jnp.int32).at[order].set(
            jnp.arange(M, dtype=jnp.int32), unique_indices=True)  # pair -> row
        group_sizes = jnp.sum(key[:, None] == jnp.arange(G)[None, :],
                              axis=0, dtype=jnp.int32)
    ws = (w if w is None else w.astype(dt) for w in (w1, w3, w2))
    out = _sorted_experts_for(M, G, experts)(
        act or SWIGLU, x, *ws,
        topk_w.T.astype(jnp.float32), order, inv, group_sizes)
    return out, group_sizes


# -- how a router scores and weighs its experts ------------------------------
# (here, below ``_routed_experts``: the grouped kernels' compiled payload
# holds the line of that function's call of ``_sorted_experts_for``, so a
# line added above it re-keys the kernels of every cell that runs them --
# PERF.md section 7)

def _router_scores(cfg, logits):
    """Each expert's score ``[..., E]`` from the router's float32 logits: a
    softmax over all of them, or under a config whose ``router_scoring`` is
    ``"sigmoid"`` (``models/laguna.py``) each expert's own sigmoid; the
    top-k is of the scores either way."""
    if getattr(cfg, "router_scoring", "softmax") == "sigmoid":
        return jax.nn.sigmoid(logits)
    return jax.nn.softmax(logits, axis=-1)


def _routed_scale(cfg, topk_w):
    """The chosen experts' weights times the config's
    ``routed_scaling_factor`` (after their normalisation), where it has one
    that is not 1."""
    scale = getattr(cfg, "routed_scaling_factor", 1.0)
    return topk_w if scale == 1.0 else topk_w * scale


class Activation(NamedTuple):
    """An expert's activation as ``_sorted_experts`` takes it: whether a
    second first product ``h3 = xs w3`` gates it (no ``w3`` exists where
    not); ``forward(h1, h3)``, the hidden rows in the compute dtype; and
    ``rule(a1, a3)`` on the float32 first products, the hidden rows and
    the function from their cotangent and a dtype to ``(d a1, d a3)`` in
    that dtype -- the derivative written out beside the value it shares its
    terms with."""

    gated: bool
    forward: Callable
    rule: Callable


def _swiglu_rule(a1, a3):
    sig = jax.nn.sigmoid(a1)
    return a1 * sig * a3, lambda dh, dt: (
        (dh * a3 * sig * (1 + a1 * (1 - sig))).astype(dt),
        (dh * a1 * sig).astype(dt))


def _relu2_rule(a1, _):
    r = jnp.maximum(a1, 0)
    return r * r, lambda dh, dt: ((dh * 2 * r).astype(dt), None)


#: ``silu(x w1) * (x w3)``: every Mixtral-family layer's
SWIGLU = Activation(True, lambda h1, h3: nn.silu(h1) * h3, _swiglu_rule)
#: ``relu(x w1) ** 2``, ungated (``models/nemotron_h.py``)
RELU2 = Activation(False, lambda h1, _: jnp.square(nn.relu(h1)), _relu2_rule)
_ACTIVATIONS = {"swiglu": SWIGLU, "relu2": RELU2}


def _activation(cfg) -> Activation:
    """The experts' activation: the config's ``expert_activation`` where it
    has that field (a key of ``_ACTIVATIONS``), else ``SWIGLU``."""
    return _ACTIVATIONS[getattr(cfg, "expert_activation", "swiglu")]


@jax.named_scope("ds.moe_experts")
def _expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx):
    """The stacked expert MLP and the weighted combine: ``(out [B, T, H],
    rows)``, ``rows [E]`` the (token, expert) pairs each expert computed
    (None on the decode path, which sorts nothing)."""
    B, T, H = x.shape
    dt = x.dtype
    E, K = cfg.num_local_experts, cfg.num_experts_per_tok
    act = _activation(cfg)
    if not act.gated and (T == 1 or _expert_axis_active()):
        raise NotImplementedError(
            "ungated experts are built for one device's training step: the "
            "decode path and the expert axis' shard_map read w3")
    if T == 1 and E > K and cfg.router_experts is None \
            and not _expert_axis_active():
        # decode fast path (replicated experts): GATHER only the K
        # touched experts' weights per token instead of computing all E
        # — a decode step is bound by the weight bytes it streams (the
        # reference's einsum_sec_sm_ecm / moe_res_matmul kernels exist
        # for exactly this; no cell measures it yet: ROADMAP W1).
        # XLA's gather reads only the indexed expert rows from HBM.
        idx = topk_idx[:, 0]                        # [B, K]
        w1g = jnp.take(w1, idx, axis=0).astype(dt)  # [B, K, H, I]
        w3g = jnp.take(w3, idx, axis=0).astype(dt)
        w2g = jnp.take(w2, idx, axis=0).astype(dt)  # [B, K, I, H]
        xt = x[:, 0]                                # [B, H]
        hidden = nn.silu(jnp.einsum("bh,bkhi->bki", xt, w1g)) * \
            jnp.einsum("bh,bkhi->bki", xt, w3g)
        y = jnp.einsum("bki,bkih->bkh", hidden, w2g)
        out = jnp.einsum("bk,bkh->bh",
                         topk_w[:, 0].astype(dt), y)[:, None]
        return out, None

    def experts(x, w1, w2, w3, topk_w, topk_idx, first=cfg.first_expert):
        out, rows = _routed_experts(
            x.reshape(-1, H), w1, w2, w3, topk_w.reshape(-1, K),
            topk_idx.reshape(-1, K), first, cfg.router_experts, act)
        return out.reshape(x.shape), rows

    mesh = get_mesh()
    ep = _expert_axis_size(mesh)
    if ep == 1:
        return experts(x, w1, w2, w3, topk_w, topk_idx)

    # expert parallel: tokens move, weights never do. Tokens all-gather
    # over `expert` on entry (or are already whole on it), each shard
    # computes its share of the layer (expert_layout: every pair over its
    # columns of all experts, or the pairs routed to its own experts), and
    # the partial outputs sum back onto the batch layout.
    layout = expert_layout(E, w1.shape[2], ep)
    _log_expert_layout(layout, E, w1.shape[2], ep)
    columns = layout == "columns"
    # the engine's batch layout, as far as it divides B
    batch = batch_axes(B)
    gathered = "expert" in batch
    others = tuple(a for a in batch if a != "expert")

    def shard(x, w1, w2, w3, topk_w, topk_idx):
        # the hand-written backward passes return cotangents that vary over
        # every axis the rows do; an input that enters whole on one of them
        # is marked varying there, or its cotangent misses the sum over it
        if gathered:
            x, topk_w, topk_idx = (
                jax.lax.all_gather(t, "expert", axis=0, tiled=True)
                for t in (x, topk_w, topk_idx))
        else:
            x, topk_w = jax.lax.pcast((x, topk_w), "expert", to="varying")
        if others:
            w1, w2, w3 = jax.lax.pcast((w1, w2, w3), others, to="varying")
        out, rows = experts(
            x, w1, w2, w3, topk_w, topk_idx,
            0 if columns else jax.lax.axis_index("expert") * w1.shape[0])
        if gathered:
            out = jax.lax.psum_scatter(out, "expert", scatter_dimension=0,
                                       tiled=True)
            if columns:     # every chip counted the same E groups: say so
                rows = jax.lax.pmax(rows, "expert")
        else:
            out = jax.lax.psum(out, "expert")
        return out, (jax.lax.psum(rows, others) if others else rows)

    tokens = P(batch or None, None, None)
    up, down = _expert_weight_specs(layout)
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(tokens, up, down, up, tokens, tokens),
        out_specs=(tokens, P() if columns else P("expert")))(
            x, w1, w2, w3, topk_w, topk_idx)


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask, token_mask=None, layer_cache=None,
                 cache_index=None, deterministic=True):
        cfg = self.config
        # ds.norm / ds.residual as in models/llama.py LlamaBlock
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        out = LlamaAttention(cfg, name="self_attn")(
            h, cos, sin, mask, layer_cache, cache_index, deterministic)
        attn, layer_cache = out[:2]
        # under sa_config a third value: the selection's statistics
        extra = dict(out[2]) if cfg.sa_config is not None else {}
        with jax.named_scope("ds.residual"):
            x = x + name_if_kept(attn, REMAT_ATTN_OUT)
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps,
                        name="post_attention_layernorm")(x)
        moe_out, frac, prob, rows = MixtralSparseMoeBlock(
            cfg, name="block_sparse_moe")(h, token_mask)
        with jax.named_scope("ds.residual"):
            x = x + moe_out
        C = _compact_rows(x.shape[0] * x.shape[1] * cfg.num_experts_per_tok,
                          cfg.num_local_experts, cfg.router_experts)
        if C is not None:
            extra["compact_hit"] = _fits(rows, C).astype(jnp.float32)
        return x, layer_cache, frac, prob, extra


class _ScanBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, carry, layer_cache):
        (x, cos, sin, mask, tok_mask, cache_index, det, frac_sum, prob_sum,
         extra_sum) = carry
        y, layer_cache, frac, prob, extra = MixtralBlock(
            self.config, name="block")(
            x, cos, sin, mask, tok_mask, layer_cache, cache_index, det)
        return (y, cos, sin, mask, tok_mask, cache_index, det,
                frac_sum + frac, prob_sum + prob,
                _add_stats(extra_sum, extra)), layer_cache


class MixtralModel(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None,
                 deterministic=True, cache=None, cache_index=None):
        cfg = self.config
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        if positions is None:
            start = 0 if cache_index is None else cache_index
            positions = jnp.broadcast_to(start + jnp.arange(T)[None, :], (B, T))
        cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta,
                                    dtype=x.dtype)
        mask = None
        tok_mask = attention_mask
        if attention_mask is not None:
            if cache is not None:
                mask = attention_mask
                tok_mask = None  # decode: aux is not consumed
            else:
                mask = jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                                 -1e9).astype(jnp.float32)

        E = cfg.router_width
        training = cache is None
        zero_e = jnp.zeros((E,), jnp.float32)
        extra_sum = dict.fromkeys(
            _extra_stats(cfg, B * T * cfg.num_experts_per_tok),
            jnp.float32(0))
        remat_policy = resolve_remat_policy(
            cfg.remat_policy, remat_offers(cfg, x, cfg.num_hidden_layers))
        # ds.layer_stack: what the loop over the layers costs beyond what
        # the layers' own scopes name (models/llama.py LlamaModel)
        with jax.named_scope("ds.layer_stack"):
            if cfg.scan_layers:
                block_cls = _ScanBlock
                if cfg.remat and cache is None:
                    block_cls = nn.remat(_ScanBlock, prevent_cse=False,
                                         policy=remat_policy)
                scan = nn.scan(block_cls,
                               variable_axes={"params": 0, "intermediates": 0},
                               split_rngs={"params": True, "dropout": True},
                               length=cfg.num_hidden_layers, metadata_params={})
                (x, *_, frac_sum, prob_sum, extra_sum), cache = \
                    scan(cfg, name="layers")(
                        (x, cos, sin, mask, tok_mask, cache_index,
                         deterministic, zero_e, zero_e, extra_sum), cache)
            else:
                block_cls = nn.remat(MixtralBlock, prevent_cse=False,
                                     policy=remat_policy) \
                    if (cfg.remat and cache is None) else MixtralBlock
                frac_sum, prob_sum = zero_e, zero_e
                new_cache = [] if cache is not None else None
                for i in range(cfg.num_hidden_layers):
                    layer_cache = None if cache is None else \
                        jax.tree_util.tree_map(lambda c: c[i], cache)
                    x, layer_cache, frac, prob, extra = block_cls(
                        cfg, name=f"layers_{i}")(
                        x, cos, sin, mask, tok_mask, layer_cache, cache_index,
                        deterministic)
                    frac_sum, prob_sum = frac_sum + frac, prob_sum + prob
                    extra_sum = _add_stats(extra_sum, extra)
                    if new_cache is not None:
                        new_cache.append(layer_cache)
                if new_cache is not None:
                    cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls),
                                                   *new_cache)
        with jax.named_scope(head_scope(cache)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        # HF load_balancing_loss_func: means over ALL layers' tokens
        # concatenated (= mean over layers of per-layer masked means), THEN
        # the expert-wise product
        L = cfg.num_hidden_layers
        aux = E * jnp.sum((frac_sum / L) * (prob_sum / L))
        # frac_sum: each expert's share of the tokens, summed over layers;
        # extra_sum: the layers' _extra_stats, summed too
        return (x, aux, (frac_sum, extra_sum)) if training \
            else (x, aux, cache)


class MixtralForCausalLM(nn.Module):
    """Same interface as ``LlamaForCausalLM`` (the engines are agnostic):
    training call returns the LM loss + aux-weighted router loss; cached
    call returns ``(logits, cache)``."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        # the third is the updated cache, or without one the experts' load
        # and the layers' other statistics
        hidden, aux, load = MixtralModel(cfg, name="model")(
            input_ids, positions, attention_mask, deterministic, cache,
            cache_index)
        if cache is not None:
            cache = load
        else:
            load, extra = load
        with jax.named_scope(head_scope(cache)):
            logits, lm = lm_head_output(self, cfg, hidden, labels, cache)
            if cache is not None:
                return logits, cache
            if labels is None:
                return logits
            if lm is None:
                lm = cross_entropy_loss(logits, shift_labels(labels))
        loss = lm + cfg.router_aux_loss_coef * aux
        if extra or cfg.router_experts is not None:
            return _share_loss_and_gauges(cfg, loss, load, extra,
                                          input_ids.size)
        if not cfg.report_expert_load:
            return loss
        load = load / jnp.mean(load)
        named = {"moe_rows_max_over_mean": jnp.max(load),
                 "moe_rows_min_over_mean": jnp.min(load)}
        ep = _expert_axis_size(get_mesh())
        if ep > 1:
            # a chip of the expert axis computes the rows of its whole
            # experts, or a slice of every row
            whole = expert_layout(cfg.num_local_experts,
                                  cfg.expert_width, ep) == "experts"
            named["moe_chip_rows_max_over_mean"] = jnp.max(jnp.mean(
                load.reshape(ep if whole else 1, -1), axis=1))
        return loss, named

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.config
        return init_kv_cache(batch, max_len, cfg.num_key_value_heads,
                             cfg.head_dim, n_layers=cfg.num_hidden_layers,
                             dtype=dtype)

    @staticmethod
    def partition_rules(config: "MixtralConfig"):
        """TP for attention (Megatron layout) + EP for the stacked expert
        weights: the ``expert`` mesh axis on the dim ``expert_layout`` names
        for the mesh the rules are resolved on (a spec that is a function of
        the mesh: ``runtime/zero/partition.py state_shardings``)."""
        L = (None,) if config.scan_layers else ()

        def experts(mesh):
            return _expert_weight_specs(expert_layout(
                config.num_local_experts, config.expert_width,
                _expert_axis_size(mesh)))

        # a per-head norm's [head_dim] scales are whole on every chip (and
        # so is the indexer's k_norm, which the pattern would also catch)
        qk_norm = [] if config.qk_norm_per_head else [
            (r"(q_norm|k_norm)/scale", P(*L, "model"))]
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"(q_proj|k_proj|v_proj)/kernel", P(*L, None, "model")),
            (r"o_proj/kernel", P(*L, "model", None)),
            (r"block_sparse_moe/(w1|w3)", lambda m: P(*L, *experts(m)[0])),
            (r"block_sparse_moe/w2", lambda m: P(*L, *experts(m)[1])),
            (r"lm_head/kernel", P(None, "model")),
            *qk_norm,
        ]

    @staticmethod
    def frozen_parameters(config: "MixtralConfig"):
        """Parameter paths the optimizer never moves: the router's weights
        where ``router_trainable`` is off (a held share trained alone)."""
        return [] if config.router_trainable \
            else [r"block_sparse_moe/gate/kernel$"]


# -- one chip's share of a wider router's experts ---------------------------
# ``deepseek_v3.py`` and ``zaya.py`` hold ``held`` experts, ``first ..`` of
# the ``experts`` the router scores, and call ``_routed_experts`` with that
# ``first``.

def _check_held_share(first, held, experts):
    if not 0 <= first <= experts - held:
        raise ValueError(f"experts {first}..+{held} are not among the "
                         f"router's {experts}")
    if _expert_axis_size(get_mesh()) > 1:
        raise NotImplementedError(
            "a held share under an `expert` mesh axis is not built: give "
            "each chip its own first_expert on a mesh without that axis")


def _balancing_delta(idx, width, rate):
    """The sign rule of auxiliary-loss-free balancing: what a step that
    chose the router columns ``idx`` adds to the selection bias ``[width]``
    — ``-rate`` where the step sent a column more than the mean number of
    choices, ``+rate`` where fewer."""
    load = jnp.zeros((width,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    return rate * jnp.sign(jnp.mean(load) - load)


def _held_load_gauges(rows, expected):
    """The two registry gauges of a held share from ``rows [G]``, the pairs
    each held expert computed, and ``expected``, the pairs a level load of
    the deployment would send to all of them."""
    return {"moe_rows_max_over_mean": jnp.max(rows) / jnp.mean(rows),
            "moe_held_rows_over_expected": jnp.sum(rows) / expected}


# -- a small held share's row buffer follows the rows it holds --------------
# ``_routed_experts``' stable sort puts the held pairs first, so the layer
# of a caller that holds few of its router's experts runs on the first ``C``
# sorted rows; a step whose held pairs do not fit takes the ``N*K``-row
# buffer, in the same executable. Nothing is dropped either way.

#: The compact buffer's rows over a LEVEL load of the held experts (their
#: share of the router's pairs). 2 from the load the chip shows: a balancing
#: rule holds the SUM over kimi 8k's five layers near 0.95 of level, but one
#: layer's load swings about it — through two 51 s windows the busiest layer
#: stood at 1.4 of level in the mean and 2.3 and 3.1 at most, and 98.9% and
#: 98.8% of the layer calls fitted (PERF.md section 6, PR 36). Every pass
#: under the buffer costs by its rows: a margin of 3 would add about 5 ms to
#: every step to save the 0.8 ms a step that the overflowing calls cost.
_COMPACT_MARGIN = 2


def _compact_rows(pairs, held, experts):
    """Rows ``C`` of the compact buffer of a layer that holds ``held`` of
    its router's ``experts`` and sorts ``pairs`` rows — the margin over the
    level load, in whole 512-row tiles of the grouped kernel — or None where
    the layer has no compact buffer: no router width given (every row of a
    Mixtral layer is a real pair) or a share over a quarter (``C`` would
    pass half the rows: ZAYA's 8 of 17)."""
    if experts is None:
        return None
    rows = -(-_COMPACT_MARGIN * pairs * held // (experts * 512)) * 512
    return rows if rows <= pairs // 2 else None


def _fits(group_sizes, C):
    """Whether the held pairs of ``group_sizes [..., G]`` fit the compact
    buffer: its last row stays a row of no group, the zero row that every
    pair held elsewhere is gathered from."""
    return jnp.sum(group_sizes, axis=-1) < C


def _sorted_experts_for(pairs, held, experts):
    """``_sorted_experts``, or the same function of the same arguments over
    the compact buffer ``_compact_rows`` gives."""
    C = _compact_rows(pairs, held, experts)
    return _sorted_experts if C is None else \
        functools.partial(_compact_experts, C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _compact_experts(C, act, x, w1, w3, w2, topk_w, order, inv, group_sizes):
    """``_sorted_experts`` over the first ``C`` sorted rows where the held
    pairs fit them, over all ``K * N`` rows where they do not: one
    ``lax.cond`` in the forward pass and one in the backward pass, both
    INSIDE the ``custom_vjp``, so no ``cond`` is ever differentiated (a
    ``lax.switch`` differentiated through under ``jax.checkpoint`` returned
    zero ``dx`` rows on XLA:TPU, PR 26). The residuals have the compact
    shape; an overflowing step's backward pass computes its own again."""
    return _compact_experts_fwd(C, act, x, w1, w3, w2, topk_w, order, inv,
                                group_sizes)[0]


def _compact_index(C, order, inv):
    """``(order, inv)`` of the compact buffer: its ``C`` rows' pairs, and
    each pair's row — a pair held elsewhere sorts past row ``C - 1`` and
    reads that row, which ``_fits`` keeps a row of zeros."""
    return order[:C], jnp.minimum(inv, C - 1)


def _in_a_branch(*args):
    """``_sorted_experts_fwd`` as a ``cond``'s branch runs it: nothing is
    named in there (what the rule keeps is named once, after the ``cond``)."""
    with remat_room(0):
        return _sorted_experts_fwd(*args)


def _compact_experts_fwd(C, act, x, w1, w3, w2, topk_w, order, inv,
                         group_sizes):
    def run(order, inv):
        out, res = _in_a_branch(act, x, w1, w3, w2, topk_w, order, inv,
                                group_sizes)
        return out, res[0], res[-2], res[-1]         # xs, h1, h3

    def full():     # its backward pass reads none of the three
        out, *firsts = run(order, inv)
        return out, *(t if t is None else t[:C] for t in firsts)

    out, xs, h1, h3 = jax.lax.cond(
        _fits(group_sizes, C),
        lambda: run(*_compact_index(C, order, inv)), full)
    return out, (x, *_named(xs, w1, w3, w2, topk_w, order, inv, group_sizes,
                            h1, h3))


def _compact_experts_bwd(C, act, res, g):
    x, xs, w1, w3, w2, topk_w, order, inv, group_sizes, h1, h3 = res

    def compact():
        return _sorted_experts_bwd(
            act, (xs, w1, w3, w2, topk_w, *_compact_index(C, order, inv),
                  group_sizes, h1, h3), g)[:5]

    def full():
        return _sorted_experts_bwd(act, _in_a_branch(
            act, x, w1, w3, w2, topk_w, order, inv, group_sizes)[1], g)[:5]

    return (*jax.lax.cond(_fits(group_sizes, C), compact, full),
            None, None, None)


_compact_experts.defvjp(_compact_experts_fwd, _compact_experts_bwd)


def _compact_hit_gauge(layer_rows, pairs, experts):
    """The registry gauge ``moe_compact_hit_share`` from ``layer_rows
    [L, G]``, the pairs each held expert computed in each of a step's ``L``
    layer calls of ``pairs`` sorted rows: the calls that took the compact
    buffer over the calls that have one. No gauge where none has."""
    C = _compact_rows(pairs, layer_rows.shape[-1], experts)
    if C is None:
        return {}
    return {"moe_compact_hit_share":
            jnp.mean(_fits(layer_rows, C).astype(jnp.float32))}


# -- what a selection or a held share reports, layer by layer ---------------

def _extra_stats(cfg, pairs):
    """Names of the float32 scalars a layer of ``pairs`` sorted rows hands
    up beside the router's statistics, summed over the layers: the indexer's
    loss and (with ``report_expert_load``) the share of causal tiles its
    selection keeps, from ``LlamaAttention``; whether the held pairs fitted
    the compact buffer, where the layer has one."""
    names = []
    if cfg.sa_config is not None:
        names.append("sa_index_loss")
        if cfg.report_expert_load:
            names.append("sa_kept_tile_share")
    if _compact_rows(pairs, cfg.num_local_experts,
                     cfg.router_experts) is not None:
        names.append("compact_hit")
    return names


def _add_stats(sums, stats):
    return {k: v + stats[k] for k, v in sums.items()}


def _share_loss_and_gauges(cfg, loss, frac_sum, extra, tokens):
    """The training call's ``(loss, named scalars)`` from the layers'
    ``_extra_stats`` summed: the indexer's loss (coefficient 1, the
    published sparse stage) joins the loss and is named beside it; with
    ``report_expert_load`` the selection's kept tile share and a held
    share's gauges as ``deepseek_v3.py`` names them."""
    L, named = cfg.num_hidden_layers, {}
    if "sa_index_loss" in extra:
        loss = loss + extra["sa_index_loss"]
        named["sa_index_loss"] = extra["sa_index_loss"]
    if not cfg.report_expert_load:
        return (loss, named) if named else loss
    if "sa_kept_tile_share" in extra:
        named["sa_kept_tile_share"] = extra["sa_kept_tile_share"] / L
    G, first = cfg.num_local_experts, cfg.first_expert
    pairs = tokens * cfg.num_experts_per_tok              # of one layer
    rows = frac_sum[first:first + G] * tokens             # summed over layers
    named.update(_held_load_gauges(rows, L * pairs * G / cfg.router_width))
    if "compact_hit" in extra:
        named["moe_compact_hit_share"] = extra["compact_hit"] / L
    return loss, named


# -- what a remat'ed block offers its policy ---------------------------------
# (``layers.resolve_remat_policy``: kept where the engine's budget has room)

def _named(xs, w1, w3, w2, topk_w, order, inv, group_sizes, h1, h3):
    """The residuals of ``_sorted_experts_fwd`` (``_compact_experts_fwd``:
    the compact-shaped ones, after its ``cond``) under the two names
    ``expert_offers`` counts, each where the rule kept it: with the gate and
    up products kept the replay runs no grouped product, with the sorted
    rows and the sort's index vectors no ``argsort``, scatter or gather --
    the down product and the combine are no residuals and never replayed."""
    xs, topk_w, order, inv, group_sizes = (
        name_if_kept(t, REMAT_MOE_ROWS)
        for t in (xs, topk_w, order, inv, group_sizes))
    h1, h3 = (name_if_kept(t, REMAT_MOE_UP) for t in (h1, h3))
    return xs, w1, w3, w2, topk_w, order, inv, group_sizes, h1, h3


def expert_offers(x, K, I, held, experts, applications: int, firsts=2):
    """What ``_named`` names, as a block wrapper offers it: ``[(name, bytes
    over ``applications`` expert layers)]`` for a stream ``x [B, T, hidden]``
    routed to ``K`` experts of width ``I`` with ``firsts`` first products a
    row (gate and up; 1 for an ungated ``Activation``), ``held`` of the
    router's ``experts`` here -- counted over the rows the layer sorts onto:
    the compact buffer's where it has one, every pair's where it has none.
    The index vectors (``order``, ``inv``, ``topk_w``: a word a pair each, and
    ``group_sizes``) go with the rows. Under an ``expert`` mesh axis what
    ONE device names inside ``_expert_mlp``'s ``shard_map``: the rows of
    the batch's other axes' part, whole on ``expert`` (all-gathered, or
    never divided by it), at ``expert_layout``'s columns or experts a
    chip; without that axis the layer sorts the whole batch's pairs under
    the partitioner, and the offer counts them all."""
    B, T, H = x.shape
    ep = _expert_axis_size(get_mesh())
    if ep > 1:
        B = device_part(B, but=("expert",))
        if expert_layout(held, I, ep) == "columns":
            I //= ep
        else:
            held //= ep
    pairs, item = B * T * K, x.dtype.itemsize
    rows = _compact_rows(pairs, held, experts) or pairs
    return ((REMAT_MOE_UP, applications * firsts * rows * I * item),
            (REMAT_MOE_ROWS,
             applications * (rows * H * item + 4 * (3 * pairs + held))))


def remat_offers(cfg, x, applications: int):
    """What a ``MixtralBlock`` names, as ``MixtralModel`` and
    ``mellum.MellumModel`` offer it to ``layers.resolve_remat_policy`` for a
    stream ``x [B, T, hidden]`` through ``applications`` blocks, costliest
    replay a byte first (mellum2 8k, ms of replay a step for a GB kept: the
    attention's output projection 4.0 for 0.15; q, k, v as ``LlamaAttention``
    names them and ``llama.remat_offers`` counts them 6.9 for 0.34; the
    experts' gate and up products 2.0 for 0.23, their sorted rows 1.9 for
    0.30)."""
    _, qkv = _llama_offers(cfg, x, applications)
    B, T, H = x.shape
    return ((REMAT_ATTN_OUT,
             applications * device_part(B) * T * H * x.dtype.itemsize), qkv,
            *expert_offers(x, cfg.num_experts_per_tok, cfg.expert_width,
                           cfg.num_local_experts, cfg.router_experts,
                           applications))
