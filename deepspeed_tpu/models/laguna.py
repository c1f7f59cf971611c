"""A GQA / sparse-expert stack under a pattern of layer kinds whose kinds
differ in their HEAD COUNT too, behind leading dense layers. Laguna-XS.2's
decoder (``poolside/Laguna-XS.2`` ``config.json``, ``model_type``
``laguna``): layer ``l`` attends the whole causal prefix where ``l mod
full_attention_period = 0`` and a ``sliding_window`` otherwise
(``layer_types``), with ``num_attention_heads`` query heads on a full layer
and ``sliding_num_attention_heads`` on a sliding one over the same
``num_key_value_heads`` (``num_attention_heads_per_layer``); every head's
output stands under a learned gate (``gating``); each kind rotates with a
table of its own (``rope_parameters``: the sliding layers plain RoPE at
``sliding_rope_theta`` over every column, the full layers YaRN over the
first ``partial_rotary_factor`` of a head's columns); the first
``first_k_dense`` layers' feed-forward is a dense SwiGLU and every later one
sigmoid-routed experts beside a shared expert (``mlp_layer_types``).

What is here is the pattern as config data (``period_kinds``,
``kind_config``, ``rope_tables``), a block, and what ``layers.scan_periods``
is told of the stack: the leading dense blocks unrolled, then ONE scan over
the periods, every block under its kind's outer scope (``ds.layer_dense``,
``ds.layer_window``, ``ds.layer_full``) and its kind's own config. The
attention is ``llama.LlamaAttention`` (the gate and the partial rotation are
config fields it reads), the expert layer ``mixtral.MixtralSparseMoeBlock``
with its held share and compact buffer (the sigmoid scores and the scale are
fields it reads), the dense and the shared SwiGLU ``deepseek_v3._SwiGLU``.

A depth behind the dense layers that is no whole number of periods is
refused (``_check``): the published 40 layers are one dense layer, nine
periods and three trailing sliding layers, and no tail is built. Training
only: a serving cache would hold a ring of ``sliding_window`` keys for the
window layers beside the full layers' pages, with head counts that differ by
kind (ROADMAP R2).

``models/__init__.py`` does not import this module; a configuration names it
by path (``deepspeed_tpu.models.laguna:LagunaConfig``).
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import REMAT_ATTN_OUT, REMAT_MLP, REMAT_QKV
from .deepseek_v3 import _SwiGLU
from .layers import (RMSNorm, cross_entropy_loss, device_part, head_scope,
                     name_if_kept, rotary_embedding, scan_periods,
                     seeded_embed_tokens, seeded_lm_head, shift_labels,
                     yarn_rotary_embedding)
from .llama import LlamaAttention
from .mellum import FULL, KIND_SCOPES as _MELLUM_SCOPES, WINDOW, MellumConfig
from .mixtral import (MixtralForCausalLM, MixtralSparseMoeBlock, _add_stats,
                      _compact_rows, _extra_stats, _fits,
                      _share_loss_and_gauges, expert_offers)

DENSE = "dense"
#: the outer scope of a block of each kind; a dense block attends as a full
#: layer does
KIND_SCOPES = {DENSE: "ds.layer_dense", **_MELLUM_SCOPES}


@dataclasses.dataclass(frozen=True)
class LagunaConfig(MellumConfig):
    #: query heads of a SLIDING layer (``num_attention_heads`` is a full
    #: layer's); both kinds share ``num_key_value_heads``
    sliding_num_attention_heads: int = 64
    #: the sliding layers' plain table (``rope_theta`` and the ``yarn_*``
    #: fields are the full layers')
    sliding_rope_theta: float = 10000.0
    #: the share of a FULL layer's head columns that rotate (the first); a
    #: sliding layer rotates them all
    partial_rotary_factor: float = 1.0
    #: leading layers whose feed-forward is one dense SwiGLU of
    #: ``intermediate_size``
    first_k_dense: int = 1
    shared_expert_intermediate_size: int = 512
    #: the router's scores: each expert's own ``"sigmoid"``, this family's
    #: (a configuration file hands on numbers alone, so the default says
    #: it), or a ``"softmax"`` over all of them; the top-k is of the scores
    #: either way (``MixtralSparseMoeBlock`` reads it)
    router_scoring: str = "sigmoid"
    #: on the chosen experts' weights, after their normalisation
    #: (``MixtralSparseMoeBlock`` reads it)
    routed_scaling_factor: float = 1.0
    #: ``sigmoid(h W_g) [T, heads]`` on each head's output before ``o_proj``
    #: (``LlamaAttention`` reads it)
    attn_head_gate: bool = True
    #: a head's leading columns that rotate, set a KIND by ``kind_config``
    #: (``LlamaAttention`` reads it; None: all)
    rotary_dim: Optional[int] = None

    @staticmethod
    def laguna_xs2(**over):
        """Laguna-XS.2 as published: 40 layers of hidden 2048, 48 / 64 query
        heads by kind over 8 key-value heads of 128, window 512, one dense
        layer of 8192, then 256 experts of 512 with top-8 of sigmoid scores
        normalised and scaled by 2.5, beside a shared expert of 512."""
        return LagunaConfig(**{**dict(
            vocab_size=100352, hidden_size=2048, intermediate_size=8192,
            moe_intermediate_size=512, shared_expert_intermediate_size=512,
            num_hidden_layers=40, num_attention_heads=48,
            sliding_num_attention_heads=64, num_key_value_heads=8,
            head_dim_override=128, max_position_embeddings=262144,
            rms_norm_eps=1e-6, sliding_window=512, full_attention_period=4,
            first_k_dense=1, rope_theta=500000.0, sliding_rope_theta=10000.0,
            partial_rotary_factor=0.5, yarn_factor=64.0,
            yarn_original_max_position_embeddings=4096, yarn_beta_fast=64.0,
            yarn_beta_slow=1.0, yarn_attention_factor=1.4158883083359672,
            num_local_experts=256, num_experts_per_tok=8,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            router_aux_loss_coef=0.0,
            per_expert_init=True), **over})

    @staticmethod
    def tiny(**over):
        return LagunaConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_hidden_layers=9, num_attention_heads=4,
            sliding_num_attention_heads=6, num_key_value_heads=2,
            head_dim_override=16, max_position_embeddings=64,
            rms_norm_eps=1e-6, sliding_window=8, full_attention_period=4,
            first_k_dense=1, rope_theta=100.0, sliding_rope_theta=50.0,
            partial_rotary_factor=0.5, yarn_factor=4.0,
            yarn_original_max_position_embeddings=16, yarn_beta_fast=4.0,
            num_local_experts=4, num_experts_per_tok=2,
            routed_scaling_factor=2.5, router_aux_loss_coef=0.0,
            per_expert_init=True, remat=False),
            **over})


def layer_kind(cfg, layer: int) -> str:
    """The kind of layer ``layer`` (from 0) of the stack."""
    if layer < cfg.first_k_dense:
        return DENSE
    return WINDOW if layer % cfg.full_attention_period else FULL


def period_kinds(cfg) -> tuple:
    """The kinds of one period's layers behind the dense ones, in order."""
    return tuple(layer_kind(cfg, cfg.first_k_dense + i)
                 for i in range(cfg.full_attention_period))


def kind_config(cfg, kind):
    """``cfg`` as a block of ``kind`` reads it: its kind's window, query
    heads and rotated columns."""
    if kind == WINDOW:
        return dataclasses.replace(
            cfg, num_attention_heads=cfg.sliding_num_attention_heads)
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    return dataclasses.replace(
        cfg, sliding_window=None,
        rotary_dim=rot if rot < cfg.head_dim else None)


@jax.named_scope("ds.rope_tables")
def rope_tables(cfg, positions, dtype):
    """``{kind: (cos, sin)}`` as ``LlamaAttention`` takes them: the full
    layers' over their rotated columns alone."""
    rot = kind_config(cfg, FULL).rotary_dim or cfg.head_dim
    full = rotary_embedding(positions, rot, cfg.rope_theta, dtype=dtype) \
        if cfg.yarn_factor is None else yarn_rotary_embedding(
            positions, rot, cfg.rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow, cfg.yarn_attention_factor, dtype=dtype)
    return {WINDOW: rotary_embedding(positions, cfg.head_dim,
                                     cfg.sliding_rope_theta, dtype=dtype),
            FULL: full, DENSE: full}


def _check(cfg):
    n, sparse = cfg.full_attention_period, \
        cfg.num_hidden_layers - cfg.first_k_dense
    if n < 1 or cfg.first_k_dense < 0 or sparse < n or sparse % n:
        raise ValueError(
            f"{cfg.num_hidden_layers} layers are not {cfg.first_k_dense} "
            f"dense and whole periods of {n}: the trailing layers of a "
            "partial period are not built")
    for heads in (cfg.num_attention_heads, cfg.sliding_num_attention_heads):
        if heads % cfg.num_key_value_heads:
            raise ValueError("each key-value head serves a whole number of "
                             "query heads in every kind")
    if cfg.sa_config is not None:
        raise NotImplementedError(
            "a learned selection under a window is not built")
    if cfg.tie_word_embeddings or cfg.loss_chunk:
        raise NotImplementedError(
            "the head is a table of its own whose logits are whole: no "
            "tied table, no chunked loss")
    if cfg.report_expert_load and cfg.router_experts is None:
        raise NotImplementedError(
            "report_expert_load names a held share's gauges: give "
            "router_experts")


def _shared_expert(cfg, h):
    """The shared expert's SwiGLU over every token, alike on every chip of
    the deployment."""
    return _SwiGLU(cfg, cfg.shared_expert_intermediate_size, "ds.moe_shared",
                   name="shared_expert")(h)


class LagunaBlock(nn.Module):
    """One decoder layer under its kind's config: ``(x, each expert's token
    fraction [E], mean router score [E], the layer's other statistics)`` as
    ``MixtralBlock`` hands them up (a dense layer routes nothing), with the
    mean of the attention's head gate among them."""

    config: LagunaConfig
    dense: bool = False

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: RMSNorm(eps=cfg.rms_norm_eps, name=name)
        with jax.named_scope("ds.norm"):
            h = norm("input_layernorm")(x)
        attn, _, *extra = LlamaAttention(cfg, name="self_attn")(
            h, cos, sin, None)
        with jax.named_scope("ds.residual"):
            x = x + name_if_kept(attn, REMAT_ATTN_OUT)
        with jax.named_scope("ds.norm"):
            h = norm("post_attention_layernorm")(x)
        extra = dict(*extra)      # the head gate's mean, where there is one
        if self.dense:
            out = _SwiGLU(cfg, cfg.intermediate_size, "ds.mlp", name="mlp")(h)
            frac = prob = jnp.zeros((cfg.router_width,), jnp.float32)
        else:
            out, frac, prob, rows = MixtralSparseMoeBlock(
                cfg, name="block_sparse_moe")(h)
            out = out + _shared_expert(cfg, h)
        with jax.named_scope("ds.residual"):
            x = x + out
        C = _compact_rows(x.shape[0] * x.shape[1] * cfg.num_experts_per_tok,
                          cfg.num_local_experts, cfg.router_experts)
        if C is not None:       # a dense layer has no buffer to fit
            extra["compact_hit"] = jnp.float32(0) if self.dense \
                else _fits(rows, C).astype(jnp.float32)
        return x, frac, prob, extra


def _call(block, kind, x, tables):
    x, frac, prob, extra = block(x, *tables[kind])
    return x, (frac, prob, extra)


def _fold(sums, stats):
    frac_sum, prob_sum, extra_sum = sums
    frac, prob, extra = stats
    return frac_sum + frac, prob_sum + prob, _add_stats(extra_sum, extra)


class LagunaModel(nn.Module):
    config: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        """``(final-normed hidden, (each expert's share of the tokens summed
        over the expert layers, the layers' other statistics summed))``."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = seeded_embed_tokens(cfg, input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        tables = rope_tables(cfg, positions, x.dtype)
        zero_e = jnp.zeros((cfg.router_width,), jnp.float32)
        sums = (zero_e, zero_e, dict.fromkeys(
            ["attn_gate"] * cfg.attn_head_gate + _extra_stats(
                cfg, B * T * cfg.num_experts_per_tok), jnp.float32(0)))
        x, (frac_sum, _, extra_sum) = scan_periods(
            cfg, period_kinds(cfg), x, sums, (tables,),
            leading=(DENSE,) * cfg.first_k_dense,
            block=lambda kind, name: LagunaBlock(
                kind_config(cfg, kind), kind == DENSE, name=name),
            call=_call, fold=_fold, scopes=KIND_SCOPES,
            offers=lambda x: remat_offers(cfg, x))
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        return x, (frac_sum, extra_sum)


class LagunaForCausalLM(nn.Module):
    """``MixtralForCausalLM``'s training interface over ``LagunaModel``:
    logits without labels; with them the LM loss (no router loss: the source
    has no coefficient) and, with ``report_expert_load``, ``(loss, named
    scalars)``: the held share's gauges and ``attn_gate_mean``, the mean of
    the head gates over tokens, heads and layers."""

    config: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a stack of layer kinds is built for training only: no "
                "cache holds a window layer's ring beside a full layer's "
                "keys and values, at head counts that differ by kind")
        if attention_mask is not None:
            raise NotImplementedError(
                "packed sequences only: no padding mask is composed here")
        hidden, (load, extra) = LagunaModel(cfg, name="model")(
            input_ids, positions)
        with jax.named_scope(head_scope(None)):
            logits = seeded_lm_head(cfg, hidden)
            if labels is None:
                return logits
            loss = cross_entropy_loss(logits, shift_labels(labels))
        if cfg.router_experts is None:
            return loss
        # the held share's gauges are over the EXPERT layers
        sparse = dataclasses.replace(
            cfg, num_hidden_layers=cfg.num_hidden_layers - cfg.first_k_dense)
        out = _share_loss_and_gauges(sparse, loss, load, extra,
                                     input_ids.size)
        if not (cfg.report_expert_load and cfg.attn_head_gate):
            return out
        return out[0], {**out[1], "attn_gate_mean":
                        extra["attn_gate"] / cfg.num_hidden_layers}

    @staticmethod
    def partition_rules(config: "LagunaConfig"):
        """``MixtralForCausalLM``'s rules (one leading scanned axis, the
        periods, where Mixtral's is the layers) and, ahead of them, the
        names this stack adds: the head gate's projection by heads, the
        dense and the shared SwiGLU by columns (Megatron layout), and the
        unrolled leading blocks without a scanned axis."""
        L = (None,) if config.scan_layers else ()
        col = r"(q_proj|k_proj|v_proj|g_proj|gate_proj|up_proj)/kernel"
        row = r"(o_proj|down_proj)/kernel"
        return [
            (r"leading/.*" + col, P(None, "model")),
            (r"leading/.*" + row, P("model", None)),
            (r"(g_proj|gate_proj|up_proj)/kernel", P(*L, None, "model")),
            (r"down_proj/kernel", P(*L, "model", None)),
            *MixtralForCausalLM.partition_rules(config),
        ]

    frozen_parameters = staticmethod(MixtralForCausalLM.frozen_parameters)


def remat_offers(cfg, x):
    """What the blocks of this stack name, as ``LagunaModel`` offers it to
    ``layers.resolve_remat_policy`` for a stream ``x [B, T, hidden]`` through
    all its layers, in ``deepseek_v3.remat_offers``' order: the attention's
    output projection; the gate and up products ``_SwiGLU`` names (the dense
    layers' and the shared experts'); q, k, v as ``LlamaAttention`` names
    them, each KIND's layers at its own head count; what the expert layers
    name (``mixtral.expert_offers``)."""
    per_column = device_part(x.shape[0]) * x.shape[1] * x.dtype.itemsize
    dense = cfg.first_k_dense
    sparse = cfg.num_hidden_layers - dense
    heads = sum(kind_config(cfg, layer_kind(cfg, l)).num_attention_heads
                + 2 * cfg.num_key_value_heads
                for l in range(cfg.num_hidden_layers))
    return ((REMAT_ATTN_OUT,
             cfg.num_hidden_layers * cfg.hidden_size * per_column),
            (REMAT_MLP, 2 * per_column * (
                dense * cfg.intermediate_size
                + sparse * cfg.shared_expert_intermediate_size)),
            (REMAT_QKV, heads * cfg.head_dim * per_column),
            *expert_offers(x, cfg.num_experts_per_tok, cfg.expert_width,
                           cfg.num_local_experts, cfg.router_experts,
                           sparse))
