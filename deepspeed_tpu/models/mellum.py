"""The GQA / sparse-expert stack of ``models/mixtral.py`` under a PATTERN of
layer kinds: every ``full_attention_period``-th layer attends the whole
causal prefix, the others a ``sliding_window``, and each kind rotates with a
table of its own (the window layers plain RoPE at ``rope_theta``, the full
layers YaRN's blended frequencies and attention factor,
``layers.yarn_rotary_embedding``). Mellum2-12B-A2.5B's decoder
(``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``: ``layer_types``
three ``sliding_attention`` and one ``full_attention`` a period,
``rope_parameters`` a section a kind).

Every block is ``mixtral.MixtralBlock`` as it is, under a config whose
``sliding_window`` is its kind's: attention, router, expert layer, the held
share and its compact buffer are that file's. What is here is the pattern as
config data, what ``layers.scan_periods`` is told of a period (its blocks,
each under an outer ``ds.layer_window`` / ``ds.layer_full`` scope, the
kinds' ``(cos, sin)`` as the scan's broadcast inputs), and the causal-LM
wrapper. Training only: a serving cache would hold a ring of
``sliding_window`` keys for the window layers beside the full layers' pages
(ROADMAP R2).

``models/__init__.py`` does not import this module; a configuration names it
by path (``deepspeed_tpu.models.mellum:MellumConfig``).
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (RMSNorm, cross_entropy_loss, head_scope, lm_head_output,
                     rotary_embedding, scan_periods, seeded_embed_tokens,
                     seeded_lm_head, shift_labels, yarn_rotary_embedding)
from .mixtral import (MixtralBlock, MixtralConfig, MixtralForCausalLM,
                      _add_stats, _extra_stats, _share_loss_and_gauges,
                      remat_offers)

WINDOW, FULL = "window", "full"
#: the outer scope of a block of each kind (every inner name stays what
#: ``MixtralBlock`` gives it)
KIND_SCOPES = {WINDOW: "ds.layer_window", FULL: "ds.layer_full"}


@dataclasses.dataclass(frozen=True)
class MellumConfig(MixtralConfig):
    #: layers a period: the last of each is the full-attention layer, the
    #: others attend ``sliding_window``. 1: every layer is full
    full_attention_period: int = 4
    #: the full layers' rotary table (``rope_parameters.full_attention``):
    #: YaRN at this factor over ``rope_theta``; None: plain RoPE, as the
    #: window layers always have
    yarn_factor: Optional[float] = None
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    #: on the full layers' cos AND sin; None: ``0.1 ln(yarn_factor) + 1``
    yarn_attention_factor: Optional[float] = None
    #: the standard deviation the input table's rows are SEEDED at; None:
    #: flax's ``1 / sqrt(hidden_size)``. At that scale a token's own row
    #: stands under the first layers' attention output, which neighbouring
    #: tokens share, and a frozen seeded router then routes a whole batch
    #: alike (PERF.md section 6, PR 49)
    embed_init_std: Optional[float] = None
    #: the same of the head's rows; None: flax's ``1 / sqrt(hidden_size)``,
    #: at which the first logits are noise of unit scale that training on
    #: ids with nothing to learn first of all erases
    head_init_std: Optional[float] = None

    @staticmethod
    def mellum2_12b_a2_5b(**over):
        """Mellum2-12B-A2.5B as published: 28 layers of hidden 2304, GQA
        32 / 4 heads of 128 with a per-head q/k norm, 64 experts of 896
        with top-8 of a softmax renormalised, no shared expert."""
        return MellumConfig(**{**dict(
            vocab_size=98304, hidden_size=2304, intermediate_size=7168,
            moe_intermediate_size=896, num_hidden_layers=28,
            num_attention_heads=32, num_key_value_heads=4,
            head_dim_override=128, max_position_embeddings=131072,
            rms_norm_eps=1e-6, rope_theta=500000.0, sliding_window=1024,
            full_attention_period=4, yarn_factor=16.0,
            yarn_original_max_position_embeddings=8192,
            yarn_attention_factor=1.2772588722239782,
            num_local_experts=64, num_experts_per_tok=8,
            norm_topk_prob=True, router_aux_loss_coef=0.0,
            qk_norm_per_head=True, per_expert_init=True), **over})

    @staticmethod
    def tiny(**over):
        return MellumConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            sliding_window=8, full_attention_period=4, yarn_factor=4.0,
            yarn_original_max_position_embeddings=16, yarn_beta_fast=4.0,
            num_local_experts=4, num_experts_per_tok=2, remat=False),
            **over})


def period_kinds(cfg) -> tuple:
    """The kinds of one period's layers, in order."""
    return (WINDOW,) * (cfg.full_attention_period - 1) + (FULL,)


def kind_config(cfg, kind):
    """``cfg`` as a block of ``kind`` reads it: its kind's window."""
    window = cfg.sliding_window if kind == WINDOW else None
    return dataclasses.replace(cfg, sliding_window=window)


@jax.named_scope("ds.rope_tables")
def rope_tables(cfg, positions, dtype):
    """``{kind: (cos, sin)}`` as ``apply_rotary`` takes them."""
    plain = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta,
                             dtype=dtype)
    if cfg.yarn_factor is None:
        return {WINDOW: plain, FULL: plain}
    return {WINDOW: plain, FULL: yarn_rotary_embedding(
        positions, cfg.head_dim, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow, cfg.yarn_attention_factor, dtype=dtype)}


def _check(cfg):
    n = cfg.full_attention_period
    if n < 1 or cfg.num_hidden_layers % n:
        raise ValueError(f"{cfg.num_hidden_layers} layers are no whole "
                         f"periods of {n}")
    if cfg.sa_config is not None:
        raise NotImplementedError(
            "a learned selection under a window is not built")
    if cfg.head_init_std is not None and (cfg.tie_word_embeddings
                                          or cfg.loss_chunk):
        raise NotImplementedError(
            "head_init_std seeds a head of its own whose logits are whole: "
            "no tied table, no chunked loss")
    if cfg.report_expert_load and cfg.router_experts is None:
        raise NotImplementedError(
            "report_expert_load names a held share's gauges: give "
            "router_experts (MixtralForCausalLM reports a whole layer's)")


def _call(block, kind, x, tables, mask, tok_mask, deterministic):
    """A block over the stream with its kind's rotary table: ``(x, (each
    expert's token fraction, the mean router probability, the layer's other
    statistics))``."""
    x, _, frac, prob, extra = block(x, *tables[kind], mask, tok_mask, None,
                                    None, deterministic)
    return x, (frac, prob, extra)


def _fold(sums, stats):
    frac_sum, prob_sum, extra_sum = sums
    frac, prob, extra = stats
    return frac_sum + frac, prob_sum + prob, _add_stats(extra_sum, extra)


class MellumModel(nn.Module):
    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None,
                 deterministic=True):
        """``(final-normed hidden, router aux loss, (each expert's share of
        the tokens summed over layers, the layers' other statistics))``, as
        ``MixtralModel``'s training call."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        kinds = period_kinds(cfg)
        with jax.named_scope("ds.embed"):
            x = seeded_embed_tokens(cfg, input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        tables = rope_tables(cfg, positions, x.dtype)
        mask = None if attention_mask is None else jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                jnp.float32)
        E = cfg.router_width
        zero_e = jnp.zeros((E,), jnp.float32)
        sums = (zero_e, zero_e, dict.fromkeys(
            _extra_stats(cfg, B * T * cfg.num_experts_per_tok),
            jnp.float32(0)))
        # every block is a ``MixtralBlock`` under its kind's window,
        # remat'ed by itself as ``MixtralModel``'s are
        x, (frac_sum, prob_sum, extra_sum) = scan_periods(
            cfg, kinds, x, sums, (tables, mask, attention_mask, deterministic),
            block=lambda kind, name: MixtralBlock(kind_config(cfg, kind),
                                                  name=name),
            call=_call, fold=_fold, scopes=KIND_SCOPES,
            offers=lambda x: remat_offers(cfg, x, cfg.num_hidden_layers))
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        L = cfg.num_hidden_layers
        aux = E * jnp.sum((frac_sum / L) * (prob_sum / L))
        return x, aux, (frac_sum, extra_sum)


class MellumForCausalLM(nn.Module):
    """``MixtralForCausalLM``'s training interface over ``MellumModel``:
    logits without labels; with them the LM loss plus the aux-weighted
    router loss, and for a held share ``(loss, named scalars)``."""

    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a stack of layer kinds is built for training only: no "
                "cache holds a window layer's ring beside a full layer's "
                "keys and values")
        hidden, aux, (load, extra) = MellumModel(cfg, name="model")(
            input_ids, positions, attention_mask, deterministic)
        with jax.named_scope(head_scope(None)):
            if cfg.head_init_std is None:
                logits, lm = lm_head_output(self, cfg, hidden, labels, None)
            else:
                logits, lm = seeded_lm_head(cfg, hidden), None
            if labels is None:
                return logits
            if lm is None:
                lm = cross_entropy_loss(logits, shift_labels(labels))
        loss = lm + cfg.router_aux_loss_coef * aux
        if cfg.router_experts is None:
            return loss
        return _share_loss_and_gauges(cfg, loss, load, extra, input_ids.size)

    #: one leading scanned axis (the periods) where Mixtral's is the layers:
    #: the same rules and the same frozen router
    partition_rules = staticmethod(MixtralForCausalLM.partition_rules)
    frozen_parameters = staticmethod(MixtralForCausalLM.frozen_parameters)
