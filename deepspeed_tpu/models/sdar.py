"""A block-diffusion decoder's TRAINING step over a Qwen3-MoE stack:
SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat`` ``config.json``, ``model_type``
``sdar_moe``), trained as "Block Diffusion: Interpolating Between
Autoregressive and Diffusion Language Models" (Arriola et al., ICLR 2025)
defines its one-pass objective. The stack is ``mixtral.MixtralModel`` whole
(GQA with a per-head q/k norm, a softmax router over every expert with the
top-k renormalised, a held share of the experts); what is new is around it.

A sequence ``x0`` of ``L`` tokens in ``K = L / B`` blocks of ``B =
block_length``:

- *Noise.* Block ``k`` draws ``t_k = eps + (1 - eps) u_k``, token ``i`` of it
  ``v_i``, both uniform on ``[0, 1)``; ``m_i = [v_i < t_k]``; ``xt_i = MASK if
  m_i else x0_i`` (the linear schedule ``alpha_t = 1 - t``, one ``t`` a
  block, ``eps`` = ``NOISE_EPS``; drawn on 16-bit integers, ``block_noise``).
  The key of a sequence's draws is ``fold_in(base, checksum(x0))``. Called
  ``deterministic`` (the default: evaluation, and a check that repeats a
  batch) ``base`` is a constant and the noise a function of the sequence
  alone; a training call that passes ``deterministic=False`` folds the
  call's ``dropout`` rng in, so an epoch noises its sequences anew.
- *Input.* ``[xt ; x0]``, ``2L`` positions numbered ``[0 .. L-1 ; 0 .. L-1]``,
  ONE forward pass under ``flash_attention.BlockDiffusion(L, B)``
  (``llama.LlamaAttention`` builds it from ``block_length`` and the ``2L``
  rows it is given: every pass of this stack is the doubled one): a noised
  block sees itself and the clean blocks before it, the clean blocks are
  block-causal, a clean query never sees a noised key. The rule is a static
  entry of the flash kernels' tile table, never a ``[2L, 2L]`` array.
- *Loss.* Logits of the ``L`` noised rows at the noised position itself (no
  shift): ``(1 / (batch L)) sum_i m_i (1 / t_k(i)) (-log softmax(W h_i)[x0_i])``,
  through a chunked head (no ``[L, vocab]`` float32 logits). A label of
  ``IGNORE`` is never masked (a prompt stays clean).
- *Without labels*: the same pass up to the head, and the denoiser's logits
  ``[batch, L, vocab]`` at the noised rows -- what the loss reads, for an
  evaluation (and for a check) to read too. The mask is the table's LAST row.

Training only: generation prefills clean ids block-causally (the rule's
``half = 0``) and denoises a block at a time against a block-wise cache, and
a serving step then yields a block, not a token (ROADMAP R6).
``models/__init__.py`` does not import this module; a configuration names it
by path.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.pallas.flash_attention import BlockDiffusion, rule_tile_share
from .layers import head_scope
from .mixtral import (MixtralConfig, MixtralForCausalLM, MixtralModel,
                      _share_loss_and_gauges)
from .ouro import IGNORE, chunked_token_nll, token_nll


@dataclasses.dataclass(frozen=True)
class SdarConfig(MixtralConfig):
    #: ``B``: tokens a block; divides every sequence. ``llama.LlamaAttention``
    #: reads it as it reads ``sliding_window``: a stack under this config
    #: sees ``[x_t ; x_0]`` and nothing else
    block_length: int = 4

    @staticmethod
    def sdar_30b_a3b(**over):
        """SDAR-30B-A3B-Chat as published: 48 layers of hidden 2048, GQA
        32 / 4 heads of 128 with a per-head q/k norm, 128 experts of 768 with
        top-8 of a softmax renormalised, no shared expert, an untied
        vocabulary of 151,936."""
        return SdarConfig(**{**dict(
            vocab_size=151936, hidden_size=2048, intermediate_size=6144,
            moe_intermediate_size=768, num_hidden_layers=48,
            num_attention_heads=32, num_key_value_heads=4,
            head_dim_override=128, max_position_embeddings=32768,
            rms_norm_eps=1e-6, rope_theta=1e6, num_local_experts=128,
            num_experts_per_tok=8, norm_topk_prob=True,
            router_aux_loss_coef=0.0, qk_norm_per_head=True,
            per_expert_init=True), **over})

    @staticmethod
    def tiny(**over):
        return SdarConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            num_local_experts=4, num_experts_per_tok=2, qk_norm_per_head=True,
            router_aux_loss_coef=0.0, remat=False), **over})


def checksum(ids):
    """int32 ``>= 0`` of one sequence's ids ``[L]``: ``sum_i (ids_i + 1)(2i +
    1)`` modulo ``2**32``, its top bit dropped. Every id and every position
    moves it."""
    n = ids.shape[0]
    terms = (ids.astype(jnp.uint32) + 1) * (2 * jnp.arange(n, dtype=jnp.uint32)
                                            + 1)
    return (jnp.sum(terms, dtype=jnp.uint32)
            & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


#: the noise is drawn on integers of this many bits: ``m_i = [V_i < T_k]``
#: holds bit for bit in any program, whatever a compiler fuses or contracts
#: (a float ``eps + (1 - eps) u`` read an ulp apart under two jits on one CPU)
NOISE_BITS = 16
#: ``t`` is drawn from ``[NOISE_EPS, 1)``: the loss weighs by ``1 / t``
NOISE_EPS = 1e-3


def block_noise(ids, block: int, base=None):
    """``(masked [B, L] bool, t [B, L] float32)`` of the sequences ``ids [B,
    L]`` in blocks of ``block``: which tokens the noise replaces, and each
    token's block's ``t``. A sequence's key is ``fold_in(base, checksum)``;
    ``base`` None is a constant, and the noise then the sequence's alone.
    With ``n = 2**NOISE_BITS``, ``E = round(eps n)`` and ``U_k``, ``V_i``
    uniform on ``0 .. n - 1``: ``T_k = E + floor((n - E) U_k / n)``, ``t_k =
    T_k / n`` (``eps + (1 - eps) u_k`` on a grid of ``n``), ``m_i = [V_i <
    T_k]``, so a token of block ``k`` is masked with probability ``t_k``
    exactly."""
    L, n = ids.shape[1], 1 << NOISE_BITS
    floor = round(NOISE_EPS * n)
    if base is None:
        base = jax.random.key(0, impl="threefry2x32")

    def draw(key, count):
        return jax.random.bits(key, (count,), jnp.uint32) >> (32 - NOISE_BITS)

    def one(seq):
        key_t, key_v = jax.random.split(
            jax.random.fold_in(base, checksum(seq)))
        level = floor + (((n - floor) * draw(key_t, L // block))
                         >> NOISE_BITS)
        level = jnp.repeat(level, block)
        return draw(key_v, L) < level, level.astype(jnp.float32) / n

    return jax.vmap(one)(ids)


def doubled_positions(batch: int, length: int):
    """``[0 .. L-1 ; 0 .. L-1]``: a token's noised and clean copy stand at
    the same position."""
    return jnp.broadcast_to(jnp.tile(jnp.arange(length), 2)[None, :],
                            (batch, 2 * length))


def loss_weights(masked, t):
    """A token's weight in the loss: ``m_i / t_k(i)``."""
    return masked / t


class SdarForCausalLM(nn.Module):
    """``MixtralForCausalLM``'s interface: the training call returns the
    block-diffusion loss (with ``report_expert_load``, ``(loss, {name:
    scalar})``: ``bd_masked_share``, ``bd_kept_tile_share``,
    ``bd_loss_weight_mean`` beside a held share's ``moe_*`` gauges); without
    labels the logits of the same pass's noised rows."""

    config: SdarConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "block diffusion is built for training only: generation "
                "denoises a block at a time against a block-wise cache, and "
                "a serving step then yields a block (ROADMAP R6)")
        if attention_mask is not None or positions is not None \
                or cfg.sliding_window is not None or cfg.sa_config is not None:
            raise NotImplementedError(
                "packed sequences under the block rule alone: padding would "
                "cut blocks, positions are the wrapper's, and neither a "
                "window nor a learned selection is composed with the rule")
        B, L = input_ids.shape
        if L % cfg.block_length:
            raise ValueError(f"blocks of {cfg.block_length} tokens do not "
                             f"divide the {L} of a sequence")

        with jax.named_scope("ds.bd_noise"):
            masked, t = block_noise(
                input_ids, cfg.block_length,
                None if deterministic else self.make_rng("dropout"))
            if labels is not None:      # a prompt stays clean
                masked = masked & (labels != IGNORE)
            both = jnp.concatenate(
                [jnp.where(masked, cfg.vocab_size - 1, input_ids),
                 input_ids], axis=1)
            twice = doubled_positions(B, L)
        hidden, aux, (load, extra) = MixtralModel(cfg, name="model")(
            both, twice, None, deterministic)
        with jax.named_scope("ds.bd_gather"):
            hidden = hidden[:, :L]
        head = nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                        param_dtype=jnp.float32)
        with jax.named_scope(head_scope(None)):
            if labels is None:
                return head(hidden)
            weight = loss_weights(masked, t)
            target = jnp.where(weight > 0, input_ids, IGNORE)
            if cfg.loss_chunk:
                head(hidden[:, :0])     # the parameters, and no product
                nll = chunked_token_nll(
                    hidden, self.variables["params"]["lm_head"]["kernel"],
                    target, cfg.loss_chunk)
            else:
                nll = token_nll(head(hidden), target)
            loss = jnp.sum(nll * weight) / (B * L) \
                + cfg.router_aux_loss_coef * aux
        if not cfg.report_expert_load:
            return loss
        named = {
            "bd_masked_share": jnp.mean(masked.astype(jnp.float32)),
            "bd_loss_weight_mean": jnp.mean(weight),
            "bd_kept_tile_share": jnp.float32(rule_tile_share(
                BlockDiffusion(L, cfg.block_length), 2 * L,
                cfg.flash_block_q, cfg.flash_block_k)),
        }
        if extra or cfg.router_experts is not None:
            # every count of the held share is over the 2L rows the stack ran
            loss, moe = _share_loss_and_gauges(cfg, loss, load, extra,
                                               both.size)
            named.update(moe)
        return loss, named

    partition_rules = staticmethod(MixtralForCausalLM.partition_rules)
    frozen_parameters = staticmethod(MixtralForCausalLM.frozen_parameters)
