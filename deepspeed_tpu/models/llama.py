"""Llama-family decoder (the flagship training model).

TPU-native from scratch: RoPE + RMSNorm + SwiGLU + GQA, layers run under
``nn.scan`` (one compiled block body regardless of depth — essential for
ZeRO-3 gather-in-scan and fast compiles) with optional ``nn.remat``
(activation checkpointing, the analog of the reference's
``runtime/activation_checkpointing/checkpointing.py:743``).

The reference has no Llama module (it wraps user torch models); this model is
the framework's first-class citizen the way DeepSpeed's examples wrap
Megatron-GPT. Tensor-parallel partition rules follow Megatron sharding
(column-parallel QKV/gate/up, row-parallel o/down — the layout the
reference's inference injection applies in ``module_inject/layers.py:9``).
"""

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import REMAT_MLP, REMAT_QKV
from .layers import (RMSNorm, apply_rotary, apply_rotary_partial,
                     cached_attention_xla, cross_entropy_loss, device_part,
                     dot_product_attention, flash_prefill_from_empty,
                     head_scope, init_kv_cache, init_paged_kv_cache,
                     is_paged_index, key_mask_to_bias, lm_head_output,
                     model_dense, name_if_kept, paged_attention_reference,
                     paged_prefill_attention_reference,
                     ragged_mixed_attention_reference, repeat_kv,
                     resolve_remat_policy, rotary_embedding, shift_labels,
                     update_kv_cache, update_paged_kv_cache)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    #: Mistral-style sliding-window attention: query i attends keys j with
    #: 0 <= i - j < window (None = full causal)
    sliding_window: Optional[int] = None
    #: Qwen2-style: biases on q/k/v projections (o/mlp stay bias-free)
    attention_qkv_bias: bool = False
    #: OLMoE-style: RMSNorm over the WHOLE projected query and key (widths
    #: Hq*D and Hkv*D, scales ``q_norm``/``k_norm``), before the split into
    #: heads and before RoPE. Off: no parameter exists
    qk_norm: bool = False
    #: Gemma-style knobs: explicit head_dim (H*D need not equal hidden),
    #: gelu-tanh MLP activation, sqrt(hidden) embedding scaling
    head_dim_override: Optional[int] = None
    mlp_activation: str = "silu"  # "silu" | "gelu_tanh"
    embed_scale: Optional[float] = None
    attention_impl: str = "xla"  # "xla" | "flash"
    #: cached single-token attention: "xla" (repeat_kv + full-cache softmax)
    #: or "pallas" (ops/pallas/decode_attention.py — the softmax_context
    #: kernel equivalent; streams the cache per kv head, skips unfilled
    #: blocks)
    decode_attention_impl: str = "xla"
    #: cached PREFILL via the flash kernel with in-kernel key masking —
    #: avoids the [B, H, T, S] logits tensor of the XLA cached path (tens
    #: of GB at serving shapes like batch 64 x prompt 2048). CONTRACT:
    #: only enable when every multi-token cached apply starts from an
    #: EMPTY cache (the inference engine's generate does) — the flash
    #: prefill attends the fresh K/V only, which equals cache attention
    #: iff nothing preceded it. Chunked prefill must keep this False.
    prefill_flash_from_empty: bool = False
    # flash kernel tile sizes (VMEM blocks); tuned per chip generation
    flash_block_q: int = 512
    flash_block_k: int = 512
    scan_layers: bool = True
    remat: bool = True
    # activation-checkpoint policy (reference: the CONFIG knobs of
    # ``activation_checkpointing/checkpointing.py`` trade memory for FLOPs):
    #   "nothing"  - save nothing, recompute the whole block in backward
    #                (max memory savings, ~1/3 extra FLOPs)
    #   "dots"     - save matmul outputs, recompute only elementwise chains
    #                (near-zero extra FLOPs; memory ~= no-remat for big dots)
    #   "dots_no_batch" - save only non-batch matmuls (middle ground)
    #   "offload_dots_no_batch" - like dots_no_batch but residuals live in
    #                pinned host memory (CPU activation checkpointing)
    # Every policy also keeps the flash kernel's output and log-sum-exp, so
    # the replay never calls the forward kernel (layers.resolve_remat_policy)
    remat_policy: str = "nothing"
    #: >0: training loss runs as a remat'd scan over token chunks of this
    #: size — the [tokens, vocab] logits tensor is never materialized
    #: (models/layers.py chunked_cross_entropy_loss). 0 = plain loss.
    loss_chunk: int = 0
    # -- quantized serving (set via init_inference, never by hand: the
    # engine rewrites the fp param tree to match) ----------------------
    #: store attention/MLP projection kernels quantized ("int8" per-channel
    #: codes, or "int4" packed two-per-byte with grouped scales) with
    #: dequant fused into the consumer matmul (models/layers.py QuantDense;
    #: Pallas grouped-dequant kernel when decode_attention_impl="pallas").
    #: Embeddings, norms and the lm_head stay fp.
    quantize_weights: Optional[str] = None
    #: scale-group length along K for quantized weights (0 = one group =
    #: per-output-column). int4 accuracy wants grouping (e.g. 64); the
    #: engine aligns the effective group to the TP shard width.
    quantize_group_size: int = 0
    #: EQuARX-style quantized TP collectives: the row-parallel o_proj /
    #: down_proj partial sums all-reduce over int8 wire payloads
    #: (comm/quantized.py quantized_psum) instead of the partitioner's
    #: full-width psum. No-op at model-axis world size 1.
    quantized_collectives: bool = False
    #: quantized_psum wire block (values per absmax scale on the wire)
    quantized_psum_block: int = 256
    #: the TP width the quantized weights were written for (set by
    #: init_inference; row-parallel scale groups align to it — carried
    #: in the config so param-shape validation never consults the
    #: mutable process-global mesh)
    quantize_row_shards: int = 1

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama3_8b(**over):
        return LlamaConfig(**{**dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=8192, rope_theta=500000.0), **over})

    @staticmethod
    def mistral_7b(**over):
        """Mistral-7B-v0.1 as published (``mistralai/Mistral-7B-v0.1``
        ``config.json``): the dense GQA + sliding-window control that
        ``chip_smoke.py`` runs at full width with depth cut."""
        return LlamaConfig(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
            max_position_embeddings=32768, rms_norm_eps=1e-5,
            rope_theta=10000.0, sliding_window=4096), **over})

    @staticmethod
    def tiny(**over):
        return LlamaConfig(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128), **over})


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask, layer_cache=None, cache_index=None,
                 deterministic=True):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        dense = lambda feats, name, bias=False, row=False: model_dense(
            cfg, feats, name, use_bias=bias, row_parallel=row)
        qb = cfg.attention_qkv_bias
        # ds.* scopes: the layer boundaries every model family shares, as
        # a profiler trace names them (docs/observability.md). ds.attention
        # holds score-softmax-value only — the Pallas kernels here, their
        # XLA counterparts in layers.py — so both are measured alike
        with jax.named_scope("ds.attn_proj"):
            # a replay keeps q, k ahead of a norm: its backward reads its input
            kept = lambda t, on=True: name_if_kept(t, REMAT_QKV) if on else t
            norm = lambda t, name, on: RMSNorm(
                eps=cfg.rms_norm_eps, name=name)(kept(t)) if on else t
            per_head = getattr(cfg, "qk_norm_per_head", False)  # scales [D]
            bare = not (cfg.qk_norm or per_head)    # named after RoPE
            # a head's first ``rotary_dim`` columns rotate (cos and sin are
            # that wide), the others pass; None: all of them; 0: none, and
            # no table is read (a stack whose recurrences carry position)
            rot = getattr(cfg, "rotary_dim", None)
            rotate = apply_rotary if rot is None else (
                lambda t, cos, sin: apply_rotary_partial(t, cos, sin, rot)
                if rot else t)
            q = norm(dense(H * D, "q_proj", qb)(x), "q_norm",
                     cfg.qk_norm).reshape(B, T, H, D)
            k = norm(dense(Hkv * D, "k_proj", qb)(x), "k_norm",
                     cfg.qk_norm).reshape(B, T, Hkv, D)
            v = dense(Hkv * D, "v_proj", qb)(x).reshape(B, T, Hkv, D)
            q, k = norm(q, "q_norm", per_head), norm(k, "k_norm", per_head)
            q, k = (rotate(t, cos, sin) for t in (q, k))
            q, k, v = kept(q, bare), kept(k, bare), kept(v)
            # a learned gate a head on the core's output, from the layer's
            # normed input through a projection of its own
            gated = getattr(cfg, "attn_head_gate", False)
            if gated:
                gate = _head_gate(dense(H, "g_proj")(x))        # [B, T, H]
        if getattr(cfg, "sa_config", None) is not None:
            # a learned indexer chooses each query's keys (training only):
            # no cache, and a third value: what the loss needs of this layer
            if layer_cache is not None or mask is not None:
                raise NotImplementedError(
                    "sa_config is built for training on packed sequences: "
                    "no cache holds the indexer's keys and no padding mask "
                    "is composed with the selection")
            from .indexed_attention import indexed_attention

            out, sa_stats = indexed_attention(cfg, x, q, k, v, cos, sin)
        elif layer_cache is not None and is_paged_index(cache_index):
            # paged serving path (inference/serving/): KV appends scatter
            # into the shared block pool through this sequence's block
            # table; ragged-ness (per-sequence lengths) lives in the index
            # bundle, so ONE compiled step serves any mix of lengths
            layer_cache = update_paged_kv_cache(layer_cache, k, v, cache_index)
            if "token_rows" in cache_index:
                # unified ragged MIXED step (the serving engine's ONE
                # resident program): the token axis is a packed batch of
                # per-sequence segments — decode rows and prefill chunks
                # side by side — and raggedness rides the descriptor
                # arrays (query_start/len, chunk_start, context_len) as
                # DATA, so any traffic mix reuses one compiled step
                if cfg.decode_attention_impl == "pallas":
                    from ..ops.pallas.ragged_attention import \
                        ragged_paged_attention

                    with jax.named_scope("ds.attention"):
                        out = ragged_paged_attention(
                            q[0], layer_cache["k"], layer_cache["v"],
                            cache_index["block_tables"],
                            cache_index["query_start"],
                            cache_index["query_len"],
                            cache_index["chunk_start"],
                            cache_index["context_len"],
                            k_scale=layer_cache.get("k_scale"),
                            v_scale=layer_cache.get("v_scale"),
                            window=cfg.sliding_window)[None]
                else:
                    out = ragged_mixed_attention_reference(
                        q, layer_cache, cache_index,
                        window=cfg.sliding_window)
            elif T == 1:
                if cfg.decode_attention_impl == "pallas":
                    from ..ops.pallas.decode_attention import \
                        paged_decode_attention

                    with jax.named_scope("ds.attention"):
                        out = paged_decode_attention(
                            q[:, 0], layer_cache["k"], layer_cache["v"],
                            cache_index["block_tables"],
                            cache_index["context_len"],
                            k_scale=layer_cache.get("k_scale"),
                            v_scale=layer_cache.get("v_scale"),
                            window=cfg.sliding_window)[:, None]
                else:
                    out = paged_attention_reference(
                        q[:, 0], layer_cache, cache_index["block_tables"],
                        cache_index["context_len"],
                        window=cfg.sliding_window)[:, None]
            elif "chunk_start" in cache_index:
                # CHUNKED prefill: this chunk may sit mid-prompt, with the
                # cached prefix (prefix-cache hits + earlier chunks) living
                # only in the POOL — fresh-KV attention would drop it. The
                # chunk offset and prefix length ride as data, so every
                # chunk position / hit length reuses one compiled program.
                # Shared (refcount>1) pages are never appended into: the
                # engine copies-on-write before routing writes here.
                if cfg.decode_attention_impl == "pallas":
                    from ..ops.pallas.decode_attention import \
                        paged_prefill_attention

                    with jax.named_scope("ds.attention"):
                        out = paged_prefill_attention(
                            q, layer_cache["k"], layer_cache["v"],
                            cache_index["block_tables"],
                            cache_index["chunk_start"],
                            cache_index["context_len"],
                            k_scale=layer_cache.get("k_scale"),
                            v_scale=layer_cache.get("v_scale"),
                            window=cfg.sliding_window)
                else:
                    out = paged_prefill_attention_reference(
                        q, layer_cache, cache_index["block_tables"],
                        cache_index["append_pos"],
                        cache_index["context_len"],
                        window=cfg.sliding_window)
            else:
                # serving prefill always starts a sequence from an EMPTY
                # span of pages, so attention over the FRESH K/V equals
                # cache attention (the prefill_flash_from_empty contract);
                # pads carry append_pos = -1
                key_mask = (cache_index["append_pos"] >= 0).astype(jnp.int32)
                if cfg.prefill_flash_from_empty:
                    # same gate as the dense branch: the masked flash
                    # kernel avoids the [B, H, T, T] logits tensor the XLA
                    # path materializes at serving prompt lengths
                    out = flash_prefill_from_empty(
                        q, k, v, key_mask=key_mask,
                        block_q=cfg.flash_block_q, block_k=cfg.flash_block_k,
                        window=cfg.sliding_window)
                else:
                    out = dot_product_attention(
                        q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv),
                        bias=key_mask_to_bias(key_mask), causal=True,
                        window=cfg.sliding_window)
        elif layer_cache is not None:
            # decode / cached-prefill path (reference: softmax_context KV-cache
            # append, pt_binding.cpp). mask carries the [B, S] key-padding mask.
            layer_cache = update_kv_cache(layer_cache, k, v, cache_index)
            if T == 1 and cfg.decode_attention_impl == "pallas":
                # Pallas decode kernel: streams the cache once per kv head
                # (GQA heads share the pass, no repeat_kv copy) and skips
                # blocks beyond the filled prefix; an int8 cache is
                # dequantized per block in VMEM (HBM reads stay int8)
                from ..ops.pallas.decode_attention import decode_attention

                with jax.named_scope("ds.attention"):
                    out = decode_attention(
                        q[:, 0], layer_cache["k"], layer_cache["v"],
                        cache_index, key_mask=mask,
                        k_scale=layer_cache.get("k_scale"),
                        v_scale=layer_cache.get("v_scale"),
                        window=cfg.sliding_window)[:, None]
            elif T > 1 and cfg.prefill_flash_from_empty:
                # from-empty prefill over the FRESH K/V (== cache attention
                # when nothing precedes it; see the config flag's contract):
                # masked flash kernel, GQA-native — the XLA cached path
                # would materialize [B, H, T, S] logits (tens of GB at
                # serving shapes)
                out = flash_prefill_from_empty(
                    q, k, v, key_mask=mask, block_q=cfg.flash_block_q,
                    block_k=cfg.flash_block_k, window=cfg.sliding_window)
            else:
                # head-major XLA math: no cache-sized transpose per step
                out = cached_attention_xla(q, layer_cache, cache_index,
                                           key_mask=mask,
                                           window=cfg.sliding_window)
        else:
            k = repeat_kv(k, H // Hkv)
            v = repeat_kv(v, H // Hkv)
            # Mistral windowed causality (0 <= i - j < window) threads into
            # the attention core: the flash kernel masks AND block-skips by
            # it (O(T*window) work), the xla path applies it on the logits
            out = dot_product_attention(q, k, v, bias=mask, causal=True,
                                        attention_impl=cfg.attention_impl,
                                        flash_block_q=cfg.flash_block_q,
                                        flash_block_k=cfg.flash_block_k,
                                        window=_window_of(cfg, T))
        if gated:
            with jax.named_scope("ds.attn_gate"):
                out = out * gate[..., None].astype(out.dtype)
        with jax.named_scope("ds.attn_proj"):
            out = dense(cfg.hidden_size, "o_proj", row=True)(
                out.reshape(B, T, H * D))
        # a third value where the layer has statistics to hand up: the
        # selection's, the mean of a head gate
        if getattr(cfg, "sa_config", None) is not None:
            return out, layer_cache, sa_stats
        if gated:
            return out, layer_cache, {"attn_gate": jnp.mean(gate)}
        return out, layer_cache


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, use_bias=False, row_parallel=row)
        act = nn.silu if cfg.mlp_activation == "silu" else \
            (lambda g: nn.gelu(g, approximate=True))  # gemma gelu_pytorch_tanh
        with jax.named_scope("ds.mlp"):
            # the two products a wrapper may offer its remat policy
            # (remat_offers); the activation's product is not: one
            # element-wise pass recomputes it
            gate = name_if_kept(
                dense(cfg.intermediate_size, "gate_proj")(x), REMAT_MLP)
            up = name_if_kept(
                dense(cfg.intermediate_size, "up_proj")(x), REMAT_MLP)
            return dense(cfg.hidden_size, "down_proj", row=True)(
                act(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask, layer_cache=None, cache_index=None,
                 deterministic=True):
        cfg = self.config
        # ds.norm / ds.residual name the block's own element-wise passes at
        # their call sites: a norm inside a projection's or the head's scope
        # stays that scope's
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        attn, layer_cache = LlamaAttention(cfg, name="self_attn")(
            h, cos, sin, mask, layer_cache, cache_index, deterministic)
        with jax.named_scope("ds.residual"):
            x = x + attn
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps,
                        name="post_attention_layernorm")(x)
        out = LlamaMLP(cfg, name="mlp")(h)
        with jax.named_scope("ds.residual"):
            x = x + out
        return x, layer_cache


class _ScanBlock(nn.Module):
    """Carry-through wrapper so nn.scan can thread (x) while broadcasting
    (cos, sin, mask); the per-layer KV cache AND the per-layer PLD gate ride
    the scan xs/ys."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, carry, xs):
        layer_cache, pld_gate = xs
        x, cos, sin, mask, cache_index, det = carry
        y, layer_cache = LlamaBlock(self.config, name="block")(
            x, cos, sin, mask, layer_cache, cache_index, det)
        if pld_gate is not None:
            # stochastic depth: gate = keep/p (inverted-dropout scaling);
            # dropped layers pass the residual stream through unchanged
            y = x + pld_gate * (y - x)
        return (y, cos, sin, mask, cache_index, det), layer_cache


class LlamaModel(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None, deterministic=True,
                 cache=None, cache_index=None, pld_theta=None):
        """``cache`` (from ``init_cache``) switches to the KV-cached decode
        path: ``attention_mask`` is then a ``[B, cache_len]`` key-padding mask
        and the return value is ``(hidden, new_cache)``.

        ``pld_theta`` (traced scalar) enables progressive layer drop for this
        step (reference ``progressive_layer_drop.py:5``): layer l keeps with
        ``p_l = 1 - (l+1)/L * (1 - theta)``, sampled from the ``pld`` rng."""
        cfg = self.config
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
            if cfg.embed_scale is not None:
                # gemma: hidden states scaled by sqrt(hidden) in the embed
                # dtype
                x = x * jnp.asarray(cfg.embed_scale, x.dtype)
        if positions is None:
            if cache_index is not None and is_paged_index(cache_index):
                # paged serving: each token's absolute position IS its
                # append slot (pads, marked -1, are masked anyway)
                positions = jnp.maximum(cache_index["append_pos"], 0)
            else:
                start = 0 if cache_index is None else cache_index
                positions = jnp.broadcast_to(start + jnp.arange(T)[None, :], (B, T))
        cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta, dtype=x.dtype)
        # causality is applied inside the attention core (flash-compatible);
        # the bias only carries the padding mask (cached path: raw [B, S] mask)
        mask = None
        if attention_mask is not None:
            if cache is not None:
                mask = attention_mask
            else:
                mask = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                    jnp.float32)

        # progressive layer drop: one gate per layer for this step
        pld_gate = None
        if pld_theta is not None and cache is None:
            L = cfg.num_hidden_layers
            depth = (jnp.arange(L) + 1.0) / L
            p_keep = 1.0 - depth * (1.0 - jnp.asarray(pld_theta, jnp.float32))
            keep = jax.random.bernoulli(self.make_rng("pld"), p_keep)
            # guard p_keep -> 0 (theta=0 makes the deepest layer's p hit
            # exactly 0; keep is then always False and 0/0 would be NaN)
            pld_gate = jnp.where(keep, 1.0 / jnp.maximum(p_keep, 1e-6),
                                 0.0).astype(x.dtype)

        remat_policy = resolve_remat_policy(
            cfg.remat_policy,
            remat_offers(cfg, x, cfg.num_hidden_layers)
            if cfg.remat and cache is None else ())
        # ds.layer_stack: what the loop over the layers costs beyond what the
        # layers' own scopes name — under nn.scan each layer's weights
        # sliced out of the stacked tree (and again in the backward pass),
        # its gradients and kept values written back into it
        with jax.named_scope("ds.layer_stack"):
            if cfg.scan_layers:
                block_cls = _ScanBlock
                if cfg.remat and cache is None:
                    block_cls = nn.remat(
                        _ScanBlock, static_argnums=(),
                        prevent_cse=False,
                        policy=remat_policy)
                scan = nn.scan(block_cls, variable_axes={"params": 0},
                               split_rngs={"params": True, "dropout": True},
                               length=cfg.num_hidden_layers, metadata_params={})
                (x, *_), cache = scan(cfg, name="layers")(
                    (x, cos, sin, mask, cache_index, deterministic),
                    (cache, pld_gate))
            else:
                block_cls = nn.remat(LlamaBlock, prevent_cse=False, policy=remat_policy) \
                    if (cfg.remat and cache is None) else LlamaBlock
                new_cache = [] if cache is not None else None
                for i in range(cfg.num_hidden_layers):
                    layer_cache = None if cache is None else \
                        jax.tree_util.tree_map(lambda c: c[i], cache)
                    x_in = x
                    x, layer_cache = block_cls(cfg, name=f"layers_{i}")(
                        x, cos, sin, mask, layer_cache, cache_index, deterministic)
                    if pld_gate is not None:
                        x = x_in + pld_gate[i] * (x - x_in)
                    if new_cache is not None:
                        new_cache.append(layer_cache)
                if new_cache is not None:
                    cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *new_cache)
        with jax.named_scope(head_scope(cache)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        return x if cache is None else (x, cache)


class LlamaForCausalLM(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, attention_mask=None,
                 deterministic=True, cache=None, cache_index=None, pld_theta=None):
        cfg = self.config
        hidden = LlamaModel(cfg, name="model")(input_ids, positions, attention_mask,
                                               deterministic, cache, cache_index,
                                               pld_theta)
        if cache is not None:
            hidden, cache = hidden
        with jax.named_scope(head_scope(cache)):
            logits, loss = lm_head_output(self, cfg, hidden, labels, cache)
            if cache is not None:
                return logits, cache
            if labels is None:
                return logits
            if loss is not None:
                return loss
            return cross_entropy_loss(logits, shift_labels(labels))

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Empty KV cache for incremental decoding."""
        cfg = self.config
        return init_kv_cache(batch, max_len, cfg.num_key_value_heads, cfg.head_dim,
                             n_layers=cfg.num_hidden_layers, dtype=dtype)

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16):
        """Empty paged KV pool for the continuous-batching serving engine."""
        cfg = self.config
        return init_paged_kv_cache(num_blocks, block_size,
                                   cfg.num_key_value_heads, cfg.head_dim,
                                   n_layers=cfg.num_hidden_layers, dtype=dtype)

    @staticmethod
    def partition_rules(config: LlamaConfig):
        """Tensor-parallel base specs (engine overlays ZeRO on top).

        Scanned params carry a leading layer axis, hence the extra None.
        Megatron layout: qkv/gate/up column-parallel (output dim on
        ``model``), o/down row-parallel (input dim on ``model``) — the same
        layout ``module_inject/replace_module.py:190`` slices for inference.
        """
        L = (None,) if config.scan_layers else ()
        rules = [
            (r"embed_tokens/embedding", P("model", None)),
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel", P(*L, None, "model")),
            (r"(o_proj|down_proj)/kernel", P(*L, "model", None)),
            (r"lm_head/kernel", P(None, "model")),
            # qk_norm's scales lie along the q/k kernels' sharded columns
            (r"(q_norm|k_norm)/scale", P(*L, "model")),
        ]
        if getattr(config, "quantize_weights", None):
            # quantized-weight scales ride as sibling [G, N] leaves:
            # column-parallel scales shard on N exactly like their
            # kernels; row-parallel scales replicate (G may be 1 —
            # per-column — which no axis divides; they are KB-sized, and
            # the QuantDense shard_map seam re-slices its own groups)
            rules += [
                (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/wscale",
                 P(*L, None, "model")),
                (r"(o_proj|down_proj)/wscale", P(*L, None, None)),
            ]
        return rules

    @staticmethod
    def quantizable_projections(config: "LlamaConfig"):
        """(path_regex, role) of every kernel ``init_inference`` may
        store quantized. Roles drive scale-group/TP alignment: "col" =
        output features on ``model``, "row" = input features on
        ``model`` (see ``inference/quant.py``)."""
        return [
            (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel$", "col"),
            (r"(o_proj|down_proj)/kernel$", "row"),
        ]


def remat_offers(cfg, x, applications: int):
    """What ``LlamaMLP`` / ``LlamaAttention`` name, as a block wrapper offers
    it to ``layers.resolve_remat_policy``: ``[(name, bytes over
    ``applications`` layer applications)]`` for a stream ``x [B, T, hidden]``,
    costliest replay a byte first (train.8k: the gate and up products replay
    in 21.3 ms a step for 0.94 GB, q / k / v with RoPE in 4.7 for 0.20)."""
    per_value = device_part(x.shape[0]) * x.shape[1] * x.dtype.itemsize * \
        applications
    heads = cfg.num_attention_heads + 2 * cfg.num_key_value_heads
    return ((REMAT_MLP, 2 * cfg.intermediate_size * per_value),
            (REMAT_QKV, heads * cfg.head_dim * per_value))


def _head_gate(logits):
    """``sigmoid`` of a head gate's logits, in float32."""
    return jax.nn.sigmoid(logits.astype(jnp.float32))


def _window_of(cfg, T: int):
    """What the uncached attention core takes as ``window=``: the config's
    ``sliding_window``, or under a config with a ``block_length``
    (``models/sdar.py``: every sequence its stack sees is ``[x_t ; x_0]``)
    the ``flash_attention.BlockDiffusion`` of these ``T`` positions' halves,
    which stands for causality too."""
    block = getattr(cfg, "block_length", None)
    if block is None:
        return cfg.sliding_window
    from ..ops.pallas import flash_attention

    if T % (2 * block):
        raise ValueError(f"{T} positions are not a noised and a clean copy "
                         f"of one sequence in blocks of {block}")
    return flash_attention.BlockDiffusion(T // 2, block)
