"""Generic transformer graphs covering the reference's injection-policy
model families (BERT/OPT/BLOOM/GPT-NeoX/...).

The reference implements ONE fused CUDA block (``DeepSpeedTransformerInference``,
``ops/transformer/inference/transformer_inference.py:735``) parameterized per
architecture by its policies (``module_inject/replace_policy.py:66-435``:
pre/post-LN, rotary vs learned vs alibi positions, activation, parallel
residual, fused-QKV layouts). This module is the TPU-native equivalent: one
flax block covering those option axes, compiled by XLA per configuration —
policies in ``module_inject/replace_policy.py`` map HF checkpoints onto it.

Decoder configs (OPT/BLOOM/NeoX) get the same scan/remat/KV-cache machinery
as the flagship Llama model; ``causal=False`` + ``mlm_head`` yields the BERT
encoder with its MLM head.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import (apply_rotary_partial, cache_attention_bias,
                     cached_attention_xla,
                     flash_prefill_from_empty,
                     cross_entropy_loss,
                     key_mask_to_bias,
                     dot_product_attention,
                     lm_head_output,
                     init_kv_cache, repeat_kv, resolve_remat_policy,
                     rotary_embedding, shift_labels, update_kv_cache)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    num_key_value_heads: Optional[int] = None  # GQA; None = MHA
    max_position_embeddings: int = 2048
    causal: bool = True
    # positions: "learned" (BERT/OPT), "rope" (NeoX), "alibi" (BLOOM), "none"
    pos_embedding: str = "learned"
    pos_offset: int = 0          # OPT stores positions at index pos+2
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0      # NeoX partial rotary (first pct of head_dim)
    rope_style: str = "half"     # "half" (rotate-half) | "interleaved" (GPT-J)
    activation: str = "gelu"     # "gelu" | "gelu_new" | "relu"
    norm_eps: float = 1e-5
    pre_layernorm: bool = True   # False = post-LN (BERT, OPT-350m)
    parallel_residual: bool = False  # NeoX: x + attn(ln1 x) + mlp(ln2 x)
    shared_parallel_ln: bool = False  # GPT-J: ONE LN feeds both branches
    embedding_layernorm: bool = False  # BLOOM word_embeddings_layernorm / BERT
    final_layernorm: bool = True
    type_vocab_size: int = 0     # BERT token-type embeddings
    attention_bias: bool = True
    #: output-projection bias override (GPT-Neo: q/k/v bias-free, o biased)
    attention_out_bias: Optional[bool] = None
    #: None = 1/sqrt(head_dim); GPT-Neo uses UNscaled attention (1.0)
    attention_scale: Optional[float] = None
    mlp_bias: bool = True
    tie_word_embeddings: bool = False
    lm_head_bias: bool = False   # GPT-J's lm_head carries a bias
    mlm_head: bool = False       # BERT cls.predictions transform+decoder
    attention_impl: str = "xla"
    #: cached single-token attention: "xla" or "pallas"
    #: (ops/pallas/decode_attention.py); the kernel path engages only for
    #: configs it can represent (no alibi, no per-layer local kinds)
    decode_attention_impl: str = "xla"
    #: cached prefill via the masked flash kernel (same eligibility
    #: rules; from-empty contract per LlamaConfig)
    prefill_flash_from_empty: bool = False
    # GPT-Neo: per-layer attention kind, e.g. ("global","local",...) cycled
    # over layers; "local" limits causal attention to a sliding window
    attention_layers: Optional[tuple] = None
    attention_window: int = 256
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "nothing"
    #: dropout (BERT convention: on attention probs and on each sublayer
    #: output pre-residual); active only when a caller passes
    #: deterministic=False and provides a "dropout" rng
    attn_dropout: float = 0.0
    hidden_dropout: float = 0.0
    #: compute dtype for the matmuls (None = flax promotion, i.e. fp32 with
    #: fp32 params); layernorms always compute fp32
    compute_dtype: Optional[Any] = None
    #: kernel init: N(0, initializer_range) when set (BERT-style); flax
    #: default (lecun_normal) when None. adjust_init_range additionally
    #: scales the residual-output projections by 1/sqrt(2*num_hidden_layers)
    initializer_range: Optional[float] = None
    adjust_init_range: bool = False
    #: >0: training loss runs as a remat'd scan over token chunks of this
    #: size — the [tokens, vocab] logits tensor is never materialized
    #: (models/layers.py chunked_cross_entropy_loss). 0 = plain loss.
    loss_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    def pallas_decode_eligible(self, q_len: int) -> bool:
        """Static predicate shared by the model (bias construction) and the
        attention (kernel dispatch): the decode kernel represents triangular
        + key-padding masking only."""
        return (self.decode_attention_impl == "pallas" and q_len == 1
                and self.pos_embedding != "alibi"
                and self.attention_layers is None)

    def prefill_flash_eligible(self, q_len: int) -> bool:
        """Cached prefill through the masked flash kernel (see
        LlamaConfig.prefill_flash_from_empty for the from-empty
        contract); triangular + key-padding masking only."""
        return (self.prefill_flash_from_empty and q_len > 1
                and self.pos_embedding != "alibi"
                and self.attention_layers is None)

    @property
    def rotary_dim(self) -> int:
        # round (not truncate): policies reconstruct rotary_dim from a float
        # ratio, and int(d/h*h) underestimates for many integer pairs
        d = int(round(self.head_dim * self.rotary_pct))
        return d - d % 2


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (geometric sequence; non-power-of-two heads get
    the interleaved tail, the standard construction)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(n_heads).is_integer():
        return pow2_slopes(n_heads).astype(np.float32)
    base = 2 ** int(np.floor(np.log2(n_heads)))
    slopes = list(pow2_slopes(base))
    extra = pow2_slopes(2 * base)[0::2][:n_heads - base]
    return np.asarray(slopes + list(extra), np.float32)


def alibi_bias(n_heads: int, kv_len: int) -> jnp.ndarray:
    """[1, H, 1, S] additive bias: slope_h * key_position. Per-row constants
    (slope * query_position) cancel in softmax, so this single form is exact
    for full, cached-prefill, and decode attention."""
    slopes = jnp.asarray(alibi_slopes(n_heads))
    return (slopes[:, None] * jnp.arange(kv_len)[None, :])[None, :, None, :]


def _kernel_init(cfg, residual_out: bool):
    """BERT-style N(0, initializer_range) when configured; residual-output
    projections optionally scaled by 1/sqrt(2*L) (reference
    adjust_init_range, ``transformer.py:74-78``)."""
    if cfg.initializer_range is None:
        return nn.linear.default_kernel_init
    std = cfg.initializer_range
    if residual_out and cfg.adjust_init_range:
        std = std / float(np.sqrt(2.0 * max(1, cfg.num_hidden_layers)))
    return nn.initializers.normal(stddev=std)


def _act(name: str):
    return {
        "gelu": lambda x: nn.gelu(x, approximate=False),
        "gelu_new": lambda x: nn.gelu(x, approximate=True),
        "relu": nn.relu,
    }[name]


class GenericAttention(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, cos, sin, bias, layer_cache=None, cache_index=None,
                 deterministic=True):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name, bias, out=False: nn.Dense(
            feats, use_bias=bias, name=name, param_dtype=jnp.float32,
            dtype=cfg.compute_dtype, kernel_init=_kernel_init(cfg, out))
        ab = cfg.attention_bias
        q = dense(H * D, "q_proj", ab)(x).reshape(B, T, H, D)
        k = dense(Hkv * D, "k_proj", ab)(x).reshape(B, T, Hkv, D)
        v = dense(Hkv * D, "v_proj", ab)(x).reshape(B, T, Hkv, D)
        if cfg.pos_embedding == "rope":
            q = apply_rotary_partial(q, cos, sin, cfg.rotary_dim, cfg.rope_style)
            k = apply_rotary_partial(k, cos, sin, cfg.rotary_dim, cfg.rope_style)
        if layer_cache is not None:
            layer_cache = update_kv_cache(layer_cache, k, v, cache_index)
            if cfg.pallas_decode_eligible(T):
                # bias carries the RAW [B, S] key mask on this path (the
                # model skipped the dense bias; see TransformerModel)
                from ..ops.pallas.decode_attention import decode_attention

                out = decode_attention(q[:, 0], layer_cache["k"],
                                       layer_cache["v"], cache_index,
                                       key_mask=bias,
                                       k_scale=layer_cache.get("k_scale"),
                                       v_scale=layer_cache.get("v_scale"),
                                       sm_scale=cfg.attention_scale)[:, None]
            elif cfg.prefill_flash_eligible(T):
                # from-empty prefill via the masked flash kernel; bias is
                # the RAW [B, S] key mask on this path (see TransformerModel)
                out = flash_prefill_from_empty(q, k, v, key_mask=bias,
                                               sm_scale=cfg.attention_scale)
            else:
                # head-major XLA math (no cache-sized transpose); bias here
                # is the model-level composite (cache causality + ALiBi)
                out = cached_attention_xla(q, layer_cache, bias=bias,
                                           scale=cfg.attention_scale)
        else:
            k = repeat_kv(k, H // Hkv)
            v = repeat_kv(v, H // Hkv)
            # encoder (causal=False) relies on bias for padding; flash path
            # only fires for pure-causal no-bias configs
            impl = cfg.attention_impl if bias is None else "xla"
            drng = self.make_rng("dropout") if (cfg.attn_dropout > 0 and
                                                not deterministic) else None
            out = dot_product_attention(q, k, v, bias=bias, causal=cfg.causal,
                                        attention_impl=impl,
                                        dropout_rng=drng,
                                        dropout_rate=cfg.attn_dropout,
                                        deterministic=deterministic,
                                        scale=cfg.attention_scale)
        out = out.reshape(B, T, H * D)
        ob = ab if cfg.attention_out_bias is None else cfg.attention_out_bias
        return dense(cfg.hidden_size, "o_proj", ob, out=True)(out), layer_cache


class GenericMLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = nn.Dense(cfg.intermediate_size, use_bias=cfg.mlp_bias, name="fc_in",
                     param_dtype=jnp.float32, dtype=cfg.compute_dtype,
                     kernel_init=_kernel_init(cfg, False))(x)
        h = _act(cfg.activation)(h)
        return nn.Dense(cfg.hidden_size, use_bias=cfg.mlp_bias, name="fc_out",
                        param_dtype=jnp.float32, dtype=cfg.compute_dtype,
                        kernel_init=_kernel_init(cfg, True))(h)


class TransformerBlock(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, cos, sin, bias, layer_cache=None, cache_index=None,
                 deterministic=True):
        cfg = self.config
        ln = lambda name: nn.LayerNorm(epsilon=cfg.norm_eps, name=name,
                                       param_dtype=jnp.float32)
        attn = GenericAttention(cfg, name="attn")
        mlp = GenericMLP(cfg, name="mlp")
        # BERT convention: dropout each sublayer output pre-residual
        drop = lambda y: nn.Dropout(cfg.hidden_dropout)(
            y, deterministic=deterministic or cfg.hidden_dropout == 0)
        if cfg.parallel_residual:
            # NeoX: both branches read the SAME input, residual-summed once;
            # GPT-J shares ONE LayerNorm between the branches
            h = ln("ln_attn")(x)
            a, layer_cache = attn(h, cos, sin, bias, layer_cache, cache_index,
                                  deterministic)
            m = mlp(h if cfg.shared_parallel_ln else ln("ln_mlp")(x))
            x = x + drop(a) + drop(m)
        elif cfg.pre_layernorm:
            a, layer_cache = attn(ln("ln_attn")(x), cos, sin, bias,
                                  layer_cache, cache_index, deterministic)
            x = x + drop(a)
            x = x + drop(mlp(ln("ln_mlp")(x)))
        else:
            # post-LN (BERT, OPT-350m)
            a, layer_cache = attn(x, cos, sin, bias, layer_cache, cache_index,
                                  deterministic)
            x = ln("ln_attn")(x + drop(a))
            x = ln("ln_mlp")(x + drop(mlp(x)))
        return x, layer_cache


class _ScanBlock(nn.Module):
    config: TransformerConfig
    deterministic: bool = True  # trace-static; an attribute, NOT a carry
    # leaf (a carried bool would be traced and break python short-circuits)

    @nn.compact
    def __call__(self, carry, xs):
        layer_cache, local_sel = xs
        x, cos, sin, bias, cache_index = carry
        layer_bias = bias
        if local_sel is not None:
            # bias is (global_bias, local_bias); select this layer's variant
            # (carry keeps the PAIR so the scan structure stays invariant)
            layer_bias = jnp.where(local_sel, bias[1], bias[0])
        x, layer_cache = TransformerBlock(self.config, name="block")(
            x, cos, sin, layer_bias, layer_cache, cache_index,
            self.deterministic)
        return (x, cos, sin, bias, cache_index), layer_cache


class TransformerModel(nn.Module):
    """Embeddings + block stack (+ final LN). ``cache`` switches to the
    KV-cached decode path exactly like ``LlamaModel``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None,
                 token_type_ids=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        B, T = input_ids.shape
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                     param_dtype=jnp.float32)(input_ids)
        if positions is None:
            start = 0 if cache_index is None else cache_index
            positions = jnp.broadcast_to(start + jnp.arange(T)[None, :], (B, T))
        if cfg.pos_embedding == "learned":
            wpe = nn.Embed(cfg.max_position_embeddings + cfg.pos_offset,
                           cfg.hidden_size, name="embed_positions",
                           param_dtype=jnp.float32)
            x = x + wpe(positions + cfg.pos_offset)
        if cfg.type_vocab_size:
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                             name="token_type_embeddings",
                             param_dtype=jnp.float32)(token_type_ids)
        if cfg.embedding_layernorm:
            x = nn.LayerNorm(epsilon=cfg.norm_eps, name="embed_ln",
                             param_dtype=jnp.float32)(x)

        cos = sin = jnp.zeros((B, T, 0), x.dtype)
        if cfg.pos_embedding == "rope":
            cos, sin = rotary_embedding(positions, cfg.rotary_dim, cfg.rope_theta,
                                        dtype=x.dtype)

        # additive attention bias: padding (+ ALiBi). The cached path folds
        # causality in via cache_attention_bias; the full path lets the
        # attention core apply causality.
        kv_len = T if cache is None else \
            jax.tree_util.tree_leaves(cache)[0].shape[-2]  # [.., Hkv, S, D]
        bias = None
        if cache is not None:
            if not cfg.causal:
                raise ValueError("KV cache requires a causal decoder config")
            key_mask = attention_mask  # [B, S] over the cache
            if cfg.pallas_decode_eligible(T) or cfg.prefill_flash_eligible(T):
                # kernel path: the attention consumes the RAW key mask (the
                # kernel folds triangular masking itself; None = no padding,
                # the kernel's own default)
                bias = key_mask
            else:
                bias = cache_attention_bias(T, kv_len, cache_index,
                                            key_mask=key_mask)
        elif attention_mask is not None:
            bias = key_mask_to_bias(attention_mask)
        if cfg.pos_embedding == "alibi":
            ab = alibi_bias(cfg.num_attention_heads, kv_len)
            bias = ab if bias is None else bias + ab

        # per-layer local-window masking (GPT-Neo): layer i's bias gets a
        # sliding-window restriction when its kind is "local". The window
        # bias is built ONCE and selected per layer by a scalar riding the
        # scan xs, so the compiled block stays uniform.
        local_sel = None
        kinds = None
        if cfg.attention_layers is not None:
            kinds = [cfg.attention_layers[i % len(cfg.attention_layers)]
                     for i in range(cfg.num_hidden_layers)]
            if not any(k == "local" for k in kinds):
                kinds = None  # all-global: no window machinery, flash stays on
        if kinds is not None:
            local_sel = jnp.asarray([k == "local" for k in kinds], jnp.bool_)
            if cache is not None:
                q_pos = (cache_index + jnp.arange(T))[:, None]
                k_pos = jnp.arange(kv_len)[None, :]
            else:
                q_pos = jnp.arange(T)[:, None]
                k_pos = jnp.arange(kv_len)[None, :]
            in_window = (q_pos - k_pos) < cfg.attention_window
            window_bias = jnp.where(in_window, 0.0, -1e9)[None, None]
            zero = jnp.zeros_like(window_bias)
            local_bias = window_bias if bias is None else bias + window_bias
            bias = zero if bias is None else bias
            # pack both variants; the block indexes by the layer selector
            bias = (bias, local_bias)

        if cfg.scan_layers:
            block_cls = _ScanBlock
            if cfg.remat and cache is None:
                block_cls = nn.remat(_ScanBlock, prevent_cse=False,
                                     policy=resolve_remat_policy(cfg.remat_policy))
            scan = nn.scan(block_cls, variable_axes={"params": 0},
                           split_rngs={"params": True, "dropout": True},
                           length=cfg.num_hidden_layers, metadata_params={})
            (x, *_), cache = scan(cfg, deterministic, name="layers")(
                (x, cos, sin, bias, cache_index), (cache, local_sel))
        else:
            block_cls = nn.remat(
                TransformerBlock, prevent_cse=False, static_argnums=(7,),
                policy=resolve_remat_policy(cfg.remat_policy)) \
                if (cfg.remat and cache is None) else TransformerBlock
            new_cache = [] if cache is not None else None
            for i in range(cfg.num_hidden_layers):
                layer_cache = None if cache is None else \
                    jax.tree_util.tree_map(lambda c: c[i], cache)
                lbias = bias if kinds is None else \
                    (bias[1] if kinds[i] == "local" else bias[0])
                x, layer_cache = block_cls(cfg, name=f"layers_{i}")(
                    x, cos, sin, lbias, layer_cache, cache_index,
                    deterministic)
                if new_cache is not None:
                    new_cache.append(layer_cache)
            if new_cache is not None:
                cache = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *new_cache)
        if cfg.final_layernorm:
            x = nn.LayerNorm(epsilon=cfg.norm_eps, name="final_ln",
                             param_dtype=jnp.float32)(x)
        return x if cache is None else (x, cache)


class TransformerLMHeadModel(nn.Module):
    """Causal LM head over ``TransformerModel`` (OPT/BLOOM/NeoX)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None, attention_mask=None,
                 deterministic=True, cache=None, cache_index=None):
        cfg = self.config
        hidden = TransformerModel(cfg, name="model")(
            input_ids, positions, attention_mask, None, deterministic, cache,
            cache_index)
        if cache is not None:
            hidden, cache = hidden
        logits, loss = lm_head_output(self, cfg, hidden, labels, cache,
                                      head_bias=cfg.lm_head_bias)
        if cache is not None:
            return logits, cache
        if labels is None:
            return logits
        if loss is not None:
            return loss
        return cross_entropy_loss(logits, shift_labels(labels))

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.config
        return init_kv_cache(batch, max_len, cfg.kv_heads, cfg.head_dim,
                             n_layers=cfg.num_hidden_layers, dtype=dtype)

    @staticmethod
    def partition_rules(config: TransformerConfig):
        from jax.sharding import PartitionSpec as P

        L = (None,) if config.scan_layers else ()
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"(q_proj|k_proj|v_proj)/kernel", P(*L, None, "model")),
            (r"(q_proj|k_proj|v_proj)/bias", P(*L, "model")),
            (r"o_proj/kernel", P(*L, "model", None)),
            (r"fc_in/kernel", P(*L, None, "model")),
            (r"fc_in/bias", P(*L, "model")),
            (r"fc_out/kernel", P(*L, "model", None)),
            (r"lm_head/kernel", P(None, "model")),
        ]


class TransformerForMaskedLM(nn.Module):
    """BERT-style encoder + MLM head (reference policy: ``HFBertLayerPolicy``,
    ``replace_policy.py:66``)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 positions=None, deterministic=True):
        cfg = self.config
        hidden = TransformerModel(cfg, name="model")(
            input_ids, positions, attention_mask, token_type_ids, deterministic)
        if cfg.mlm_head:
            h = nn.Dense(cfg.hidden_size, name="mlm_dense",
                         param_dtype=jnp.float32)(hidden)
            h = _act(cfg.activation)(h)
            h = nn.LayerNorm(epsilon=cfg.norm_eps, name="mlm_ln",
                             param_dtype=jnp.float32)(h)
        else:
            h = hidden
        embed = self.variables["params"]["model"]["embed_tokens"]["embedding"]
        logits = h @ embed.T.astype(h.dtype)
        logits = logits + self.param("mlm_bias", nn.initializers.zeros,
                                     (cfg.vocab_size,))
        return logits

    @staticmethod
    def partition_rules(config: TransformerConfig):
        return TransformerLMHeadModel.partition_rules(config)
