"""SambaY decoder-hybrid-decoder for training (Microsoft's
Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``; Ren et al.,
"Decoder-Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation", 2025): Mamba layers under a chunked selective scan, differential
attention over a sliding window, and a cross-decoder that computes no keys,
values or recurrence of its own -- it reads ONE layer's keys and values and ONE
layer's scan output.

Every layer is ``x <- x + Mixer(LN(x))`` then ``x <- x + MLP(LN(x))``; ``LN``
is LayerNorm with scale and bias, ``MLP(h) = W_down(up * SiLU(gate))`` with
``[gate ; up] = W_gate_up h``. The table is tied, and there is NO positional
encoding: the recurrence carries position.

*The layer pattern is data.* With ``S = self_decoder_layers`` (half the
layers as published) and ``mb_per_layer = 2``:

- the self-decoder, layers ``0 .. S-1``: ``S / 2`` periods of (Mamba, window
  attention), a scan over periods with the period's two layers unrolled in it;
- layer ``S``, Mamba, also hands on its scan output ``m`` (before the gate),
  and layer ``S + 1``, full attention, its keys and values: both unrolled,
  each its own parameters and remat;
- the cross-decoder, layers ``S+2 ..``: periods of (gated memory unit,
  cross-attention), a scan that closes over ``m``, ``k`` and ``v`` (broadcast
  inputs of the scan, not carries).

*Mamba*: ``[u ; z] = W_in h``; ``u <- SiLU(causal_conv(u))`` (depthwise,
``mamba_d_conv`` taps, bias); ``[dt ; B_t ; C_t] = W_x u``; ``delta =
softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``; the selective scan
(``ops/pallas/selective_scan.py``: delta, A, the state and ``y`` in float32);
out ``W_out(y * SiLU(z))``.

*Differential attention*: ``[q ; k ; v] = W_qkv h + b``; heads in (even, odd)
pairs; ``a1 = softmax(q1 k1^T / sqrt(d)) [v1 ; v2]`` and ``a2`` alike from the
odd heads, both over the pair's two values side by side (``2 d`` wide);
``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
0.8 - 0.6 exp(-0.3 i)`` at the layer's PUBLISHED index ``i``; ``a = (1 -
lambda_init) RMSNorm(a1 - lambda a2)`` over the ``2 d`` columns, which go back
to the pair's two heads; out ``W_o a + b_o``. Both streams are ONE call of the
attention core: head ``2 p + s`` is stream ``s`` of pair ``p`` as the
projection wrote it, its key head ``2 (p // g) + s`` and its value the key
pair's two heads side by side -- the flash kernels at their own value width
and, in the self-decoder, their window tile table.

*Gated memory unit*: ``W_out(m * SiLU(W_in h))``, position by position.
*Cross-attention*: ``q = W_q h + b`` only; differential attention over layer
``S + 1``'s keys and values with its own lambda vectors, norm and ``W_o``.

Training only: a cache would hold a convolution window and a state a Mamba
layer beside keys and values, and the cross-decoder's prefill would skip all
but the last position; the serving cache manager knows neither.
"""

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas.selective_scan import selective_scan
from .layers import (causal_conv, cross_entropy_loss, dot_product_attention,
                     head_scope, lm_head_output, model_dense,
                     resolve_remat_policy, shift_labels)
from .llama import LlamaConfig

#: the kinds of layer, in the order a model meets them
MAMBA, WINDOW, MEMORY, FULL, GMU, CROSS = (
    "mamba", "window", "memory", "full", "gmu", "cross")


@dataclasses.dataclass(frozen=True)
class SambaYConfig(LlamaConfig):
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    sliding_window: Optional[int] = 512
    layer_norm_eps: float = 1e-5
    #: every ``mb_per_layer``-th layer is a Mamba layer (a gated memory unit
    #: in the cross-decoder); the published family has 2
    mb_per_layer: int = 2
    #: layers of the self-decoder; None: half of them, as published
    self_decoder_layers: Optional[int] = None
    #: the PUBLISHED index of layer ``self_decoder_layers`` (``lambda_init``
    #: reads a layer's published index); None: the layer's own, a model cut
    #: in depth says where its cross-decoder sat
    cross_decoder_first_index: Optional[int] = None
    # -- the Mamba layers --------------------------------------------------
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    #: None: ``ceil(hidden / 16)``
    mamba_dt_rank: Optional[int] = None
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    #: "pallas": the kernels on a one-chip TPU; "xla": the same chunking as a
    #: ``lax.scan`` (a CPU, a multi-device mesh)
    ssm_impl: str = "pallas"
    ssm_chunk: int = 128
    #: the training call names ``ssm_chunk_decay_max`` beside its loss: the
    #: largest, over layers, channels and chunks, of ``sum_{t in chunk}
    #: delta_t max_n |A|`` -- the exponent a chunked scan has to represent
    report_ssm_decay: bool = False

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    @property
    def self_layers(self) -> int:
        return self.num_hidden_layers // 2 \
            if self.self_decoder_layers is None else self.self_decoder_layers

    @staticmethod
    def phi4_mini_flash(**over):
        """``microsoft/Phi-4-mini-flash-reasoning`` as published (the
        defaults)."""
        return SambaYConfig(**over)

    @staticmethod
    def tiny(**over):
        return SambaYConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=6, self_decoder_layers=2,
            num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
            max_position_embeddings=64, mamba_d_state=4, mamba_dt_rank=4,
            ssm_impl="xla", ssm_chunk=16, remat=False), **over})


def layer_kinds(cfg):
    """The kind of every layer, from ``num_hidden_layers``,
    ``self_decoder_layers`` and ``mb_per_layer``."""
    S, mb = cfg.self_layers, cfg.mb_per_layer
    kinds = []
    for i in range(cfg.num_hidden_layers):
        state_space = i % mb == 0
        if i < S:
            kinds.append(MAMBA if state_space else WINDOW)
        elif i < S + mb:
            kinds.append(MEMORY if state_space else FULL)
        else:
            kinds.append(GMU if state_space else CROSS)
    return tuple(kinds)


def published_index(cfg, i):
    """Layer ``i``'s index in the published model."""
    S = cfg.self_layers
    first = S if cfg.cross_decoder_first_index is None \
        else cfg.cross_decoder_first_index
    return i if i < S else first + i - S


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _check(cfg):
    S, L, mb = cfg.self_layers, cfg.num_hidden_layers, cfg.mb_per_layer
    if mb != 2:
        raise ValueError("mb_per_layer is 2: a period is one state-space "
                         "layer and one attention layer")
    if S % mb or (L - S) % mb or L - S < mb:
        raise ValueError(f"{L} layers with a self-decoder of {S} are no "
                         f"whole periods of {mb} and a memory + key/value "
                         f"pair")
    if cfg.num_attention_heads % cfg.num_key_value_heads or \
            cfg.num_key_value_heads % 2:
        raise ValueError("differential attention pairs the heads: an even "
                         "number of key heads that divides the query heads")


def _uniform(bound):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _dt_bias(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform draw in [1e-3, 1e-1], as
    published for Mamba: the step sizes the recurrence starts with."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log(key, shape, dtype=jnp.float32):
    """``log(1 .. N)`` a channel: a random ``A_log`` is not the model, and
    is not stable."""
    return jnp.broadcast_to(jnp.log(jnp.arange(
        1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dtype)


def _step_size(dt, kernel, bias):
    """``delta = softplus(W_dt dt + b_dt)``, float32."""
    return jax.nn.softplus(jnp.einsum(
        "btr,rc->btc", dt, kernel.astype(dt.dtype),
        preferred_element_type=jnp.float32) + bias.astype(jnp.float32))


def _decay_rate(a_log):
    """``A = -exp(A_log)``, float32."""
    return -jnp.exp(a_log.astype(jnp.float32))


def _skip_weight(d):
    """``D`` of the scan's own skip connection ``y + D u``."""
    return d.astype(jnp.float32)


def chunk_decay_max(delta, a, chunk):
    """``max over channels and chunks of sum_{t in chunk} delta_t max_n
    |A|``, the largest exponent one chunk's decay holds."""
    B, T, C = delta.shape
    pad = (-T) % chunk
    sums = jnp.sum(jnp.pad(delta, ((0, 0), (0, pad), (0, 0))).reshape(
        B, -1, chunk, C), axis=2)
    return jnp.max(sums * jnp.max(jnp.abs(a), axis=-1))


class MambaMixer(nn.Module):
    """``(out [B, T, H], y [B, T, d_inner] -- the scan's output before the
    gate, what the memory layer hands on --, the chunk decay or 0)``."""

    config: SambaYConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        C, N, R, K = (cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank,
                      cfg.mamba_d_conv)
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, use_bias=cfg.mamba_proj_bias, row_parallel=row)
        f32 = jnp.float32
        with jax.named_scope("ds.ssm_mix"):
            uz = dense(2 * C, "in_proj")(h)
            u, z = uz[..., :C], uz[..., C:]
            # depthwise taps and bias seeded as a Conv1d's: uniform over
            # 1 / sqrt(taps)
            w = self.param("conv_weight", _uniform(K ** -0.5), (K, C), f32)
            b = self.param("conv_bias", _uniform(K ** -0.5), (C,), f32) \
                if cfg.mamba_conv_bias else None
            u = nn.silu(causal_conv(u, w.astype(u.dtype),
                                    None if b is None else b.astype(u.dtype)))
            dbc = dense(R + 2 * N, "x_proj")(u)
            delta = _step_size(
                dbc[..., :R],
                self.param("dt_kernel", _uniform(R ** -0.5), (R, C), f32),
                self.param("dt_bias", _dt_bias, (C,), f32))
            a = _decay_rate(self.param("A_log", _a_log, (C, N), f32))
            d = _skip_weight(self.param("D", nn.initializers.ones, (C,),
                                        f32))
            decay = chunk_decay_max(delta, a, cfg.ssm_chunk) \
                if cfg.report_ssm_decay else jnp.zeros((), f32)
        with jax.named_scope("ds.ssm_scan"):
            y = selective_scan(u, delta, a, dbc[..., R:R + N],
                               dbc[..., R + N:], d,
                               impl=cfg.ssm_impl, chunk=cfg.ssm_chunk)
        with jax.named_scope("ds.ssm_mix"):
            y = y.astype(h.dtype)
            gated = _gated(y, z)
            out = dense(cfg.hidden_size, "out_proj", row=True)(gated)
        return out, _memory(y, gated), decay


def _gated(y, z):
    """``y * SiLU(z)``."""
    return y * nn.silu(z)


def _memory(y, gated):
    """What a Mamba layer hands on: the scan's output BEFORE the gate."""
    return y


def _pair_values(cfg, k, v):
    """``k [B, T, Hkv, d]`` and ``v [B, T, Hkv, d]`` as the attention core
    takes them, one head a QUERY head: head ``2 p + s`` reads key head ``2 (p
    // g) + s`` and the key pair's two value heads side by side."""
    B, T, Hkv, d = k.shape
    rep = cfg.num_attention_heads // Hkv
    k = jnp.broadcast_to(k.reshape(B, T, Hkv // 2, 1, 2, d),
                         (B, T, Hkv // 2, rep, 2, d))
    v = jnp.broadcast_to(v.reshape(B, T, Hkv // 2, 1, 2 * d),
                         (B, T, Hkv // 2, 2 * rep, 2 * d))
    H = cfg.num_attention_heads
    return k.reshape(B, T, H, d), v.reshape(B, T, H, 2 * d)


def _positional(cfg, q, k):
    """No positional encoding: queries and keys go on as projected."""
    return q, k


def _cross_kv(h, kv):
    """What cross-attention reads: the keys and values layer ``S + 1``
    handed on, nothing of its own input ``h``."""
    return kv


def _lambda(lq1, lk1, lq2, lk2, init):
    f32 = jnp.float32
    return jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32))) \
        - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) + init


def _pair_norm(a, scale, eps):
    """RMSNorm over the pair's ``2 d`` columns, float32."""
    return a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rescale(a, init):
    return (1.0 - init) * a


class DiffAttention(nn.Module):
    """Differential attention: over the layer's own keys and values (the
    window of the self-decoder, full in layer ``S + 1``, which also hands
    them on) or, with ``kv`` given, over those another layer handed on."""

    config: SambaYConfig
    window: Optional[int] = None

    @nn.compact
    def __call__(self, h, lam_init, kv=None):
        cfg = self.config
        B, T, _ = h.shape
        Hq, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, use_bias=True, row_parallel=row)
        with jax.named_scope("ds.attn_proj"):
            if kv is None:
                qkv = dense((Hq + 2 * Hkv) * d, "Wqkv")(h)
                q = qkv[..., :Hq * d]
                k = qkv[..., Hq * d:(Hq + Hkv) * d].reshape(B, T, Hkv, d)
                v = qkv[..., (Hq + Hkv) * d:].reshape(B, T, Hkv, d)
                q, k = _positional(cfg, q.reshape(B, T, Hq, d), k)
            else:
                k, v = _cross_kv(h, kv)
                q, _ = _positional(cfg, dense(Hq * d, "Wq")(h).reshape(
                    B, T, Hq, d), None)
            heads = _pair_values(cfg, k, v)
        out = dot_product_attention(
            q, *heads, causal=True, attention_impl=cfg.attention_impl,
            flash_block_q=cfg.flash_block_q, flash_block_k=cfg.flash_block_k,
            window=self.window)
        with jax.named_scope("ds.da_mix"):
            vec = lambda name: self.param(
                name, nn.initializers.normal(0.1), (d,), jnp.float32)
            lam = _lambda(vec("lambda_q1"), vec("lambda_k1"),
                          vec("lambda_q2"), vec("lambda_k2"), lam_init)
            scale = self.param("subln_scale", nn.initializers.ones, (2 * d,),
                               jnp.float32)
            out = out.astype(jnp.float32)
            a = out[:, :, 0::2] - lam * out[:, :, 1::2]   # [B, T, pairs, 2d]
            a = _rescale(_pair_norm(a, scale, cfg.layer_norm_eps), lam_init)
            a = a.astype(h.dtype).reshape(B, T, Hq * d)
        with jax.named_scope("ds.attn_proj"):
            return dense(cfg.hidden_size, "out_proj", row=True)(a), (k, v)


class GatedMemoryUnit(nn.Module):
    config: SambaYConfig

    @nn.compact
    def __call__(self, h, memory):
        cfg = self.config
        with jax.named_scope("ds.gmu"):
            gate = model_dense(cfg, cfg.d_inner, "in_proj")(h)
            return model_dense(cfg, cfg.hidden_size, "out_proj",
                               row_parallel=True)(
                _gmu_gate(memory.astype(h.dtype), gate))


def _gmu_gate(memory, gate):
    return memory * nn.silu(gate)


class SambaYMLP(nn.Module):
    config: SambaYConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        I = cfg.intermediate_size
        with jax.named_scope("ds.mlp"):
            gate_up = model_dense(cfg, 2 * I, "gate_up_proj")(h)
            return model_dense(cfg, cfg.hidden_size, "down_proj",
                               row_parallel=True)(
                gate_up[..., I:] * nn.silu(gate_up[..., :I]))


class SambaYBlock(nn.Module):
    """One layer of ``kind``: ``(x, what it hands on, chunk decay)``: the
    scan's output from a Mamba layer, ``(k, v)`` from an attention layer."""

    config: SambaYConfig
    kind: str

    @nn.compact
    def __call__(self, x, lam_init=None, memory=None, kv=None):
        cfg, kind = self.config, self.kind
        norm = lambda name: nn.LayerNorm(epsilon=cfg.layer_norm_eps,
                                         dtype=x.dtype, name=name)
        # ds.norm / ds.residual as in models/llama.py LlamaBlock
        with jax.named_scope("ds.norm"):
            h = norm("input_layernorm")(x)
        handed, decay = None, jnp.zeros((), jnp.float32)
        if kind in (MAMBA, MEMORY):
            out, handed, decay = MambaMixer(cfg, name="mixer")(h)
        elif kind == GMU:
            out = GatedMemoryUnit(cfg, name="mixer")(h, memory)
        else:
            out, handed = DiffAttention(
                cfg, cfg.sliding_window if kind == WINDOW else None,
                name="mixer")(h, lam_init, kv if kind == CROSS else None)
        with jax.named_scope("ds.residual"):
            x = x + out
        with jax.named_scope("ds.norm"):
            h = norm("post_attention_layernorm")(x)
        out = SambaYMLP(cfg, name="mlp")(h)
        with jax.named_scope("ds.residual"):
            x = x + out
        return x, handed, decay


class _Period(nn.Module):
    """One period of a decoder, its layers unrolled: a scan's body.
    ``lam_init`` is the period's attention layer's; ``memory`` and ``kv``
    are the scan's broadcast inputs (the cross-decoder's)."""

    config: SambaYConfig
    kinds: tuple

    @nn.compact
    def __call__(self, x, lam_init, memory=None, kv=None):
        decay = jnp.zeros((), jnp.float32)
        for kind in self.kinds:
            x, _, d = SambaYBlock(self.config, kind, name=kind)(
                x, lam_init, memory, kv)
            decay = jnp.maximum(decay, d)
        return x, decay


class SambaYModel(nn.Module):
    config: SambaYConfig

    def _periods(self, name, kinds, first, count, remat, x, memory=None,
                 kv=None):
        """``count`` periods of ``kinds`` from layer ``first`` on: a scan
        (``<name>/period``) or, unrolled, ``<name>_<p>``."""
        cfg = self.config
        mb = len(kinds)
        attn = kinds.index(WINDOW if WINDOW in kinds else CROSS)
        inits = jnp.asarray([lambda_init(published_index(
            cfg, first + p * mb + attn)) for p in range(count)], jnp.float32)
        decay = jnp.zeros((), jnp.float32)
        if not count:
            return x, decay
        if cfg.scan_layers:
            scan = nn.scan(
                remat(_Period), variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(0, nn.broadcast, nn.broadcast), length=count,
                metadata_params={})
            x, decays = scan(cfg, kinds, name=name)(x, inits, memory, kv)
            return x, jnp.max(decays)
        for p in range(count):
            x, d = remat(_Period)(cfg, kinds, name=f"{name}_{p}")(
                x, inits[p], memory, kv)
            decay = jnp.maximum(decay, d)
        return x, decay

    @nn.compact
    def __call__(self, input_ids):
        """``(final-normed hidden [B, T, H], chunk decay)``."""
        cfg = self.config
        _check(cfg)
        kinds, S, mb = layer_kinds(cfg), cfg.self_layers, cfg.mb_per_layer
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        policy = resolve_remat_policy(cfg.remat_policy)
        remat = lambda cls: nn.remat(cls, prevent_cse=False, policy=policy) \
            if cfg.remat else cls
        # ds.layer_stack: what the loops over the layers cost beyond what
        # the layers' own scopes name (models/llama.py LlamaModel)
        with jax.named_scope("ds.layer_stack"):
            x, decay = self._periods("self_decoder", kinds[:mb], 0, S // mb,
                                     remat, x)
            x, memory, d = remat(SambaYBlock)(cfg, kinds[S],
                                              name="memory_layer")(x)
            decay = jnp.maximum(decay, d)
            x, kv, _ = remat(SambaYBlock)(cfg, kinds[S + 1],
                                          name="kv_layer")(
                x, lambda_init(published_index(cfg, S + 1)))
            x, _ = self._periods(
                "cross_decoder", kinds[S + mb:S + 2 * mb], S + mb,
                (cfg.num_hidden_layers - S - mb) // mb, remat, x, memory, kv)
        with jax.named_scope(head_scope(None)):
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=x.dtype,
                             name="final_layernorm")(x)
        return x, decay


class SambaYForCausalLM(nn.Module):
    """``LlamaForCausalLM``'s training interface: logits without labels,
    the token-mean cross entropy with them (beside it the named scalar where
    the configuration asks). The head is the embedding table."""

    config: SambaYConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a decoder-hybrid-decoder is built for training only: no "
                "cache holds a Mamba layer's convolution window and state, "
                "nor the one layer's keys, values and scan output the "
                "cross-decoder reads")
        if attention_mask is not None:
            raise NotImplementedError(
                "the recurrence has no padding mask: train on packed "
                "sequences")
        hidden, decay = SambaYModel(cfg, name="model")(input_ids)
        with jax.named_scope(head_scope(None)):
            logits, loss = lm_head_output(self, cfg, hidden, labels, None)
            if labels is None:
                return logits
            if loss is None:
                loss = cross_entropy_loss(logits, shift_labels(labels))
        if not cfg.report_ssm_decay:
            return loss
        return loss, {"ssm_chunk_decay_max": jax.lax.stop_gradient(decay)}

    @staticmethod
    def partition_rules(config: "SambaYConfig"):
        """The tied table divided by rows; every layer whole on its chip."""
        return [(r"embed_tokens/embedding", P("model", None))]
