"""Shared transformer building blocks (flax.linen), TPU-first.

These replace the reference's fused CUDA transformer kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` fwd/bwd: fused QKV GEMM,
softmax, LayerNorm, GELU, dropout) with modules whose XLA lowering fuses the
same chains onto MXU/VPU; the attention core can switch to the Pallas flash
kernel (``ops/pallas/flash_attention.py``) via ``attention_impl="flash"``.

Conventions: weights live in fp32 (master); the engine casts to the compute
dtype (bf16) before apply. Shapes are static; batch/heads stay multiples of
the lane layout so XLA tiles cleanly onto the 128x128 MXU.
"""

import contextlib
import functools
import math
import threading
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..parallel.topology import BATCH_AXES, get_mesh, tokens_replicated


def resolve_remat_policy(name: str, offered=()):
    """Activation-checkpoint policy by name (shared by all models so the
    accepted strings cannot drift between model files).

    Under EVERY policy the flash kernel's output and log-sum-exp are kept
    (``ds_flash_out``, ``ds_flash_lse``), as the layer's input always is:
    64 MB a layer at 8k that only the forward kernel can produce, so the
    replay never runs that kernel; a learned selection's bit-packed mask
    (``ds_sa_mask``: the replay must see the set the forward chose) and its
    loss's row statistics (``ds_sa_kl_rows``) the same. Everything else gets
    the named policy's answer -- but for what the model file OFFERS:
    ``offered`` is its ordered ``[(checkpoint_name, bytes over every layer
    application)]``, costliest replay first, and ``keep_for_room`` keeps as
    many as the budget the ENGINE states for the trace has room for
    (``remat_room``: the device's free memory before the step
    is built; the engine checks the compiled step and takes the choice back
    where it was wrong); budget and bytes are ONE device's whatever the
    mesh (``device_part``). Nothing is set by hand. With no budget -- a bare
    ``model.apply``, a CPU -- nothing offered is kept, the names are the
    identity and the policy is the plain one.

    ``offload_dots_no_batch`` is the CPU-activation-checkpointing analog:
    its ``dots_no_batch`` residuals go to PINNED HOST memory, not HBM."""
    from ..ops.pallas import (FLASH_LSE, FLASH_OUT,  # ops imports this module
                              SA_KL_ROWS, SA_MASK)

    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_saveable,
        "dots_no_batch": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "offload_dots_no_batch":
            jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host"),
    }
    if name not in policies:
        raise ValueError(f"unknown remat_policy {name!r}; one of {sorted(policies)}")
    base = policies[name]
    flash_named = jax.checkpoint_policies.save_only_these_names(
        FLASH_OUT, FLASH_LSE, SA_MASK, SA_KL_ROWS, *keep_for_room(offered))

    # written out, not save_from_both_policies: that helper refuses the
    # Offloadable / Recompute answers of the offload policy
    def policy(prim, *args, **params):
        if flash_named(prim, *args, **params):
            return True
        return base(prim, *args, **params)

    return policy


class QuantDense(nn.Module):
    """``nn.Dense`` whose kernel may be STORED quantized and whose TP
    reduction may ride the quantized collective — the serving path's
    projection layer (``models/llama.py`` / ``gpt2.py`` build every
    attention/MLP projection through :func:`model_dense`).

    With ``quantize=None`` and ``tp_reduce=None`` this is parameter- and
    math-identical to ``nn.Dense`` (same ``kernel``/``bias`` names, inits
    and shapes), so fp checkpoints and partition rules are untouched.

    ``quantize="int8"|"int4"``: the ``kernel`` param holds absmax codes
    (int8 ``[K, N]``, or uint8 ``[K//2, N]`` packed two int4 per byte
    along K) and a sibling ``wscale`` param holds fp32 grouped scales
    ``[G, N]`` (``ops/pallas/quant_matmul.quantize_linear_weight``
    produces both; ``inference.engine.init_inference`` rewrites fp param
    trees into this layout). Dequantization happens in the CONSUMER:
    the XLA reference path multiplies codes by scales inline (fused into
    the matmul operand read — CPU tier-1 stays token-exact-testable
    against it), and ``dequant_impl="pallas"`` on TPU streams the codes
    through the grouped-dequant matmul kernel (int8/int4 in HBM,
    dequantized per K-block in VMEM — the KV cache's int8 pattern
    applied to the projection operands).

    ``tp_reduce="quantized"``: a ROW-parallel projection (o_proj /
    down_proj — input features sharded over ``model``) runs its matmul
    inside ``shard_map`` and reduces partial sums with
    :func:`~deepspeed_tpu.comm.quantized.quantized_psum` (int8 wire
    payloads) instead of the partitioner's full-width psum. Engages only
    when the active mesh's ``model`` axis is > 1; the bias (replicated)
    is added AFTER the reduction.
    """

    features: int
    use_bias: bool = True
    quantize: Optional[str] = None      # None | "int8" | "int4"
    group_size: int = 0                 # scale group along K (0 = default)
    dequant_impl: str = "xla"           # "xla" | "pallas"
    #: input features sharded over `model` (o_proj/down_proj): scale
    #: groups align to the TP shard width, and tp_reduce may engage
    row_parallel: bool = False
    #: the TP width the weights were QUANTIZED for (config-carried, not
    #: read from the mutable global mesh: two engines of different mp in
    #: one process must each validate their own scale shapes)
    row_shards: int = 1
    tp_reduce: Optional[str] = None     # None | "quantized"
    psum_block: int = 256               # quantized_psum wire block
    param_dtype: Any = jnp.float32

    def _model_axis(self):
        from ..parallel.topology import get_mesh

        mesh = get_mesh()
        mp = 1 if mesh is None else dict(
            zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        return mesh, mp

    def _matmul(self, x, kernel, wscale):
        """Local (per-shard, under tp_reduce) quantized-or-plain matmul."""
        if self.quantize is None:
            return x @ kernel.astype(x.dtype)
        if self.dequant_impl == "pallas" and \
                jax.default_backend() == "tpu":
            from ..ops.pallas.quant_matmul import quant_matmul

            lead = x.shape[:-1]
            y = quant_matmul(x.reshape(-1, x.shape[-1]), kernel, wscale,
                             self.quantize)
            return y.reshape(lead + (y.shape[-1],))
        from ..ops.pallas.quant_matmul import dequantize_linear_weight

        return x @ dequantize_linear_weight(kernel, wscale, self.quantize,
                                            x.dtype)

    @nn.compact
    def __call__(self, x):
        feats, mode = self.features, self.quantize
        K = x.shape[-1]
        if mode is None:
            kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                (K, feats), self.param_dtype)
            wscale = None
        else:
            from ..ops.pallas.quant_matmul import effective_group_size

            # init produces zero codes / unit scales of the right SHAPES
            # (a from-scratch init of a quantized model is only ever used
            # for shape inference; real codes come from init_inference's
            # quantization of fp master weights). The group derivation is
            # SHARED with inference/quant.py — row-parallel kernels align
            # groups to `row_shards`, the TP width the engine quantized
            # for — so the wscale shape flax validates always matches
            # what the engine wrote.
            rows = K // 2 if mode == "int4" else K
            kdtype = jnp.uint8 if mode == "int4" else jnp.int8
            shards = self.row_shards if self.row_parallel else 1
            g = effective_group_size(K, mode, self.group_size, shards)
            kernel = self.param(
                "kernel", lambda rng, shape, dtype: jnp.zeros(shape, dtype),
                (rows, feats), kdtype)
            wscale = self.param("wscale", nn.initializers.ones,
                                (K // g, feats), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (feats,),
                          self.param_dtype) if self.use_bias else None

        mesh = None
        if self.tp_reduce is not None:
            mesh, mp = self._model_axis()
            if mp <= 1:
                mesh = None  # world size 1: plain path, zero overhead
        if mesh is None:
            y = self._matmul(x, kernel, wscale)
        else:
            from jax.sharding import PartitionSpec as P

            from ..comm.quantized import quantized_psum

            # row-parallel seam: x's features and the kernel's K dim (the
            # packed dim for int4) split over `model`; each shard matmuls
            # its slice and the partial sums reduce over int8 payloads.
            # Scales ride [G, N]: sharded along G when the groups split
            # evenly (engine-aligned int4 grouping), else replicated —
            # either way the dequant uses each shard's own K-groups.
            xspec = P(*((None,) * (x.ndim - 1)), "model")
            kspec = P("model", None)
            block = self.psum_block

            if wscale is None:
                def body(xl, kl):
                    return quantized_psum(self._matmul(xl, kl, None),
                                          "model", block=block)

                y = jax.shard_map(body, mesh=mesh, in_specs=(xspec, kspec),
                                  out_specs=P(*((None,) * x.ndim)),
                                  check_vma=False)(x, kernel)
            else:
                sspec = P("model", None) if wscale.shape[0] % mp == 0 \
                    else P(None, None)

                def body(xl, kl, sl):
                    return quantized_psum(self._matmul(xl, kl, sl),
                                          "model", block=block)

                y = jax.shard_map(body, mesh=mesh,
                                  in_specs=(xspec, kspec, sspec),
                                  out_specs=P(*((None,) * x.ndim)),
                                  check_vma=False)(x, kernel, wscale)
        if bias is not None:
            y = y + bias
        return y


def model_dense(cfg, feats: int, name: str, use_bias: bool = False,
                row_parallel: bool = False):
    """The ONE projection-layer factory the model families share.

    Returns a plain ``nn.Dense`` unless the model config asks for
    quantized weights (``quantize_weights``) or — on a ROW-parallel
    projection — quantized TP collectives (``quantized_collectives``),
    in which case a :class:`QuantDense` carries the corresponding mode.
    Keeping the fp path on literal ``nn.Dense`` guarantees existing
    param trees, inits and checkpoints are byte-identical.
    """
    quant = getattr(cfg, "quantize_weights", None)
    qcoll = bool(getattr(cfg, "quantized_collectives", False)) and \
        row_parallel
    if quant is None and not qcoll:
        return nn.Dense(feats, use_bias=use_bias, name=name,
                        param_dtype=jnp.float32)
    return QuantDense(
        feats, use_bias=use_bias, name=name, quantize=quant,
        group_size=getattr(cfg, "quantize_group_size", 0),
        dequant_impl="pallas"
        if getattr(cfg, "decode_attention_impl", "xla") == "pallas"
        else "xla",
        row_parallel=row_parallel,
        row_shards=getattr(cfg, "quantize_row_shards", 1),
        tp_reduce="quantized" if qcoll else None,
        psum_block=getattr(cfg, "quantized_psum_block", 256))


class RMSNorm(nn.Module):
    """RMS LayerNorm (Llama-style)."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * scale).astype(dtype)


def make_causal_mask(q_len: int, kv_len: int, dtype=jnp.float32, offset: int = 0):
    """Lower-triangular additive mask (0 keep / -inf drop)."""
    i = jnp.arange(q_len)[:, None] + offset
    j = jnp.arange(kv_len)[None, :]
    return jnp.where(i >= j, 0.0, -1e9).astype(dtype)


def rotary_embedding(positions: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                     dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RoPE cos/sin tables for given positions [B, T] → [B, T, head_dim/2]."""
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    freqs = positions[..., None].astype(jnp.float32) * inv_freq[None, None, :]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, H, D]; cos/sin: [B, T, D/2]. Counterpart of the reference's
    ``apply_rotary_pos_emb.cu`` kernel — here a fused elementwise XLA chain."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA: expand KV heads [B, T, Hkv, D] → [B, T, Hkv*n_rep, D]."""
    if n_rep == 1:
        return x
    b, t, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, t, h, n_rep, d)).reshape(b, t, h * n_rep, d)


@jax.named_scope("ds.attention")
def dot_product_attention(q, k, v, bias=None, causal: bool = False,
                          attention_impl: str = "xla", dropout_rng=None,
                          dropout_rate: float = 0.0, deterministic: bool = True,
                          scale: Optional[float] = None,
                          flash_block_q: int = 512, flash_block_k: int = 512,
                          window: Optional[int] = None):
    """[B, T, H, D] attention core.

    ``attention_impl='flash'`` routes to the Pallas flash-attention kernel
    (TPU); 'xla' is the einsum softmax reference (XLA fuses it well for
    moderate T). This mirrors the reference's split between fused CUDA
    softmax kernels and stock torch attention.

    ``causal`` applies bottom-right-aligned causality and ``window`` a
    sliding window (or a ``BlockDiffusion``); ``bias`` any ADDITIVE mask (e.g.
    padding). The flash kernels take causality, a window, a key-padding
    mask (forward only) and a ``[B, Tq, Tk]`` selection that is data
    (``flash_attention(mask=...)``, which ``models/indexed_attention.py``
    calls directly: it needs the log-sum-exp too) — but no additive bias
    and no dropout: those cases fall back to the XLA path here so semantics
    never silently change.
    """
    use_dropout = dropout_rate > 0.0 and not deterministic
    if attention_impl == "flash" and bias is None and not use_dropout:
        from ..ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, sm_scale=scale,
                               block_q=flash_block_q, block_k=flash_block_k,
                               window=window)
    if window is not None and attention_impl in ("ulysses", "ring"):
        raise NotImplementedError(
            f"sliding-window attention is not composed with "
            f"attention_impl={attention_impl!r} yet; use 'flash' or 'xla'")
    if attention_impl == "ulysses_flash":
        # DeepSpeed-Ulysses execution shape for LONG T: explicit all_to_all
        # head<->token swap in shard_map, flash kernel per shard
        if scale is not None or use_dropout or bias is not None:
            raise NotImplementedError(
                "attention_impl='ulysses_flash' supports causal masking only "
                "(no bias/dropout/custom scale); drop padding via the loss "
                "mask")
        from ..sequence.ulysses import ulysses_flash_attention

        return ulysses_flash_attention(q, k, v, causal=causal,
                                       block_q=flash_block_q,
                                       block_k=flash_block_k,
                                       window=window)
    if attention_impl == "ulysses":
        if scale is not None:
            raise NotImplementedError(
                "attention_impl='ulysses' does not support a custom "
                "attention scale")
        if use_dropout:
            # falling back to plain attention would quietly materialize the
            # O(T^2) logits sequence parallelism exists to avoid
            raise NotImplementedError(
                "attention dropout is not supported with attention_impl="
                "'ulysses'; set attn dropout to 0")
        from ..sequence.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=causal, bias=bias)
    if attention_impl == "ring":
        if scale is not None:
            raise NotImplementedError(
                "attention_impl='ring' does not support a custom attention "
                "scale")
        if use_dropout or bias is not None:
            raise NotImplementedError(
                "ring attention supports causal masking only (no additive "
                "bias / attention dropout); drop padding via the loss mask")
        from ..sequence.ring import ring_attention

        return ring_attention(q, k, v, causal=causal)

    depth = q.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(depth)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    from ..ops.pallas.flash_attention import BlockDiffusion, window_mask

    if causal and not isinstance(window, BlockDiffusion):
        logits = logits + make_causal_mask(q.shape[1], k.shape[1], dtype=jnp.float32,
                                           offset=k.shape[1] - q.shape[1])[None, None]
    if window is not None:  # a width, or a rule that stands for causality too
        logits = jnp.where(
            window_mask(window, q.shape[1], k.shape[1])[None, None],
            logits, -1e9)
    if bias is not None:
        logits = logits + bias
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if use_dropout:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  n_layers: Optional[int] = None, dtype=jnp.bfloat16):
    """Allocate an empty KV cache.

    Counterpart of the reference decode kernels' persistent KV workspace
    (``csrc/transformer/inference/csrc/pt_binding.cpp`` ``softmax_context``
    appends into a preallocated cache). Layout ``[L?, B, Hkv, S, D]`` —
    head-major so the Pallas decode kernel's ``(1, 1, block_k, D)`` blocks
    tile cleanly (Mosaic tiles the last two dims; a seq-major layout would
    either pad 1-sized minor dims ~16-32x in VMEM or force an O(S)
    transpose of the whole cache every decode step). Appends transpose
    only the NEW tokens (O(T), not O(S)); ``read_kv_cache`` returns the
    seq-major view the XLA attention math uses. The leading layer axis is
    present when the model scans its blocks, so the cache threads through
    ``nn.scan`` as per-layer xs/ys.
    """
    if dtype == jnp.int8:
        # int8 cache: values quantized per (position, kv head) with an
        # absmax scale — halves the HBM traffic of every decode step (the
        # cache read IS the decode bottleneck). Scales live alongside in
        # fp32; the Pallas decode kernel dequantizes per block in VMEM, the
        # XLA fallback dequantizes on read. Counterpart of the reference's
        # int8 inference kernels (SURVEY row 46 "int8").
        shape = (batch, num_kv_heads, max_len, head_dim)
        sshape = (batch, num_kv_heads, max_len)
        if n_layers is not None:
            shape = (n_layers,) + shape
            sshape = (n_layers,) + sshape
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    shape = (batch, num_kv_heads, max_len, head_dim)
    if n_layers is not None:
        shape = (n_layers,) + shape
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x):
    """[..., D] -> (int8 values, fp32 absmax-per-row scales over the last
    axis); used on head-major [B, Hkv, T, D] cache slices."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0
    q = jnp.round(x.astype(jnp.float32)
                  / jnp.maximum(scale, 1e-8)[..., None]).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of ``_quantize_kv`` (broadcast the per-row scale over D)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def read_kv_cache(layer_cache, dtype):
    """Materialize seq-major ``(k, v)`` ``[B, S, Hkv, D]`` in ``dtype`` from
    a (head-major) cache dict (an int8 cache dequantizes here; reading
    ``layer_cache["k"]`` directly would hand raw int8 codes — in cache
    layout — to the attention math). NOTE: this materializes a transposed
    view of the WHOLE cache — hot decode paths should use
    ``cached_attention_xla`` (head-major math, no transpose) or the Pallas
    decode kernel instead."""
    if "k_scale" in layer_cache:
        k = dequantize_kv(layer_cache["k"], layer_cache["k_scale"], dtype)
        v = dequantize_kv(layer_cache["v"], layer_cache["v_scale"], dtype)
    else:
        k = layer_cache["k"].astype(dtype)
        v = layer_cache["v"].astype(dtype)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)


@jax.named_scope("ds.attention")
def flash_prefill_from_empty(q, k, v, key_mask=None, sm_scale=None,
                             block_q=512, block_k=512, window=None):
    """From-empty cached prefill via the masked flash kernel — the ONE
    dispatch shared by every model family (see
    ``LlamaConfig.prefill_flash_from_empty`` for the contract). ``q``:
    ``[B, T, H, D]``; ``k``/``v`` are the FRESH (un-repeated, GQA ok)
    projections ``[B, T, Hkv, D]``; ``key_mask`` is the full ``[B, S]``
    cache mask or None (sliced to the prompt span here)."""
    from ..ops.pallas.flash_attention import flash_attention

    B, T = q.shape[0], q.shape[1]
    local_mask = jnp.ones((B, T), jnp.int32) if key_mask is None \
        else key_mask[:, :T]
    return flash_attention(q, k, v, causal=True, key_mask=local_mask,
                           sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, window=window)


@jax.named_scope("ds.attention")
def cached_attention_xla(q, layer_cache, cache_index=None, key_mask=None,
                         window=None, scale=None, bias=None):
    """XLA attention over the head-major KV cache with NO cache-sized
    transpose: K/V stay ``[B, Hkv, S, D]`` end to end (GQA repeats over the
    head axis as a broadcast the compiler folds into the einsum; the
    seq-major contraction ``bqhd,bhkd->bhqk`` is layout-identical work).
    ``q``: ``[B, T, H, D]``; returns ``[B, T, H, D]``. Pass either a full
    precomputed additive ``bias`` (``[B, H, T, S]``-broadcastable, e.g. the
    generic transformer's cache+ALiBi composite) OR ``cache_index`` (+
    optional ``key_mask``/``window``) to build the standard cache bias."""
    B, T, H, D = q.shape
    if "k_scale" in layer_cache:
        k = dequantize_kv(layer_cache["k"], layer_cache["k_scale"], q.dtype)
        v = dequantize_kv(layer_cache["v"], layer_cache["v_scale"], q.dtype)
    else:
        k = layer_cache["k"].astype(q.dtype)
        v = layer_cache["v"].astype(q.dtype)
    Hkv, S = k.shape[1], k.shape[2]
    rep = H // Hkv
    if rep > 1:  # GQA: expand over the head axis [B, Hkv*rep, S, D]
        k = jnp.broadcast_to(k[:, :, None], (B, Hkv, rep, S, D)).reshape(
            B, H, S, D)
        v = jnp.broadcast_to(v[:, :, None], (B, Hkv, rep, S, D)).reshape(
            B, H, S, D)
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is None:
        bias = cache_attention_bias(T, S, cache_index, key_mask=key_mask,
                                    window=window)
    logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bqhd", probs, v)


@jax.named_scope("ds.kv_append")
def update_kv_cache(layer_cache, k, v, cache_index):
    """Append ``[B, T, Hkv, D]`` keys/values at ``cache_index`` (traced ok).
    Only the NEW tokens are transposed into the head-major cache layout
    (O(T) per call — during decode T=1). An int8 cache (see
    ``init_kv_cache``) quantizes at append time."""
    k = jnp.swapaxes(k, 1, 2)  # [B, Hkv, T, D]
    v = jnp.swapaxes(v, 1, 2)
    idx = (0, 0, cache_index, 0)
    if "k_scale" in layer_cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        sidx = (0, 0, cache_index)
        return {
            "k": jax.lax.dynamic_update_slice(layer_cache["k"], kq, idx),
            "v": jax.lax.dynamic_update_slice(layer_cache["v"], vq, idx),
            "k_scale": jax.lax.dynamic_update_slice(
                layer_cache["k_scale"], ks, sidx),
            "v_scale": jax.lax.dynamic_update_slice(
                layer_cache["v_scale"], vs, sidx),
        }
    return {
        "k": jax.lax.dynamic_update_slice(layer_cache["k"], k.astype(layer_cache["k"].dtype), idx),
        "v": jax.lax.dynamic_update_slice(layer_cache["v"], v.astype(layer_cache["v"].dtype), idx),
    }


# ---------------------------------------------------------------------------
# Paged KV cache (serving layer)
#
# The serving engine (inference/serving/) replaces the dense per-call cache
# with a PREALLOCATED block pool shared by every in-flight request: pages of
# ``block_size`` token positions, indexed per sequence through a block table.
# Layout ``[L?, N, Hkv, bs, D]`` keeps the same well-tiled minor dims
# ``(bs, D)`` as the dense head-major cache, so the Pallas paged decode
# kernel's ``(1, 1, bs, D)`` blocks tile identically (see
# ``ops/pallas/decode_attention.py paged_decode_attention``). The shape of
# the fix follows "Ragged Paged Attention" (arxiv 2604.15464): one
# fixed-shape decode step serves arbitrary mixes of sequence lengths via
# block-table indexing, with no per-shape recompilation.
# ---------------------------------------------------------------------------


def init_paged_kv_cache(num_blocks: int, block_size: int, num_kv_heads: int,
                        head_dim: int, n_layers: Optional[int] = None,
                        dtype=jnp.bfloat16):
    """Allocate an empty paged KV pool ``[L?, N, Hkv, bs, D]``.

    ``dtype=jnp.int8`` mirrors the dense ``init_kv_cache`` int8 contract:
    values are absmax-quantized per (position, kv head) at append time with
    fp32 scales stored alongside (``[L?, N, Hkv, bs]``).
    """
    shape = (num_blocks, num_kv_heads, block_size, head_dim)
    sshape = (num_blocks, num_kv_heads, block_size)
    if n_layers is not None:
        shape = (n_layers,) + shape
        sshape = (n_layers,) + sshape
    if dtype == jnp.int8:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_cache_index(block_tables: jnp.ndarray, append_pos: jnp.ndarray,
                      context_len: jnp.ndarray, chunk_start=None,
                      token_rows=None, query_start=None, query_len=None):
    """Bundle the per-sequence paging state that rides through the model as
    ``cache_index`` (a plain dict threads the flax scan carry unchanged).

    ``block_tables``: int32 ``[B, nb_max]`` pool page ids per sequence; the
    sentinel value ``num_blocks`` (one past the pool) marks unallocated
    entries — appends routed there are DROPPED by the scatter and gathers
    clamp to a real page that the context-length mask then hides.
    ``append_pos``: int32 ``[B, T]`` absolute position of each incoming
    token (``-1`` = padding, its KV write is dropped).
    ``context_len``: int32 ``[B]`` number of valid cached tokens AFTER this
    append (prefill: the prompt length; decode: ``seq_len + 1``).
    ``chunk_start``: int32 ``[B]`` — present only on the CHUNKED prefill
    path: absolute position of the chunk's first token. Its presence
    switches the models' multi-token paged branch from fresh-KV (from-
    empty) attention to pool attention over the cached prefix + chunk.

    **Packed ragged MIXED batch** (the serving engine's unified step —
    "Ragged Paged Attention", arxiv 2604.15464): the token axis is a flat
    PACKED batch of contiguous per-sequence segments — decode rows
    (1 token) and prefill chunks (many) side by side — and raggedness
    rides three extra descriptor arrays, never the compiled shape:

    - ``token_rows``: int32 same shape as ``append_pos`` — for each packed
      token, the row of ``block_tables``/``context_len`` it belongs to
      (``-1`` = padding; its KV write is dropped). Its presence switches
      the models to the unified ragged attention path.
    - ``query_start``: int32 ``[R]`` — each row's first token's offset in
      the packed token axis (rows with no tokens this step: length 0).
    - ``query_len``: int32 ``[R]`` — each row's packed segment length
      (decode rows 1, prefill chunks n, inactive rows 0).

    ``block_tables``/``context_len``/``chunk_start`` are then per-ROW
    ``[R, nb_max]``/``[R]``/``[R]`` while ``append_pos``/``token_rows``
    stay per-token.
    """
    out = {"block_tables": jnp.asarray(block_tables, jnp.int32),
           "append_pos": jnp.asarray(append_pos, jnp.int32),
           "context_len": jnp.asarray(context_len, jnp.int32)}
    if chunk_start is not None:
        out["chunk_start"] = jnp.asarray(chunk_start, jnp.int32)
    if token_rows is not None:
        out["token_rows"] = jnp.asarray(token_rows, jnp.int32)
        out["query_start"] = jnp.asarray(query_start, jnp.int32)
        out["query_len"] = jnp.asarray(query_len, jnp.int32)
    return out


def is_paged_index(cache_index) -> bool:
    """True when ``cache_index`` is a paged-cache bundle (vs a scalar)."""
    return isinstance(cache_index, dict) and "block_tables" in cache_index


@jax.named_scope("ds.kv_append")
def update_paged_kv_cache(layer_cache, k, v, cache_index):
    """Append fresh ``[B, T, Hkv, D]`` keys/values into the block pool.

    Each token scatters to ``pool[table[pos // bs], :, pos % bs]``; invalid
    tokens (``append_pos < 0``) and unallocated table entries (the
    ``num_blocks`` sentinel) map out of bounds, which JAX scatter DROPS —
    inactive decode slots and prompt padding cost nothing and corrupt
    nothing. An int8 pool quantizes at append (absmax per token, kv head).
    """
    num_blocks, _, bs, _ = layer_cache["k"].shape
    pos = cache_index["append_pos"]                       # [B, T]
    blk = jnp.maximum(pos, 0) // bs
    off = jnp.maximum(pos, 0) % bs
    tables = cache_index["block_tables"]
    nb = tables.shape[1]
    if "token_rows" in cache_index:
        # packed ragged mixed batch: each token names its OWN table row —
        # the batch axis of ``pos`` no longer lines up with the tables'
        rows = cache_index["token_rows"]                  # [B, T]
        bids = tables[jnp.clip(rows, 0, tables.shape[0] - 1),
                      jnp.minimum(blk, nb - 1)]
        valid = (pos >= 0) & (rows >= 0) & (blk < nb)
    else:
        bids = jnp.take_along_axis(tables, jnp.minimum(blk, nb - 1), axis=1)
        # drop pads AND positions beyond the table width (over-length
        # appends must never alias another sequence's page)
        valid = (pos >= 0) & (blk < nb)
    bids = jnp.where(valid, bids, num_blocks)             # OOB -> dropped
    if "k_scale" in layer_cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {
            "k": layer_cache["k"].at[bids, :, off, :].set(kq, mode="drop"),
            "v": layer_cache["v"].at[bids, :, off, :].set(vq, mode="drop"),
            "k_scale": layer_cache["k_scale"].at[bids, :, off].set(
                ks, mode="drop"),
            "v_scale": layer_cache["v_scale"].at[bids, :, off].set(
                vs, mode="drop"),
        }
    return {
        "k": layer_cache["k"].at[bids, :, off, :].set(
            k.astype(layer_cache["k"].dtype), mode="drop"),
        "v": layer_cache["v"].at[bids, :, off, :].set(
            v.astype(layer_cache["v"].dtype), mode="drop"),
    }


def _gather_pages_dense(layer_cache, block_tables, dtype, num_heads):
    """Gather each sequence's pages into dense seq-major K/V rows
    ``[B, H, S, D]`` (S = nb_max * bs), dequantizing an int8 pool and
    expanding GQA kv heads over the head axis. Shared by the XLA paged
    attention fallbacks (decode + chunked prefill)."""
    num_blocks, Hkv, bs, D = layer_cache["k"].shape
    bt = jnp.minimum(jnp.asarray(block_tables, jnp.int32), num_blocks - 1)
    B, nb = bt.shape
    S = nb * bs
    k = layer_cache["k"][bt]                              # [B, nb, Hkv, bs, D]
    v = layer_cache["v"][bt]
    if "k_scale" in layer_cache:
        k = dequantize_kv(k, layer_cache["k_scale"][bt], dtype)
        v = dequantize_kv(v, layer_cache["v_scale"][bt], dtype)
    else:
        k = k.astype(dtype)
        v = v.astype(dtype)
    k = jnp.swapaxes(k, 1, 2).reshape(B, Hkv, S, D)
    v = jnp.swapaxes(v, 1, 2).reshape(B, Hkv, S, D)
    rep = num_heads // Hkv
    if rep > 1:
        k = jnp.broadcast_to(k[:, :, None], (B, Hkv, rep, S, D)).reshape(
            B, num_heads, S, D)
        v = jnp.broadcast_to(v[:, :, None], (B, Hkv, rep, S, D)).reshape(
            B, num_heads, S, D)
    return k, v


@jax.named_scope("ds.attention")
def paged_attention_reference(q, layer_cache, block_tables, context_len,
                              window: Optional[int] = None,
                              scale: Optional[float] = None):
    """Single-position attention over the paged pool, pure-XLA fallback.

    ``q``: ``[B, H, D]`` (the one new token's heads, ALREADY appended to the
    pool); gathers each sequence's pages into dense ``[B, Hkv, S, D]`` rows
    (S = nb_max * bs) and masks ``kv_pos >= context_len``. Runs everywhere;
    the TPU path is the block-table Pallas kernel
    (``ops/pallas/decode_attention.py paged_decode_attention``).
    """
    B, H, D = q.shape
    k, v = _gather_pages_dense(layer_cache, block_tables, q.dtype, H)
    S = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    clen = jnp.asarray(context_len, jnp.int32)
    kv_pos = jnp.arange(S)[None, :]
    visible = kv_pos < clen[:, None]
    if window is not None:
        visible = visible & ((clen[:, None] - 1 - kv_pos) < window)
    bias = jnp.where(visible, 0.0, -1e9).astype(jnp.float32)[:, None, :]
    logits = jnp.einsum("bhd,bhsd->bhs", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits + bias, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bhsd->bhd", probs, v)


@jax.named_scope("ds.attention")
def paged_prefill_attention_reference(q, layer_cache, block_tables,
                                      append_pos, context_len,
                                      window: Optional[int] = None,
                                      scale: Optional[float] = None):
    """Chunked-prefill attention over the paged pool, pure-XLA fallback.

    Unlike the from-empty serving prefill (attention over the FRESH K/V
    only), a chunk arriving mid-prompt must attend the sequence's CACHED
    prefix too — prefix-cache hits and earlier chunks live only in the
    pool. ``q``: ``[B, T, H, D]`` (this chunk's queries, KV ALREADY
    appended); ``append_pos``: ``[B, T]`` each query's absolute position
    (``-1`` = padding — nothing visible, output dropped by the caller);
    ``context_len``: ``[B]`` valid pool tokens after the append. Query at
    position p sees kv positions <= p: causal across chunk boundaries with
    the chunk offset riding as DATA, so one compiled program serves every
    chunk position and cached-prefix length. TPU path:
    ``ops/pallas/decode_attention.py paged_prefill_attention``.
    """
    B, T, H, D = q.shape
    k, v = _gather_pages_dense(layer_cache, block_tables, q.dtype, H)
    S = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    q_pos = jnp.asarray(append_pos, jnp.int32)            # [B, T]
    clen = jnp.asarray(context_len, jnp.int32)
    kv_pos = jnp.arange(S)[None, None, :]
    visible = (kv_pos <= q_pos[:, :, None]) & (kv_pos < clen[:, None, None])
    if window is not None:
        visible = visible & (q_pos[:, :, None] - kv_pos < window)
    # pad queries (append_pos < 0) see nothing; the uniform softmax they
    # produce stays finite and the caller never reads those rows
    bias = jnp.where(visible, 0.0, -1e9).astype(jnp.float32)[:, None]
    logits = jnp.einsum("bqhd,bhsd->bhqs", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits + bias, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bhsd->bqhd", probs, v)


@jax.named_scope("ds.attention")
def ragged_mixed_attention_reference(q, layer_cache, cache_index,
                                     window: Optional[int] = None,
                                     scale: Optional[float] = None):
    """Unified ragged mixed-batch attention over the paged pool, pure-XLA
    fallback — the reference semantics of the serving engine's ONE
    resident step ("Ragged Paged Attention", arxiv 2604.15464).

    ``q``: ``[B, T, H, D]`` where the token axis is a PACKED ragged batch
    (decode rows of 1 token and prefill chunks side by side, KV ALREADY
    appended); ``cache_index`` is the packed bundle from
    :func:`paged_cache_index` (``token_rows`` maps each token to its
    block-table row). Masking is the chunked-prefill rule applied per
    packed token — query at absolute position p sees its row's kv
    positions ``<= p`` (and ``< context_len``) — so decode rows (one
    token at ``context_len - 1``) and chunk rows share one definition by
    construction; padding tokens (``token_rows < 0``) see nothing and
    return finite garbage the caller never reads.

    Cost shape: pages are gathered dense once per ROW (``[R, Hkv, S,
    D]``), then expanded to a per-TOKEN ``[B*T, Hkv, S, D]`` via a
    contiguous-row copy — ~``T/R``x the volume the split decode
    reference paid, the price of one fixed-shape program over variable
    segments (a per-row formulation needs data-dependent query shapes;
    the earlier per-token PAGE-walk gather + ``repeat_kv`` cost ~2x this
    form). GQA heads ride a grouped einsum, never a materialized
    ``repeat_kv``. On TPU the real kernel
    (``ops/pallas/ragged_attention.py ragged_paged_attention``) pays
    none of this — dead q-tiles are skipped and pages stream per row.
    """
    B, T, H, D = q.shape
    tables = cache_index["block_tables"]                  # [R, nb]
    R = tables.shape[0]
    num_blocks, Hkv, bs, _ = layer_cache["k"].shape
    rows = cache_index["token_rows"].reshape(B * T)       # [B*T]
    pos = jnp.asarray(cache_index["append_pos"], jnp.int32).reshape(B * T)
    safe = jnp.clip(rows, 0, R - 1)
    clen_row = jnp.asarray(cache_index["context_len"], jnp.int32)
    # dense per-ROW K/V in the pool's head-major layout [R, Hkv, S, D] —
    # NO GQA expansion (grouped einsum below) and no seq-major transpose
    bt = jnp.minimum(jnp.asarray(tables, jnp.int32), num_blocks - 1)
    S = bt.shape[1] * bs
    k = layer_cache["k"][bt]                              # [R, nb, Hkv, bs, D]
    v = layer_cache["v"][bt]
    if "k_scale" in layer_cache:
        k = dequantize_kv(k, layer_cache["k_scale"][bt], q.dtype)
        v = dequantize_kv(v, layer_cache["v_scale"][bt], q.dtype)
    else:
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)
    k = jnp.swapaxes(k, 1, 2).reshape(R, Hkv, S, D)
    v = jnp.swapaxes(v, 1, 2).reshape(R, Hkv, S, D)
    k = k[safe]                                           # [N, Hkv, S, D]
    v = v[safe]
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    G = H // Hkv
    qg = q.reshape(B * T, Hkv, G, D)
    logits = jnp.einsum("nhgd,nhsd->nhgs", qg, k).astype(jnp.float32) \
        * scale
    q_pos = pos[:, None]                                  # [N, 1]
    clen = jnp.where((rows >= 0) & (pos >= 0), clen_row[safe], 0)
    kv_pos = jnp.arange(S)[None, :]
    visible = (kv_pos <= q_pos) & (kv_pos < clen[:, None])
    if window is not None:
        visible = visible & (q_pos - kv_pos < window)
    bias = jnp.where(visible, 0.0, -1e9).astype(jnp.float32)[:, None, None]
    probs = jax.nn.softmax(logits + bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("nhgs,nhsd->nhgd", probs, v)
    return out.reshape(B, T, H, D)


def harvest_packed_logits(logits, token_rows, num_rows, corrupt=None):
    """Multi-position harvest of the packed ragged mixed step.

    ``logits``: ``[1, T, V]`` over the packed token axis; ``token_rows``:
    ``[1, T]`` (or ``[T]``) mapping each packed token to its descriptor
    row (``-1`` = padding). Returns ``(lg, bad)``:

    - ``lg``: ``[T, V]`` per-POSITION logits, chaos-corruption applied
      (``corrupt``: optional ``[R]`` bool — flagged rows' valid tokens go
      NaN as DATA, so drills never recompile). The caller samples every
      position and gathers what it needs per row: position ``query_start``
      alone for a plain decode row, all ``k + 1`` positions of a verify
      row (speculative decoding's accept-prefix input), the last position
      of a final prefill chunk. Padding positions carry garbage the host
      never reads.
    - ``bad``: ``[R]`` per-row NaN/Inf flag OR-reduced over the row's
      valid tokens — one poisoned position anywhere in a verify row or
      chunk quarantines that row's request, never the batch.
    """
    lg = logits[0]
    rows = jnp.asarray(token_rows, jnp.int32).reshape(-1)
    valid = rows >= 0
    safe = jnp.clip(rows, 0, num_rows - 1)
    if corrupt is not None:
        hit = jnp.asarray(corrupt, bool)[safe] & valid
        lg = jnp.where(hit[:, None], jnp.asarray(jnp.nan, lg.dtype), lg)
    bad_tok = ~jnp.isfinite(lg).all(axis=-1) & valid
    bad = jnp.zeros((num_rows,), bool).at[safe].max(bad_tok)
    return lg, bad


def copy_paged_blocks(pool, src_ids, dst_ids):
    """Device-side page copy ``pool[:, dst] = pool[:, src]`` across every
    pool array (K, V, int8 scales) — the copy half of copy-on-write when a
    sequence must append into a page other sequences still reference. Pool
    arrays carry the leading layer axis ``[L, N, ...]`` (the serving
    engine's layout); ``src_ids``/``dst_ids`` are equal-length int32
    vectors."""
    src = jnp.asarray(src_ids, jnp.int32)
    dst = jnp.asarray(dst_ids, jnp.int32)
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pool)


def key_mask_to_bias(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """[B, S] 1/0 key mask -> additive [B, 1, 1, S] bias (0 keep, -1e9 drop).
    The ONE conversion used by every entry point that accepts a key mask."""
    return jnp.where(attention_mask[:, None, None, :] > 0, 0.0,
                     -1e9).astype(jnp.float32)


def cache_attention_bias(q_len: int, cache_len: int, cache_index,
                         key_mask: Optional[jnp.ndarray] = None,
                         window: Optional[int] = None) -> jnp.ndarray:
    """Additive bias for attention over a partially-filled KV cache.

    Query t sits at absolute position ``cache_index + t``; key j is visible iff
    ``j <= cache_index + t`` (this covers both causal prefill and decode) and,
    with ``window`` (Mistral sliding-window), additionally
    ``(cache_index + t) - j < window``. ``key_mask`` ``[B, S]`` (1 = real
    token) additionally hides padding. Counterpart of the triangular masking
    in the reference's ``softmax_context`` inference kernel.
    """
    q_pos = cache_index + jnp.arange(q_len)
    kv_pos = jnp.arange(cache_len)
    visible = q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        visible = visible & (q_pos[:, None] - kv_pos[None, :] < window)
    bias = jnp.where(visible, 0.0, -1e9)[None, None]
    if key_mask is not None:
        bias = bias + jnp.where(key_mask > 0, 0.0, -1e9)[:, None, None, :]
    return bias.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       ignore_index: int = -100) -> jnp.ndarray:
    """Token-mean cross entropy with ignore mask; stable in fp32.

    A ``custom_vjp``: its backward rule hands the head ONE cotangent, made
    in one pass in the logits' own dtype (``_cross_entropy_bwd``)."""
    return _cross_entropy_fwd(logits, labels, ignore_index)[0]


def _cross_entropy_fwd(logits, labels, ignore_index):
    mask = (labels != ignore_index).astype(jnp.float32)
    safe_labels = jnp.where(labels == ignore_index, 0, labels)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    # gathered BEFORE the cast: a gather from the float32 cast makes the
    # head's product write a second, float32 copy of the logits for it
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)
    gold = gold.squeeze(-1).astype(jnp.float32)
    nll = (logz - gold) * mask
    count = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / count, (logits, labels, logz, count)


def _cross_entropy_bwd(ignore_index, residuals, g):
    """``(softmax - onehot) * mask * g / count`` in float32, rounded ONCE to
    the logits' dtype -- where plain autodiff rounds it, at the transpose
    of the forward's cast -- and held as one array. Left to autodiff, the
    element-wise float32 graph ahead of that cast is either written out as
    a float32 ``[tokens, vocab]`` and converted in a second pass, or cloned
    into both of the head's backward products as their operand's producer
    (PERF.md section 5, PR 64); behind the barrier both read one array."""
    logits, labels, logz, count = residuals
    scale = jnp.where(labels != ignore_index, g / count, 0.0)
    probs = jnp.exp(logits.astype(jnp.float32) - logz[..., None])
    onehot = labels[..., None] == jnp.arange(logits.shape[-1])
    cotangent = ((probs - onehot) * scale[..., None]).astype(logits.dtype)
    return jax.lax.optimization_barrier(cotangent), None


cross_entropy_loss.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


def chunked_cross_entropy_loss(hidden: jnp.ndarray, w_out: jnp.ndarray,
                               labels: jnp.ndarray, *,
                               bias: jnp.ndarray = None,
                               ignore_index: int = -100,
                               chunk: int = 2048) -> jnp.ndarray:
    """Token-mean cross entropy WITHOUT materializing ``[tokens, vocab]``.

    The plain path (``cross_entropy_loss`` over ``lm_head_output``'s whole
    logits) holds two ``[B,T,V]`` arrays in the logits' dtype -- the logits
    and, in the backward, their cotangent, made in one pass -- and nothing
    of that shape in float32: at 8,192 tokens x 32,000 words 0.52 GB each,
    the cotangent read by the head's two backward products as it stands.
    Here the head projection + logsumexp run inside a
    ``lax.scan`` over token chunks with a rematerialized body, so peak
    memory is ``O(chunk * vocab)`` and the full logits never exist; the
    backward recomputes each chunk's logits (≈ +1/3 of the lm-head FLOPs,
    a few % of the model) while the head-weight gradient accumulates
    across chunks in the scan's backward. Matches ``cross_entropy_loss``
    math (fp32 logsumexp, fp32 matmul accumulation) up to reduction order
    and — for an untied fp32 head with low-precision activations — the
    head weights being rounded to the activation dtype for the MXU.
    Reference counterpart: the fused softmax/xent CUDA kernels
    (``csrc/transformer/softmax_kernels.cu``) — the TPU-native answer is a
    compiler-scheduled chunk scan, not a hand-written kernel.

    ``hidden``: [B, T, H] pre-head activations (any float dtype);
    ``w_out``: [H, V] head projection (``embed.T`` when tied);
    ``labels``: [B, T] ALREADY shifted, ``ignore_index`` masked out.
    """
    b, t, h = hidden.shape
    n = b * t
    hs = hidden.reshape(n, h)
    ys = labels.reshape(n)
    pad = (-n) % chunk
    if pad:
        hs = jnp.concatenate([hs, jnp.zeros((pad, h), hs.dtype)], axis=0)
        ys = jnp.concatenate(
            [ys, jnp.full((pad,), ignore_index, ys.dtype)], axis=0)
    hs = hs.reshape(-1, chunk, h)
    ys = ys.reshape(-1, chunk)

    def body(carry, hy):
        hc, yc = hy
        # operands in the activation dtype (bf16 on chip -> MXU-native),
        # accumulation in fp32: for an untied fp32 head this rounds the
        # WEIGHTS to bf16 where the plain path runs an fp32 matmul — the
        # standard TPU head discipline, and the only numeric difference
        # beyond reduction order (exact when activations are fp32)
        logits = jnp.dot(hc, w_out.astype(hc.dtype),
                         preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        mask = (yc != ignore_index)
        safe = jnp.where(mask, yc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        nll = jnp.where(mask, logz - gold, 0.0)
        s, c = carry
        return (s + nll.sum(), c + mask.sum().astype(jnp.float32)), None

    (s, c), _ = jax.lax.scan(jax.checkpoint(body),
                             (jnp.float32(0.0), jnp.float32(0.0)), (hs, ys))
    return s / jnp.maximum(c, 1.0)


def shift_labels(input_ids: jnp.ndarray, ignore_index: int = -100) -> jnp.ndarray:
    """HF convention: labels == input_ids; shift left, pad tail with ignore."""
    return jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], ignore_index)], axis=1)


def head_scope(cache) -> str:
    """Trace scope of final norm -> head (-> loss): ``ds.lm_head`` on the
    cached (serving) path, ``ds.lm_head_loss`` where the loss follows."""
    return "ds.lm_head" if cache is not None else "ds.lm_head_loss"


def lm_head_output(parent, cfg, hidden, labels, cache, head_bias=False):
    """Shared LM-head dispatch for the causal-LM model classes.

    Returns ``(logits, loss)`` where exactly one is non-None: the training
    path with ``cfg.loss_chunk > 0`` goes through
    :func:`chunked_cross_entropy_loss` and never materializes logits
    (``logits is None``); every other path returns full logits and leaves
    the loss to the caller. Must be called from the parent module's compact
    ``__call__`` frame (it creates the ``lm_head`` Dense there; the
    zero-width ``head(hidden[:, :0, :])`` call creates the params without
    computing logits when only the kernel is needed).
    """
    import flax.linen as nn

    chunked = bool(getattr(cfg, "loss_chunk", 0)) \
        and cache is None and labels is not None
    bias = None
    if cfg.tie_word_embeddings:
        w_out = parent.variables["params"]["model"]["embed_tokens"][
            "embedding"].T
        logits = None if chunked else hidden @ w_out.astype(hidden.dtype)
    else:
        head = nn.Dense(cfg.vocab_size, use_bias=head_bias, name="lm_head",
                        param_dtype=jnp.float32)
        if chunked:
            head(hidden[:, :0, :])
            w_out = parent.variables["params"]["lm_head"]["kernel"]
            if head_bias:
                bias = parent.variables["params"]["lm_head"]["bias"]
            logits = None
        else:
            logits = head(hidden)
    if not chunked:
        return logits, None
    return None, chunked_cross_entropy_loss(hidden, w_out,
                                            shift_labels(labels), bias=bias,
                                            chunk=cfg.loss_chunk)


def seeded_embed_tokens(cfg, input_ids):
    """The input table ``embed_tokens`` of a model whose configuration may
    state the scale its rows are SEEDED at (``embed_init_std``; None: flax's
    ``1 / sqrt(hidden_size)``), looked up. Called from the parent module's
    compact ``__call__`` frame, as ``lm_head_output`` is."""
    seeded = {} if cfg.embed_init_std is None else {
        "embedding_init": nn.initializers.normal(cfg.embed_init_std)}
    return nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                    param_dtype=jnp.float32, **seeded)(input_ids)


def seeded_lm_head(cfg, hidden):
    """Whole logits through an untied ``lm_head`` whose configuration may
    state the scale its rows are SEEDED at (``head_init_std``; None: flax's
    ``1 / sqrt(hidden_size)``). Called from the parent module's compact
    ``__call__`` frame."""
    seeded = {} if cfg.head_init_std is None else {
        "kernel_init": nn.initializers.normal(cfg.head_init_std)}
    return nn.Dense(cfg.vocab_size, use_bias=False, name="lm_head",
                    param_dtype=jnp.float32, **seeded)(hidden)


def apply_rotary_interleaved(x, cos, sin):
    """GPT-J-style rotate_every_two: pairs are (x[2i], x[2i+1]), not the
    rotate-half (x[i], x[i+D/2]) convention."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape)


def apply_rotary_partial(x, cos, sin, rotary_dim, style="half"):
    """Partial rotary: rotate the first ``rotary_dim`` channels."""
    rot_fn = apply_rotary if style == "half" else apply_rotary_interleaved
    if rotary_dim >= x.shape[-1]:
        return rot_fn(x, cos, sin)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([rot_fn(rot, cos, sin), rest], axis=-1)


def shift_tokens(x: jnp.ndarray) -> jnp.ndarray:
    """``x [B, T, ...]`` one token back along the sequence: row ``t`` holds
    ``x[t - 1]`` and row 0 zeros (never a wrapped row)."""
    return jnp.pad(x[:, :-1], [(0, 0), (1, 0)] + [(0, 0)] * (x.ndim - 2))


def causal_conv(x: jnp.ndarray, weight: jnp.ndarray, bias=None) -> jnp.ndarray:
    """Causal convolution along the sequence, zeros before position 0:
    ``y[t] = sum_j weight[j] (x) x[t - (taps - 1) + j] + bias``, as
    shifted multiply-adds (tap ``taps - 1`` meets the current token).

    ``x [B, T, C]``; ``weight [taps, C]`` is depthwise (one factor a
    channel), ``weight [taps, G, C/G, C/G]`` grouped (one ``in x out``
    matrix a group of channels and a tap); ``bias [C]``."""
    B, T, C = x.shape
    y, xs = 0, x
    for j in range(weight.shape[0] - 1, -1, -1):
        if weight.ndim == 2:
            y = y + xs * weight[j]
        else:
            G = weight.shape[1]
            y = y + jnp.einsum("btgi,gio->btgo", xs.reshape(B, T, G, C // G),
                               weight[j]).reshape(B, T, C)
        if j:
            xs = shift_tokens(xs)
    return y if bias is None else y + bias


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's blended rotary frequencies ``[head_dim / 2]`` (float64 numpy)
    and the corrected range ``(low, high)``: pair ``i`` keeps its frequency
    ``theta^(-2i/d)`` below ``low`` (it turns more than ``beta_fast`` times
    over the original length), takes it divided by ``factor`` above ``high``
    (fewer than ``beta_slow`` turns), and a linear ramp of the two between."""
    d = head_dim
    extrap = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(turns):
        return d * np.log(original_max_position_embeddings
                          / (2 * np.pi * turns)) / (2 * np.log(theta))

    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extrap / factor * ramp + extrap * (1 - ramp), (low, high)


def yarn_rotary_embedding(positions: jnp.ndarray, head_dim: int, theta: float,
                          factor: float,
                          original_max_position_embeddings: int,
                          beta_fast: float = 32.0, beta_slow: float = 1.0,
                          attention_factor: Optional[float] = None,
                          dtype=jnp.float32):
    """``rotary_embedding`` under YaRN (``rope_type`` "yarn"): cos/sin
    ``[B, T, head_dim/2]`` at ``yarn_inv_freq``, BOTH multiplied by
    ``attention_factor`` (None: ``0.1 ln(factor) + 1``), so rotated queries
    and keys carry it and their scores its square. The same table at every
    sequence length: no switch at the original length."""
    inv_freq, _ = yarn_inv_freq(head_dim, theta, factor,
                                original_max_position_embeddings, beta_fast,
                                beta_slow)
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0
    freqs = positions[..., None].astype(jnp.float32) \
        * inv_freq.astype(np.float32)[None, None, :]
    return ((jnp.cos(freqs) * attention_factor).astype(dtype),
            (jnp.sin(freqs) * attention_factor).astype(dtype))


# -- a stack of layer kinds: the scan over its periods -----------------------

class _Period(nn.Module):
    """One period, its blocks unrolled: a scan's body. Block ``i`` is
    ``block(kinds[i], "block_<i>")`` under its kind's outer scope, remat'ed
    by itself as a one-kind stack's layers are. ``lone``: the scan has this
    one trip. XLA then removes the loop, and without ``prevent_cse`` it
    merges each block's replay with its forward pass: the step keeps every
    activation ``remat`` was asked to drop."""

    config: Any
    kinds: tuple
    block: Callable
    call: Callable
    fold: Callable
    scopes: Any
    offers: Callable
    lone: bool = False

    @nn.compact
    def __call__(self, carry, *inputs):
        cfg = self.config
        x, stats = carry
        apply = lambda block, *args: block(*args)
        if cfg.remat:
            apply = nn.remat(apply, prevent_cse=self.lone,
                             policy=resolve_remat_policy(
                                 cfg.remat_policy, self.offers(x)))
        for i, kind in enumerate(self.kinds):
            block = self.block(kind, f"block_{i}")
            with jax.named_scope(self.scopes[kind]):
                x, got = self.call(functools.partial(apply, block), kind, x,
                                   *inputs)
            stats = self.fold(stats, got)
        return (x, stats), None


def scan_periods(cfg, kinds, x, stats, inputs, *, block, call, fold, scopes,
                 offers, leading=()):
    """``(x, stats)`` after ``cfg.num_hidden_layers`` layers: the layers of
    the kinds ``leading`` (a stack's dense layers in front of its pattern),
    unrolled as ``leading/block_<i>``, then whole periods of ``kinds``: ONE
    scan over the periods (``periods``; with ``scan_layers`` off, unrolled
    as ``periods_<p>``) whose body unrolls a period's blocks, each remat'ed
    by itself under ``resolve_remat_policy(cfg.remat_policy, offers(x))``.
    What a model file says of its stack is data: ``block(kind, name)`` makes
    a layer, ``call(block, kind, x, *inputs)`` applies it to the stream and
    the scan's broadcast ``inputs`` and returns ``(x, its statistics)``,
    ``scopes[kind]`` is a kind's outer scope, and ``fold(stats, a block's
    statistics)`` carries what the layers report: a pytree that rides the
    scan's carry behind ``x`` and is not looked into here."""
    periods = (cfg.num_hidden_layers - len(leading)) // len(kinds)
    fields = (cfg, kinds, block, call, fold, scopes, offers)
    carry = (x, stats)
    # ds.layer_stack: what the loop over the periods costs beyond what the
    # layers' own scopes name (models/llama.py LlamaModel)
    with jax.named_scope("ds.layer_stack"):
        if leading:
            # no loop to remove: each block's replay is fenced as a lone
            # period's is
            carry, _ = _Period(cfg, leading, *fields[2:], True,
                               name="leading")(carry, *inputs)
        if cfg.scan_layers:
            scan = nn.scan(
                _Period, variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,) * len(inputs), length=periods,
                metadata_params={})
            carry, _ = scan(*fields, periods == 1, name="periods")(
                carry, *inputs)
        else:
            for p in range(periods):
                carry, _ = _Period(*fields, name=f"periods_{p}")(
                    carry, *inputs)
    return carry


# -- what a remat'ed block keeps beyond its policy ---------------------------

#: the rule's constants (PERF.md section 3 has the readings they were fixed
#: on; they are not options). A named byte costs the compiled step up to
#: ``REMAT_FACTOR`` bytes of temp (the kept value, stacked over the layers,
#: beside the layer's own copy of it while the stack is written); the step
#: plans with ``REMAT_SHARE`` of the device's memory, less what is in use
#: before it is built; and a compiled step whose footprint stands over
#: ``REMAT_MARGIN`` of the device's memory is built again with nothing kept
REMAT_FACTOR = 2
REMAT_SHARE = 0.75
REMAT_MARGIN = 0.95


class _Room(threading.local):
    """The budget stated for the trace open on this thread, and what the
    rule kept under it ``{name: bytes}``."""

    budget = 0
    kept = None


_room = _Room()


@contextlib.contextmanager
def remat_room(budget: int):
    """State ``budget`` bytes of free device memory for every trace made
    inside (the engine's, around the lowering of its train step); yields
    ``{name: bytes}``, which fills as block wrappers ask ``keep_for_room``.
    Outside any such context the budget is 0."""
    before = _room.budget, _room.kept
    _room.budget, _room.kept = max(int(budget), 0), {}
    try:
        yield _room.kept
    finally:
        _room.budget, _room.kept = before


def keep_for_room(offered) -> Tuple[str, ...]:
    """The names of ``offered`` a remat policy keeps: ``offered`` is ONE
    model's ``[(checkpoint_name, bytes over all its layer applications)]``
    in the model's order, walked once -- a name is kept (for all its layer
    applications, or for none) while ``REMAT_FACTOR`` times the bytes kept
    so far, it among them, stay inside the budget ``remat_room`` states for
    this trace (0 outside one). A name that does not fit is passed over and
    the walk goes on: a later, smaller one may."""
    kept, total = [], 0
    for name, nbytes in offered:
        if REMAT_FACTOR * (total + nbytes) <= _room.budget:
            kept.append(name)
            total += nbytes
            _room.kept[name] = nbytes
    return tuple(kept)


def batch_axes(batch: int) -> Tuple[str, ...]:
    """The mesh axes a batch of ``batch`` samples is sharded over: the
    engine's batch layout (``("data", "expert")``, ``("data",)`` under
    ``moe.replicate_tokens``), the axes of several devices, as far along as
    their product divides ``batch``. ``()`` with no mesh and on one device."""
    mesh = get_mesh()
    size = {} if mesh is None else mesh.shape
    axes = ("data",) if tokens_replicated() else BATCH_AXES
    axes = tuple(a for a in axes if size.get(a, 1) > 1)
    while axes and batch % math.prod(size[a] for a in axes):
        axes = axes[:-1]
    return axes


def device_part(batch: int, but=()) -> int:
    """What ONE device holds of a batch of ``batch`` samples: ``batch`` over
    the devices of ``batch_axes(batch)``, less the axes ``but`` (the ones a
    value has been gathered over where it is named). A model file's offer
    counts a device's part of each value through here, as the budget it is
    held against is a device's (``remat_room``); on one device that is the
    whole batch. Only the batch is divided: whether heads or columns are
    sharded over ``model`` is the caller's ``partition_rules`` to say, which
    no model file sees, so they count whole -- a count too high keeps less,
    one too low plans what does not fit."""
    mesh = get_mesh()
    return batch // math.prod(
        mesh.shape[a] for a in batch_axes(batch) if a not in but)


def name_if_kept(x, name: str):
    """``checkpoint_name(x, name)`` where the rule kept ``name`` for the
    trace open on this thread, else ``x`` itself: a step that keeps nothing
    holds no ``name`` equation, so it lowers to the very text it had before
    any name was offered (a ``name`` lowers to nothing, but it moves the
    numbers XLA's private functions are called by, which the compile
    cache's key holds). For a module's own body, traced anew under every
    trace -- not for a function jitted by itself, whose trace is cached."""
    if _room.kept and name in _room.kept:
        return checkpoint_name(x, name)
    return x
