"""Mixtral-family sparse-MoE decoder (BASELINE north star: Mixtral-8x7B
expert parallel).

The Llama block with the MLP replaced by a top-k sparse mixture of experts,
HF-``MixtralForCausalLM``-exact routing semantics: router logits → softmax
over ALL experts → top-k → renormalize the selected weights → weighted sum
of the selected experts' SwiGLU outputs (plus the Switch load-balancing aux
loss scaled by ``router_aux_loss_coef`` during training).

TPU-native dispatch: expert weights live STACKED ``[E, ...]`` and shard over
the ``expert`` mesh axis; every expert's matmuls run on its own shard with
tokens broadcast, and the top-k-masked combine is the cross-expert psum the
partitioner inserts. This is exact (no capacity drops — decisive for HF
logits parity) at the cost of dense E-way MLP FLOPs; for capacity-based
all_to_all dispatch at training scale use ``deepspeed_tpu.moe.MoE`` (GShard
gating, reference ``sharded_moe.py``) — the reference makes the same
split between its inference MoE kernels (``moe_res_matmul``) and its
training-time gated dispatch.

Attention/rotary/cache machinery is shared with ``models/llama.py``.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (RMSNorm, cross_entropy_loss, head_scope, init_kv_cache,
                     lm_head_output,
                     resolve_remat_policy, rotary_embedding, shift_labels)
from .llama import LlamaAttention, LlamaConfig


def _expert_axis_active() -> bool:
    """True when the active mesh shards the ``expert`` axis (>1): the
    gather decode path would pull sharded expert rows cross-device, so it
    only engages with replicated experts."""
    from ..parallel.topology import get_mesh

    mesh = get_mesh()
    if mesh is None:
        return False
    return dict(zip(mesh.axis_names,
                    mesh.devices.shape)).get("expert", 1) > 1


def _ep_constraint(t, *spec):
    """Pin a MoE-internal tensor's sharding (axes present in the active mesh
    only; no-op off-mesh). Without these pins the partitioner must invent a
    layout for the [B,T,E,·] intermediates — the batch arrives sharded over
    (data, expert) while the stacked expert weights shard E over expert, and
    XLA's guess triggered an 'involuntary full rematerialization' warning
    (a replicate-then-repartition perf cliff) in the r3 multichip dryrun.

    TPU-only (override: ``DS_EP_CONSTRAINTS=1``): the entry pin makes the
    partitioner all-gather tokens over the expert axis inside the layer
    scan, which the XLA:CPU thunk runtime cannot execute (its collective
    rendezvous aborts — same environmental limit as ``__graft_entry__``
    section 2d). On CPU meshes use the engine's
    ``{"moe": {"replicate_tokens": true}}`` layout instead, which needs no
    in-layer batch reshard (tokens already replicated over the expert axis;
    the only in-layer collective is the combine psum)."""
    import os

    from ..parallel.topology import get_mesh, tokens_replicated

    if tokens_replicated():
        # the engine chose the data-only token layout — these (data, expert)
        # entry/exit pins would reintroduce the per-layer batch reshard the
        # flag exists to avoid
        return t
    if jax.default_backend() != "tpu" and not os.environ.get("DS_EP_CONSTRAINTS"):
        return t
    mesh = get_mesh()
    if mesh is None:
        return t
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))

    def keep(ax):
        if ax is None:
            return None
        axes = ax if isinstance(ax, tuple) else (ax,)
        kept = tuple(a for a in axes if shape.get(a, 1) > 1)
        return kept or None

    spec = [keep(s) for s in spec]
    if all(s is None for s in spec):
        return t
    return jax.lax.with_sharding_constraint(
        t, jax.sharding.NamedSharding(mesh, P(*spec)))


@dataclasses.dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02

    @staticmethod
    def mixtral_8x7b(**over):
        return MixtralConfig(**{**dict(
            vocab_size=32000, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=32768,
            rope_theta=1e6, num_local_experts=8, num_experts_per_tok=2),
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
    """HF ``MixtralSparseMoeBlock`` semantics. Returns ``(out, frac, prob)``
    where ``frac``/``prob`` are this layer's per-expert token-fraction and
    mean-router-probability vectors ``[E]`` (token-masked), accumulated
    across layers by the caller — HF's ``load_balancing_loss_func``
    concatenates all layers' tokens BEFORE taking the means, so the product
    must happen at the top, not per layer."""

    config: MixtralConfig

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.config
        B, T, H = x.shape
        E, K = cfg.num_local_experts, cfg.num_experts_per_tok
        I = cfg.intermediate_size

        with jax.named_scope("ds.moe_router"):
            router_logits = nn.Dense(E, use_bias=False, name="gate",
                                     param_dtype=jnp.float32)(x)  # [B, T, E]
            probs = jax.nn.softmax(router_logits.astype(jnp.float32),
                                   axis=-1)
            topk_w, topk_idx = jax.lax.top_k(probs, K)
            topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
            # one-hot routing (also feeds the aux-loss stats below)
            onehot = jax.nn.one_hot(topk_idx, E,
                                    dtype=topk_w.dtype)  # [B,T,K,E]

        # stacked expert SwiGLU: [E, H, I] / [E, I, H], sharded over "expert"
        w1 = self.param("w1", nn.initializers.lecun_normal(), (E, H, I),
                        jnp.float32)  # gate
        w3 = self.param("w3", nn.initializers.lecun_normal(), (E, H, I),
                        jnp.float32)  # up
        w2 = self.param("w2", nn.initializers.lecun_normal(), (E, I, H),
                        jnp.float32)  # down
        out = _expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx, onehot)
        frac, prob = _router_stats(onehot, probs, token_mask, B * T)
        return out, frac, prob


@jax.named_scope("ds.moe_experts")
def _expert_mlp(cfg, x, w1, w2, w3, topk_w, topk_idx, onehot):
    """The stacked expert SwiGLU and the weighted combine: ``[B, T, H]``."""
    T, dt = x.shape[1], x.dtype
    E, K = cfg.num_local_experts, cfg.num_experts_per_tok
    if T == 1 and E > K and not _expert_axis_active():
        # decode fast path (replicated experts): GATHER only the K
        # touched experts' weights per token instead of computing all E
        # — the stacked einsum streams E/K x the weight bytes a decode
        # step needs (the reference's einsum_sec_sm_ecm / moe_res_matmul
        # kernels exist for exactly this; tools/bench_moe_decode.py
        # measures it as gather_speedup_vs_all_e). XLA's gather reads
        # only the indexed expert rows from HBM.
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
    else:
        # dense [B, T, E] combine weights, zero outside the top-k;
        # the combine joins the expert-axis-gathered tokens in the
        # final einsum
        combine = jnp.einsum("btk,btke->bte", topk_w, onehot)
        combine = _ep_constraint(combine, "data", None, None)
        # EP layout (GShard-style): tokens all-gather over the expert
        # axis at entry (B drops to data-only sharding), the [B,T,E,·]
        # intermediates keep E on the expert axis, and the combine
        # contraction over E reduce-scatters B back onto (data, expert)
        xg = _ep_constraint(x, "data", None, None)
        h = nn.silu(jnp.einsum("bth,ehi->btei", xg, w1.astype(dt))) * \
            jnp.einsum("bth,ehi->btei", xg, w3.astype(dt))
        h = _ep_constraint(h, "data", None, "expert", None)
        y = jnp.einsum("btei,eih->bteh", h, w2.astype(dt))
        y = _ep_constraint(y, "data", None, "expert", None)
        out = jnp.einsum("bte,bteh->bth", combine.astype(dt), y)
        out = _ep_constraint(out, ("data", "expert"), None, None)
    return out


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask, token_mask=None, layer_cache=None,
                 cache_index=None, deterministic=True):
        cfg = self.config
        h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        attn, layer_cache = LlamaAttention(cfg, name="self_attn")(
            h, cos, sin, mask, layer_cache, cache_index, deterministic)
        x = x + attn
        h = RMSNorm(eps=cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        moe_out, frac, prob = MixtralSparseMoeBlock(
            cfg, name="block_sparse_moe")(h, token_mask)
        return x + moe_out, layer_cache, frac, prob


class _ScanBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, carry, layer_cache):
        x, cos, sin, mask, tok_mask, cache_index, det, frac_sum, prob_sum = carry
        y, layer_cache, frac, prob = MixtralBlock(self.config, name="block")(
            x, cos, sin, mask, tok_mask, layer_cache, cache_index, det)
        return (y, cos, sin, mask, tok_mask, cache_index, det,
                frac_sum + frac, prob_sum + prob), layer_cache


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

        E = cfg.num_local_experts
        zero_e = jnp.zeros((E,), jnp.float32)
        remat_policy = resolve_remat_policy(cfg.remat_policy)
        if cfg.scan_layers:
            block_cls = _ScanBlock
            if cfg.remat and cache is None:
                block_cls = nn.remat(_ScanBlock, prevent_cse=False,
                                     policy=remat_policy)
            scan = nn.scan(block_cls, variable_axes={"params": 0},
                           split_rngs={"params": True, "dropout": True},
                           length=cfg.num_hidden_layers, metadata_params={})
            (x, *_, frac_sum, prob_sum), cache = scan(cfg, name="layers")(
                (x, cos, sin, mask, tok_mask, cache_index, deterministic,
                 zero_e, zero_e), cache)
        else:
            block_cls = nn.remat(MixtralBlock, prevent_cse=False,
                                 policy=remat_policy) \
                if (cfg.remat and cache is None) else MixtralBlock
            frac_sum, prob_sum = zero_e, zero_e
            new_cache = [] if cache is not None else None
            for i in range(cfg.num_hidden_layers):
                layer_cache = None if cache is None else \
                    jax.tree_util.tree_map(lambda c: c[i], cache)
                x, layer_cache, frac, prob = block_cls(cfg, name=f"layers_{i}")(
                    x, cos, sin, mask, tok_mask, layer_cache, cache_index,
                    deterministic)
                frac_sum, prob_sum = frac_sum + frac, prob_sum + prob
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
        return (x, aux) if cache is None else (x, aux, cache)


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
        out = MixtralModel(cfg, name="model")(
            input_ids, positions, attention_mask, deterministic, cache,
            cache_index)
        if cache is not None:
            hidden, aux, cache = out
        else:
            hidden, aux = out
        with jax.named_scope(head_scope(cache)):
            logits, lm = lm_head_output(self, cfg, hidden, labels, cache)
            if cache is not None:
                return logits, cache
            if labels is None:
                return logits
            if lm is None:
                lm = cross_entropy_loss(logits, shift_labels(labels))
        return lm + cfg.router_aux_loss_coef * aux

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.config
        return init_kv_cache(batch, max_len, cfg.num_key_value_heads,
                             cfg.head_dim, n_layers=cfg.num_hidden_layers,
                             dtype=dtype)

    @staticmethod
    def partition_rules(config: "MixtralConfig"):
        """TP for attention (Megatron layout) + EP for the stacked expert
        weights (``expert`` mesh axis on the leading E dim)."""
        L = (None,) if config.scan_layers else ()
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"(q_proj|k_proj|v_proj)/kernel", P(*L, None, "model")),
            (r"o_proj/kernel", P(*L, "model", None)),
            (r"block_sparse_moe/(w1|w2|w3)", P(*L, "expert", None, None)),
            (r"lm_head/kernel", P(None, "model")),
        ]
