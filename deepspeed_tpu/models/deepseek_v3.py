"""DeepSeek-V3-shaped decoder for training (HF ``modeling_deepseek_v3.py``;
the language model of Kimi-VL-A3B and Moonlight is this shape): latent
attention, one or more leading dense layers and then expert layers, a
sigmoid router that selects by a bias, shared experts, and ONE CHIP'S SHARE
of each expert layer.

*Latent attention* (MLA, every layer): ``q_proj`` gives each head a query of
``qk_nope_head_dim + qk_rope_head_dim``; ``kv_a_proj_with_mqa`` gives the
``kv_lora_rank`` latent and ONE rotary key shared by all heads; the latent is
RMS-normed (``kv_a_layernorm``) and ``kv_b_proj`` expands it to each head's
non-rotary key and its value of ``v_head_dim``. RoPE turns only the rotary
columns of the queries and the shared key. Keys and queries are then
``qk_nope + qk_rope`` wide and values ``v_head_dim``: the flash kernels take
the two widths as they are (``ops/pallas/flash_attention.py``).

*Layers of two kinds*: the first ``first_k_dense_replace`` layers carry a
dense SwiGLU of ``intermediate_size`` (unrolled, ``layers_<i>``), the others
the expert layer (scanned, ``layers/block``).

*The router and the experts as configuration data*: scores
``sigmoid(W_g x)`` (or softmax) in float32 over ALL ``router_experts``;
the choice is the top-k of ``score + e_score_correction_bias`` (a buffer of
the published model: here a parameter the optimizer never updates,
``frozen_parameters``; the published balancing rule moves it where
``router_bias_update_rate`` says, through the training call's
``"param_deltas"``), the weights are the SCORES of the chosen experts,
divided by their sum (``norm_topk_prob``) and times
``routed_scaling_factor``; ``n_shared_experts`` shared experts run as one
SwiGLU beside the routed ones.

*One chip's share*: the layer HOLDS ``n_routed_experts`` experts, the
deployment's ``first_expert .. first_expert + n_routed_experts`` of the
router's ``router_experts``. It routes over all of them, computes the part
of the result its own experts give through ``mixtral._routed_experts``
(pairs routed elsewhere add zero, no token is dropped, nothing stands in for
the absent chips), adds the shared expert whole, and that partial result
goes on. The weights' normalisation is over all chosen experts, held or not.
A share trained ALONE gives its router a partial gradient (the absent
experts' terms are missing) that starves the held experts within tens of
steps; ``router_trainable=False`` leaves the router's weights to the
deployment that sees every expert. What a held share needs besides is
``mixtral.py``'s, one copy for this file and ``zaya.py``: the check of the
held range and of the mesh (``_check_held_share``), the balancing rule's
step from a step's choices (``_balancing_delta``), the two gauges of
``report_expert_load`` (``_held_load_gauges``), and the compact row buffer a
share of at most a quarter runs on (``_compact_rows``; its gauge
``_compact_hit_gauge``).

Training only: the latent paged cache and absorbed decode are not built.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import REMAT_ATTN_OUT, REMAT_MLP, REMAT_QKV
from .layers import (RMSNorm, apply_rotary, cross_entropy_loss, device_part,
                     dot_product_attention, head_scope, lm_head_output,
                     model_dense, name_if_kept, resolve_remat_policy,
                     rotary_embedding, shift_labels)
from .llama import LlamaConfig
from .mixtral import (_balancing_delta, _check_held_share,
                      _compact_hit_gauge, _held_load_gauges, _routed_experts,
                      expert_offers)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(LlamaConfig):
    # -- latent attention ------------------------------------------------
    kv_lora_rank: int = 512
    #: the published models of this size have no low-rank query path
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: the checkpoint's rotary columns are (real, imaginary) pairs: they are
    #: de-interleaved (a fixed permutation) before rotate-half RoPE, as the
    #: published class does by default
    rope_interleave: bool = True
    #: no column rotates (the published key of ``kimi_linear``: the model's
    #: recurrent layers carry position): the ``qk_rope_head_dim`` columns of
    #: the queries and the one shared key join the product as projected, and
    #: ``cos`` / ``sin`` are never read
    mla_use_nope: bool = False
    # -- layers of two kinds ---------------------------------------------
    #: leading layers whose feed-forward is a dense SwiGLU of
    #: ``intermediate_size``; every later layer is an expert layer
    first_k_dense_replace: int = 1
    # -- the router and the experts --------------------------------------
    moe_intermediate_size: int = 1408
    #: experts HELD here (the stacked kernels' leading size)
    n_routed_experts: int = 64
    #: the router's width, the deployment's experts; None: all are held
    router_experts: Optional[int] = None
    #: which of the router's experts is the first held one
    first_expert: int = 0
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    #: grouped selection; only the identity (one group) is built
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    #: "sigmoid" | "softmax"
    scoring_func: str = "sigmoid"
    #: "noaux_tc": choose by score + bias; "greedy": by score (no bias
    #: parameter exists)
    topk_method: str = "noaux_tc"
    #: standard deviation the bias is seeded with (the published buffer
    #: starts at zero and is moved by the balancing rule, which is not
    #: built; at zero, selection and weighting cannot be told apart)
    router_bias_init: float = 0.0
    #: the published training recipe's balancing rule (DeepSeek-V3, "auxiliary-
    #: loss-free load balancing"): after every step each expert's bias falls by
    #: this rate if the step sent it more than the mean number of tokens and
    #: rises by it if fewer; counted over ALL the router's experts, held or not.
    #: The training call hands the deltas to the engine beside its loss
    #: (``"param_deltas"``); 0 leaves the bias where it is
    router_bias_update_rate: float = 0.0
    #: False: the optimizer never moves the router's weights ``mlp/gate``
    #: (their gradient is computed all the same). For ONE CHIP'S SHARE
    #: trained alone: its router gradient lacks the terms of the experts on
    #: the other chips, and applied for tens of steps it teaches the router
    #: to send every token to experts that are not here (the held experts'
    #: load falls from its level share to a thousandth of it, PERF.md
    #: section 6). A deployment's router sees all its experts
    router_trainable: bool = True
    #: the training call returns ``(loss, {"moe_rows_max_over_mean",
    #: "moe_held_rows_over_expected"})``, registry gauges of the train
    #: engine, and where the held share is small enough for the compact row
    #: buffer also ``"moe_compact_hit_share"``: the step's expert layers
    #: whose held pairs fitted it, over all of them
    report_expert_load: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def router_width(self) -> int:
        return self.router_experts or self.n_routed_experts

    @staticmethod
    def kimi_vl_a3b(**over):
        """The language model of ``moonshotai/Kimi-VL-A3B-Instruct``
        (``config.json`` ``text_config``) as published: 27 layers, 64
        experts of 1408, top-6 of sigmoid scores, 2 shared experts."""
        return DeepseekV3Config(**{**dict(
            vocab_size=163840, hidden_size=2048, intermediate_size=11264,
            moe_intermediate_size=1408, num_hidden_layers=27,
            num_attention_heads=16, num_key_value_heads=16,
            max_position_embeddings=131072, rope_theta=800000.0,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_routed_experts=64, num_experts_per_tok=6,
            n_shared_experts=2, first_k_dense_replace=1,
            routed_scaling_factor=2.446), **over})

    @staticmethod
    def tiny(**over):
        return DeepseekV3Config(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=64, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            n_routed_experts=8, num_experts_per_tok=3, n_shared_experts=2,
            router_bias_init=0.1, remat=False), **over})


#: the selection bias: a buffer of the published model, here a parameter
#: that only the balancing rule moves
BIAS = "e_score_correction_bias"


def _check(cfg):
    """What is not built raises before any parameter is made; the held
    range and the `expert` mesh axis are ``mixtral._check_held_share``'s."""
    if cfg.q_lora_rank is not None:
        raise NotImplementedError("q_lora_rank: the low-rank query path is "
                                  "not built")
    if (cfg.n_group, cfg.topk_group) != (1, 1):
        raise NotImplementedError("grouped expert selection (n_group > 1) "
                                  "is not built")
    if cfg.scoring_func not in ("sigmoid", "softmax") or \
            cfg.topk_method not in ("noaux_tc", "greedy"):
        raise ValueError(f"scoring_func {cfg.scoring_func!r} / topk_method "
                         f"{cfg.topk_method!r}")
    _check_held_share(cfg.first_expert, cfg.n_routed_experts,
                      cfg.router_width)


def _rotate(x, cos, sin, interleave):
    """RoPE over ``x [B, T, heads, qk_rope_head_dim]``."""
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rotary(x, cos, sin)


def _kv_norm(cfg, latent):
    return RMSNorm(eps=cfg.rms_norm_eps, name="kv_a_layernorm")(latent)


class DeepseekV3Attention(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, cos, sin, mask):
        cfg = self.config
        B, T, _ = x.shape
        H, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, row_parallel=row)
        # ds.attn_proj holds the low-rank kv path and its norm;
        # ds.attention the core alone
        with jax.named_scope("ds.attn_proj"):
            q = dense(H * (dn + dr), "q_proj")(x).reshape(B, T, H, dn + dr)
            kv_a = dense(cfg.kv_lora_rank + dr, "kv_a_proj_with_mqa")(x)
            latent, k_rot = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
            kv = dense(H * (dn + dv), "kv_b_proj")(
                _kv_norm(cfg, latent)).reshape(B, T, H, dn + dv)
            k_nope, v = jnp.split(kv, [dn], axis=-1)
            if cfg.mla_use_nope:
                k_rot = k_rot[:, :, None, :]
            else:
                q_nope, q_rot = jnp.split(q, [dn], axis=-1)
                q_rot = _rotate(q_rot, cos, sin, cfg.rope_interleave)
                k_rot = _rotate(k_rot[:, :, None, :], cos, sin,
                                cfg.rope_interleave)
                q = jnp.concatenate([q_nope, q_rot], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rot, (B, T, H, dr))], axis=-1)
            # as the flash kernel takes them: the replay then runs neither
            # projection, norm, rotation nor the key's assembly
            q, k, v = (name_if_kept(t, REMAT_QKV) for t in (q, k, v))
        out = dot_product_attention(
            q, k, v, bias=mask, causal=True,
            attention_impl=cfg.attention_impl,
            scale=float(cfg.qk_head_dim) ** -0.5,
            flash_block_q=cfg.flash_block_q, flash_block_k=cfg.flash_block_k)
        with jax.named_scope("ds.attn_proj"):
            return dense(cfg.hidden_size, "o_proj", row=True)(
                out.reshape(B, T, H * dv))


def route(cfg, logits, bias):
    """Router logits ``[..., E]`` (float32) -> ``(weights, experts)``, both
    ``[..., K]``: the choice is by score + bias, the weights are the scores
    themselves, normalised over ALL K chosen experts and scaled."""
    scores = jax.nn.sigmoid(logits) if cfg.scoring_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choice = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, idx


class _SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))`` of ``features`` columns under the
    trace scope ``trace_scope``."""

    config: DeepseekV3Config
    features: int
    trace_scope: str

    @nn.compact
    def __call__(self, x):
        dense = lambda feats, name, row=False: model_dense(
            self.config, feats, name, row_parallel=row)
        with jax.named_scope(self.trace_scope):
            gate = name_if_kept(dense(self.features, "gate_proj")(x),
                                REMAT_MLP)
            up = name_if_kept(dense(self.features, "up_proj")(x), REMAT_MLP)
            return dense(self.config.hidden_size, "down_proj", row=True)(
                nn.silu(gate) * up)


def _shared_experts(cfg, x):
    """The ``n_shared_experts`` shared experts: one SwiGLU of their summed
    width, over every token."""
    return _SwiGLU(cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
                   "ds.moe_shared", name="shared_experts")(x)


class DeepseekV3MoE(nn.Module):
    """The expert layer at this chip's share: ``(out [B, T, H], rows [G],
    bias_delta [E] or None)``, ``rows`` the (token, expert) pairs each HELD
    expert computed, ``bias_delta`` what the balancing rule adds to the
    selection bias after this step (the sign rule over ALL ``E`` columns,
    held or not: the load it levels is the deployment's, not this chip's)."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, H = x.shape
        E, G, K = cfg.router_width, cfg.n_routed_experts, \
            cfg.num_experts_per_tok
        I = cfg.moe_intermediate_size
        with jax.named_scope("ds.moe_router"):
            gate = self.param("gate", nn.initializers.lecun_normal(), (H, E),
                              jnp.float32)
            bias = self.param(
                BIAS, nn.initializers.normal(cfg.router_bias_init), (E,),
                jnp.float32) if cfg.topk_method == "noaux_tc" else None
            logits = jnp.einsum("bth,he->bte", x, gate.astype(x.dtype),
                                preferred_element_type=jnp.float32)
            topk_w, topk_idx = route(cfg, logits, bias)
            delta = None
            if bias is not None and cfg.router_bias_update_rate:
                # handed to the engine as "param_deltas", never a gradient
                delta = _balancing_delta(topk_idx, E,
                                         cfg.router_bias_update_rate)
        # each expert's kernels seeded over its own fan-in
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = self.param("w1", init, (G, H, I), jnp.float32)  # gate
        w3 = self.param("w3", init, (G, H, I), jnp.float32)  # up
        w2 = self.param("w2", init, (G, I, H), jnp.float32)  # down
        with jax.named_scope("ds.moe_experts"):
            out, rows = _routed_experts(
                x.reshape(-1, H), w1, w2, w3, topk_w.reshape(-1, K),
                topk_idx.reshape(-1, K), cfg.first_expert, E)
        out = out.reshape(B, T, H)
        if cfg.n_shared_experts:
            out = out + _shared_experts(cfg, x)
        return out, rows, delta


class DeepseekV3Block(nn.Module):
    """One decoder layer; ``dense`` says which kind. Returns ``(x, rows,
    bias_delta)``: for a dense layer ``rows [G]`` zeros and no delta."""

    config: DeepseekV3Config
    dense: bool = False

    @nn.compact
    def __call__(self, x, cos, sin, mask):
        cfg = self.config
        # ds.norm / ds.residual as in models/llama.py LlamaBlock
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        attn = DeepseekV3Attention(cfg, name="self_attn")(h, cos, sin, mask)
        with jax.named_scope("ds.residual"):
            x = x + name_if_kept(attn, REMAT_ATTN_OUT)
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps,
                        name="post_attention_layernorm")(x)
        if self.dense:
            out = _SwiGLU(cfg, cfg.intermediate_size, "ds.mlp",
                          name="mlp")(h)
            rows, delta = jnp.zeros((cfg.n_routed_experts,), jnp.float32), None
        else:
            out, rows, delta = DeepseekV3MoE(cfg, name="mlp")(h)
        with jax.named_scope("ds.residual"):
            x = x + out
        return x, rows.astype(jnp.float32), delta


class _ScanBlock(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, carry, _):
        x, cos, sin, mask = carry
        x, rows, delta = DeepseekV3Block(self.config, name="block")(
            x, cos, sin, mask)
        return (x, cos, sin, mask), (rows, delta)


class DeepseekV3Model(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None):
        """``(final-normed hidden [B, T, H], rows [L, G], bias deltas)``:
        ``rows`` the pairs each held expert computed in each of the ``L``
        expert layers; the deltas ``{parameter path: [.., E]}`` of the
        balancing rule, empty where it is off."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        cos, sin = rotary_embedding(positions, cfg.qk_rope_head_dim,
                                    cfg.rope_theta, dtype=x.dtype)
        mask = None if attention_mask is None else jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                jnp.float32)

        first = min(cfg.first_k_dense_replace, cfg.num_hidden_layers)
        scanned = cfg.scan_layers and cfg.num_hidden_layers > first
        # the scanned layers offer what they name; an unrolled layer does
        # not: XLA merges its replay with its forward pass, so its values
        # are held already (kimi 8k's dense layer shows no replay)
        remat = lambda cls, offered=(): nn.remat(
            cls, prevent_cse=False, policy=resolve_remat_policy(
                cfg.remat_policy, offered)) if cfg.remat else cls
        rows = jnp.zeros((0, cfg.n_routed_experts), jnp.float32)
        deltas = {}
        # ds.layer_stack: what the loop over the layers costs beyond what
        # the layers' own scopes name (models/llama.py LlamaModel)
        with jax.named_scope("ds.layer_stack"):
            for i in range(first):
                x, _, _ = remat(DeepseekV3Block)(cfg, dense=True,
                                                 name=f"layers_{i}")(x, cos, sin,
                                                                     mask)
            if scanned:
                scan = nn.scan(remat(_ScanBlock, remat_offers(
                    cfg, x, cfg.num_hidden_layers - first)),
                               variable_axes={"params": 0},
                               split_rngs={"params": True, "dropout": True},
                               length=cfg.num_hidden_layers - first,
                               metadata_params={})
                (x, *_), (rows, delta) = scan(cfg, name="layers")(
                    (x, cos, sin, mask), None)
                deltas[f"{self.name}/layers/block/mlp/{BIAS}"] = delta
            else:
                for i in range(first, cfg.num_hidden_layers):
                    x, r, delta = remat(DeepseekV3Block)(
                        cfg, name=f"layers_{i}")(x, cos, sin, mask)
                    rows = jnp.concatenate([rows, r[None]])
                    deltas[f"{self.name}/layers_{i}/mlp/{BIAS}"] = delta
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        return x, rows, {k: jax.lax.stop_gradient(v)
                         for k, v in deltas.items() if v is not None}


class DeepseekV3ForCausalLM(nn.Module):
    """``LlamaForCausalLM``'s training interface (the train engine is
    agnostic): logits without labels, the token-mean cross entropy with
    them. No auxiliary loss: the published code computes none."""

    config: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "latent attention is built for training only: no latent "
                "cache, no absorbed decode")
        hidden, rows, deltas = DeepseekV3Model(cfg, name="model")(
            input_ids, positions, attention_mask)
        with jax.named_scope(head_scope(None)):
            logits, loss = lm_head_output(self, cfg, hidden, labels, None)
            if labels is None:
                return logits
            if loss is None:
                loss = cross_entropy_loss(logits, shift_labels(labels))
        named = {"param_deltas": deltas} if deltas else {}
        if not cfg.report_expert_load:
            return (loss, named) if named else loss
        pairs = input_ids.size * cfg.num_experts_per_tok      # of one layer
        # the deployment's level load of this chip: its share of the pairs
        expected = max(rows.shape[0], 1) * pairs \
            * cfg.n_routed_experts / cfg.router_width
        return loss, {
            **named, **_held_load_gauges(jnp.sum(rows, axis=0), expected),
            **_compact_hit_gauge(rows, pairs, cfg.router_width)}

    @staticmethod
    def frozen_parameters(config: "DeepseekV3Config"):
        """Parameter paths the optimizer never moves (no gradient step, no
        weight decay): the router's selection bias is a buffer of the
        published model (only the balancing rule moves it); the router's
        weights too where ``router_trainable`` is off."""
        return [BIAS] + ([] if config.router_trainable else [r"mlp/gate$"])

    @staticmethod
    def partition_rules(config: "DeepseekV3Config"):
        """Tensor parallelism over heads and feed-forward columns (Megatron
        layout); the latent projection, the router and the held experts are
        whole on every chip."""
        L = (None,) if config.scan_layers else ()
        col = r"(q_proj|kv_b_proj|gate_proj|up_proj)/kernel"
        row = r"(o_proj|down_proj)/kernel"
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"layers_\d+/.*" + col, P(None, "model")),
            (r"layers_\d+/.*" + row, P("model", None)),
            (col, P(*L, None, "model")),
            (row, P(*L, "model", None)),
            (r"lm_head/kernel", P(None, "model")),
        ]


def remat_offers(cfg, x, applications: int):
    """What an expert ``DeepseekV3Block`` names, as ``DeepseekV3Model``
    offers it to ``layers.resolve_remat_policy`` for a stream ``x [B, T,
    hidden]`` through its ``applications`` scanned layers, costliest replay a
    byte first (kimi 8k, ms of replay a step for a GB kept: the attention's
    output projection 2.2 for 0.17, the shared experts' gate and up products
    in ``_SwiGLU`` 5.0 for 0.46, q, k, v as the flash kernel takes them --
    every head's key holds the shared rotary columns -- 5.6 for 0.67, the
    held experts' gate and up products 2.7 for 0.35, their sorted rows 2.0
    for 0.25)."""
    per_column = device_part(x.shape[0]) * x.shape[1] * x.dtype.itemsize * \
        applications
    heads = cfg.num_attention_heads * (2 * cfg.qk_head_dim + cfg.v_head_dim)
    shared = cfg.n_shared_experts * cfg.moe_intermediate_size
    return ((REMAT_ATTN_OUT, cfg.hidden_size * per_column),
            (REMAT_MLP, 2 * shared * per_column),
            (REMAT_QKV, heads * per_column),
            *expert_offers(x, cfg.num_experts_per_tok,
                           cfg.moe_intermediate_size, cfg.n_routed_experts,
                           cfg.router_width, applications))
