"""ZAYA1-shaped decoder for training (Zyphra's ZAYA1-8B, ``model_type``
``zaya``; the CCA paper arXiv 2510.04476 and the ZAYA1 report arXiv
2511.17127): compressed convolutional attention ahead of the flash kernels,
an expert sublayer whose router is an MLP with a state carried from layer to
layer and may choose an expert that computes nothing, residuals scaled by
learned vectors, a tied head, and ONE CHIP'S SHARE of each expert sublayer.

A layer is two sublayers, each on the RMS-normed stream ``h``.

*CCA* (``ZayaAttention``): ``q~ = W_q h`` (``Hq`` heads of ``D``) and
``k~ = W_k h`` (``Hkv`` heads) are laid side by side and pass two causal
convolutions along the sequence (zeros before position 0): a depthwise one of
``cca_time0`` taps, then one grouped by head of ``cca_time1`` taps. To the
result is added the query-key mean: each query head gets half of (itself +
its key head) of the unconvolved ``q~, k~``, each key head half of (the mean
of its query heads + itself). Each head's query and key is then brought to
length ``sqrt(D)`` in float32 and the key times a learned temperature, one a
key head. The value is ``[W_v1 h_t ; W_v2 h_{t-1}]``: its second half comes
from the token before. Rotate-half RoPE turns the first
``partial_rotary_factor`` of each head's columns; causal grouped-query
attention at scale ``D ** -0.5`` runs through ``dot_product_attention``
(the flash kernels), and ``W_o`` brings the ``Hq * D`` latent back.

*The expert sublayer* (``ZayaMoE``): the router projects ``h`` down to
``router_hidden_size``, adds the previous layer's router state times a learned
vector (the sum is this layer's state and goes on beside the hidden state),
RMS-norms it and scores ``router_experts`` experts AND a skip expert through
a three-layer GELU MLP; softmax in float32; the choice is the top
``num_experts_per_tok`` of probability + a balancing bias (a buffer: no
gradient, moved only by the sign rule through ``"param_deltas"``), the weight
the probability itself. A chosen expert adds ``p * SwiGLU_e(h)``; the skip
expert adds ``p * h`` and computes nothing.

*One chip's share*, as ``deepseek_v3.py``: the sublayer HOLDS
``n_routed_experts`` experts, ``first_expert ..`` of the router's
``router_experts``; it routes over all of them and the skip column, computes
its own experts' part through ``mixtral._routed_experts`` (the skip column
lies past every held range, so the grouped products never see its rows) and,
alike on every chip, the skip expert's part. ``router_trainable=False`` leaves
the whole router to the deployment that sees every expert.

*Residual* of either sublayer with output ``y``: ``x <- (x + b_r) * a_r +
(y + b_y) * a_y``, four learned vectors a sublayer.

*What a remat'ed layer keeps* is the rule's (``layers.keep_for_room``): the
block names its costliest residuals, each where the backward of its consumer
reads it, and ``remat_offers`` counts them for ``resolve_remat_policy``.

Training only: no cache holds the one token of ``h``, of the convolutions'
input and of the first convolution's output that CCA's decode would need.
"""

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import (REMAT_CCA_MIX, REMAT_MOE_OUT, REMAT_QKV,
                          REMAT_ROUTER)
from .layers import (RMSNorm, apply_rotary_partial, causal_conv,
                     cross_entropy_loss, device_part, dot_product_attention,
                     head_scope, lm_head_output, model_dense, name_if_kept,
                     repeat_kv, resolve_remat_policy, rotary_embedding,
                     shift_labels, shift_tokens)
from .llama import LlamaConfig
from .mixtral import (_balancing_delta, _check_held_share, _held_load_gauges,
                      _routed_experts, expert_offers)


@dataclasses.dataclass(frozen=True)
class ZayaConfig(LlamaConfig):
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    #: attention runs in a latent of ``heads * 128`` columns, not of the
    #: hidden size
    head_dim_override: Optional[int] = 128
    max_position_embeddings: int = 131072
    rope_theta: float = 5e6
    tie_word_embeddings: bool = True
    # -- compressed convolutional attention ------------------------------
    #: taps of the depthwise and of the head-grouped causal convolution
    cca_time0: int = 2
    cca_time1: int = 2
    #: share of each head's columns RoPE turns (the first ones)
    partial_rotary_factor: float = 0.5
    # -- the router and the experts --------------------------------------
    router_hidden_size: int = 256
    moe_intermediate_size: int = 2048
    #: experts HELD here (the stacked kernels' leading size)
    n_routed_experts: int = 16
    #: the router's experts, the deployment's; None: all are held
    router_experts: Optional[int] = None
    #: which of the router's experts is the first held one
    first_expert: int = 0
    num_experts_per_tok: int = 1
    #: standard deviation the balancing bias is seeded with
    router_bias_init: float = 0.0
    #: the sign rule's step (``deepseek_v3.py``): after every step a
    #: column's bias falls by this if the step sent it more than the mean
    #: number of tokens and rises if fewer, over ALL columns, the skip
    #: expert's too; handed to the engine as ``"param_deltas"``
    router_bias_update_rate: float = 0.0
    #: False: the optimizer never moves the router (down-projection, state
    #: scale, norm, MLP); a share trained alone starves its own experts
    #: (``deepseek_v3.py``, PERF.md section 6)
    router_trainable: bool = True
    #: the training call names ``moe_rows_max_over_mean``,
    #: ``moe_held_rows_over_expected`` and ``moe_skip_share`` beside its
    #: loss, registry gauges of the train engine
    report_expert_load: bool = False

    @property
    def router_width(self) -> int:
        """The router's columns: its experts and, past them, the skip
        expert (chosen, it adds ``p * h`` and computes nothing)."""
        return (self.router_experts or self.n_routed_experts) + 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @staticmethod
    def zaya1_8b(**over):
        """``Zyphra/ZAYA1-8B`` as published (the defaults)."""
        return ZayaConfig(**over)

    @staticmethod
    def tiny(**over):
        return ZayaConfig(**{**dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2,
            head_dim_override=8, max_position_embeddings=64,
            rope_theta=10000.0, router_hidden_size=16,
            moe_intermediate_size=16, n_routed_experts=8,
            router_bias_init=0.05, remat=False), **over})


#: the balancing bias: a buffer, here a parameter only the rule moves
BIAS = "balancing_bias"


def _check(cfg):
    _check_held_share(cfg.first_expert, cfg.n_routed_experts,
                      cfg.router_width - 1)
    if cfg.num_attention_heads % cfg.num_key_value_heads or \
            cfg.num_key_value_heads % 2:
        raise ValueError("query heads divide over the key heads, and the "
                         "value's halves over an even number of them")


def _about(mean, std):
    """Seeded normal about ``mean``: a vector the forward pass would not
    notice at exactly one or zero is one ``correct`` cannot see."""
    def init(key, shape, dtype=jnp.float32):
        return mean + std * jax.random.normal(key, shape, dtype)
    return init


def _qk_mean(q, k):
    """The query-key mean of the unconvolved ``q~ [B, T, Hq, D]`` and
    ``k~ [B, T, Hkv, D]``: what is added to the convolved queries and keys."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    grouped = q.reshape(B, T, Hkv, Hq // Hkv, D)
    mq = 0.5 * (grouped + k[:, :, :, None, :])
    mk = 0.5 * (jnp.mean(grouped, axis=3) + k)
    return mq.reshape(B, T, Hq, D), mk


def _unit_length(x):
    """Rows of ``x [..., D]`` at length ``sqrt(D)``, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))


def _temperature(k, tau):
    """``k [B, T, Hkv, D]`` (float32) times one temperature a key head."""
    return k * tau.astype(jnp.float32)[:, None]


def _shifted_value(v1, v2):
    """``[W_v1 h_t ; W_v2 h_{t-1}]`` from the two projections of ``h``."""
    return jnp.concatenate([v1, shift_tokens(v2)], axis=-1)


def _rotary(cfg, x, cos, sin):
    return apply_rotary_partial(x, cos, sin, cfg.rotary_dim)


def _softmax_scale(cfg):
    return float(cfg.head_dim) ** -0.5


class ZayaAttention(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask):
        cfg = self.config
        B, T, _ = x.shape
        Hq, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        C, G = (Hq + Hkv) * D, Hq + Hkv
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, row_parallel=row)
        # offered as the projections write them (remat_offers): the
        # convolutions, the mean and the unit length read them from there
        proj = lambda feats, name: name_if_kept(dense(feats, name)(x),
                                                REMAT_QKV)
        mixed = lambda t: name_if_kept(t, REMAT_CCA_MIX)
        with jax.named_scope("ds.attn_proj"):
            q0 = proj(Hq * D, "q_proj")
            k0 = proj(Hkv * D, "k_proj")
            v1 = proj(Hkv * D // 2, "v1_proj")
            v2 = proj(Hkv * D // 2, "v2_proj")
        # what CCA adds ahead of the kernels, beside the projections
        with jax.named_scope("ds.cca_mix"):
            wa = self.param("conv_a_weight", _about(0.0, cfg.cca_time0 ** -0.5),
                            (cfg.cca_time0, C), jnp.float32)
            ba = self.param("conv_a_bias", _about(0.0, 0.05), (C,),
                            jnp.float32)
            wb = self.param("conv_b_weight",
                            _about(0.0, (cfg.cca_time1 * D) ** -0.5),
                            (cfg.cca_time1, G, D, D), jnp.float32)
            bb = self.param("conv_b_bias", _about(0.0, 0.05), (C,),
                            jnp.float32)
            tau = self.param("temperature", _about(1.0, 0.1), (Hkv,),
                             jnp.float32)
            dt = x.dtype
            c = jnp.concatenate([q0, k0], axis=-1)
            # offered where this scope's own backward reads them: the
            # first convolution's output (the grouped one's weight
            # gradient), q and k ahead of their unit length
            c = mixed(causal_conv(c, wa.astype(dt), ba.astype(dt)))
            c = causal_conv(c, wb.astype(dt), bb.astype(dt))
            mq, mk = _qk_mean(q0.reshape(B, T, Hq, D),
                              k0.reshape(B, T, Hkv, D))
            q = mixed(c[..., :Hq * D].reshape(B, T, Hq, D) + mq)
            k = mixed(c[..., Hq * D:].reshape(B, T, Hkv, D) + mk)
            q = _unit_length(q).astype(dt)
            k = _temperature(_unit_length(k), tau).astype(dt)
            v = _shifted_value(v1, v2).reshape(B, T, Hkv, D)
        with jax.named_scope("ds.attn_proj"):
            q, k = _rotary(cfg, q, cos, sin), _rotary(cfg, k, cos, sin)
            # the differentiable kernels take equal head counts
            k, v = repeat_kv(k, Hq // Hkv), repeat_kv(v, Hq // Hkv)
        out = dot_product_attention(
            q, k, v, bias=mask, causal=True,
            attention_impl=cfg.attention_impl, scale=_softmax_scale(cfg),
            flash_block_q=cfg.flash_block_q, flash_block_k=cfg.flash_block_k)
        with jax.named_scope("ds.attn_proj"):
            return dense(cfg.hidden_size, "o_proj", row=True)(
                out.reshape(B, T, Hq * D))


def _carry_state(r, gamma, state):
    """This layer's router state from its down-projection ``r`` and the
    previous layer's ``state``."""
    return r + gamma * state


def route(cfg, logits, bias):
    """Router logits ``[..., E + 1]`` (float32) -> ``(weights, columns)``,
    both ``[..., K]``: the choice is by probability + bias, the weights are
    the probabilities themselves."""
    p = jax.nn.softmax(logits, axis=-1)
    choice = p if bias is None else \
        p + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    # offered (remat_offers) ahead of the gather, whose backward reads it
    idx = name_if_kept(idx, REMAT_ROUTER)
    return name_if_kept(jnp.take_along_axis(p, idx, axis=-1),
                        REMAT_ROUTER), idx


class ZayaRouter(nn.Module):
    """``(weights [B, T, K], columns [B, T, K], state [B, T, R], bias
    delta [E + 1] or None)``. Float32 throughout, its products at the
    highest precision: the choice is a step function of the logits, and the
    whole router is a sixtieth of a layer's operations."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, h, state):
        cfg = self.config
        H, R, E = cfg.hidden_size, cfg.router_hidden_size, cfg.router_width
        f32, lecun = jnp.float32, nn.initializers.lecun_normal()
        small = _about(0.0, 0.02)
        # float32 master parameters, read in float32 whatever the engine's
        # compute copy holds
        param = lambda name, init, *shape: self.param(
            name, init, shape, f32).astype(f32)
        down = param("down_kernel", lecun, H, R)
        r = jnp.einsum("bth,hr->btr", h, down.astype(h.dtype),
                       preferred_element_type=f32) \
            + param("down_bias", small, R)
        # offered (remat_offers), each where the backward reads it: the
        # state under its norm, the norm's output under fc1's weight
        # gradient, a GELU's input, the logits under the softmax (route names
        # the choice and its weight)
        kept = lambda t: name_if_kept(t, REMAT_ROUTER)
        state = kept(_carry_state(
            r, param("state_scale", _about(1.0, 0.1), R), state))
        # RMSNorm, its scale seeded about one (at one it only rescales)
        z = kept(state * jax.lax.rsqrt(
            jnp.mean(state * state, axis=-1, keepdims=True)
            + cfg.rms_norm_eps) * param("norm_scale", _about(1.0, 0.1), R))
        dot = lambda a, w: jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
        gelu = lambda a: nn.gelu(a, approximate=False)
        z = gelu(kept(dot(z, param("fc1_kernel", lecun, R, R))
                      + param("fc1_bias", small, R)))
        z = gelu(kept(dot(z, param("fc2_kernel", lecun, R, R))
                      + param("fc2_bias", small, R)))
        logits = kept(dot(z, param("fc3_kernel", lecun, R, E)))
        bias = param(BIAS, nn.initializers.normal(cfg.router_bias_init), E)
        w, idx = route(cfg, logits, bias)
        delta = _balancing_delta(idx, E, cfg.router_bias_update_rate) \
            if cfg.router_bias_update_rate else None
        return w, idx, state, delta


def _skip_expert(h, weight):
    """The expert that computes nothing: ``weight [B, T]`` (float32, the
    skip column's probability where it was chosen, else zero) times ``h``."""
    return (weight[..., None] * h.astype(jnp.float32)).astype(h.dtype)


class ZayaMoE(nn.Module):
    """The expert sublayer at this chip's share: ``(out [B, T, H], router
    state, rows [G], tokens that chose the skip expert, bias delta)``."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, h, state):
        cfg = self.config
        B, T, H = h.shape
        G, K, I = (cfg.n_routed_experts, cfg.num_experts_per_tok,
                   cfg.moe_intermediate_size)
        with jax.named_scope("ds.moe_router"):
            w, idx, state, delta = ZayaRouter(cfg, name="router")(h, state)
        # each expert's kernels seeded over its own fan-in
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w1 = self.param("w1", init, (G, H, I), jnp.float32)  # gate
        w3 = self.param("w3", init, (G, H, I), jnp.float32)  # up
        w2 = self.param("w2", init, (G, I, H), jnp.float32)  # down
        with jax.named_scope("ds.moe_experts"):
            out, rows = _routed_experts(
                h.reshape(-1, H), w1, w2, w3, w.reshape(-1, K),
                idx.reshape(-1, K), cfg.first_expert, cfg.router_width)
        with jax.named_scope("ds.moe_skip"):
            chose = idx == cfg.router_width - 1
            out = out.reshape(B, T, H) + _skip_expert(
                h, jnp.sum(jnp.where(chose, w, 0.0), axis=-1))
            skipped = jnp.sum(chose, dtype=jnp.float32)
        return out, state, rows.astype(jnp.float32), skipped, delta


class ScaledResidual(nn.Module):
    """``(x + b_r) * a_r + (y + b_y) * a_y``."""

    @nn.compact
    def __call__(self, x, y):
        vec = lambda name, mean: self.param(
            name, _about(mean, 0.1 if mean else 0.02), (x.shape[-1],),
            jnp.float32).astype(x.dtype)
        return _residual(x, y, vec("residual_bias", 0.0),
                         vec("residual_scale", 1.0), vec("output_bias", 0.0),
                         vec("output_scale", 1.0))


def _residual(x, y, b_r, a_r, b_y, a_y):
    return (x + b_r) * a_r + (y + b_y) * a_y


class ZayaBlock(nn.Module):
    """One layer: the CCA sublayer, then the expert sublayer. Returns
    ``(x, router state, rows, skipped, bias delta)``."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, x, state, cos, sin, mask):
        cfg = self.config
        # ds.norm / ds.residual as in models/llama.py LlamaBlock
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        attn = ZayaAttention(cfg, name="self_attn")(h, cos, sin, mask)
        with jax.named_scope("ds.residual"):
            x = ScaledResidual(name="attn_residual")(x, attn)
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps,
                        name="post_attention_layernorm")(x)
        out, state, rows, skipped, delta = ZayaMoE(cfg, name="mlp")(h, state)
        # offered (remat_offers): the residual's output scale reads it, so
        # without it the replay runs the whole expert sublayer for that alone
        out = name_if_kept(out, REMAT_MOE_OUT)
        with jax.named_scope("ds.residual"):
            x = ScaledResidual(name="mlp_residual")(x, out)
        return x, state, rows, skipped, delta


class _ScanBlock(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, carry, _):
        x, state, cos, sin, mask, rows_sum, skipped_sum = carry
        x, state, rows, skipped, delta = ZayaBlock(self.config, name="block")(
            x, state, cos, sin, mask)
        return (x, state, cos, sin, mask, rows_sum + rows,
                skipped_sum + skipped), delta


class ZayaModel(nn.Module):
    config: ZayaConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, attention_mask=None):
        """``(final-normed hidden [B, T, H], rows [G], skipped, bias
        deltas)``: ``rows`` the tokens each held expert computed and
        ``skipped`` the tokens that chose the skip expert, summed over the
        layers; the deltas ``{parameter path: [.., E + 1]}`` of the balancing
        rule, empty where it is off."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        cos, sin = rotary_embedding(positions, cfg.rotary_dim,
                                    cfg.rope_theta, dtype=x.dtype)
        mask = None if attention_mask is None else jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                jnp.float32)

        policy = resolve_remat_policy(
            cfg.remat_policy, remat_offers(cfg, x, cfg.num_hidden_layers))
        remat = lambda cls: nn.remat(cls, prevent_cse=False, policy=policy) \
            if cfg.remat else cls
        state = jnp.zeros((B, T, cfg.router_hidden_size), jnp.float32)
        rows = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
        skipped = jnp.float32(0.0)
        deltas = {}
        path = f"mlp/router/{BIAS}"
        # ds.layer_stack: what the loop over the layers costs beyond what
        # the layers' own scopes name (models/llama.py LlamaModel)
        with jax.named_scope("ds.layer_stack"):
            if cfg.scan_layers:
                scan = nn.scan(remat(_ScanBlock), variable_axes={"params": 0},
                               split_rngs={"params": True, "dropout": True},
                               length=cfg.num_hidden_layers, metadata_params={})
                (x, _, _, _, _, rows, skipped), delta = scan(cfg, name="layers")(
                    (x, state, cos, sin, mask, rows, skipped), None)
                deltas[f"{self.name}/layers/block/{path}"] = delta
            else:
                for i in range(cfg.num_hidden_layers):
                    x, state, r, s, delta = remat(ZayaBlock)(
                        cfg, name=f"layers_{i}")(x, state, cos, sin, mask)
                    rows, skipped = rows + r, skipped + s
                    deltas[f"{self.name}/layers_{i}/{path}"] = delta
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        return x, rows, skipped, {k: jax.lax.stop_gradient(v)
                                  for k, v in deltas.items() if v is not None}


class ZayaForCausalLM(nn.Module):
    """``LlamaForCausalLM``'s training interface: logits without labels,
    the token-mean cross entropy with them (beside it the named scalars and
    ``"param_deltas"`` where the configuration asks). The head is the
    embedding table."""

    config: ZayaConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "compressed convolutional attention is built for training "
                "only: no cache holds the previous token's convolution and "
                "value inputs")
        hidden, rows, skipped, deltas = ZayaModel(cfg, name="model")(
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
        choices = cfg.num_hidden_layers * input_ids.size
        # the deployment's level load of this chip: its share of the columns
        expected = choices * cfg.num_experts_per_tok \
            * cfg.n_routed_experts / cfg.router_width
        return loss, {**named, **_held_load_gauges(rows, expected),
                      "moe_skip_share": skipped / choices}

    @staticmethod
    def frozen_parameters(config: "ZayaConfig"):
        """Parameter paths the optimizer never moves: the balancing bias is
        a buffer (only the rule moves it); the whole router too where
        ``router_trainable`` is off."""
        return [BIAS] if config.router_trainable else [r"mlp/router/"]

    @staticmethod
    def partition_rules(config: "ZayaConfig"):
        """Tensor parallelism over the vocabulary rows and the query heads'
        projections; the convolutions, the key and value paths, the router
        and the held experts are whole on every chip."""
        L = (None,) if config.scan_layers else ()
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"q_proj/kernel", P(*L, None, "model")),
            (r"o_proj/kernel", P(*L, "model", None)),
        ]


def remat_offers(cfg, x, applications: int):
    """What a ``ZayaBlock`` names, as ``ZayaModel`` offers it to
    ``layers.resolve_remat_policy`` for a stream ``x [B, T, hidden]`` through
    ``applications`` blocks, the name worth most a byte first (zaya 8k, ms a
    step the six-layer scan runs longer WITHOUT the name -- its replay less
    what its stack costs to write and read -- for a GB kept): the expert
    sublayer's output, without which the replay runs the whole expert
    forward, the down product too, for the residual's output scale, 6.4 for
    0.20; the four projections of ``h`` ahead of the convolutions 3.1 for
    0.15; the held experts' gate and up products (``mixtral.expert_offers``:
    every pair has a row, 8 of 17 is no compact share; worth nothing without
    the first name) 4.7 for 0.40; the router's float32 values (four
    ``[.., R]``, the logits, a word each for a choice and its weight) 2.2
    for 0.20; what ``ds.cca_mix`` hands on, the first convolution's output
    and q, k ahead of their unit length, 1.7 for 0.25. Two values are NOT
    offered, by the same measurement (PERF.md section 5): the attention's
    output projection as it enters its residual (the step ran 0.65 ms
    SHORTER without it: 1.1 ms of replay for 1.2 ms of stack) and the
    experts' sorted rows, which ``mixtral._named`` names all the same
    (1.3 ms shorter: 0.8 ms of sort, scatter and gather for 2.1)."""
    per_column = device_part(x.shape[0]) * x.shape[1] * applications
    item = x.dtype.itemsize
    latent = (cfg.num_attention_heads + cfg.num_key_value_heads) * cfg.head_dim
    values = cfg.num_key_value_heads * cfg.head_dim
    router = 4 * (4 * cfg.router_hidden_size + cfg.router_width
                  + 2 * cfg.num_experts_per_tok)
    gate_up, _ = expert_offers(
        x, cfg.num_experts_per_tok, cfg.moe_intermediate_size,
        cfg.n_routed_experts, cfg.router_width, applications)
    return ((REMAT_MOE_OUT, cfg.hidden_size * item * per_column),
            (REMAT_QKV, (latent + values) * item * per_column),
            gate_up,
            (REMAT_ROUTER, router * per_column),
            (REMAT_CCA_MIX, 2 * latent * item * per_column))
