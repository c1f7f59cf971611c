"""A stack whose every layer is ONE residual branch -- a Mamba-2 mixer, an
attention layer OR an expert layer alone -- under a pattern string.
NVIDIA-Nemotron-3-Nano-30B-A3B's decoder (``nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16`` ``config.json``, ``model_type`` ``nemotron_h``): layer ``l`` of
kind ``hybrid_override_pattern[l]`` computes ``x <- x + Mixer_l(norm_l(x))``,
every norm an RMSNorm with a learned scale; then the final norm, the untied
head and the cross entropy of the shifted labels.

*``M``, Mamba-2* (``MambaMixer``; ``H`` heads of ``P`` over a state of ``N``,
``G`` groups, ``d = H P``): ``[z ; xBC ; dt] = h W_in`` of widths ``d``, ``d
+ 2 G N`` and ``H``; ``xBC <- silu(causal_conv(xBC) + b)``, depthwise;
``[x ; B ; C] = xBC`` with ``x [T, H, P]`` and ``B, C [T, G, N]``, head ``i``
reading group ``i // (H / G)``; ``dt <- softplus(dt + dt_bias)``, ``A =
-exp(A_log)`` a head; the recurrence of ``ssd`` on a float32 state ``[H, P,
N]``; ``g = y * silu(z)``; ``g <- scale * g / rms(g)`` over EACH group's ``d
/ G`` columns (the gate BEFORE the norm); ``g W_out``. Projections,
convolution, gate and norm stand under ``ds.ssm_mix``, the recurrence under
``ds.ssm_scan``.

*``*``, attention*: ``llama.LlamaAttention`` at ``num_attention_heads`` query
heads over ``num_key_value_heads`` key-value heads of ``head_dim``, with NO
rotation (``rotary_dim`` 0: the recurrences carry position).

*``E``, experts*: ``mixtral.MixtralSparseMoeBlock`` with its held share and
compact buffer, told sigmoid scores, the routed scale and UNGATED ``relu^2``
experts (``router_scoring``, ``routed_scaling_factor``,
``expert_activation``: two matrices an expert), beside a shared expert of
the same form over every token (``ds.moe_shared``).

The pattern is config data: ``num_hidden_layers`` layers from
``first_layer`` of the string. ``runs`` cuts that slice into maximal runs of
a repeated segment, and each run is ONE ``layers.scan_periods`` (a scan over
its repeats whose body unrolls the segment; what repeats once is a scan of
one trip, which XLA removes), every block remat'ed by itself under its
kind's outer scope (``ds.layer_mamba``, ``ds.layer_full``,
``ds.layer_moe``). The published 52 layers are ``MEMEM*E`` five times,
``ME`` three times, ``M*`` once, ``EM`` four times and ``E``.

Training only: a serving cache would hold a convolution window and a ``[H,
P, N]`` state a Mamba layer beside the attention layers' pages.
``models/__init__.py`` does not import this module; a configuration names it
by path (``deepspeed_tpu.models.nemotron_h:NemotronHConfig``).
"""

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.pallas import REMAT_QKV, REMAT_SSM_IN
from .layers import (RMSNorm, causal_conv, cross_entropy_loss, device_part,
                     head_scope, model_dense, name_if_kept, scan_periods,
                     seeded_embed_tokens, seeded_lm_head, shift_labels)
from .llama import LlamaAttention
from .mixtral import (MixtralConfig, MixtralForCausalLM, MixtralSparseMoeBlock,
                      _activation, _add_stats, _compact_rows, _extra_stats,
                      _fits, _share_loss_and_gauges, expert_offers)

MAMBA, FULL, MOE = "M", "*", "E"
#: the outer scope of a block of each kind
KIND_SCOPES = {MAMBA: "ds.layer_mamba", FULL: "ds.layer_full",
               MOE: "ds.layer_moe"}
#: the published ``hybrid_override_pattern``
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MixtralConfig):
    #: a character a layer: ``M`` Mamba-2, ``*`` attention, ``E`` experts (a
    #: configuration file hands on numbers alone, so the default says it)
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    #: the pattern's index of this stack's layer 0: the stack is
    #: ``pattern[first_layer : first_layer + num_hidden_layers]``
    first_layer: int = 0
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    #: groups of heads that share ``B`` and ``C``, and the columns of one
    #: gated norm
    n_groups: int = 8
    #: taps of the depthwise causal convolution over ``xBC``
    conv_kernel: int = 4
    #: positions a chunk of ``ssd``
    chunk_size: int = 128
    #: the step sizes ``dt_bias`` is seeded for: log-uniform in ``[min,
    #: max]``, floored
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    moe_shared_expert_intermediate_size: int = 3712
    #: ``MixtralSparseMoeBlock`` reads these three (``mixtral._router_scores``,
    #: ``_routed_scale``, ``_activation``)
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    expert_activation: str = "relu2"
    #: ``LlamaAttention`` reads it: no column of a head rotates
    rotary_dim: Optional[int] = 0
    #: the standard deviations the two tables' rows are SEEDED at; None:
    #: flax's ``1 / sqrt(hidden_size)`` (``mellum.MellumConfig`` has why a
    #: held share behind a frozen seeded router wants them stated)
    embed_init_std: Optional[float] = None
    head_init_std: Optional[float] = None

    @property
    def pattern(self) -> str:
        """The kinds of this stack's layers, in order."""
        return self.hybrid_override_pattern[
            self.first_layer:self.first_layer + self.num_hidden_layers]

    @property
    def mamba_width(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @staticmethod
    def nemotron_3_nano_30b_a3b(**over):
        """Nemotron-3-Nano-30B-A3B as published: 52 layers of hidden 2688;
        Mamba-2 of 64 heads of 64 over a state of 128 in 8 groups, 4 taps,
        chunks of 128; 32 / 2 attention heads of 128; 128 experts of 1856
        under relu^2, top-6 of sigmoid scores normalised and scaled by 2.5,
        beside a shared expert of 3712."""
        return NemotronHConfig(**{**dict(
            vocab_size=131072, hidden_size=2688, intermediate_size=1856,
            moe_intermediate_size=1856,
            moe_shared_expert_intermediate_size=3712, num_hidden_layers=52,
            num_attention_heads=32, num_key_value_heads=2,
            head_dim_override=128, max_position_embeddings=262144,
            rms_norm_eps=1e-5, num_local_experts=128, num_experts_per_tok=6,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            router_aux_loss_coef=0.0, per_expert_init=True), **over})

    @staticmethod
    def tiny(**over):
        """Published layers 3-12, ``EM*EMEMEM*``: every kind twice, a run
        that repeats and runs that do not."""
        return NemotronHConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=16,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=24,
            first_layer=3, num_hidden_layers=10, num_attention_heads=8,
            num_key_value_heads=2, head_dim_override=8,
            max_position_embeddings=64, mamba_num_heads=4, mamba_head_dim=8,
            ssm_state_size=8, n_groups=2, chunk_size=8, num_local_experts=4,
            num_experts_per_tok=2, routed_scaling_factor=2.5,
            router_aux_loss_coef=0.0, per_expert_init=True, remat=False),
            **over})


def runs(pattern: str) -> tuple:
    """``pattern`` cut into ``((segment, repeats), ...)``, left to right: at
    each position the repeated segment that covers most (the shortest of
    those), or with none the layers up to the next one that repeats, once."""
    out, i, loose = [], 0, ""
    while i < len(pattern):
        rest = pattern[i:]
        covers = lambda u: u * _repeats(rest, u) if _repeats(rest, u) > 1 \
            else 0
        u = max(range(1, len(rest) // 2 + 1), default=0,
                key=lambda u: (covers(u), -u))
        if not u or not covers(u):
            loose, i = loose + pattern[i], i + 1
            continue
        if loose:
            out.append((loose, 1))
        out.append((rest[:u], _repeats(rest, u)))
        loose, i = "", i + covers(u)
    return tuple(out + ([(loose, 1)] if loose else []))


def _repeats(s: str, u: int) -> int:
    """How often ``s`` opens with its own first ``u`` characters in a row."""
    r = 1
    while s[r * u:(r + 1) * u] == s[:u]:
        r += 1
    return r


# -- the state-space duality form of Mamba-2's recurrence ---------------------

def ssd(x, dt, a, b, c, chunk):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + (dt_t x_t)
    B_t^T`` from ``S = 0`` a head, over ``T`` positions in chunks of
    ``chunk``: ``x [B, T, H, P]``, ``dt [B, T, H]`` float32 ``>= 0``, ``a
    [H]`` float32 ``< 0``, ``b, c [B, T, G, N]`` (head ``i`` reads group ``i
    // (H / G)``) -> ``(y [B, T, H, P] float32, the largest sum of dt |A|
    over one chunk)``.

    With ``gamma_i`` the running sum of ``dt A`` inside a chunk and ``S`` the
    state at its start: ``Y = ((C B^T) * L) (dt x) + exp(gamma) C S`` with
    ``L_ij = exp(gamma_i - gamma_j)`` for ``j <= i``, and the next chunk
    starts from ``exp(gamma_end) S + sum_j exp(gamma_end - gamma_j) (dt
    x)_j B_j^T``. The chunks' own states are carried to every later
    boundary in ONE product with the table ``exp(sum of the whole chunks'
    decays between)``, at the highest precision. Every exponent is a
    difference that is ``<= 0``: nothing divides by a decay (a chunk may
    hold hundreds of nats). ``gamma``, the tables, the states and every
    accumulation are float32; the products read their operands in ``x``'s
    type, rounded once; ``C B^T`` is made once a GROUP. A ragged tail is
    padded with ``dt = 0``: no decay, no update. No array holds a state a
    position; the backward pass is autodiff's, inside the block's remat."""
    B, T, H, P = x.shape
    G, N, Q, f32 = b.shape[2], b.shape[3], chunk, jnp.float32
    pad = (-T) % Q
    n, r = (T + pad) // Q, H // G
    fold = lambda t: jnp.pad(
        t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)).reshape(
            B, n, Q, *t.shape[2:])
    dt = fold(dt.astype(f32))                              # [B, n, Q, H]
    xd = (fold(x).astype(f32) * dt[..., None]).astype(x.dtype).reshape(
        B, n, Q, G, r, P)
    b, c = fold(b), fold(c)                                # [B, n, Q, G, N]
    # head-major tables: the last two axes are a chunk's positions
    gamma = jnp.cumsum((dt * a).transpose(0, 1, 3, 2), axis=-1)  # <= 0
    total = gamma[..., -1]                                 # [B, n, H]
    by_group = lambda t: t.reshape(B, n, G, r, *t.shape[3:])
    i = jnp.arange(Q)
    # inside a chunk: ((C B^T) * L) (dt x)
    decay = jnp.exp(jnp.where(
        i[:, None] >= i[None, :],
        gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    cb = jnp.einsum("bnigs,bnjgs->bngij", c, b, preferred_element_type=f32)
    m = (cb[:, :, :, None] * by_group(decay)).astype(x.dtype)
    y = jnp.einsum("bngrij,bnjgrp->bnigrp", m, xd,
                   preferred_element_type=f32)
    # a chunk's own state, as its end sees it
    out_of = by_group(jnp.exp(total[..., None] - gamma))   # [B, n, G, r, Q]
    own = jnp.einsum(
        "bnjgrp,bnjgs->bngrps",
        (xd.astype(f32) * out_of.transpose(0, 1, 4, 2, 3)[..., None]
         ).astype(x.dtype), b, preferred_element_type=f32)
    # the state at each chunk's start: every earlier chunk's own state under
    # the whole chunks between them, their decays summed segment by segment
    # (no difference of two running sums over the sequence)
    k = jnp.arange(n)
    later = k[:, None] > k[None, :]                        # chunk m before k
    tot = total.transpose(0, 2, 1)                         # [B, H, n]
    seg = jnp.cumsum(jnp.where(later, tot[..., :, None], 0), axis=-2) \
        - tot[..., :, None]                                # m + 1 .. k - 1
    between = jnp.exp(jnp.where(later, jnp.minimum(seg, 0), -jnp.inf))
    start = jnp.einsum("bhkm,bmhps->bkhps", between,
                       own.reshape(B, n, H, P, N),
                       precision=jax.lax.Precision.HIGHEST)
    into = by_group(jnp.exp(gamma)).transpose(0, 1, 4, 2, 3)[..., None]
    y = y + into * jnp.einsum(
        "bnigs,bngrps->bnigrp", c,
        start.astype(x.dtype).reshape(B, n, G, r, P, N),
        preferred_element_type=f32)
    return y.reshape(B, n * Q, H, P)[:, :T], jnp.max(-total)


# the mixer's small formulas by name (tests/benchmark/nemotron_h_wrong.py
# replaces one at a time)

def _split(zxbcdt, d, gn):
    """``[z ; xBC ; dt]`` of ``in_proj``'s columns."""
    return jnp.split(zxbcdt, (d, 2 * d + 2 * gn), axis=-1)


def _conv_act(xbc, taps, bias):
    return nn.silu(causal_conv(xbc, taps.astype(xbc.dtype),
                               bias.astype(xbc.dtype)))


def _step_size(dt, dt_bias):
    """``softplus(dt + dt_bias)`` a head, float32."""
    return jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)


def _groups(t):
    """``B`` or ``C [B, T, G, N]`` as ``ssd`` takes it, which pairs head
    ``i`` with group ``i // (H / G)``: each group its own."""
    return t


def _skip(y, x, d):
    """``y + D x`` a head, float32."""
    return y + d[:, None] * x.astype(jnp.float32)


def _gated_norm(y, z, scale, eps, groups):
    """``scale * g / rms(g)`` over each of ``groups`` groups of columns, ``g
    = y * silu(z)``: float32, in z's type."""
    g = y * nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return (g.reshape(y.shape) * scale).astype(z.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log(1 .. H)``, as the published class seeds ``A_log``."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(cfg):
    """The inverse softplus of a log-uniform step size in ``[time_step_min,
    time_step_max]``, floored at ``time_step_floor``."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _uniform(bound):
    """``U(-bound, bound)``: a Conv1d's taps and bias over their fan-in."""
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class MambaMixer(nn.Module):
    """The Mamba-2 mixer: ``(out [B, T, hidden], the recurrence's largest
    chunk decay in nats)``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        B, T, _ = h.shape
        H, P, N, G, K = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel)
        d, f32 = cfg.mamba_width, jnp.float32
        with jax.named_scope("ds.ssm_mix"):
            z, xbc, dt = _split(name_if_kept(model_dense(
                cfg, 2 * d + 2 * G * N + H, "in_proj")(h), REMAT_SSM_IN),
                d, G * N)
            taps = self.param("conv_weight", _uniform(K ** -0.5),
                              (K, d + 2 * G * N), f32)
            bias = self.param("conv_bias", _uniform(K ** -0.5),
                              (d + 2 * G * N,), f32)
            x, b, c = jnp.split(_conv_act(xbc, taps, bias), (d, d + G * N),
                                axis=-1)
            x = x.reshape(B, T, H, P)
            b, c = (_groups(t.reshape(B, T, G, N)) for t in (b, c))
            dt = _step_size(dt, self.param("dt_bias", _dt_bias_init(cfg),
                                           (H,), f32))
            a = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
            skip = self.param("D", nn.initializers.ones, (H,), f32)
        with jax.named_scope("ds.ssm_scan"):
            y, decay = ssd(x, dt, a, b, c, cfg.chunk_size)
        with jax.named_scope("ds.ssm_mix"):
            y = _skip(y, x, skip).reshape(B, T, d)
            scale = self.param("norm_scale", nn.initializers.ones, (d,), f32)
            g = _gated_norm(y, z, scale, cfg.rms_norm_eps, G)
            out = model_dense(cfg, cfg.hidden_size, "out_proj",
                              row_parallel=True)(g)
        return out, jax.lax.stop_gradient(decay)


class SharedExpert(nn.Module):
    """``down(act(up(x)))`` under the experts' ungated activation, over
    every token, alike on every chip of the deployment."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope("ds.moe_shared"):
            up = model_dense(cfg, cfg.moe_shared_expert_intermediate_size,
                             "up_proj")(x)
            return model_dense(cfg, cfg.hidden_size, "down_proj",
                               row_parallel=True)(
                                   _activation(cfg).forward(up, None))


class NemotronHBlock(nn.Module):
    """One layer of ``kind``, one residual branch: ``(x, each expert's token
    fraction [E], the layer's other statistics, the chunk decay)`` -- zeros
    where the kind has none."""

    config: NemotronHConfig
    kind: str = MAMBA

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        frac = jnp.zeros((cfg.router_width,), jnp.float32)
        decay, hit = jnp.zeros((), jnp.float32), jnp.float32(0)
        C = _compact_rows(x.shape[0] * x.shape[1] * cfg.num_experts_per_tok,
                          cfg.num_local_experts, cfg.router_experts)
        if self.kind == MAMBA:
            out, decay = MambaMixer(cfg, name="mixer")(h)
        elif self.kind == FULL:
            out, _ = LlamaAttention(cfg, name="self_attn")(h, None, None,
                                                           None)
        else:
            out, frac, _, rows = MixtralSparseMoeBlock(
                cfg, name="block_sparse_moe")(h)
            out = out + SharedExpert(cfg, name="shared_expert")(h)
            if C is not None:
                hit = _fits(rows, C).astype(jnp.float32)
        with jax.named_scope("ds.residual"):
            x = x + out
        return x, frac, ({} if C is None else {"compact_hit": hit}), decay


def _check(cfg):
    if not 0 <= cfg.first_layer <= len(cfg.hybrid_override_pattern) \
            - cfg.num_hidden_layers or cfg.num_hidden_layers < 1:
        raise ValueError(
            f"layers {cfg.first_layer}..+{cfg.num_hidden_layers} are not "
            f"among the pattern's {len(cfg.hybrid_override_pattern)}")
    if set(cfg.pattern) - set(KIND_SCOPES):
        raise ValueError(f"a layer is one of {sorted(KIND_SCOPES)}: "
                         f"{cfg.pattern!r}")
    if cfg.mamba_num_heads % cfg.n_groups:
        raise ValueError("each group serves a whole number of heads")
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError("each key-value head serves a whole number of "
                         "query heads")
    if cfg.sa_config is not None or cfg.sliding_window is not None:
        raise NotImplementedError(
            "the attention layers attend the whole causal prefix: no "
            "window, no learned selection")
    if cfg.tie_word_embeddings or cfg.loss_chunk:
        raise NotImplementedError(
            "the head is a table of its own whose logits are whole: no "
            "tied table, no chunked loss")
    if cfg.report_expert_load and cfg.router_experts is None:
        raise NotImplementedError(
            "report_expert_load names a held share's gauges: give "
            "router_experts")


def _call(block, kind, x):
    x, frac, extra, decay = block(x)
    return x, (frac, extra, decay)


def _fold(sums, stats):
    frac_sum, extra_sum, decay_max = sums
    frac, extra, decay = stats
    return (frac_sum + frac, _add_stats(extra_sum, extra),
            jnp.maximum(decay_max, decay))


class _Run(nn.Module):
    """One run of the pattern, ``segment`` repeated ``repeats`` times: ONE
    ``layers.scan_periods`` of its own, under its own name."""

    config: NemotronHConfig
    segment: str
    repeats: int

    @nn.compact
    def __call__(self, x, sums):
        cfg = self.config
        run = dataclasses.replace(
            cfg, num_hidden_layers=len(self.segment) * self.repeats)
        return scan_periods(
            run, tuple(self.segment), x, sums, (),
            block=lambda kind, name: NemotronHBlock(cfg, kind, name=name),
            call=_call, fold=_fold, scopes=KIND_SCOPES,
            offers=lambda x: remat_offers(cfg, x))


class NemotronHModel(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids):
        """``(final-normed hidden, (each expert's share of the tokens summed
        over the expert layers, the layers' other statistics summed, the
        recurrence's largest chunk decay))``."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = seeded_embed_tokens(cfg, input_ids)
        sums = (jnp.zeros((cfg.router_width,), jnp.float32), dict.fromkeys(
            _extra_stats(cfg, B * T * cfg.num_experts_per_tok),
            jnp.float32(0)), jnp.zeros((), jnp.float32))
        for i, (segment, repeats) in enumerate(runs(cfg.pattern)):
            x, sums = _Run(cfg, segment, repeats, name=f"run_{i}")(x, sums)
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        return x, sums


class NemotronHForCausalLM(nn.Module):
    """``MixtralForCausalLM``'s training interface over ``NemotronHModel``:
    logits without labels; with them the LM loss (no router loss: the source
    has no coefficient) and, with ``report_expert_load``, ``(loss, named
    scalars)``: the held share's gauges over the EXPERT layers and
    ``ssm_chunk_decay_max``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a stack of state-space, attention and expert layers is "
                "built for training only: no cache holds a convolution "
                "window and a state a Mamba layer beside an attention "
                "layer's keys and values")
        if attention_mask is not None:
            raise NotImplementedError(
                "the recurrence has no padding mask: train on packed "
                "sequences")
        hidden, (load, extra, decay) = NemotronHModel(cfg, name="model")(
            input_ids)
        with jax.named_scope(head_scope(None)):
            logits = seeded_lm_head(cfg, hidden)
            if labels is None:
                return logits
            loss = cross_entropy_loss(logits, shift_labels(labels))
        if cfg.router_experts is None:
            return loss
        sparse = dataclasses.replace(
            cfg, num_hidden_layers=max(cfg.pattern.count(MOE), 1))
        out = _share_loss_and_gauges(sparse, loss, load, extra,
                                     input_ids.size)
        if not cfg.report_expert_load:
            return out
        return out[0], {**out[1], "ssm_chunk_decay_max": decay}

    #: one leading scanned axis (a run's repeats) where Mixtral's is the
    #: layers: its rules for the attention's projections, the experts (``w1``
    #: and ``w2`` alone here) and the two tables; a Mamba mixer and the
    #: shared expert stay whole on every chip; its frozen router
    partition_rules = staticmethod(MixtralForCausalLM.partition_rules)
    frozen_parameters = staticmethod(MixtralForCausalLM.frozen_parameters)


def remat_offers(cfg, x):
    """What the blocks of this stack name, as every ``_Run`` offers it to
    ``layers.resolve_remat_policy`` for a stream ``x [B, T, hidden]`` through
    ALL the stack's layers, the costliest replay of each kind: a Mamba
    layer's ``in_proj`` output (the stack's widest product; the replay then
    runs the convolution and the recurrence alone); q, k, v as
    ``LlamaAttention`` names them; what the expert layers name
    (``mixtral.expert_offers``, ONE first product a row)."""
    B, T, _ = x.shape
    per_column = device_part(B) * T * x.dtype.itemsize
    kinds = cfg.pattern
    return ((REMAT_SSM_IN, kinds.count(MAMBA) * per_column * (
                2 * cfg.mamba_width + 2 * cfg.n_groups * cfg.ssm_state_size
                + cfg.mamba_num_heads)),
            (REMAT_QKV, kinds.count(FULL) * per_column * cfg.head_dim * (
                cfg.num_attention_heads + 2 * cfg.num_key_value_heads)),
            *expert_offers(x, cfg.num_experts_per_tok, cfg.expert_width,
                           cfg.num_local_experts, cfg.router_experts,
                           kinds.count(MOE),
                           firsts=1 + _activation(cfg).gated))
