"""Kimi-Linear-shaped decoder for training
(``moonshotai/Kimi-Linear-48B-A3B-Instruct`` ``config.json``, ``model_type``
``kimi_linear``; the mixer is the Kimi Linear report's "Kimi Delta Attention",
arXiv:2510.26692): a PATTERN of two mixers read from two published LISTS --
``kda_layers`` delta-rule layers whose decay is a VECTOR a head, one number a
channel of the key, and ``full_attn_layers`` latent-attention layers that
rotate nothing -- over ``deepseek_v3.py``'s feed-forward parts (a leading dense
SwiGLU, then the sigmoid-routed expert layer at ONE CHIP'S SHARE).

*Block*: ``h = x + Mixer_kind(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

*KDA layer* (``KimiDeltaAttention``; ``H`` heads of ``D``): with ``u`` the
normed input, ``q, k, v = SiLU(causal_conv(W_. u))`` (three matrices, three
depthwise convolutions, no bias); ``q <- q / |q| / sqrt(D)``, ``k <- k / |k|``
a head; ``beta = sigmoid(W_b u)`` a head; the log decay A CHANNEL OF THE KEY
``g = -exp(A_log_h) softplus(W_fb (W_fa u) + dt_bias)``, float32. A head keeps
``S [D, D]`` in float32 from ``S = 0``:

    S <- Diag(exp(g_t)) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

(``kda_rule``, in chunks, under ``ds.kda_rule``), then ``o_t <- w *
rms_norm(o_t) * sigmoid(W_gb (W_ga u))`` a head (the norm before the gate) and
``W_o``.

*MLA layer*: ``deepseek_v3.DeepseekV3Attention`` with ``mla_use_nope``: the
64 shared key columns and their query columns join the product as projected
(the recurrences carry position).

*The pattern as data*: stack layer ``i`` is published layer ``first_layer +
i`` (1-indexed, as the lists are); it is dense where that index is ``<=
first_k_dense_replace``. ``stack_kinds`` derives the leading (dense) blocks
and one period's kinds from the lists, and ``_check`` refuses a depth that is
no leading part + whole periods: THE PUBLISHED 27 END TWO LAYERS INTO A
PERIOD (layers 26, 27 are KDA, MLA where a period starts KDA, KDA) and are
refused here -- trailing blocks are not built (ROADMAP R8).

Training only: a serving cache would hold each KDA layer's ``[H, D, D]``
float32 states and three convolution tails beside the latent pages (ROADMAP
R4). ``models/__init__.py`` does not import this module; a configuration
names it by path.
"""

import contextlib
import dataclasses
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas import (REMAT_ATTN_OUT, REMAT_KDA_RULE, REMAT_MLP,
                          REMAT_QKV)
from .deepseek_v3 import (BIAS, DeepseekV3Attention, DeepseekV3Config,
                          DeepseekV3ForCausalLM, DeepseekV3MoE, _SwiGLU)
from .deepseek_v3 import _check as _check_share
from .layers import (RMSNorm, causal_conv, cross_entropy_loss, device_part,
                     head_scope, model_dense, name_if_kept, scan_periods,
                     seeded_embed_tokens, seeded_lm_head, shift_labels)
from .mixtral import _compact_hit_gauge, _held_load_gauges, expert_offers
# _beta, _unit_length: the scalar rule's mixer has the same two formulas
from .qwen3_next import _beta, _unit_length, _unit_lower_solve

KDA, MLA = "kda", "mla"
#: the outer scope of a block by its mixer; inside it a dense block stands
#: under ``ds.layer_dense`` and a latent-attention one under ``ds.layer_full``
#: (the names other stacks give those kinds, which their readers look for)
MIXER_SCOPES = {KDA: "ds.layer_kda", MLA: "ds.layer_mla"}
#: ``linear_attn_config`` as published, 1-indexed
PUBLISHED_KDA_LAYERS = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                        21, 22, 23, 25, 26)
PUBLISHED_FULL_ATTN_LAYERS = (4, 8, 12, 16, 20, 24, 27)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DeepseekV3Config):
    #: the two published lists (a configuration file hands on numbers alone,
    #: so the defaults say them)
    kda_layers: Tuple[int, ...] = PUBLISHED_KDA_LAYERS
    full_attn_layers: Tuple[int, ...] = PUBLISHED_FULL_ATTN_LAYERS
    #: the published (1-indexed) layer this stack's layer 0 is
    first_layer: int = 1
    #: ``linear_attn_config``'s ``num_heads``, ``head_dim`` and
    #: ``short_conv_kernel_size``
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4
    #: positions a chunk of ``kda_rule``, and a block inside it
    kda_chunk: int = 64
    kda_block: int = 16
    #: the latent attention rotates nothing
    mla_use_nope: bool = True
    #: the standard deviations the two tables' rows are SEEDED at; None:
    #: flax's ``1 / sqrt(hidden_size)`` (``mellum.MellumConfig`` has why a
    #: held share behind a frozen seeded router wants them stated)
    embed_init_std: Optional[float] = None
    head_init_std: Optional[float] = None

    @staticmethod
    def kimi_linear_48b_a3b(**over):
        """Kimi-Linear-48B-A3B as published: 27 layers of hidden 2304, 32
        heads; KDA heads of 128 with 4 taps; latent attention of rank 512 at
        128 + 64 / 128 columns a head; a dense layer of 9216, then 256
        experts of 1024, top-8 of sigmoid scores renormalised x 2.446, one
        shared expert."""
        return KimiLinearConfig(**{**dict(
            vocab_size=163840, hidden_size=2304, intermediate_size=9216,
            moe_intermediate_size=1024, num_hidden_layers=27,
            num_attention_heads=32, num_key_value_heads=32,
            max_position_embeddings=1048576, rms_norm_eps=1e-5,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, n_routed_experts=256, num_experts_per_tok=8,
            n_shared_experts=1, first_k_dense_replace=1,
            routed_scaling_factor=2.446), **over})

    @staticmethod
    def tiny(**over):
        return KimiLinearConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=9,
            num_attention_heads=4, num_key_value_heads=4,
            max_position_embeddings=64, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, kda_num_heads=4,
            kda_head_dim=8, kda_chunk=8, kda_block=4, n_routed_experts=8,
            num_experts_per_tok=3, n_shared_experts=1, router_bias_init=0.1,
            remat=False), **over})


# -- the pattern, from the two lists ----------------------------------------

def layer_kind(cfg, layer: int) -> tuple:
    """``(mixer, dense)`` of stack layer ``layer`` (from 0)."""
    published = cfg.first_layer + layer
    kda, full = published in cfg.kda_layers, published in cfg.full_attn_layers
    if kda == full:
        raise ValueError(
            f"published layer {published} is in "
            f"{'both lists' if kda else 'neither list'}: kda_layers "
            f"{cfg.kda_layers}, full_attn_layers {cfg.full_attn_layers}")
    return (KDA if kda else MLA, published <= cfg.first_k_dense_replace)


def stack_kinds(cfg) -> tuple:
    """``(the leading blocks' kinds, one period's kinds)``: the leading
    blocks are the stack's dense layers; a period is as long as the first
    two full-attention layers lie apart (the sparse layers all, where the
    lists name fewer than two)."""
    kinds = [layer_kind(cfg, l) for l in range(cfg.num_hidden_layers)]
    lead = sum(1 for _, dense in kinds if dense)
    full = sorted(cfg.full_attn_layers)
    n = full[1] - full[0] if len(full) > 1 else len(kinds) - lead
    return tuple(kinds[:lead]), tuple(kinds[lead:lead + n])


def _check(cfg):
    _check_share(cfg)
    kinds = [layer_kind(cfg, l) for l in range(cfg.num_hidden_layers)]
    leading, period = stack_kinds(cfg)
    lead, n = len(leading), len(period)
    if any(dense for _, dense in kinds[lead:]) or not n:
        raise ValueError(
            f"the dense layers are the stack's first: first_layer "
            f"{cfg.first_layer}, first_k_dense_replace "
            f"{cfg.first_k_dense_replace}, {cfg.num_hidden_layers} layers")
    wrong = [l for l in range(lead, len(kinds))
             if kinds[l] != period[(l - lead) % n]]
    tail = (len(kinds) - lead) % n
    if wrong or tail:
        last = cfg.first_layer + len(kinds) - 1
        raise ValueError(
            f"published layers {cfg.first_layer}..{last} are not {lead} "
            f"dense and whole periods of {n} "
            f"({', '.join(m for m, _ in period)}): "
            + (f"layer {cfg.first_layer + wrong[0]} breaks the period"
               if wrong else f"the last {tail} end {tail} layers into a "
               "period") + " -- the trailing layers of a partial period are "
            "not built (the published 27 end two layers into one: 26, 27 "
            "are KDA, MLA)")
    if cfg.kda_chunk % cfg.kda_block:
        raise ValueError("a chunk of kda_rule is whole blocks")
    if cfg.tie_word_embeddings or cfg.loss_chunk:
        raise NotImplementedError(
            "the head is a table of its own whose logits are whole: no "
            "tied table, no chunked loss")


# -- the delta rule under a decay a channel ---------------------------------

def _pair_tables(q, k, gamma, block):
    """``(sum_d k_id k_jd e^(gamma_id - gamma_jd) for j < i, sum_d q_id k_jd
    e^(gamma_id - gamma_jd) for j <= i)``, each ``[..., C, C]`` with zeros
    elsewhere, from ``q, k, gamma [..., C, dk]`` float32 (``gamma`` the
    running sum of the log decays inside the chunk, falling). The decay does
    not factor out of the product, and ``(q e^gamma)(k e^-gamma)^T`` would
    divide by one; in blocks of ``block`` positions instead:

    * a block pair OFF the diagonal takes the row block's first position
      ``r`` as reference, ``(q_i e^(gamma_i - gamma_r)) . (k_j e^(gamma_r -
      gamma_j))``: ``j < r <= i``, both exponents ``<= 0``, a plain product;
    * a DIAGONAL block forms ``e^(gamma_id - gamma_jd)`` a channel for ``j <=
      i``, a column ``j`` at a time (``block`` element-wise passes over
      ``[..., block, dk]``; all blocks' ``[block, block, dk]`` at once would
      be gigabytes at 8,192 positions)."""
    C, dk = q.shape[-2:]
    nb, lead = C // block, q.shape[:-2]
    x = jnp.stack([k, q])                                # both tables' rows
    # the diagonal blocks, every block of the chunk at once
    xb = x.reshape(2, *lead, nb, block, dk)
    kb, gb = (t.reshape(*lead, nb, block, dk) for t in (k, gamma))
    i = jnp.arange(block)[:, None]
    cols = []
    for j in range(block):
        since = jnp.where(i >= j, gb - gb[..., j:j + 1, :], -jnp.inf)
        cols.append(jnp.sum(xb * (kb[..., j:j + 1, :] * jnp.exp(since)), -1))
    diag = jnp.stack(cols, -1)                           # [2, .., nb, b, b]
    rows = []
    for r in range(nb):
        lo, hi = r * block, (r + 1) * block
        parts = [diag[..., r, :, :]]
        if r:
            ref = gamma[..., lo:lo + 1, :]
            left = x[..., lo:hi, :] * jnp.exp(gamma[..., lo:hi, :] - ref)
            right = k[..., :lo, :] * jnp.exp(ref - gamma[..., :lo, :])
            parts.insert(0, jnp.einsum("s...id,...jd->s...ij", left, right))
        if hi < C:
            parts.append(jnp.zeros((2, *lead, block, C - hi), q.dtype))
        rows.append(jnp.concatenate(parts, -1))
    kk, qk = jnp.concatenate(rows, -2)
    return jnp.tril(kk, -1), qk


#: what one float32 ``[B, T, heads, dk]`` operand of a pass of ``kda_rule``
#: may hold: the rule's backward holds some thirty of them at once (PERF.md
#: section 6, PR 68: all 32 heads of 8,192 positions at once asked the chip
#: for 18.4 GB, 8 a pass put the step over the configuration's memory rule)
_PASS_BYTES = 16 << 20


def _heads_a_pass(B, T, H, dk):
    """The heads ``kda_rule`` takes at once: the most that divide ``H`` and
    keep an operand inside ``_PASS_BYTES`` (all of them at the tiny sizes, 4
    of 32 at 8,192 positions of 128 channels)."""
    fit = max(1, _PASS_BYTES // (4 * B * T * dk))
    return max(g for g in range(1, H + 1) if H % g == 0 and g <= fit)


def kda_rule(q, k, v, g, beta, chunk=64, block=16):
    """The recurrence of the module's docstring over ``T`` positions, in
    chunks of ``chunk``: ``q, k [B, T, H, dk]`` (unit length, ``q`` scaled),
    ``v [B, T, H, dv]``, ``g [B, T, H, dk]`` float32 ``<= 0`` (the log decay
    a CHANNEL) and ``beta [B, T, H]`` float32 -> ``(o [B, T, H, dv] in v's
    dtype, the largest -sum of g over a chunk, over heads and channels)``.

    With ``gamma_i [dk]`` the running sum of ``g`` inside a chunk of ``C``
    and ``S`` the state at its start, the rows ``d_i`` solve ``(I + A) D =
    beta (V - (e^gamma K) S)`` with ``A_ij = beta_i sum_d k_id k_jd
    e^(gamma_id - gamma_jd)`` for ``j < i`` (``_pair_tables``); ``O =
    (e^gamma Q) S + M D`` with ``M`` the same sum over ``q_i`` for ``j <=
    i``; ``S' = Diag(e^gamma_C) S + (e^(gamma_C - gamma) K)^T D``. Every
    exponent is a difference that is ``<= 0``: nothing divides by a decay
    (``e^-gamma`` overflows from 88 nats, and a chunk's channel may hold a
    thousand) and no ``g`` is clamped. A ragged tail is padded with ``k = v
    = q = 0``, ``beta = 0``, ``g = 0``: no update, no decay, no output.

    In XLA alone: the heads are independent, so they go ``_heads_a_pass`` at
    a time through ``_rule_pass`` (a ``lax.map``), each pass rematerialised:
    the backward keeps a pass's operands and recomputes its tables, solve
    and boundary states. State, ``d`` and the solve are float32; the
    products' other operands travel in q's dtype (what the matrix unit
    rounds them to at that dtype's precision)."""
    B, T, H, _ = q.shape
    G = _heads_a_pass(B, T, H, q.shape[-1])
    one_pass = jax.checkpoint(
        lambda xs: _rule_pass(*xs, chunk=chunk, block=block))
    if G == H:
        return one_pass((q, k, v, g, beta))
    # [B, T, H, ...] -> [H / G, B, T, G, ...]
    split = lambda x: jnp.moveaxis(
        x.reshape(B, T, H // G, G, *x.shape[3:]), 2, 0)
    o, decay = jax.lax.map(one_pass, tuple(split(x) for x in
                                           (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2).reshape(B, T, H, -1), jnp.max(decay)


def _rule_pass(q, k, v, g, beta, chunk, block):
    """``kda_rule`` over the heads it is given: what does not need ``S`` is
    computed for all chunks at once (the tables, the solve's two right-hand
    sides), as ``qwen3_next._rule_xla`` does; a ``lax.scan`` over the chunks
    carries ``S`` in float32."""
    B, T, H, dk = q.shape
    dv, C, f32, low = v.shape[-1], chunk, jnp.float32, q.dtype
    pad = (-T) % C
    n = (T + pad) // C

    def fold(x):        # [B, T, H, d] -> [n, B, H, C, d] float32
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(B, n, C, H, -1).transpose(1, 0, 3, 2, 4)

    q, k, v32, beta = fold(q), fold(k), fold(v), fold(beta[..., None])
    gamma = jnp.cumsum(fold(g), axis=-2)                 # [n, B, H, C, dk]
    total = gamma[..., -1:, :]
    kk, m = _pair_tables(q, k, gamma, block)
    into, out_of = jnp.exp(gamma), jnp.exp(total - gamma)
    uw = _unit_lower_solve(beta * kk, jnp.concatenate(
        [beta * v32, beta * into * k], axis=-1))
    u, w = uw[..., :dv], uw[..., dv:].astype(low)
    m, qg, kd = m.astype(low), (into * q).astype(low), \
        (out_of * k).astype(low)
    product = lambda eq, x, y: jnp.einsum(eq, x, y.astype(x.dtype),
                                          preferred_element_type=f32)

    def step(S, xs):
        u, w, m, qg, kd, last = xs
        d = u - product("...ck,...kv->...cv", w, S)
        o = product("...ck,...kv->...cv", qg, S) \
            + product("...ij,...jv->...iv", m, d)
        S = last[..., None] * S + product("...ck,...cv->...kv", kd, d)
        return S, o

    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32),
                        (u, w, m, qg, kd, jnp.exp(total[..., 0, :])))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, n * C, H, dv)[:, :T]
    return o.astype(v.dtype), jnp.max(-total)


# the mixer's small formulas by name, ``_beta`` and ``_unit_length`` among
# them (tests/benchmark/kimi_linear_wrong.py replaces one at a time)
_conv_act = nn.silu


def _log_decay(a_log, f, dt_bias):
    """``g = -exp(A_log_h) softplus(f + dt_bias)`` a head and channel:
    ``a_log [H]``, ``f [B, T, H, D]``, ``dt_bias [H, D]``, float32."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + dt_bias)


def _out_gate(gate):
    return jax.nn.sigmoid(gate)


def _gated_norm(o, gate, scale, eps):
    """``scale * rms_norm(o) * sigmoid(gate)`` a head in float32 (the norm
    before the gate), in o's type."""
    o32 = o.astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True)
                              + eps) * scale
    return (o32 * _out_gate(gate.astype(jnp.float32))).astype(o.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(1, 16)`` a head, as the published class seeds ``A_log``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform step size in ``[1e-3, 0.1]``."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KimiDeltaAttention(nn.Module):
    """The KDA mixer: ``(out [B, T, hidden], the rule's largest chunk decay
    in nats)``."""

    config: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, _ = x.shape
        H, D, f32 = cfg.kda_num_heads, cfg.kda_head_dim, jnp.float32
        rank = D        # of the decay's and the output gate's low-rank paths
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, row_parallel=row)
        with jax.named_scope("ds.attn_proj"):
            q, k, v = (dense(H * D, f"{n}_proj")(x) for n in "qkv")
            b = dense(H, "b_proj")(x)
            f = dense(H * D, "f_b_proj")(dense(rank, "f_a_proj")(x))
            gate = dense(H * D, "g_b_proj")(dense(rank, "g_a_proj")(x))
        with jax.named_scope("ds.kda_mix"):
            taps = lambda n: self.param(
                f"{n}_conv1d", nn.initializers.lecun_normal(
                    in_axis=0, out_axis=1, batch_axis=()),
                (cfg.kda_conv_kernel, H * D), f32).astype(x.dtype)
            # _conv_act, _unit_length: looked up here, at trace time
            q, k, v = (_conv_act(causal_conv(t, taps(n))).reshape(B, T, H, D)
                       for t, n in ((q, "q"), (k, "k"), (v, "v")))
            q = (_unit_length(q) * D ** -0.5).astype(x.dtype)
            k = _unit_length(k).astype(x.dtype)
            a_log = self.param("A_log", _a_log_init, (H,), f32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (H * D,), f32)
            beta = _beta(b.astype(f32))
            g = _log_decay(a_log, f.astype(f32).reshape(B, T, H, D),
                           dt_bias.reshape(H, D))
        with jax.named_scope("ds.kda_rule"):
            o, decay = kda_rule(q, k, v, g, beta, cfg.kda_chunk,
                                cfg.kda_block)
            o = name_if_kept(o, REMAT_KDA_RULE)
        with jax.named_scope("ds.kda_mix"):
            scale = self.param("o_norm", nn.initializers.ones, (D,), f32)
            o = _gated_norm(o, gate.reshape(B, T, H, D), scale,
                            cfg.rms_norm_eps)
        with jax.named_scope("ds.attn_proj"):
            out = dense(cfg.hidden_size, "o_proj", row=True)(
                o.reshape(B, T, H * D))
        return out, jax.lax.stop_gradient(decay)


class KimiLinearBlock(nn.Module):
    """One decoder layer of ``mixer`` over a dense SwiGLU or the expert
    layer: ``(x, the pairs each held expert computed [G], the balancing
    rule's step [E] or None, the rule's largest chunk decay)``."""

    config: KimiLinearConfig
    mixer: str = KDA
    dense: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(eps=cfg.rms_norm_eps, name=name)
        with jax.named_scope("ds.norm"):
            h = norm("input_layernorm")(x)
        decay = jnp.zeros((), jnp.float32)
        if self.mixer == KDA:
            mixed, decay = KimiDeltaAttention(cfg, name="linear_attn")(h)
        else:
            mixed = DeepseekV3Attention(cfg, name="self_attn")(
                h, None, None, None)
        with jax.named_scope("ds.residual"):
            x = x + name_if_kept(mixed, REMAT_ATTN_OUT)
        with jax.named_scope("ds.norm"):
            h = norm("post_attention_layernorm")(x)
        if self.dense:
            out = _SwiGLU(cfg, cfg.intermediate_size, "ds.mlp", name="mlp")(h)
            rows, delta = jnp.zeros((cfg.n_routed_experts,), jnp.float32), None
        else:
            out, rows, delta = DeepseekV3MoE(cfg, name="mlp")(h)
        with jax.named_scope("ds.residual"):
            x = x + out
        return x, rows.astype(jnp.float32), delta, decay


def _call(block, kind, x):
    mixer, dense = kind
    inner = "ds.layer_dense" if dense else \
        "ds.layer_full" if mixer == MLA else None
    with jax.named_scope(inner) if inner else contextlib.nullcontext():
        x, *stats = block(x)
    return x, (dense, *stats)


def _fold(sums, stats):
    """An expert layer's rows and balancing step go to the next line of the
    stack's tables (``at`` counts the expert layers so far): they ride the
    scan's carry, which stacks nothing."""
    dense, rows, delta, decay = stats
    sums = {**sums, "decay": jnp.maximum(sums["decay"], decay)}
    if dense:
        return sums
    at = sums["at"]
    put = lambda table, line: jax.lax.dynamic_update_slice(
        table, line[None].astype(table.dtype), (at, 0))
    sums.update(rows=put(sums["rows"], rows), at=at + 1)
    if delta is not None:
        sums["delta"] = put(sums["delta"], delta)
    return sums


class KimiLinearModel(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids):
        """``(final-normed hidden [B, T, H], rows [L, G], bias deltas, the
        KDA rule's largest chunk decay)``: ``rows`` the pairs each held
        expert computed in each of the ``L`` expert layers; the deltas
        ``{parameter path: [.., E]}`` of the balancing rule, empty where it
        is off."""
        cfg = self.config
        _check(cfg)
        leading, period = stack_kinds(cfg)
        n, sparse = len(period), cfg.num_hidden_layers - len(leading)
        with jax.named_scope("ds.embed"):
            x = seeded_embed_tokens(cfg, input_ids)
        balanced = cfg.topk_method == "noaux_tc" and \
            bool(cfg.router_bias_update_rate)
        sums = {"rows": jnp.zeros((sparse, cfg.n_routed_experts), jnp.float32),
                "at": jnp.zeros((), jnp.int32),
                "decay": jnp.zeros((), jnp.float32)}
        if balanced:
            sums["delta"] = jnp.zeros((sparse, cfg.router_width), jnp.float32)
        x, sums = scan_periods(
            cfg, period, x, sums, (), leading=leading,
            block=lambda kind, name: KimiLinearBlock(cfg, *kind, name=name),
            call=_call, fold=_fold,
            scopes={kind: MIXER_SCOPES[kind[0]]
                    for kind in leading + period},
            offers=lambda x: remat_offers(cfg, x))
        with jax.named_scope(head_scope(None)):
            x = RMSNorm(eps=cfg.rms_norm_eps, name="norm")(x)
        deltas = {}
        if balanced:
            # line p n + i of the table is block i of period p
            delta = jax.lax.stop_gradient(sums["delta"])
            for i in range(n):
                path = f"block_{i}/mlp/{BIAS}"
                if cfg.scan_layers:
                    deltas[f"{self.name}/periods/{path}"] = delta[i::n]
                else:
                    deltas.update({f"{self.name}/periods_{p}/{path}":
                                   delta[p * n + i] for p in range(sparse // n)})
        return x, sums["rows"], deltas, sums["decay"]


class KimiLinearForCausalLM(nn.Module):
    """``DeepseekV3ForCausalLM``'s training interface over
    ``KimiLinearModel``: logits without labels; with them the LM loss (no
    auxiliary loss), the balancing rule's ``"param_deltas"`` where the
    configuration asks, and with ``report_expert_load`` the held share's
    gauges and ``kda_chunk_decay_max``."""

    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a stack of KDA and latent-attention layers is built for "
                "training only: no cache holds a KDA layer's matrix states "
                "and convolution tails beside the latent pages")
        if attention_mask is not None or positions is not None:
            raise NotImplementedError(
                "packed sequences from position 0 only: no padding mask or "
                "position offset is composed with the delta rule's state")
        hidden, rows, deltas, decay = KimiLinearModel(cfg, name="model")(
            input_ids)
        with jax.named_scope(head_scope(None)):
            logits = seeded_lm_head(cfg, hidden)
            if labels is None:
                return logits
            loss = cross_entropy_loss(logits, shift_labels(labels))
        named = {"param_deltas": deltas} if deltas else {}
        if not cfg.report_expert_load:
            return (loss, named) if named else loss
        pairs = input_ids.size * cfg.num_experts_per_tok      # of one layer
        expected = max(rows.shape[0], 1) * pairs \
            * cfg.n_routed_experts / cfg.router_width
        return loss, {
            **named, **_held_load_gauges(jnp.sum(rows, axis=0), expected),
            **_compact_hit_gauge(rows, pairs, cfg.router_width),
            "kda_chunk_decay_max": decay}

    frozen_parameters = staticmethod(DeepseekV3ForCausalLM.frozen_parameters)

    @staticmethod
    def partition_rules(config: "KimiLinearConfig"):
        """Tensor parallelism over the latent attention's heads and the
        feed-forward columns (Megatron layout), the unrolled leading blocks
        without a scanned axis; a KDA mixer, the latent projection, the
        router and the held experts are whole on every chip."""
        L = (None,) if config.scan_layers else ()
        col = r"(self_attn/(q_proj|kv_b_proj)|gate_proj|up_proj)/kernel"
        row = r"(self_attn/o_proj|down_proj)/kernel"
        return [
            (r"embed_tokens/embedding", P("model", None)),
            (r"leading/.*" + col, P(None, "model")),
            (r"leading/.*" + row, P("model", None)),
            (col, P(*L, None, "model")),
            (row, P(*L, "model", None)),
            (r"lm_head/kernel", P(None, "model")),
        ]


def remat_offers(cfg, x):
    """What the blocks of this stack name, as ``KimiLinearModel`` offers it
    to ``layers.resolve_remat_policy`` for a stream ``x [B, T, hidden]``
    through all its layers: the KDA rule's output (every pass of the rule
    is rematerialised by itself, so a replay that holds the output runs no
    rule at all -- the backward then runs its forward once, not twice); the
    mixer's output projection; the gate and up
    products ``_SwiGLU`` names (the dense layers' and the shared experts');
    q, k, v as the flash kernels take them on the latent-attention layers;
    what the expert layers name (``mixtral.expert_offers``). Not a KDA
    layer's three wide projections: their 0.8 GB over four layers put the
    compiled step over the configuration's memory rule (PERF.md section 6,
    PR 68)."""
    per_column = device_part(x.shape[0]) * x.shape[1] * x.dtype.itemsize
    kinds = [layer_kind(cfg, l) for l in range(cfg.num_hidden_layers)]
    dense = sum(1 for _, d in kinds if d)
    sparse = len(kinds) - dense
    kda = sum(1 for m, _ in kinds if m == KDA)
    heads = cfg.num_attention_heads * (2 * cfg.qk_head_dim + cfg.v_head_dim)
    return ((REMAT_KDA_RULE,
             kda * cfg.kda_num_heads * cfg.kda_head_dim * per_column),
            (REMAT_ATTN_OUT, len(kinds) * cfg.hidden_size * per_column),
            (REMAT_MLP, 2 * per_column * (
                dense * cfg.intermediate_size
                + sparse * cfg.n_shared_experts * cfg.moe_intermediate_size)),
            (REMAT_QKV, (len(kinds) - kda) * heads * per_column),
            *expert_offers(x, cfg.num_experts_per_tok,
                           cfg.moe_intermediate_size, cfg.n_routed_experts,
                           cfg.router_width, sparse))
