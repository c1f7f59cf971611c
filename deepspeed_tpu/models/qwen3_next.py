"""Qwen3-Next-shaped decoder for training (``Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json``, ``model_type`` ``qwen3_next``): a PATTERN of two mixers --
every ``full_attention_interval``-th layer a gated full-attention layer, the
others gated delta-rule (linear-attention) layers with a matrix state a head --
over a sparse-expert layer with a gated shared expert, at ONE CHIP'S SHARE of
each layer's experts.

*Norm* (``ZeroCentredRMSNorm``, every norm but the delta rule's output norm):
``y = x rsqrt(mean(x^2) + eps) (1 + w)`` in float32, ``w`` seeded at 0.

*Block*: ``h = x + Mixer_kind(Norm(x))``, ``y = h + MoE(Norm(h))``.

*Full layer* (``GatedAttention``): ``[q ; gate] = W_q u`` a head (``2 D``
columns a head, the first ``D`` the query), ``k``, ``v`` of
``num_key_value_heads`` heads; a zero-centred norm over each head's ``D``
columns of ``q`` and of ``k``; rotate-half RoPE on the FIRST
``partial_rotary_factor D`` columns; causal softmax attention at scale
``D ** -0.5`` through ``layers.dot_product_attention`` (the flash kernels);
``o = W_o (attn * sigmoid(gate))``.

*Delta-rule layer* (``GatedDeltaNet``): ``[q ; k ; v ; z] = W_qkvz u`` and
``[b ; a] = W_ba u``, their columns grouped by KEY head as published (a group:
``dk`` of q, ``dk`` of k, ``r dv`` of v, ``r dv`` of z; ``r`` of b, ``r`` of a;
``r`` value heads a key head); ``[q ; k ; v] <- SiLU(causal_conv(.))``,
depthwise, no bias; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)`` a value head in float32; ``q <- q / |q| / sqrt(dk)``, ``k <- k /
|k|``; a key head's q and k serve its ``r`` value heads. A value head keeps
``S [dk, dv]`` in float32 from ``S = 0``:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

(``gated_delta_rule``, in chunks, under ``ds.gdn_rule``: on one TPU device at
heads of whole lanes XLA builds each chunk's running decays, its
strictly-lower table and that table's inverse, and the two kernels of
``ops/pallas/gdn_rule.py`` do everything else with the chunk's other tables
and the head's state in VMEM; elsewhere -- a CPU, a mesh of several devices,
the tiny sizes -- XLA alone, ``_rule_xla``: a triangular solve and a
rematerialised scan over chunk boundaries). Then ``o_t <- w * rms_norm(o_t) *
SiLU(z_t)`` a head (plain scale seeded at 1, the norm before the gate) and
``W_out``. What stands AROUND the rule under ``ds.gdn_mix`` -- the
convolution, its activation and the unit length of q and k ahead of it, the
gated norm after it -- runs, where ``_mix_tiling`` has a tiling (one TPU
device, heads of whole lanes, two-byte operands), as the four kernels of
``ops/pallas/gdn_mix.py``: they read ``W_qkvz u``'s published columns in place
and write q, k, v in the rows-of-time layout the rule's kernels take, so no
array is split, regrouped, repeated or concatenated; elsewhere as XLA's
fusions, ``_premix_xla`` and ``_gate_xla``. ``beta`` and ``g`` (``[B, T, Hv]``)
stay XLA's on both paths.

*MoE*: ``mixtral.MixtralSparseMoeBlock`` as it is -- softmax router over
``router_experts``, top-k renormalised, the HELD experts
``first_expert .. + num_local_experts`` through ``_routed_experts`` and the
compact row buffer -- plus, alike on every chip, ``sigmoid(w_g . x)
SwiGLU_shared(x)``.

Training only: a serving cache would hold each delta-rule layer's matrix
states and convolution tail beside the full layers' pages (ROADMAP R4).
``models/__init__.py`` does not import this module; a configuration names it by
path. The period scan is ``models/mellum.py``'s shape with the period's blocks
of two classes (that file stays as it is: a moved line changes its cell's
lowered step).
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.pallas import (REMAT_GDN_MIX, REMAT_GDN_QKVZ, REMAT_GDN_RULE,
                          gdn_mix, gdn_rule, grouped_matmul)
from ..parallel.topology import get_mesh
from .layers import (apply_rotary_partial, causal_conv, cross_entropy_loss,
                     device_part, dot_product_attention, head_scope,
                     model_dense, name_if_kept, repeat_kv, rotary_embedding,
                     scan_periods, seeded_embed_tokens, seeded_lm_head,
                     shift_labels)
from .mixtral import (MixtralConfig, MixtralForCausalLM, MixtralSparseMoeBlock,
                      _add_stats, _compact_rows, _extra_stats, _fits,
                      _share_loss_and_gauges, expert_offers)

GDN, FULL = "gdn", "full"
#: the outer scope of a block of each kind
KIND_SCOPES = {GDN: "ds.layer_gdn", FULL: "ds.layer_full"}


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(MixtralConfig):
    #: layers a period: the last of each is the gated full-attention layer,
    #: the others gated delta-rule layers
    full_attention_interval: int = 4
    #: the share of a full layer's head columns that rotate (the first)
    partial_rotary_factor: float = 0.25
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    #: taps of the depthwise causal convolution over [q ; k ; v]
    linear_conv_kernel_dim: int = 4
    shared_expert_intermediate_size: int = 512
    #: positions a chunk of ``gated_delta_rule``
    gdn_chunk: int = 64
    #: the standard deviations the two tables' rows are SEEDED at; None:
    #: flax's ``1 / sqrt(hidden_size)`` (``mellum.MellumConfig`` has why a
    #: held share behind a frozen seeded router wants them stated)
    embed_init_std: Optional[float] = None
    head_init_std: Optional[float] = None

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @staticmethod
    def tiny(**over):
        return Qwen3NextConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, shared_expert_intermediate_size=16,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim_override=16,
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=100.0,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8, gdn_chunk=8,
            num_local_experts=4, num_experts_per_tok=2,
            router_aux_loss_coef=0.0, per_expert_init=True, remat=False),
            **over})


def period_kinds(cfg) -> tuple:
    """The kinds of one period's layers, in order."""
    return (GDN,) * (cfg.full_attention_interval - 1) + (FULL,)


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) (1 + weight)`` over the last axis in
    float32, ``weight`` seeded at 0."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],))
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * (1.0 + w)).astype(
            x.dtype)


# -- the gated delta rule ---------------------------------------------------

def _unit_lower_solve(a, rhs):
    """``(I + a)^-1 rhs`` for ``a [..., C, C]`` STRICTLY lower triangular,
    by forward substitution (a triangular solve). The series ``sum (-a)^k``
    by squarings is products alone but holds binomial-sized alternating
    terms where neighbouring keys are alike (all-ones ``a``: ``C(63, 31)``),
    which float32 cannot cancel."""
    return jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)


def _named_inverse(a):
    """``(I + a)^-1`` for ``a [..., C, C]`` strictly lower triangular, named
    with the kernel's o and boundary states (``gdn_rule._vjp_fwd``): a
    policy that keeps the three replays neither the kernel nor this solve."""
    eye = jnp.eye(a.shape[-1], dtype=a.dtype)
    return name_if_kept(_unit_lower_solve(a, jnp.broadcast_to(eye, a.shape)),
                        REMAT_GDN_RULE)


_chunk_inverse = jax.custom_jvp(_named_inverse)


@_chunk_inverse.defjvp
def _chunk_inverse_jvp(primals, tangents):
    """``d X = -X da X`` over the strictly lower part of ``da``, reading the
    NAMED ``X``: two products at the highest precision and no solve. jax's
    own rule for the solve is ``-(I + a)^-1 da X`` with one more solve, and
    closes over the solve's RAW output, which no name reaches -- the
    backward would ask the replay for it, and the replay would run the
    solve to give it; and a backward that holds ``X`` needs no substitution
    to apply ``(I + a)^-1`` (on the chip a solve is the rule's largest
    operation, 2.7 ms a layer, and its transposed twin in the backward cost
    as much once no replay shared its inverted blocks). The solve and its
    name stand HERE, in the differentiated program's own equations, where a
    remat policy sees them (a call to ``_chunk_inverse`` would hide both
    inside one opaque equation)."""
    (a,), (da,) = primals, tangents
    inverse = _named_inverse(a)
    product = functools.partial(jnp.matmul,
                                precision=jax.lax.Precision.HIGHEST)
    return inverse, -product(product(inverse, jnp.tril(da, -1)), inverse)


def _rule_tiling(dk, dv, chunk, dtype):
    """``gdn_rule.plan`` of what this call site can see: the kernels' tiling,
    or None where the rule stays in XLA (off a TPU, under a mesh of several
    devices, widths the kernels leave)."""
    mesh = get_mesh()
    return gdn_rule.plan(
        grouped_matmul.backend(), 1 if mesh is None else mesh.devices.size,
        dk, dv, chunk, jnp.dtype(dtype).itemsize,
        grouped_matmul.device_kind())


def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """The recurrence of the module's docstring over ``T`` positions, in
    chunks of ``chunk``: ``q, k [B, T, H, dk]`` (normalised, ``q`` scaled),
    ``v [B, T, H, dv]``, ``g [B, T, H]`` float32 ``<= 0`` (log decay) and
    ``beta [B, T, H]`` float32 -> ``(o [B, T, H, dv] in v's dtype, the
    largest -sum of g over a chunk)``.

    With ``gamma_i`` the running sum of ``g`` inside a chunk of ``C`` and
    ``S`` the state at its start, the rows ``d_i`` solve ``(I + A) D =
    beta (V - exp(gamma) K S)`` with ``A_ij = beta_i exp(gamma_i - gamma_j)
    k_i . k_j`` for ``j < i``; ``O = exp(gamma) Q S + (Q K^T * exp(gamma_i -
    gamma_j), j <= i) D`` and ``S' = exp(gamma_C) S + (exp(gamma_C - gamma)
    K)^T D``. Every exponent is a difference that is ``<= 0``: nothing
    divides by a decay (``exp(-gamma)`` overflows from 88 nats, and a chunk
    may hold 1,300). A ragged tail is padded with ``k = v = q = 0``, ``beta
    = 0``, ``g = 0``: no update, no decay, no output.

    Where ``_rule_tiling`` has a tiling (one TPU device, heads of whole
    lanes) XLA builds ``gamma``, ``A`` and the inverse ``(I + A)^-1`` and
    the two kernels of ``ops/pallas/gdn_rule.py`` do the rest a chunk at a
    time in VMEM, the state beside it; elsewhere ``_rule_xla``."""
    B, T, H, dk = q.shape
    dv, C, f32 = v.shape[-1], chunk, jnp.float32
    tiling = _rule_tiling(dk, dv, C, q.dtype)
    if tiling is None:
        return _rule_xla(q, k, v, g, beta, C)
    pad = (-T) % C
    n = (T + pad) // C
    seq = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    fold = lambda x: seq(x.astype(f32)).reshape(B, n, C, H).transpose(
        0, 3, 1, 2)                                      # [B, H, n, C]
    q, k, v, beta = seq(q), seq(k), seq(v), fold(beta)
    gamma = jnp.cumsum(fold(g), axis=-1)
    i = jnp.arange(C)
    below = jnp.exp(jnp.where(i[:, None] > i[None, :],
                              gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))
    kc = k.reshape(B, n, C, H, dk)
    a = beta[..., None] * below * jnp.einsum(
        "bnihd,bnjhd->bhnij", kc, kc, preferred_element_type=f32)
    o = gdn_rule.chunk_rule(q, k, v, gamma, beta, _chunk_inverse(a), tiling)
    return o[:, :T], jnp.max(-gamma[..., -1])


def _rule_xla(q, k, v, g, beta, chunk):
    """``gated_delta_rule`` in XLA alone: what does not need ``S`` is
    computed for all chunks at once (the solve's two right-hand sides among
    it); a ``lax.scan`` over the chunks carries ``S`` in float32, its body
    rematerialised, so the backward pass keeps the boundary states only."""
    B, T, H, dk = q.shape
    dv, C, f32 = v.shape[-1], chunk, jnp.float32
    pad = (-T) % C
    n = (T + pad) // C

    def fold(x):        # [B, T, H, d] -> [n, B, H, C, d]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(B, n, C, H, -1).transpose(1, 0, 3, 2, 4)

    q, k, v = fold(q), fold(k), fold(v)
    g, beta = fold(g.astype(f32)[..., None])[..., 0], \
        fold(beta.astype(f32)[..., None])                # [n,B,H,C], [..,1]
    gamma = jnp.cumsum(g, axis=-1)
    total = gamma[..., -1:]                              # [n, B, H, 1]
    i = jnp.arange(C)
    diff = gamma[..., :, None] - gamma[..., None, :]     # gamma_i - gamma_j
    below = jnp.exp(jnp.where(i[:, None] > i[None, :], diff, -jnp.inf))
    upto = jnp.exp(jnp.where(i[:, None] >= i[None, :], diff, -jnp.inf))
    into = jnp.exp(gamma)[..., None]                     # from the start
    out_of = jnp.exp(total - gamma)[..., None]           # to the end
    pairs = lambda x, y: jnp.einsum("...id,...jd->...ij", x, y,
                                    preferred_element_type=f32)
    a = beta * below * pairs(k, k)
    k32 = k.astype(f32)
    uw = _unit_lower_solve(a, jnp.concatenate(
        [beta * v.astype(f32), beta * into * k32], axis=-1))
    u, w = uw[..., :dv], uw[..., dv:]
    m = upto * pairs(q, k)
    qg, kd = into * q.astype(f32), out_of * k32

    @jax.checkpoint
    def step(S, xs):
        u, w, m, qg, kd, last = xs
        d = u - jnp.einsum("...ck,...kv->...cv", w, S)
        o = jnp.einsum("...ck,...kv->...cv", qg, S) \
            + jnp.einsum("...ij,...jv->...iv", m, d)
        S = last[..., None] * S + jnp.einsum("...ck,...cv->...kv", kd, d)
        return S, o

    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), f32),
                        (u, w, m, qg, kd, jnp.exp(total)))
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, n * C, H, dv)[:, :T]
    return o.astype(v.dtype), jnp.max(-total)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)``, as the published class seeds ``A_log``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


class GatedDeltaNet(nn.Module):
    """The delta-rule mixer: ``(out [B, T, hidden], the rule's largest chunk
    decay in nats)``."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, _ = x.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv, r = cfg.linear_key_head_dim, cfg.linear_value_head_dim, \
            cfg.linear_num_value_heads // cfg.linear_num_key_heads
        f32 = jnp.float32
        with jax.named_scope("ds.attn_proj"):
            qkvz = name_if_kept(model_dense(
                cfg, 2 * Hk * dk + 2 * Hv * dv, "in_proj_qkvz")(x),
                REMAT_GDN_QKVZ)
            ba = model_dense(cfg, 2 * Hv, "in_proj_ba")(x)
        with jax.named_scope("ds.gdn_mix"):
            b, a = jnp.split(ba.reshape(B, T, Hk, 2 * r), 2, axis=-1)
            taps = self.param("conv1d", nn.initializers.lecun_normal(
                in_axis=0, out_axis=1, batch_axis=()),
                (cfg.linear_conv_kernel_dim, 2 * Hk * dk + Hv * dv), f32)
            heads = gdn_mix.Heads(Hk, dk, r, dv, cfg.linear_conv_kernel_dim)
            tiling = _mix_tiling(heads, x.dtype)
            # _conv_act, _unit_length: looked up here, at trace time
            if tiling is None:
                q, k, v, z = _premix_xla(qkvz, taps, heads, _conv_act,
                                         _unit_length)
            else:
                q, k, v, z = gdn_mix.premix(qkvz, taps, heads, _conv_act,
                                            _unit_length, tiling)
            q, k, v = (name_if_kept(t, REMAT_GDN_MIX) for t in (q, k, v))
            a_log = self.param("A_log", _a_log_init, (Hv,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,), f32)
            beta = _beta(b.reshape(B, T, Hv).astype(f32))
            g = _log_decay(a_log, a.reshape(B, T, Hv).astype(f32), dt_bias)
        with jax.named_scope("ds.gdn_rule"):
            o, decay = gated_delta_rule(
                q.reshape(B, T, Hv, dk), k.reshape(B, T, Hv, dk),
                v.reshape(B, T, Hv, dv), g, beta, cfg.gdn_chunk)
        with jax.named_scope("ds.gdn_mix"):
            scale = self.param("norm_scale", nn.initializers.ones, (dv,), f32)
            o = o.reshape(B, T, Hv * dv)
            if tiling is None:
                o = _gate_xla(o, z, scale, cfg.rms_norm_eps, dv)
            else:       # z: premix's handle; the kernel reads qkvz in place
                o = gdn_mix.gate(o, z, qkvz, scale, cfg.rms_norm_eps, heads,
                                 tiling)
            o = name_if_kept(o, REMAT_GDN_MIX)
        with jax.named_scope("ds.attn_proj"):
            out = model_dense(cfg, cfg.hidden_size, "out_proj",
                              row_parallel=True)(o)
        return out, jax.lax.stop_gradient(decay)


def _mix_tiling(heads, dtype):
    """``gdn_mix.plan`` of what this call site can see: the tiling of the
    kernels around the rule, or None where that work stays in XLA (off a
    TPU, under a mesh of several devices, widths the kernels leave)."""
    mesh = get_mesh()
    return gdn_mix.plan(
        grouped_matmul.backend(), 1 if mesh is None else mesh.devices.size,
        heads, jnp.dtype(dtype).itemsize, grouped_matmul.device_kind())


def _premix_xla(qkvz, taps, heads, conv_act, unit_length):
    """The mixer between ``in_proj_qkvz`` and the rule in XLA: ``(q, k [B,
    T, Hv dk], v, z [B, T, Hv dv])`` from the published layout (a key head's
    q, k, its r values, its r z) -- what ``gdn_mix.premix`` computes in one
    kernel (its fourth output stands for z there)."""
    B, T, _ = qkvz.shape
    Hk, dk, r, dv, _ = heads
    q, k, v, z = jnp.split(
        qkvz.reshape(B, T, Hk, 2 * dk + 2 * r * dv),
        (dk, 2 * dk, 2 * dk + r * dv), axis=-1)
    mixed = jnp.concatenate([t.reshape(B, T, -1) for t in (q, k, v)], axis=-1)
    mixed = conv_act(causal_conv(mixed, taps.astype(qkvz.dtype)))
    q, k, v = jnp.split(mixed, (Hk * dk, 2 * Hk * dk), axis=-1)
    q, k = (unit_length(t.reshape(B, T, Hk, dk)) for t in (q, k))
    q = repeat_kv((q * dk ** -0.5).astype(qkvz.dtype), r)
    k = repeat_kv(k.astype(qkvz.dtype), r)
    flat = lambda t: t.reshape(B, T, -1)
    return flat(q), flat(k), v, flat(z)


def _gate_xla(o, z, scale, eps, dv):
    """``scale * rms_norm(o) * silu(z)`` a value head of ``dv`` columns in
    float32, in o's type (``gdn_mix.gate`` in one kernel)."""
    B, T, _ = o.shape
    o32 = o.reshape(B, T, -1, dv).astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt(jnp.mean(o32 * o32, -1, keepdims=True)
                              + eps) * scale
    return (o32 * nn.silu(z.reshape(B, T, -1, dv).astype(jnp.float32))
            ).astype(o.dtype).reshape(B, T, -1)


# the mixers' small formulas by name (tests/benchmark/qwen3_next_wrong.py
# replaces one at a time)
_conv_act = nn.silu


def _beta(b):
    return jax.nn.sigmoid(b)


def _log_decay(a_log, a, dt_bias):
    """``g = -exp(A_log) softplus(a + dt_bias)`` a value head, float32."""
    return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)


def _attn_gate(gate):
    return jax.nn.sigmoid(gate)


def _shared_gate(logit):
    return jax.nn.sigmoid(logit)


def _unit_length(x):
    """``x / sqrt(sum x^2 + 1e-6)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


class GatedAttention(nn.Module):
    """The full layer's mixer on ``LlamaAttention``'s projections: a gate
    beside each head's query, zero-centred head norms, a partial rotation,
    the output times ``sigmoid(gate)`` before ``o_proj``."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        B, T, _ = x.shape
        H, Hkv, D = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("ds.attn_proj"):
            q, gate = jnp.split(model_dense(cfg, H * 2 * D, "q_proj")(
                x).reshape(B, T, H, 2 * D), 2, axis=-1)
            k = model_dense(cfg, Hkv * D, "k_proj")(x).reshape(B, T, Hkv, D)
            v = model_dense(cfg, Hkv * D, "v_proj")(x).reshape(B, T, Hkv, D)
            q = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="q_norm")(q)
            k = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="k_norm")(k)
            q = apply_rotary_partial(q, cos, sin, cfg.rotary_dim)
            k = apply_rotary_partial(k, cos, sin, cfg.rotary_dim)
        out = dot_product_attention(
            q, repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv), causal=True,
            attention_impl=cfg.attention_impl,
            flash_block_q=cfg.flash_block_q, flash_block_k=cfg.flash_block_k)
        with jax.named_scope("ds.attn_gate"):
            out = out * _attn_gate(gate)
        with jax.named_scope("ds.attn_proj"):
            return model_dense(cfg, cfg.hidden_size, "o_proj",
                               row_parallel=True)(out.reshape(B, T, H * D))


class SharedExpert(nn.Module):
    """``sigmoid(w_g . x) SwiGLU(x)`` over every token, alike on every chip
    of the deployment."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name, row=False: model_dense(
            cfg, feats, name, row_parallel=row)
        with jax.named_scope("ds.moe_shared"):
            I = cfg.shared_expert_intermediate_size
            y = dense(cfg.hidden_size, "down_proj", row=True)(
                nn.silu(dense(I, "gate_proj")(x)) * dense(I, "up_proj")(x))
            return _shared_gate(dense(1, "shared_expert_gate")(x)) * y


class Qwen3NextBlock(nn.Module):
    """One decoder layer of ``kind``: ``(x, each expert's token fraction
    [E], mean router probability [E], the layer's other statistics)`` as
    ``MixtralBlock`` hands them up, with ``gdn_chunk_decay`` among them."""

    config: Qwen3NextConfig
    kind: str = GDN

    @nn.compact
    def __call__(self, x, cos, sin):
        cfg = self.config
        norm = lambda name: ZeroCentredRMSNorm(cfg.rms_norm_eps, name=name)
        with jax.named_scope("ds.norm"):
            h = norm("input_layernorm")(x)
        decay = jnp.zeros((), jnp.float32)
        if self.kind == GDN:
            mixed, decay = GatedDeltaNet(cfg, name="linear_attn")(h)
        else:
            mixed = GatedAttention(cfg, name="self_attn")(h, cos, sin)
        with jax.named_scope("ds.residual"):
            x = x + mixed
        with jax.named_scope("ds.norm"):
            h = norm("post_attention_layernorm")(x)
        moe_out, frac, prob, rows = MixtralSparseMoeBlock(
            cfg, name="block_sparse_moe")(h)
        shared = SharedExpert(cfg, name="shared_expert")(h)
        with jax.named_scope("ds.residual"):
            x = x + moe_out + shared
        extra = {}
        C = _compact_rows(x.shape[0] * x.shape[1] * cfg.num_experts_per_tok,
                          cfg.num_local_experts, cfg.router_experts)
        if C is not None:
            extra["compact_hit"] = _fits(rows, C).astype(jnp.float32)
        return x, frac, prob, extra, decay


def _check(cfg):
    n = cfg.full_attention_interval
    if n < 1 or cfg.num_hidden_layers % n:
        raise ValueError(f"{cfg.num_hidden_layers} layers are no whole "
                         f"periods of {n}")
    if cfg.linear_num_value_heads % cfg.linear_num_key_heads:
        raise ValueError("each key head serves a whole number of value heads")
    if cfg.sa_config is not None or cfg.sliding_window is not None:
        raise NotImplementedError(
            "the full layers attend the whole causal prefix: no window, no "
            "learned selection")
    if cfg.tie_word_embeddings or cfg.loss_chunk:
        raise NotImplementedError(
            "the head is a table of its own whose logits are whole: no "
            "tied table, no chunked loss")
    if cfg.report_expert_load and cfg.router_experts is None:
        raise NotImplementedError(
            "report_expert_load names a held share's gauges: give "
            "router_experts")


def _call(block, kind, x, cos, sin):
    x, frac, prob, extra, decay = block(x, cos, sin)
    return x, (frac, prob, extra, decay)


def _fold(sums, stats):
    frac_sum, prob_sum, extra_sum, decay_max = sums
    frac, prob, extra, decay = stats
    return (frac_sum + frac, prob_sum + prob, _add_stats(extra_sum, extra),
            jnp.maximum(decay_max, decay))


class Qwen3NextModel(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        """``(final-normed hidden, (each expert's share of the tokens summed
        over layers, the layers' other statistics summed, the delta rule's
        largest chunk decay))``."""
        cfg = self.config
        _check(cfg)
        B, T = input_ids.shape
        kinds = period_kinds(cfg)
        with jax.named_scope("ds.embed"):
            x = seeded_embed_tokens(cfg, input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        cos, sin = rotary_embedding(positions, cfg.rotary_dim, cfg.rope_theta,
                                    dtype=x.dtype)
        zero_e = jnp.zeros((cfg.router_width,), jnp.float32)
        sums = (zero_e, zero_e, dict.fromkeys(
            _extra_stats(cfg, B * T * cfg.num_experts_per_tok),
            jnp.float32(0)), jnp.zeros((), jnp.float32))
        x, (frac_sum, _, extra_sum, decay_max) = scan_periods(
            cfg, kinds, x, sums, (cos, sin),
            block=lambda kind, name: Qwen3NextBlock(cfg, kind, name=name),
            call=_call, fold=_fold, scopes=KIND_SCOPES,
            offers=lambda x: remat_offers(cfg, x, kinds))
        with jax.named_scope(head_scope(None)):
            x = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="norm")(x)
        return x, (frac_sum, extra_sum, decay_max)


class Qwen3NextForCausalLM(nn.Module):
    """``MixtralForCausalLM``'s training interface over ``Qwen3NextModel``:
    logits without labels; with them the LM loss (no router loss: the
    source has no coefficient) and, with ``report_expert_load``, ``(loss,
    named scalars)``: the held share's gauges and ``gdn_chunk_decay_max``."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        if cache is not None:
            raise NotImplementedError(
                "a stack of delta-rule and full layers is built for training "
                "only: no cache holds a delta-rule layer's matrix states and "
                "convolution tail beside a full layer's keys and values")
        if attention_mask is not None:
            raise NotImplementedError(
                "packed sequences only: no padding mask is composed with "
                "the delta rule's state")
        hidden, (load, extra, decay) = Qwen3NextModel(cfg, name="model")(
            input_ids, positions)
        with jax.named_scope(head_scope(None)):
            logits = seeded_lm_head(cfg, hidden)
            if labels is None:
                return logits
            loss = cross_entropy_loss(logits, shift_labels(labels))
        if cfg.router_experts is None:
            return loss
        out = _share_loss_and_gauges(cfg, loss, load, extra, input_ids.size)
        if not cfg.report_expert_load:
            return out
        return out[0], {**out[1], "gdn_chunk_decay_max": decay}

    #: one leading scanned axis (the periods) where Mixtral's is the layers:
    #: its rules for the full layers' projections, the experts and the two
    #: tables (a delta-rule mixer stays whole on every chip), its frozen
    #: router
    partition_rules = staticmethod(MixtralForCausalLM.partition_rules)
    frozen_parameters = staticmethod(MixtralForCausalLM.frozen_parameters)


def remat_offers(cfg, x, kinds):
    """What a delta-rule layer names, as ``Qwen3NextModel`` offers it to
    ``layers.resolve_remat_policy``: ``[(name, bytes over the stack's
    delta-rule layers)]`` for a stream ``x [B, T, hidden]``, costliest replay
    a byte first -- the rule's output, boundary states and chunk inverse
    (qwen3-next 8k: 13.4 ms a step for 1.2 GB), ``in_proj_qkvz``'s output
    (the replay then runs the premix kernel alone to hand the backward its
    q, k, v), and what the premix and gate kernels hand on. The rule's
    values are named only where its kernels run (``_rule_tiling``: one TPU
    device, which is also where an engine states a budget): nothing of the
    rule's is offered elsewhere. Last, over every layer of the stack, what
    the expert layer names (``mixtral.expert_offers``; qwen3-next 8k: 3.5 ms
    a step for 0.25 GB)."""
    B, T, _ = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, C = cfg.linear_key_head_dim, cfg.linear_value_head_dim, \
        cfg.gdn_chunk
    experts = expert_offers(
        x, cfg.num_experts_per_tok, cfg.expert_width, cfg.num_local_experts,
        cfg.router_experts, cfg.num_hidden_layers)
    if _rule_tiling(dk, dv, C, x.dtype) is None:
        return experts
    layers = cfg.num_hidden_layers // len(kinds) * kinds.count(GDN)
    B, item, n = device_part(B), x.dtype.itemsize, -(-T // C)
    return ((REMAT_GDN_RULE, layers * B * Hv * (
                n * (C * C + dk * dv) * 4 + T * dv * item)),
            (REMAT_GDN_QKVZ,
             layers * B * T * (2 * Hk * dk + 2 * Hv * dv) * item),
            (REMAT_GDN_MIX, layers * B * T * Hv * (2 * dk + 2 * dv) * item),
            *experts)
