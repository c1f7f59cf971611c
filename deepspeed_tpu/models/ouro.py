"""A looped decoder: ONE stack of sandwich-normed layers run
``total_ut_steps`` times over the same weights, the head and an exit gate
read after every pass, the training loss the gate's expectation of the
passes' losses less its entropy. Ouro-2.6B's decoder (``ByteDance/Ouro-2.6B``
``config.json``, ``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741).

With ``R = total_ut_steps``, ``L`` layers and ``N`` an RMS norm with a plain
scale:

- ``x <- E[ids]``; for pass ``t = 1 .. R``, for layer ``l = 1 .. L``, the SAME
  weights in every pass: ``a = x + N2(Attn(N1(x)))``, ``x = a + N4(MLP(N3(a)))``
  -- a norm before AND after each sublayer (``OuroBlock``). Attention and MLP
  are ``llama.py``'s, the rotary table the same in every pass.
- after the pass ``h_t = N_f(x)`` and ``x <- h_t`` (the normed state starts
  the next pass); ``logits_t = h_t W_head``; ``lambda_t = sigmoid(w_g . h_t +
  b_g)``, one gate of width 1 for all passes.
- a token's exit distribution: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
  for ``t < R``, the remainder ``p_R = prod_{j<R} (1 - lambda_j)``.
- training loss: the mean over the shifted tokens of ``sum_t p_t CE_t - beta
  H(p)``, ``CE_t`` the token's cross entropy under ``logits_t``, ``H`` the
  entropy of ``p``, ``beta = exit_entropy_coef``.
- without labels: ``logits_R`` (``early_exit_threshold`` 1 never exits early).

Everything a pass runs -- the layer stack, the final norm, the head, the gate
-- is one module, ``_Pass`` (parameters under ``loop/``), called ``R`` times:
flax shares an instance's parameters between its calls, and JAX adds the
calls' gradients. The ``[tokens, vocab]`` logits of a pass never exist in
training: ``chunked_token_nll`` is ``layers.chunked_cross_entropy_loss``
giving each token's loss, which the exit distribution weighs token by token.

Training only: a cache would hold keys and values a layer AND a pass, and a
scheduler an exit step a token (ROADMAP R14). ``models/__init__.py`` does not
import this module; a configuration names it by path.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (RMSNorm, head_scope, resolve_remat_policy,
                     rotary_embedding, shift_labels)
from .llama import (LlamaAttention, LlamaConfig, LlamaForCausalLM, LlamaMLP,
                    remat_offers)

IGNORE = -100


@dataclasses.dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    #: passes over the one stack of layers (``R``)
    total_ut_steps: int = 4
    #: the cumulative exit probability at which generation would stop
    #: looping; 1 never exits early, and nothing below 1 is built
    early_exit_threshold: float = 1.0
    #: ``beta``: the weight of the exit distribution's entropy in the loss
    exit_entropy_coef: float = 0.1
    #: the training call returns ``(loss, {name: scalar})`` naming
    #: ``loop_exit_step_mean``, ``loop_exit_entropy``, ``loop_loss_first``
    #: and ``loop_loss_last``: registry gauges of the train engine
    report_loop: bool = False

    @staticmethod
    def ouro_2_6b(**over):
        """Ouro-2.6B as published: 48 layers of hidden 2048, 16 / 16 heads
        of 128, MLP 5632, an untied vocabulary of 49,152, four passes."""
        return OuroConfig(**{**dict(
            vocab_size=49152, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=48, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=65536,
            rms_norm_eps=1e-6, rope_theta=1e6, total_ut_steps=4,
            early_exit_threshold=1.0), **over})

    @staticmethod
    def tiny(**over):
        return OuroConfig(**{**dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            total_ut_steps=4, remat=False), **over})


def _check(cfg):
    if cfg.total_ut_steps < 1:
        raise ValueError("total_ut_steps counts the passes: at least 1")
    if cfg.early_exit_threshold < 1:
        raise NotImplementedError(
            "an early_exit_threshold under 1 stops a token's loop at its "
            "exit step, which only a serving scheduler can act on "
            "(ROADMAP R14)")
    if cfg.tie_word_embeddings or cfg.sliding_window is not None \
            or cfg.quantize_weights or getattr(cfg, "sa_config", None):
        raise NotImplementedError(
            "the looped stack has an untied head, full causal attention and "
            "plain weights")


def _post_norm(cfg, name, x):
    """A sublayer's OUTPUT normed before the residual sum."""
    with jax.named_scope("ds.norm"):
        return RMSNorm(eps=cfg.rms_norm_eps, name=name)(x)


class OuroBlock(nn.Module):
    """``LlamaBlock`` with a norm after each sublayer as well as before it:
    four scales a layer, named as the published class names them."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask):
        cfg = self.config
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps, name="input_layernorm")(x)
        attn, _ = LlamaAttention(cfg, name="self_attn")(h, cos, sin, mask)
        attn = _post_norm(cfg, "input_layernorm_2", attn)
        with jax.named_scope("ds.residual"):
            x = x + attn
        with jax.named_scope("ds.norm"):
            h = RMSNorm(eps=cfg.rms_norm_eps,
                        name="post_attention_layernorm")(x)
        out = _post_norm(cfg, "post_attention_layernorm_2",
                         LlamaMLP(cfg, name="mlp")(h))
        with jax.named_scope("ds.residual"):
            return x + out


class _ScanBlock(nn.Module):
    """The layer scan's body: the stream is the carry, the rotary table and
    the mask are broadcast."""

    config: OuroConfig

    @nn.compact
    def __call__(self, x, cos, sin, mask):
        return OuroBlock(self.config, name="block")(x, cos, sin, mask), None


def chunked_token_nll(hidden, w_out, labels, chunk):
    """Each token's cross entropy ``[B, T]`` (float32; 0 where ``labels`` is
    ``IGNORE``) WITHOUT ``[tokens, vocab]`` logits: the head's product and the
    log-sum-exp run in a ``lax.scan`` over chunks of ``chunk`` tokens whose
    body is rematerialised, as ``layers.chunked_cross_entropy_loss`` runs them
    (operands in the activation dtype, float32 accumulation) -- that function
    returns the token MEAN, and a loss that weighs each token by a
    distribution of its own needs the tokens. ``labels`` are already
    shifted."""
    b, t, h = hidden.shape
    n = b * t
    hs, ys = hidden.reshape(n, h), labels.reshape(n)
    pad = (-n) % chunk
    if pad:
        hs = jnp.concatenate([hs, jnp.zeros((pad, h), hs.dtype)])
        ys = jnp.concatenate([ys, jnp.full((pad,), IGNORE, ys.dtype)])

    def body(_, hy):
        hc, yc = hy
        return None, token_nll(jnp.dot(hc, w_out.astype(hc.dtype),
                                       preferred_element_type=jnp.float32),
                               yc)

    _, nll = jax.lax.scan(jax.checkpoint(body), None,
                          (hs.reshape(-1, chunk, h), ys.reshape(-1, chunk)))
    return nll.reshape(-1)[:n].reshape(b, t)


def token_nll(logits, labels):
    """Each token's cross entropy from its logits, in float32; 0 where
    ``labels`` is ``IGNORE``."""
    logits = logits.astype(jnp.float32)
    gold = jnp.take_along_axis(
        logits, jnp.where(labels == IGNORE, 0, labels)[..., None], -1)[..., 0]
    return jnp.where(labels == IGNORE, 0.0,
                     jax.nn.logsumexp(logits, axis=-1) - gold)


def exit_log_distribution(gate_logits):
    """``log p [R, ...]`` from the passes' gate logits ``[R, ...]``: ``log
    p_t = log lambda_t + sum_{j<t} log(1 - lambda_j)``, the last pass taking
    the remainder whatever its own gate says."""
    if gate_logits.shape[0] == 1:
        return jnp.zeros_like(gate_logits)
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), axis=0)
    return jnp.concatenate([
        jax.nn.log_sigmoid(gate_logits[:1]),
        jax.nn.log_sigmoid(gate_logits[1:-1]) + stayed[:-1], stayed[-1:]])


@jax.named_scope("ds.exit_gate")
def expected_loss(cfg, nll, gate_logits, labels):
    """``(loss, gauges)`` from the passes' token losses and gate logits, both
    ``[R, B, T]`` float32: the mean over the labelled tokens of ``sum_t p_t
    CE_t - beta H(p)``."""
    log_p = exit_log_distribution(gate_logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)
    valid = (labels != IGNORE).astype(jnp.float32)
    mean = lambda x: jnp.sum(x * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    loss = mean(jnp.sum(p * nll, axis=0) - cfg.exit_entropy_coef * entropy)
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    return loss, {
        "loop_exit_step_mean": mean(jnp.tensordot(steps, p, axes=1)),
        "loop_exit_entropy": mean(entropy),
        "loop_loss_first": mean(nll[0]),
        "loop_loss_last": mean(nll[-1]),
    }


class ExitGate(nn.Module):
    """The exit gate's logit ``w_g . h + b_g``: one column and a bias,
    accumulated and biased in float32 whatever the stream's dtype (a token's
    exit distribution is products of its sigmoids, and the bias's gradient a
    sum over every token)."""

    @nn.compact
    def __call__(self, h):
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (h.shape[-1], 1), jnp.float32)
        b = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return jnp.dot(h, w.astype(h.dtype),
                       preferred_element_type=jnp.float32)[..., 0] \
            + b.astype(jnp.float32)[0]


class _Pass(nn.Module):
    """One pass: the layer stack (``llama.py``'s scan over remat'ed blocks,
    with the sandwich block), the final norm, and what is read off the normed
    state: ``(the stream as the layers left it, its final norm h_t, (each
    token's loss under the head, the gate's logit))`` -- without labels no
    loss (the caller asks ``head`` for the last state's logits).
    ``offered``: what the blocks name, its bytes over every layer of every
    pass (``llama.remat_offers``), for the remat policy to choose from."""

    config: OuroConfig
    offered: tuple = ()

    def setup(self):
        cfg = self.config
        block = _ScanBlock
        if cfg.remat:
            block = nn.remat(_ScanBlock, prevent_cse=False,
                             policy=resolve_remat_policy(cfg.remat_policy,
                                                         self.offered))
        self.layers = nn.scan(
            block, variable_axes={"params": 0}, split_rngs={"params": True},
            in_axes=(nn.broadcast,) * 3, length=cfg.num_hidden_layers,
            metadata_params={})(cfg)
        self.norm = RMSNorm(eps=cfg.rms_norm_eps)
        self.lm_head = nn.Dense(cfg.vocab_size, use_bias=False,
                                param_dtype=jnp.float32)
        self.early_exit_gate = ExitGate()

    def __call__(self, x, cos, sin, mask, labels):
        cfg = self.config
        # ds.layer_stack: what the loop over the layers costs beyond the
        # layers' own scopes (models/llama.py LlamaModel)
        with jax.named_scope("ds.layer_stack"):
            x, _ = self.layers(x, cos, sin, mask)
        with jax.named_scope(head_scope(None)):
            h = self.norm(x)
            # a zero-width call: the head's parameters exist whatever is
            # read, and nothing is computed
            self.lm_head(h[:, :0])
            if labels is None:
                nll = None
            elif cfg.loss_chunk:
                nll = chunked_token_nll(
                    h, self.lm_head.variables["params"]["kernel"], labels,
                    cfg.loss_chunk)
            else:
                nll = token_nll(self.lm_head(h), labels)
        with jax.named_scope("ds.exit_gate"):
            gate = self.early_exit_gate(h)
        return x, h, (nll, gate)

    def head(self, h):
        with jax.named_scope(head_scope(None)):
            return self.lm_head(h)


def _run_passes(cfg, one_pass, x, positions, mask, labels):
    """``(h_R, [each pass's readings])``: ``total_ut_steps`` calls of the one
    module, every pass under the same rotary table, each starting from the
    NORMED state the one before it left."""
    cos, sin = rotary_embedding(positions, cfg.head_dim, cfg.rope_theta,
                                dtype=x.dtype)
    read = []
    for _ in range(cfg.total_ut_steps):
        _, x, out = one_pass(x, cos, sin, mask, labels)
        read.append(out)
    return x, read


class OuroForCausalLM(nn.Module):
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, positions=None,
                 attention_mask=None, deterministic=True, cache=None,
                 cache_index=None):
        cfg = self.config
        _check(cfg)
        if cache is not None:
            raise NotImplementedError(
                "a looped stack is built for training only: a cache would "
                "hold keys and values a layer AND a pass, and the scheduler "
                "an exit step a token (ROADMAP R14)")
        B, T = input_ids.shape
        with jax.named_scope("ds.embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                         param_dtype=jnp.float32)(input_ids)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        mask = None if attention_mask is None else jnp.where(
            attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
                jnp.float32)
        shifted = None if labels is None else shift_labels(labels)
        one_pass = _Pass(cfg, remat_offers(
            cfg, x, cfg.num_hidden_layers * cfg.total_ut_steps)
            if cfg.remat else (), name="loop")
        # ds.loop_stack: what the loop over the passes costs beyond the
        # passes' own scopes -- the R readings' stacking, the sums of the
        # shared weights' R gradients
        with jax.named_scope("ds.loop_stack"):
            h, read = _run_passes(cfg, one_pass, x, positions, mask, shifted)
            if labels is None:
                return one_pass.head(h)
            nll, gate = (jnp.stack(part) for part in zip(*read))
        loss, gauges = expected_loss(cfg, nll, gate, shifted)
        return (loss, gauges) if cfg.report_loop else loss

    #: the layers' leading scanned axis is ``llama.py``'s; the gate is
    #: replicated
    partition_rules = staticmethod(LlamaForCausalLM.partition_rules)
