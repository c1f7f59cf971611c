"""Deliberately wrong computations of ``ouro-2.6b.train.8k``'s model, each one
thing of the loop as ISSUE 56 wrote it down left out or replaced (one of its
list is no wrong computation at all: ``NOT_WRONG``), for the cell's check to
refuse: patches of module-level names of
``deepspeed_tpu/models/ouro.py`` (every parameter still exists, so the
reference reads the same tree), and the plain reference itself computed from
weights one precision below bfloat16 (``kimi_vl_wrong.reference_from_float8``).
Four of them change the last pass's logits and the loss, four the loss alone
(the harness compares both). Used by the CPU tests at the tiny size and by the
builder's chip script at the published widths (PERF.md section 6)."""

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp

import deepspeed_tpu.models.ouro as ouro
from deepspeed_tpu.models.layers import RMSNorm, rotary_embedding
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _passes(passes=lambda cfg: cfg.total_ut_steps, advance=0,
            normed_between=True, rotated=lambda t: True):
    """``ouro._run_passes`` with one thing changed: the number of passes,
    positions that move on by ``advance`` sequence lengths a pass, the
    passes that rotate their queries and keys at all, or the un-normed
    stream handed from pass to pass."""
    def run(cfg, one_pass, x, positions, mask, labels):
        read, h = [], x
        for t in range(passes(cfg)):
            cos, sin = rotary_embedding(
                positions + t * advance * positions.shape[1], cfg.head_dim,
                cfg.rope_theta, dtype=x.dtype)
            if not rotated(t):
                cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
            stream, h, out = one_pass(x, cos, sin, mask, labels)
            x = h if normed_between else stream
            read.append(out)
        return h, read
    return lambda m: {"_run_passes": run}


def _post_norms_left_out(m):
    """The sublayer's output reaches the residual sum as it is; the norm's
    scale still exists (the reference reads it) and is read by nothing."""
    def unnormed(cfg, name, x):
        RMSNorm(eps=cfg.rms_norm_eps, name=name)(x[:, :0])
        return x
    return {"_post_norm": unnormed}


def _loss_with(**over):
    """The real ``expected_loss`` of a config with ``over`` replaced."""
    def patch(m):
        real = m.expected_loss
        return {"expected_loss": lambda cfg, *a: real(
            dataclasses.replace(cfg, **over), *a)}
    return patch


def _last_pass_loss_alone(m):
    real = m.expected_loss

    def last(cfg, nll, gate_logits, labels):
        _, gauges = real(cfg, nll, gate_logits, labels)
        return gauges["loop_loss_last"], gauges
    return {"expected_loss": last}


def _remainder_not_on_last_pass(m):
    """``p_R = lambda_R prod_{j<R} (1 - lambda_j)`` like every other pass:
    the distribution no longer sums to one."""
    def log_p(gate_logits):
        stay = jax.nn.log_sigmoid(-gate_logits)
        return jax.nn.log_sigmoid(gate_logits) \
            + jnp.cumsum(stay, axis=0) - stay
    return {"exit_log_distribution": log_p}


#: name -> [(module, patches of it ({attribute: replacement}))]
WRONG = {
    "three_passes_for_four": [(ouro, _passes(
        passes=lambda cfg: cfg.total_ut_steps - 1))],
    "post_sublayer_norms_left_out": [(ouro, _post_norms_left_out)],
    "state_not_normed_between_passes": [(ouro, _passes(
        normed_between=False))],
    "rotation_left_out_after_first_pass": [(ouro, _passes(
        rotated=lambda t: t == 0))],
    "last_pass_loss_alone": [(ouro, _last_pass_loss_alone)],
    "uniform_exit_weights": [(ouro, lambda m: {
        "exit_log_distribution": lambda g: jnp.full_like(
            g, -math.log(g.shape[0]))})],
    "entropy_left_out": [(ouro, _loss_with(exit_entropy_coef=0.0))],
    "remainder_not_on_last_pass": [(ouro, _remainder_not_on_last_pass)],
}
#: ISSUE 56 lists "positions advanced from pass to pass" among the wrong
#: computations. Under rotary attention it is none: a score reads the
#: DIFFERENCE of two positions, so a shift common to a pass's positions
#: changes nothing but rounding, and no limit can or should refuse it. Kept
#: as the control that says so (the tests hold it INSIDE the limits).
NOT_WRONG = {
    "positions_advance_by_pass": [(ouro, _passes(advance=1))],
}
#: the wrong computations the last pass's logits do not see
LOSS_ONLY = ("last_pass_loss_alone", "uniform_exit_weights",
             "entropy_left_out", "remainder_not_on_last_pass")


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = [(module, k, v)
               for module, make in {**WRONG, **NOT_WRONG}[name]
               for k, v in make(module).items()]
    saved = [(module, k, module.__dict__[k]) for module, k, _ in patches]
    try:
        for module, k, v in patches:
            setattr(module, k, v)
        yield
    finally:
        for module, k, v in saved:
            setattr(module, k, v)
