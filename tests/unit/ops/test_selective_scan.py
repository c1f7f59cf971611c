"""``ops/pallas/selective_scan.py``: the two kernels in interpret mode and
the XLA path of the same chunking against the recurrence position by
position -- values and all six gradients, chunk lengths that do and do not
divide the sequence, one chunk, a long decay -- and the names a trace reads.
(The v5e compile at the benchmark's shapes is
``test_tpu_compile.py``'s ``ssm_scan_train8k`` / ``ssm_scan_bwd_train8k``.)"""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops import pallas as names
from deepspeed_tpu.ops.pallas.selective_scan import selective_scan


def recurrence(u, delta, A, B, C, D):
    """``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) (x) B_t``, ``y_t = h_t
    C_t + D u_t``, one position at a time."""
    def step(h, x):
        u_t, d_t, b_t, c_t = x
        h = jnp.exp(d_t[:, :, None] * A) * h \
            + (d_t * u_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("bcn,bn->bc", h, c_t) + D * u_t

    seq = lambda x: jnp.swapaxes(x, 0, 1)
    h0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]))
    return seq(jax.lax.scan(step, h0, (seq(u), seq(delta), seq(B),
                                       seq(C)))[1])


def operands(T, channels=128, states=4, batch=2, step=0.1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (batch, T, channels)),
            step * jax.nn.softplus(jax.random.normal(k[1],
                                                     (batch, T, channels))),
            -jnp.exp(0.5 * jax.random.normal(k[2], (channels, states))),
            jax.random.normal(k[3], (batch, T, states)),
            jax.random.normal(k[4], (batch, T, states)),
            jax.random.normal(k[5], (channels,))), \
        jax.random.normal(k[6], (batch, T, channels))


def value_and_grads(fn, args, weight):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * weight),
                              argnums=tuple(range(6)))(*args)


PATHS = {
    "kernels": lambda chunk: lambda *a: selective_scan(
        *a, chunk=chunk, channel_tile=128, interpret=True),
    "xla": lambda chunk: lambda *a: selective_scan(*a, impl="xla",
                                                   chunk=chunk),
}
#: (sequence, chunk): chunks that divide it, that do not, one chunk
SHAPES = [(48, 16), (40, 16), (24, 64), (72, 24)]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("T,chunk", SHAPES)
def test_values_and_six_gradients_match_the_recurrence(path, T, chunk):
    args, weight = operands(T)
    want, want_g = value_and_grads(recurrence, args, weight)
    got, got_g = value_and_grads(PATHS[path](chunk), args, weight)
    assert got == pytest.approx(want, rel=1e-5, abs=1e-3)
    for g, w in zip(got_g, want_g):
        assert float(jnp.max(jnp.abs(g - w))) <= \
            2e-5 * float(jnp.max(jnp.abs(w))) + 1e-6


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_long_decay_neither_overflows_nor_loses_the_state(path):
    """Steps of about 3 against ``|A|`` up to 16: a chunk's decay underflows
    to zero, and nothing divides by it."""
    args, weight = operands(64, step=5.0)
    args = (args[0], args[1], -jnp.broadcast_to(
        jnp.arange(1.0, 5.0), args[2].shape) * 4, *args[3:])
    want, want_g = value_and_grads(recurrence, args, weight)
    got, got_g = value_and_grads(PATHS[path](16), args, weight)
    assert jnp.isfinite(got) and got == pytest.approx(want, rel=1e-5)
    for g, w in zip(got_g, want_g):
        assert bool(jnp.isfinite(g).all())
        assert float(jnp.max(jnp.abs(g - w))) <= \
            2e-5 * float(jnp.max(jnp.abs(w))) + 1e-6


def test_bf16_inputs_come_back_in_their_own_types():
    args, weight = operands(32)
    low = (args[0].astype(jnp.bfloat16), args[1], args[2],
           args[3].astype(jnp.bfloat16), args[4].astype(jnp.bfloat16),
           args[5])
    y = PATHS["kernels"](16)(*low)
    assert y.dtype == jnp.float32
    grads = value_and_grads(PATHS["kernels"](16), low, weight)[1]
    assert [g.dtype for g in grads] == [a.dtype for a in low]
    want = value_and_grads(PATHS["xla"](16), low, weight)[1]
    for g, w in zip(grads, want):
        assert float(jnp.max(jnp.abs(g.astype(jnp.float32)
                                     - w.astype(jnp.float32)))) <= \
            1e-2 * float(jnp.max(jnp.abs(w.astype(jnp.float32))))


def test_channel_tiles_and_batch_rows_are_independent():
    """256 channels in tiles of 128: the state and the resident gradient
    blocks of one tile never reach another's."""
    args, weight = operands(32, channels=256, batch=3)
    tiled = value_and_grads(PATHS["kernels"](16), args, weight)
    whole = value_and_grads(lambda *a: selective_scan(
        *a, chunk=16, channel_tile=256, interpret=True), args, weight)
    assert tiled[0] == pytest.approx(whole[0], rel=1e-6)
    for g, w in zip(tiled[1], whole[1]):
        assert jnp.allclose(g, w, rtol=1e-5, atol=1e-5)


def test_kernel_names():
    """What a trace reader matches (``benchmark/ssm_costs.py``)."""
    assert (names.SSM_SCAN_FWD, names.SSM_SCAN_BWD) == \
        ("ds_ssm_scan_fwd", "ds_ssm_scan_bwd")
    args, weight = operands(256, batch=1)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(selective_scan(
        *a, interpret=False) * weight))).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    for name in ("ds_ssm_scan_fwd", "ds_ssm_scan_bwd"):
        assert f'kernel_name = "{name}"' in text


def test_no_array_of_every_position_and_state():
    """Neither path holds ``[T, channels, states]``: 64 positions in chunks
    of 16 lower to nothing with all three sizes side by side."""
    args, weight = operands(64, channels=96, states=4, batch=1)
    for path in PATHS.values():
        text = jax.jit(jax.grad(lambda *a: jnp.sum(
            path(16)(*a) * weight))).lower(*args).as_text()
        assert "64x96x4x" not in text and "64x4x96x" not in text
        assert "16x96x4x" in text or "4x96x" in text


def test_an_unknown_implementation_is_refused():
    args, _ = operands(16)
    with pytest.raises(ValueError, match="ssm_impl"):
        selective_scan(*args, impl="cuda")
