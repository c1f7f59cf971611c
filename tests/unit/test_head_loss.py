"""The head's loss hands back ONE cotangent (``layers.cross_entropy_loss``, a
``custom_vjp``): value and gradient against plain autodiff of the float32
formula kept HERE, the cotangent's dtype and the shape of the gradient's
jaxpr -- one array in the logits' dtype between the loss and the head's two
backward products, no float32 ``[tokens, vocab]`` behind it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import llama
from deepspeed_tpu.models.layers import cross_entropy_loss
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.runtime import eigenvalue

B, T, V = 2, 12, 50


def plain(logits, labels, ignore_index=-100):
    """What ``cross_entropy_loss`` was before it had a rule of its own."""
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore_index).astype(jnp.float32)
    safe_labels = jnp.where(labels == ignore_index, 0, labels)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None],
                               axis=-1).squeeze(-1)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def _inputs(dtype, ignored):
    rs = np.random.RandomState(7)
    logits = jnp.asarray(rs.randn(B, T, V) * 3, dtype)
    labels = rs.randint(0, V, (B, T))
    if ignored == "some":
        labels[0, :5] = -100
        labels[1, -1] = -100
    elif ignored == "all":
        labels[:] = -100
    return logits, jnp.asarray(labels)


#: one rounding of the cotangent to its dtype, and XLA's reassociation
TOLERANCE = {jnp.float32: dict(rtol=1e-5, atol=1e-8),
             jnp.bfloat16: dict(rtol=2 ** -7, atol=1e-8)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("ignored", ["none", "some", "all"])
@pytest.mark.parametrize("scale", [1.0, -2.5], ids=["g1", "g_scaled"])
def test_value_and_gradient_are_plain_autodiffs(dtype, ignored, scale):
    logits, labels = _inputs(dtype, ignored)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda x: scale * plain(x, labels)))(logits)
    got, got_grad = jax.jit(jax.value_and_grad(
        lambda x: scale * cross_entropy_loss(x, labels)))(logits)
    # the forward is the plain one's operations: the same value to the bit
    assert jax.jit(cross_entropy_loss)(logits, labels) \
        == jax.jit(plain)(logits, labels)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got_grad.dtype == logits.dtype
    got_grad, want_grad = (np.asarray(g, np.float32)
                           for g in (got_grad, want_grad))
    assert np.isfinite(got_grad).all()
    np.testing.assert_allclose(got_grad, want_grad, **TOLERANCE[dtype])
    ignored_rows = np.asarray(labels) == -100
    assert (got_grad[ignored_rows] == 0).all()
    if ignored == "all":
        assert got == 0 and (got_grad == 0).all()
    else:
        assert np.abs(got_grad[~ignored_rows]).max() > 0


@pytest.mark.parametrize("call", ["positional", "keyword"])
def test_another_ignore_index(call):
    logits, labels = _inputs(jnp.float32, "none")
    loss = (lambda x: cross_entropy_loss(x, labels, 3)) \
        if call == "positional" else \
        (lambda x: cross_entropy_loss(x, labels, ignore_index=3))
    want, want_grad = jax.value_and_grad(lambda x: plain(x, labels, 3))(logits)
    got, got_grad = jax.value_and_grad(loss)(logits)
    assert got == want
    np.testing.assert_allclose(got_grad, want_grad, rtol=1e-5, atol=1e-8)
    assert (np.asarray(got_grad)[np.asarray(labels) == 3] == 0).all()


def test_a_cotangent_handed_in_scales_the_gradient():
    logits, labels = _inputs(jnp.bfloat16, "some")
    _, vjp = jax.vjp(lambda x: cross_entropy_loss(x, labels), logits)
    _, plain_vjp = jax.vjp(lambda x: plain(x, labels), logits)
    for g in (1.0, 0.0, 7.0):
        (got,), (want,) = vjp(jnp.float32(g)), plain_vjp(jnp.float32(g))
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOLERANCE[jnp.bfloat16])


def _tiny(family):
    if family == "llama":
        return LlamaForCausalLM(LlamaConfig.tiny())
    return MixtralForCausalLM(MixtralConfig.tiny())


def _loss_of(model, ids):
    def loss(params):
        out = model.apply({"params": params}, ids, labels=ids)
        return out[0] if isinstance(out, tuple) else out
    return loss


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_the_heads_backward_products_read_one_cotangent(family):
    """The gradient's jaxpr of a model in bf16: the rule's float32
    arithmetic ends in ONE ``[batch, tokens, vocab]`` array of the logits'
    dtype behind a barrier; exactly two ``dot_general``s read it (the
    weight's and the hidden state's gradient), and past it no float32 value
    of that shape exists."""
    model = _tiny(family)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16), params)
    eqns = jax.make_jaxpr(jax.grad(_loss_of(model, ids)))(params).jaxpr.eqns
    whole = ids.shape + (model.config.vocab_size,)
    barriers = [i for i, e in enumerate(eqns)
                if e.primitive.name == "optimization_barrier"
                and e.outvars[0].aval.shape == whole]
    assert len(barriers) == 1
    cotangent = eqns[barriers[0]].outvars[0]
    assert cotangent.aval.dtype == jnp.bfloat16
    readers = [e.primitive.name for e in eqns
               if any(v is cotangent for v in e.invars)]
    assert readers == ["dot_general", "dot_general"]
    behind = [v.aval for e in eqns[barriers[0]:] for v in e.outvars]
    assert not [a for a in behind
                if a.shape == whole and a.dtype == jnp.float32]


def test_a_hessian_vector_product_goes_through_both_rules(monkeypatch):
    """``eigenvalue.hvp`` is ``jvp(grad(loss))``: forward over reverse runs
    through the rule's forward AND backward functions."""
    model = _tiny("llama")
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 16)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    vec = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 0.01, p.dtype), params)
    got = eigenvalue.hvp(_loss_of(model, ids), params, vec)
    monkeypatch.setattr(llama, "cross_entropy_loss", plain)
    want = eigenvalue.hvp(_loss_of(model, ids), params, vec)
    got, want = (jnp.concatenate([x.ravel() for x in
                                  jax.tree_util.tree_leaves(t)])
                 for t in (got, want))
    assert np.isfinite(got).all() and np.abs(got).max() > 0
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)

