"""Deliberately wrong computations of ``keye-vl2-30b-a3b.train.16k``'s model,
each one thing of the published forward pass or of the loss left out or
replaced, for the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/indexed_attention.py``, ``llama.py`` and
``mixtral.py`` (every parameter still exists, so the reference reads the same
tree), and the plain reference itself computed from weights one precision
below bfloat16. What ``attention_impl="flash"`` takes from a kernel's module
instead of ``indexed_attention``'s own function (the index scores since PR
40, the indexer's loss since PR 44) is patched THERE as well, under the
signature that branch calls it with: the timed path runs the kernels'
modules, the CPU tests' default path the XLA functions. Used by the CPU
tests at the tiny size and by the builder's chip script at the published
widths (PERF.md section 6)."""

import contextlib

import jax.numpy as jnp

import deepspeed_tpu.models.indexed_attention as ia
import deepspeed_tpu.models.llama as llama
import deepspeed_tpu.models.mixtral as mixtral
import deepspeed_tpu.ops.pallas.sa_index as sa_index
import deepspeed_tpu.ops.pallas.sa_probs as sa_probs
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _causal(scores):
    T = scores.shape[1]
    return jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]


def _selection_left_out(m):
    """Dense causal attention."""
    return {"select_mask": lambda scores, topk, block=512: jnp.broadcast_to(
        _causal(scores), scores.shape).astype(jnp.int8)}


def _last_keys(m):
    """The last ``topk`` keys for the top ``topk``: a window."""
    def select(scores, topk, block=512):
        T = scores.shape[1]
        near = jnp.arange(T)[:, None] - jnp.arange(T)[None, :] < topk
        return jnp.broadcast_to(_causal(scores) & near,
                                scores.shape).astype(jnp.int8)
    return {"select_mask": select}


def _top_half(m):
    select = m.select_mask
    return {"select_mask": lambda scores, topk, block=512: select(
        scores, topk // 2, block)}


def _relu_left_out(m):
    def scores(qi, ki, w, block=512):
        def rows(_, q, w):
            pre = jnp.einsum("bqjd,bkd->bjqk", q, ki,
                             preferred_element_type=jnp.float32)
            return jnp.sum(pre * jnp.swapaxes(w, 1, 2)[..., None], axis=1)
        return ia._by_rows(rows, ia._block(qi.shape[1], block), qi, w)
    return {"index_scores": scores}


def _relu_left_out_flash(m):
    """The kernels' entry hands back the XLA scores without the ReLU (every
    pair written, where the kernels leave the tiles above the diagonal)."""
    scores = _relu_left_out(ia)["index_scores"]
    return {"index_scores": lambda qi, ki, w, block_q=512, block_k=512,
            interpret=None: scores(qi, ki, w, block_q)}


def _head_weights_left_out(m):
    scores = m.index_scores
    return {"index_scores": lambda qi, ki, w, *args, **kwargs: scores(
        qi, ki, jnp.ones_like(w), *args, **kwargs)}


def _head_norm_left_out(m):
    """The per-head q/k norm returns its input (its scale stays a
    parameter); every other norm of ``llama.py`` has three dimensions."""
    class Norm(m.RMSNorm):
        def __call__(self, x):
            y = super().__call__(x)
            return x + 0 * y if x.ndim == 4 else y
    Norm.__name__ = "RMSNorm"
    return {"RMSNorm": Norm}


def _held_zeroed(m):
    routed = m._routed_experts

    def zero(*args):
        out, rows = routed(*args)
        return 0 * out, rows
    return {"_routed_experts": zero}


#: name -> [(module, patches of it ({attribute: replacement}))]: the XLA
#: path's function and, where the flash branch of ``indexed_attention``
#: takes it from a kernel's module, that module's entry too
WRONG = {
    "selection_left_out": [(ia, _selection_left_out)],
    "last_keys_for_top_keys": [(ia, _last_keys)],
    "relu_left_out": [(ia, _relu_left_out),
                      (sa_index, _relu_left_out_flash)],
    "head_weights_left_out": [(ia, _head_weights_left_out),
                              (sa_index, _head_weights_left_out)],
    "top_half_of_topk": [(ia, _top_half)],
    "index_loss_left_out": [
        (ia, lambda m: {
            "index_loss": lambda p_hat, scores, mask: jnp.float32(0)}),
        (sa_probs, lambda m: {
            "index_kl": lambda q, k, lse, scores, mask, sm_scale=None,
            block_q=512, block_k=512, interpret=None, tiles=None:
            jnp.float32(0)})],
    "head_norm_left_out": [(llama, _head_norm_left_out)],
    "held_experts_zeroed": [(mixtral, _held_zeroed)],
}


@contextlib.contextmanager
def wrong(name):
    """The system computes ``name`` wrongly inside the block (trace inside
    it: a jitted function keeps what it was traced with)."""
    patches = [(module, k, v) for module, make in WRONG[name]
               for k, v in make(module).items()]
    saved = [(module, k, getattr(module, k)) for module, k, _ in patches]
    try:
        for module, k, v in patches:
            setattr(module, k, v)
        yield
    finally:
        for module, k, v in saved:
            setattr(module, k, v)
