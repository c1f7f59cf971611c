"""Deliberately wrong computations of ``laguna-xs.2.train.8k``'s model, each
one thing of the layers as ISSUE 63 wrote them down left out or replaced, for
the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/laguna.py`` and ``llama.py`` (every parameter still
exists, so the reference reads the same tree), and the plain
reference itself computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). The window left off is the
harness's own ``--control window_off``. Used by the CPU tests at the tiny
size and by the builder's chip script at the published widths (PERF.md
section 6)."""

import contextlib
import dataclasses

import flax.linen as nn
import jax.numpy as jnp

import deepspeed_tpu.models.laguna as laguna
import deepspeed_tpu.models.llama as llama
from deepspeed_tpu.models.layers import model_dense
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)

#: query heads a key-value head, a sliding layer's -> a full layer's: the
#: published sizes (64 and 48 over 8) and the tiny ones (6 and 4 over 2)
GROUPS = {8: 6, 3: 2}


def _kind_config_under(**over):
    """The real ``kind_config`` of a config with ``over`` replaced."""
    def patch(m):
        real = m.kind_config
        return {"kind_config": lambda cfg, kind: real(
            dataclasses.replace(cfg, **over), kind)}
    return patch


def _all_layers_window(m):
    real = m.kind_config
    return {"kind_config": lambda cfg, kind: dataclasses.replace(
        real(cfg, kind), sliding_window=cfg.sliding_window)}


def _tables_under(**over):
    """The real ``rope_tables`` of a config with ``over`` replaced."""
    def patch(m):
        real = m.rope_tables
        return {"rope_tables": lambda cfg, positions, dtype: real(
            dataclasses.replace(cfg, **over), positions, dtype)}
    return patch


def _full_table_on_window_layers(m):
    """The sliding layers rotate all their columns by the full layers'
    parameters: theta 500,000 under YaRN, its factor on cos and sin."""
    real = m.rope_tables

    def tables(cfg, positions, dtype):
        whole = real(dataclasses.replace(cfg, partial_rotary_factor=1.0),
                     positions, dtype)[m.FULL]
        return {**real(cfg, positions, dtype), m.WINDOW: whole}
    return {"rope_tables": tables}


def _full_grouping_on_window_layers(m):
    """A sliding layer's query head ``h`` reads the key-value head a FULL
    layer's grouping gives it, ``h // 6`` (the last heads the last one),
    where its own is ``h // 8``."""
    real = m.repeat_kv

    def repeat(x, n_rep):
        if n_rep not in GROUPS:
            return real(x, n_rep)
        heads = jnp.arange(x.shape[2] * n_rep) // GROUPS[n_rep]
        return x[:, :, jnp.minimum(heads, x.shape[2] - 1)]
    return {"repeat_kv": repeat}


def _dense_layer_at_an_experts_width(m):
    """Layer 0 as an expert layer none of whose routed experts is held: of
    its feed-forward the 512 columns a shared expert has, of its 8,192. (An
    expert layer proper would need a router and experts the tree of a dense
    layer does not hold; every parameter must still exist.)"""
    real = m._SwiGLU

    class Narrow(real):
        @nn.compact
        def __call__(self, x):
            cfg = self.config
            if self.trace_scope != "ds.mlp":
                return real.__call__(self, x)
            dense = lambda feats, name: model_dense(cfg, feats, name)
            h = nn.silu(dense(self.features, "gate_proj")(x)) \
                * dense(self.features, "up_proj")(x)
            keep = jnp.arange(self.features) \
                < cfg.shared_expert_intermediate_size
            return dense(cfg.hidden_size, "down_proj")(jnp.where(keep, h, 0))
    return {"_SwiGLU": Narrow}


#: name -> [(module, patches of it ({attribute: replacement}))]
WRONG = {
    "gate_left_out": [(llama, lambda m: {
        "_head_gate": lambda logits: jnp.ones(logits.shape, jnp.float32)})],
    "full_grouping_on_window_layers": [
        (llama, _full_grouping_on_window_layers)],
    "all_layers_window": [(laguna, _all_layers_window)],
    # the full layers rotate every column (YaRN computed over all 128)
    "all_columns_rotated": [(laguna, _kind_config_under(
        partial_rotary_factor=1.0))],
    "full_table_on_window_layers": [(laguna, _full_table_on_window_layers)],
    # YaRN's blended frequencies without the factor on cos and sin
    "attention_factor_left_out": [(laguna, _tables_under(
        yarn_attention_factor=1.0))],
    "softmax_scores": [(laguna, _kind_config_under(
        router_scoring="softmax"))],
    "routed_scale_left_out": [(laguna, _kind_config_under(
        routed_scaling_factor=1.0))],
    "topk_not_normalised": [(laguna, _kind_config_under(
        norm_topk_prob=False))],
    "shared_expert_left_out": [(laguna, lambda m: {
        "_shared_expert": (lambda real: lambda cfg, h: 0 * real(cfg, h))(
            m._shared_expert)})],
    "dense_layer_at_an_experts_width": [
        (laguna, _dense_layer_at_an_experts_width)],
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
