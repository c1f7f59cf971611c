"""Deliberately wrong computations of ``mellum2-12b-a2.5b.train.8k``'s model,
each one thing of the layers as ISSUE 49 wrote them down left out or
replaced, for the cell's check to refuse: patches of module-level names of
``deepspeed_tpu/models/mellum.py`` and ``llama.py`` (every parameter still
exists, so the reference reads the same tree), and the plain reference itself
computed from weights one precision below bfloat16
(``kimi_vl_wrong.reference_from_float8``). The window left off and top-1
routing are the harness's own ``--control window_off`` / ``top1_routing``.
Used by the CPU tests at the tiny size and by the builder's chip script at
the published widths (PERF.md section 6)."""

import contextlib
import dataclasses

import deepspeed_tpu.models.llama as llama
import deepspeed_tpu.models.mellum as mellum
import keye_vl2_wrong
from kimi_vl_wrong import reference_from_float8  # noqa: F401  (re-exported)


def _kinds(make):
    return lambda m: {"period_kinds": lambda cfg: make(
        cfg.full_attention_period)}


def _tables_under(**over):
    """The real ``rope_tables`` of a config with ``over`` replaced."""
    def patch(m):
        real = m.rope_tables
        return {"rope_tables": lambda cfg, positions, dtype: real(
            dataclasses.replace(cfg, **over), positions, dtype)}
    return patch


def _yarn_on_window_layers(m):
    real = m.rope_tables

    def tables(cfg, positions, dtype):
        full = real(cfg, positions, dtype)[m.FULL]
        return {m.WINDOW: full, m.FULL: full}
    return {"rope_tables": tables}


def _topk_not_renormalised(m):
    kind_config = m.kind_config
    return {"kind_config": lambda cfg, kind: dataclasses.replace(
        kind_config(cfg, kind), norm_topk_prob=False)}


#: name -> [(module, patches of it ({attribute: replacement}))]
WRONG = {
    "all_layers_window": [(mellum, _kinds(
        lambda n: (mellum.WINDOW,) * n))],
    "full_layer_first_in_period": [(mellum, _kinds(
        lambda n: (mellum.FULL,) + (mellum.WINDOW,) * (n - 1)))],
    # the full layers rotate with the window layers' plain table
    "yarn_left_off": [(mellum, _tables_under(yarn_factor=None))],
    "yarn_on_window_layers": [(mellum, _yarn_on_window_layers)],
    # YaRN's blended frequencies without the factor on cos and sin
    "attention_factor_left_out": [(mellum, _tables_under(
        yarn_attention_factor=1.0))],
    "topk_not_renormalised": [(mellum, _topk_not_renormalised)],
    # the per-head q/k norm returns its input: keye 16k's patch of llama.py
    "qk_norm_left_out": keye_vl2_wrong.WRONG["head_norm_left_out"],
}
assert WRONG["qk_norm_left_out"][0][0] is llama


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
