"""Seconds jax spent tracing and lowering inside the program's set-up spans
(``jax.monitoring``'s ``jaxpr_trace_duration``, outermost traces alone, and
``jaxpr_to_mlir_module_duration``): ``trace_s + lower_s`` of the ``ds.setup``
event (benchmark/setup_record.py). The cache spares none of it."""

from benchmark import setup_record


def read(run):
    return setup_record.value(run, "trace_s", "lower_s")
