"""Median self time per step of the serving engine's ``ds.harvest`` span
(the commit loops) inside the traced window, on the profiler's clock
(benchmark/scope_reduce.span_table)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.span_self_ms_p50(run, "serve", "ds.harvest")
