"""Share of the step's expert layers that ran on the compact row buffer
(``models/mixtral.py _compact_experts``): the mean of the program's own
``moe_compact_hit_share`` over the traced window's ``ds.counters`` events
(benchmark/counters.py). Below 100 some layer's held pairs overflowed the
buffer and ran on the full one: a slower step."""

from benchmark import counters


def read(run):
    return counters.mean(run, "moe_compact_hit_share", 100.0)
