"""Seconds of the engine's constructor under its ``ds.init`` span (shapes, the
jitted init of the parameters, the optimizer state, the jitted step built;
host time: no span fences): ``init_s`` of the ``ds.setup`` event
(benchmark/setup_record.py)."""

from benchmark import setup_record


def read(run):
    return setup_record.value(run, "init_s")
