"""Seconds inside jax's ``backend_compile_duration`` under the program's set-up
spans — XLA's compile on a miss, the read of the persistent cache on a hit
(``cache_read_s`` is inside it): ``backend_s`` of the ``ds.setup`` event
(benchmark/setup_record.py)."""

from benchmark import setup_record


def read(run):
    return setup_record.value(run, "backend_s")
