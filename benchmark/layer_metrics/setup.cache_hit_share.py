"""Share of the process's compiles that the persistent cache served, the
program's and those outside its spans together: 100 x hits / (hits + misses)
of the ``ds.setup`` event (benchmark/setup_record.py). Under 100 the run met
a miss: its ``setup_s`` is no warm reading."""

from benchmark import setup_record


def read(run):
    hits = setup_record.value(run, "cache_hits", "outside_cache_hits")
    misses = setup_record.value(run, "cache_misses", "outside_cache_misses")
    if hits is None or misses is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
