"""The busiest expert's routed pairs over the experts' mean, summed over the
layers: the mean of the program's own ``moe_rows_max_over_mean`` over the
traced window's ``ds.counters`` events (benchmark/counters.py). 1 is a level
load."""

from benchmark import counters


def read(run):
    return counters.mean(run, "moe_rows_max_over_mean")
