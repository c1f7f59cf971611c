"""Pairs routed to the experts this chip holds over what a level load would
bring them: the mean of the program's own ``moe_held_rows_over_expected`` over
the traced window's ``ds.counters`` events (benchmark/counters.py). A witness
of the traffic: 1 is the deployment's load, under 1 the cell does less than
its deployment's work."""

from benchmark import counters


def read(run):
    return counters.mean(run, "moe_held_rows_over_expected")
