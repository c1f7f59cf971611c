"""Tokens the noise masked, over the sequence's tokens: the mean of the
program's own ``bd_masked_share`` over the traced window's ``ds.counters``
events (benchmark/counters.py). The rows the loss reads; about 50 under the
linear schedule with one uniform ``t`` a block."""

from benchmark import counters


def read(run):
    return counters.mean(run, "bd_masked_share", 100.0)
