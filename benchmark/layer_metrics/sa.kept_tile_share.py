"""512 x 512 tiles of the causal triangle that hold at least one selected
pair, over its tiles: the mean of the program's own ``sa_kept_tile_share``
over the traced window's ``ds.counters`` events (benchmark/counters.py). What
a tile table built from the mask could skip is 100 minus this."""

from benchmark import counters


def read(run):
    return counters.mean(run, "sa_kept_tile_share", 100.0)
