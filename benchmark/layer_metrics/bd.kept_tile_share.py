"""512 x 512 tiles the flash kernels walk under the block-diffusion rule,
over all tiles of the ``2L x 2L`` square: the mean of the program's own
``bd_kept_tile_share`` over the traced window's ``ds.counters`` events
(benchmark/counters.py). A constant of the shapes: 288 of 1,024 at 2 x 8,192
positions and blocks of 4, where the causal rule over 16,384 walks 528."""

from benchmark import counters


def read(run):
    return counters.mean(run, "bd_kept_tile_share", 100.0)
