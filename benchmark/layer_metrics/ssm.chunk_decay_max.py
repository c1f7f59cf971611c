"""The largest exponent one chunk of the selective scan holds: the mean over
the traced window's ``ds.counters`` events (benchmark/counters.py) of the
program's own ``ssm_chunk_decay_max`` -- over layers, channels and chunks, the
largest ``sum_{t in chunk} delta_t max_n |A|``. It says whether a longer chunk
or a lower-precision state is safe."""

from benchmark import counters


def read(run):
    return counters.mean(run, "ssm_chunk_decay_max")
