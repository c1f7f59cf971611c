"""The largest exponent one chunk of the KDA rule holds: the mean over the
traced window's ``ds.counters`` events (benchmark/counters.py) of the
program's own ``kda_chunk_decay_max`` -- over layers, heads, CHANNELS and
chunks, the largest ``-sum_{t in chunk} g_t`` in nats. It says what a form
that divided by a decay would have had to represent (float32 holds 88)."""

from benchmark import counters


def read(run):
    return counters.mean(run, "kda_chunk_decay_max")
