"""The mean of the attention's head gates: the mean over the traced window's
``ds.counters`` events (benchmark/counters.py) of the program's own
``attn_gate_mean`` -- ``sigmoid(h W_g)`` over tokens, heads and layers; 0.5
at seeded weights, and a gate that training drives shut or open moves it."""

from benchmark import counters


def read(run):
    return counters.mean(run, "attn_gate_mean")
