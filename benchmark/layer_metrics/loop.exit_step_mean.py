"""The pass a token is expected to exit at: the mean over the traced
window's ``ds.counters`` events (benchmark/counters.py) of the program's own
``loop_exit_step_mean`` -- over tokens, ``sum_t t p_t`` of the exit gate's
distribution, between 1 and ``total_ut_steps``. What an early-exit server
would save follows it."""

from benchmark import counters


def read(run):
    return counters.mean(run, "loop_exit_step_mean")
