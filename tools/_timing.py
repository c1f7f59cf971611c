"""Shared benchmark timing helper: one copy of the dispatch-then-sync loop.
"""

import time


def fence(out) -> None:
    """Land ``out``. The ONE copy of the repo's device-fence convention:
    ``block_until_ready`` on the work's own outputs (dispatch is
    asynchronous — a timing without it measures the enqueue)."""
    import jax

    jax.block_until_ready(out)


def time_fn(fn, *args, steps: int = 5, warmup: int = 1) -> float:
    """Mean seconds/step. Warms up (compiles), fences, times ``steps``."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    fence(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / steps
