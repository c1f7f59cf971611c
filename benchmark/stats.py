"""Arithmetic from timelines to the end-to-end numbers. Pure Python: the
tests feed it hand-made timelines, the runners feed it what they clocked."""

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics; None for no samples."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def fence_groups(fence_ms):
    """A training window's fenced groups of steps in one line (``fence_ms``:
    each group's milliseconds a step): a stall is a group or two far above
    the median among steady ones, a slow program a high median."""
    return {"groups": len(fence_ms),
            "fence_ms_p50": percentile(fence_ms, 50),
            "fence_ms_max": max(fence_ms, default=None)}


#: a request in one of these has not failed; every other terminal state
#: (failed, timeout, cancelled/shed, rejected) has
LIVE_OR_FINISHED = ("finished", "running", "queued")


def serve_window(requests, w0, w1, grace_s):
    """Reduce one open-loop run to its samples.

    ``requests``: dicts with ``due`` and ``submit`` (seconds on the run's
    clock), ``token_times`` (when each output token was seen) and ``state``
    (the engine's terminal state, or ``running``/``queued``). The window is
    ``[w0, w1]``.

    - *attempted*: requests due in ``[w0, w1 - grace_s)``;
    - *failed*: of those, no first token by ``w1``, or a terminal state
      other than ``finished``. A request still decoding at ``w1`` is neither;
    - *ttft*: first token - due for every attempted request; one without a
      first token enters at ``w1 - due``, the least it can have been, so an
      overloaded run cannot quiet its own tail;
    - *gaps*: every gap between consecutive tokens of one request that ends
      in the window, whenever the request was due;
    - *tokens*: output tokens seen in the window.
    """
    attempted = failed = tokens = 0
    ttft, gaps, lag = [], [], []
    for r in requests:
        times = [t for t in r["token_times"] if t <= w1]
        tokens += sum(1 for t in times if t >= w0)
        gaps += [b - a for a, b in zip(times, times[1:]) if b >= w0]
        if not (w0 <= r["due"] < w1 - grace_s):
            continue
        attempted += 1
        lag.append(r["submit"] - r["due"])
        ttft.append((times[0] if times else w1) - r["due"])
        if not times or r["state"] not in LIVE_OR_FINISHED:
            failed += 1
    return {"attempted": attempted, "failed": failed, "tokens": tokens,
            "ttft_s": ttft, "gaps_s": gaps, "lag_s": lag,
            "seconds": w1 - w0}
