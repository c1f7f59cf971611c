"""Median wall time of one ``ServingEngine.step()`` in the window, on the
benchmark's clock (the step ends with the tokens on the host)."""

from benchmark.stats import percentile


def read(run):
    o = run["observed"]
    return percentile(o["step_ms"], 50) if o["kind"] == "serve" else None
