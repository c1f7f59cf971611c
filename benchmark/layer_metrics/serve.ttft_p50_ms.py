"""Median time to first token, the same samples as the tail."""

from benchmark.stats import percentile


def read(run):
    o = run["observed"]
    if o["kind"] != "serve" or not o["ttft_s"]:
        return None
    return 1e3 * percentile(o["ttft_s"], 50)
