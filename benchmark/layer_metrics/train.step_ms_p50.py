"""Median time of one ``train_batch``: the benchmark's clock over windows of
steps, each closed by ``block_until_ready``, divided by the steps in it."""

from benchmark.stats import percentile


def read(run):
    o = run["observed"]
    return percentile(o["fence_ms"], 50) if o["kind"] == "train" else None
