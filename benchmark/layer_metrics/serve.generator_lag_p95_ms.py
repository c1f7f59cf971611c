"""How late the load generator ran: 95th percentile of submit - due."""

from benchmark.stats import percentile


def read(run):
    o = run["observed"]
    if o["kind"] != "serve" or not o["lag_s"]:
        return None
    return 1e3 * percentile(o["lag_s"], 95)
