"""Share of the traced window in which no operation ran on the device:
1 - union of device operation intervals / window (benchmark/trace_reduce)."""


def read(run):
    t = run.get("trace")
    if not t or run["observed"]["kind"] != "serve":
        return None
    return 100.0 * t["idle_share"]
