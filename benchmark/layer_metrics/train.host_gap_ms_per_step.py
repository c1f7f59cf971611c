"""Device idle time inside the traced window per ``ds.train_batch`` step; the
observation line splits it by the program's span that covers each gap
(benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    if run["observed"]["kind"] != "train":
        return None
    r = scope_reduce.reduced(run)
    if not r or not r["steps"]:
        return None
    return 1e3 * r["idle_s"] / r["steps"]
