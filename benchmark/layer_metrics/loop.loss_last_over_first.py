"""What the loop buys: the mean over the traced window's ``ds.counters``
events of ``loop_loss_last`` over that of ``loop_loss_first`` -- the token-mean
cross entropy under the LAST pass's logits against the first pass's. 1 where
further passes teach nothing (random ids, as the benchmark's); under 1 where
they help."""

from benchmark import counters


def read(run):
    last = counters.mean(run, "loop_loss_last")
    first = counters.mean(run, "loop_loss_first")
    return last / first if last is not None and first else None
