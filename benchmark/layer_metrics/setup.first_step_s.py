"""Seconds from the entry of the first ``train_batch`` to the end of its wait:
the step's trace, lowering, compile or cache read and enqueue
(``first_dispatch_s``) and the fetch of its named scalars (``first_wait_s``);
``first_step_s`` of the ``ds.setup`` event (benchmark/setup_record.py)."""

from benchmark import setup_record


def read(run):
    return setup_record.value(run, "first_step_s")
