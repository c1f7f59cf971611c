"""ThroughputTimer window-fencing semantics (utils/timer.py).

The regression this guards: per-step device fences serialize the async
dispatch pipeline (two fences per train_batch).
The timer must (a) never fence between reporting windows, (b) still answer
avg/recent queries at any point, (c) produce exact fence-to-fence window
throughput. Reference counterpart: ``utils/timer.py ThroughputTimer`` —
same API, per-step ``cuda.synchronize`` replaced by window fencing.
"""

import deepspeed_tpu.utils.timer as timer_mod
from deepspeed_tpu.utils.timer import ThroughputTimer


def _run_steps(t, n):
    for _ in range(n):
        t.start()
        t.stop()


def test_no_fence_between_windows(monkeypatch):
    fences = []
    monkeypatch.setattr(timer_mod, "_synchronize", lambda: fences.append(1))
    t = ThroughputTimer(batch_size=4, start_step=2, steps_per_output=10,
                        logging_fn=lambda m: None)
    _run_steps(t, 9)  # warmup fence at step 2 only; window closes at step 10
    assert len(fences) == 1
    _run_steps(t, 1)  # step 10: window close = 1 fence
    assert len(fences) == 2


def test_query_settles_open_window(monkeypatch):
    fences = []
    monkeypatch.setattr(timer_mod, "_synchronize", lambda: fences.append(1))
    t = ThroughputTimer(batch_size=8, start_step=2, steps_per_output=0,
                        logging_fn=lambda m: None)
    _run_steps(t, 7)
    assert len(fences) == 1  # warmup only
    assert t.avg_samples_per_sec() > 0  # settle-on-demand
    assert len(fences) == 2
    assert t._fenced_steps == 5  # steps 3..7
    # an immediate re-query must not re-fence a zero-step window
    assert t.avg_samples_per_sec() > 0
    assert len(fences) == 2


def test_reported_throughput_is_positive_and_consistent():
    reports = []
    t = ThroughputTimer(batch_size=2, start_step=2, steps_per_output=4,
                        logging_fn=reports.append)
    _run_steps(t, 12)
    # windows close at steps 4 (short first window: steps 3-4), 8, and 12
    assert len(reports) == 3
    assert t.avg_samples_per_sec() > 0
    assert t.recent_samples_per_sec() > 0
    assert t._fenced_steps == 10  # 2 + 4 + 4


def test_short_run_below_one_window_still_answers():
    t = ThroughputTimer(batch_size=32, start_step=2, steps_per_output=50,
                        logging_fn=lambda m: None)
    _run_steps(t, 5)
    assert t.avg_samples_per_sec() > 0
    assert t.recent_samples_per_sec() > 0
