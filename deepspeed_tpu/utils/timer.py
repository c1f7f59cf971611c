"""Wall-clock + throughput timers.

Counterpart of the reference's ``deepspeed/utils/timer.py`` (CUDA-event
``SynchronizedWallClockTimer`` and ``ThroughputTimer``). On TPU there are no
CUDA events; synchronization is ``jax.block_until_ready`` on every live
array, which drains the dispatch queue the same way
``torch.cuda.synchronize`` does.
"""

import time
from collections import OrderedDict
from typing import Dict, List, Optional

from .logging import log_dist

try:
    import psutil

    _PSUTIL = True
except Exception:  # pragma: no cover
    _PSUTIL = False


def _synchronize() -> None:
    """Block until all dispatched device computations are complete: every
    computation still in flight has a live output array, so waiting on all
    live arrays is the fence (a fresh ``device_put`` is a transfer — it is
    ordered after nothing)."""
    import jax

    jax.block_until_ready(jax.live_arrays())


class Timer:
    """A single named timer with optional device synchronization."""

    def __init__(self, name: str, synchronize: bool = True):
        self.name = name
        self.synchronize = synchronize
        self.started = False
        self._start_time = 0.0
        self._elapsed = 0.0
        self._record: List[float] = []

    def start(self) -> None:
        if self.started:
            return
        if self.synchronize:
            _synchronize()
        self._start_time = time.perf_counter()
        self.started = True

    def stop(self, record: bool = True) -> None:
        if not self.started:
            return
        if self.synchronize:
            _synchronize()
        elapsed = time.perf_counter() - self._start_time
        self._elapsed += elapsed
        if record:
            self._record.append(elapsed)
        self.started = False

    def reset(self) -> None:
        self.started = False
        self._elapsed = 0.0
        self._record = []

    def elapsed(self, reset: bool = True) -> float:
        """Total elapsed seconds (stops/restarts a running timer)."""
        was_started = self.started
        if was_started:
            self.stop(record=False)
        total = self._elapsed
        if reset:
            self.reset()
        if was_started:
            self.start()
        return total

    def mean(self) -> float:
        return sum(self._record) / len(self._record) if self._record else 0.0


class SynchronizedWallClockTimer:
    """Named timer registry (reference: ``utils/timer.py:31``)."""

    def __init__(self):
        self.timers: "OrderedDict[str, Timer]" = OrderedDict()

    def __call__(self, name: str) -> Timer:
        if name not in self.timers:
            self.timers[name] = Timer(name)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    @staticmethod
    def memory_usage() -> str:
        if not _PSUTIL:
            return "mem: n/a"
        vm = psutil.virtual_memory()
        return f"host mem used: {vm.used / 2**30:.2f} GB ({vm.percent}%)"

    def log(self, names: Optional[List[str]] = None, normalizer: float = 1.0, reset: bool = True,
            ranks=None) -> None:
        names = names if names is not None else list(self.timers)
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed:.2f}"
        log_dist(string, ranks=ranks or [0])

    def get_mean(self, names: List[str], normalizer: float = 1.0) -> Dict[str, float]:
        return {
            name: self.timers[name].mean() * 1000.0 / normalizer
            for name in names
            if name in self.timers
        }


class ThroughputTimer:
    """Samples/sec + TFLOPs tracker (reference: ``utils/timer.py:135``).

    TPU-first timing discipline: the reference fences CUDA around every step
    (``torch.cuda.synchronize``). A device fence per step serializes the
    async dispatch pipeline, so this timer fences only at reporting-WINDOW
    boundaries: fence-to-fence wall time over a window of N steps is exactly
    the throughput, and steps in between stay fully pipelined. With
    reporting disabled the timer costs two perf_counter() calls and no
    device traffic at all.
    """

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50,
                 monitor_memory: bool = False, logging_fn=None):
        self.batch_size = max(1, batch_size)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or (lambda msg: log_dist(msg, ranks=[0]))
        self.initialized = False
        self.global_step_count = 0
        self.local_step_count = 0
        self.total_elapsed_time = 0.0  # fenced window time only
        self.step_elapsed_time = 0.0
        self._fenced_steps = 0         # steps covered by fenced windows
        self._window_steps = 0         # steps since the window fence
        self._last_window_steps = 0
        self._window_t0 = None
        self.started = False

    def update_epoch_count(self) -> None:
        self.local_step_count = 0

    def _open_window(self) -> None:
        _synchronize()
        self._window_t0 = time.perf_counter()
        self._window_steps = 0

    def start(self) -> None:
        self.started = True
        if self.global_step_count == self.start_step and self._window_t0 is None:
            self._open_window()  # the ONLY unconditional fence: warmup ends

    def stop(self, global_step: bool = True, report_speed: bool = True) -> None:
        if not self.started:
            return
        self.started = False
        if global_step:
            self.global_step_count += 1
            self.local_step_count += 1
        if self._window_t0 is None or self.global_step_count <= self.start_step:
            return
        self._window_steps += 1
        if report_speed and self.steps_per_output and \
                self.global_step_count % self.steps_per_output == 0:
            self._close_window_and_report()

    def _close_window_and_report(self) -> None:
        self._settle()
        self.logging(
            f"step={self.global_step_count}, "
            f"samples/sec (avg)={self.avg_samples_per_sec():.2f}, "
            f"samples/sec (recent)={self.recent_samples_per_sec():.2f}"
        )

    def _settle(self) -> None:
        """Fold the in-flight window into the totals (one fence) so a
        throughput query always answers — also with steps_per_output=0 or a
        run shorter than one reporting window. A query is a legitimate fence
        point; only per-STEP fences serialize the pipeline."""
        if self._window_t0 is not None and self._window_steps > 0:
            _synchronize()
            duration = time.perf_counter() - self._window_t0
            self.total_elapsed_time += duration
            self.step_elapsed_time = duration
            self._fenced_steps += self._window_steps
            self._last_window_steps = self._window_steps
            self._window_t0 = time.perf_counter()
            self._window_steps = 0

    def avg_samples_per_sec(self) -> float:
        """Average over fenced windows — exact wall time."""
        self._settle()
        if self._fenced_steps > 0 and self.total_elapsed_time > 0:
            return self.batch_size / (self.total_elapsed_time / self._fenced_steps)
        return 0.0

    def recent_samples_per_sec(self) -> float:
        """Throughput of the most recent (settled) window."""
        self._settle()
        if self._last_window_steps > 0 and self.step_elapsed_time > 0:
            return self.batch_size * self._last_window_steps / self.step_elapsed_time
        return 0.0
