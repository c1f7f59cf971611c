"""Unified metrics registry (``monitor/registry.py``): counter/gauge/
histogram semantics, O(1)-memory log-bucket quantiles vs exact values on
synthetic data, and the snapshot shape the monitor backends consume.
"""

import math

import numpy as np
import pytest

from deepspeed_tpu.monitor.registry import (Counter, Gauge, Histogram,
                                            MetricsRegistry)


# ---------------------------------------------------------------------------
# histogram quantile accuracy
# ---------------------------------------------------------------------------

#: the log-bucket quantile can land anywhere in the true value's bucket;
#: with growth g the geometric midpoint is within sqrt(g)-1 (~4.9% at the
#: default 1.1) of any point in the bucket — allow that plus nearest-rank
#: slack on finite samples
REL_TOL = 0.06


def _check_quantiles(data, lo=1e-6, hi=1e5):
    h = Histogram(lo=lo, hi=hi)
    for x in data:
        h.observe(x)
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(data, 100 * q))
        approx = h.percentile(q)
        assert approx is not None
        assert abs(approx - exact) <= REL_TOL * max(exact, abs(approx)), \
            f"q={q}: approx {approx} vs exact {exact}"


def test_quantiles_lognormal():
    rs = np.random.RandomState(0)
    _check_quantiles(np.exp(rs.normal(-3.0, 1.0, 20000)))  # latency-shaped


def test_quantiles_exponential():
    rs = np.random.RandomState(1)
    _check_quantiles(rs.exponential(0.05, 20000))


def test_quantiles_uniform():
    rs = np.random.RandomState(2)
    _check_quantiles(rs.uniform(0.001, 2.0, 20000))


def test_quantiles_bimodal_burst():
    """The case the old 4096-sample window got wrong: a burst of slow
    requests early in the run must still show up in p99 after hours of
    fast traffic, because a histogram forgets nothing."""
    slow = [2.0] * 500          # the burst
    fast = [0.01] * 99500       # sustained traffic afterwards
    h = Histogram()
    for x in slow + fast:
        h.observe(x)
    assert h.percentile(0.5) < 0.02
    # p99 with 0.5% slow outliers sits in the fast mode; p(>=0.995) must
    # still SEE the burst — the whole point of unwindowed quantiles
    assert h.percentile(0.999) > 1.0
    assert h.count == 100000


def test_quantiles_clamped_to_observed_range():
    h = Histogram()
    for x in (0.5, 0.6, 0.7):
        h.observe(x)
    assert 0.5 <= h.percentile(0.0) <= 0.7
    assert 0.5 <= h.percentile(1.0) <= 0.7
    assert h.min == 0.5 and h.max == 0.7


def test_histogram_memory_is_fixed():
    h = Histogram()
    nb = len(h.counts)
    for i in range(200000):
        h.observe((i % 1000) * 1e-4 + 1e-5)
    assert len(h.counts) == nb          # no growth, ever
    assert h.count == 200000
    assert sum(h.counts) == 200000


def test_histogram_underflow_overflow():
    h = Histogram(lo=1e-3, hi=1.0)
    h.observe(1e-9)   # below lo -> underflow bucket
    h.observe(50.0)   # above hi -> last bucket
    assert h.count == 2
    assert h.percentile(0.0) >= 1e-9
    assert h.percentile(1.0) <= 50.0


def test_histogram_validates_params():
    with pytest.raises(ValueError):
        Histogram(lo=0.0)
    with pytest.raises(ValueError):
        Histogram(lo=1.0, hi=0.5)
    with pytest.raises(ValueError):
        Histogram(growth=1.0)


def test_empty_histogram():
    h = Histogram()
    assert h.percentile(0.5) is None and h.mean is None


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counter_gauge_and_labels():
    reg = MetricsRegistry()
    reg.counter("requests", state="shed").inc()
    reg.counter("requests", state="shed").inc(2)
    reg.counter("requests", state="ok").inc()
    reg.gauge("depth").set(7)
    snap = reg.snapshot()
    assert snap["requests{state=shed}"] == 3.0
    assert snap["requests{state=ok}"] == 1.0
    assert snap["depth"] == 7.0


def test_get_or_create_is_stable():
    reg = MetricsRegistry()
    assert reg.counter("c") is reg.counter("c")
    assert reg.histogram("h") is reg.histogram("h")


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_histogram_param_conflict_raises():
    """A conflicting bucket layout on get-or-create must raise, not
    silently mis-bin the second caller's observations."""
    reg = MetricsRegistry()
    h = reg.histogram("lat_s", lo=1e-5, hi=4e3)
    assert reg.histogram("lat_s", lo=1e-5, hi=4e3) is h  # same params: ok
    with pytest.raises(ValueError, match="lat_s"):
        reg.histogram("lat_s", lo=1e-3, hi=10.0)


def test_snapshot_histogram_keys():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    snap = reg.snapshot()
    assert snap == {"lat_s_count": 0.0}  # empty: no bogus quantiles
    for x in (0.01, 0.02, 0.03):
        h.observe(x)
    snap = reg.snapshot()
    for k in ("lat_s_count", "lat_s_p50", "lat_s_p95", "lat_s_p99",
              "lat_s_mean", "lat_s_max"):
        assert k in snap, k
    assert snap["lat_s_count"] == 3.0
    assert math.isclose(snap["lat_s_mean"], 0.02)


def test_to_events_rides_monitor_shape():
    reg = MetricsRegistry()
    reg.counter("c").inc(5)
    events = reg.to_events(step=3, prefix="serving/")
    assert ("serving/c", 5.0, 3) in [tuple(e) for e in events]


# ---------------------------------------------------------------------------
# ServingMetrics rides the registry (snapshot keys stay stable)
# ---------------------------------------------------------------------------

def test_serving_metrics_snapshot_keys_stable():
    from deepspeed_tpu.inference.serving.metrics import ServingMetrics

    m = ServingMetrics(blocks_total=16)
    assert "ttft_p50_s" not in m.snapshot()  # no traffic -> no quantiles
    for x in (0.05, 0.10, 0.20):
        m.record_ttft(x)
        m.record_step(x / 10)
    snap = m.snapshot()
    # the keys monitor wiring parses — frozen
    for k in ("ttft_p50_s", "ttft_p95_s", "ttft_p99_s",
              "step_p50_s", "step_p95_s", "step_p99_s"):
        assert k in snap, k
    assert abs(snap["ttft_p50_s"] - 0.10) <= REL_TOL * 0.10
    # unbounded traffic, bounded memory: the histogram never grows
    nb = len(m.ttft_hist.counts)
    for _ in range(50000):
        m.record_ttft(0.123)
    assert len(m.ttft_hist.counts) == nb
    assert m.ttft_hist.count == 50003
