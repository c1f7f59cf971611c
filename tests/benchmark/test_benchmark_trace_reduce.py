"""trace_reduce on hand-made intervals and on the small recorded trace kept
beside this file (plain events of a chip run)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def test_union_subtract_length():
    merged = tr.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert merged == [[0, 3], [5, 8]]
    assert tr.length(merged) == 6
    assert tr.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert tr.subtract(merged, [[2, 6]]) == [[0, 2], [6, 8]]
    assert tr.subtract(merged, []) == merged


def hand_trace():
    ms = 1_000_000
    dev0 = [["fusion.1", 0, 4 * ms], ["all-gather.3", 4 * ms, 2 * ms],
            ["fusion.2", 5 * ms, 3 * ms],        # hides 1 ms of the gather
            ["while.7", 0, 20 * ms],             # a container: not work
            ["all-reduce.1", 12 * ms, 2 * ms], ["fusion.1", 16 * ms, 4 * ms]]
    dev1 = [["fusion.1", 0, 10 * ms], ["fusion.1", 10 * ms, 10 * ms]]
    host = [["bench.train_window", 0, 20 * ms], ["bench.fence", 8 * ms, 4 * ms],
            ["bench.fence", 14 * ms, 2 * ms]]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "host": host}


def test_reduce_hand_trace():
    r = tr.reduce(hand_trace())
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(0.020)
    # device 0 busy 0-8, 12-14, 16-20 = 14 ms; device 1 busy 20 ms
    assert r["busy_s"] == pytest.approx(0.017)
    assert r["idle_share"] == pytest.approx(0.15)
    # exposed: gather 4-5 (1 ms) + all-reduce 12-14 (2 ms), on device 0 only
    assert r["collective_exposed_s"] == pytest.approx(0.0015)
    assert r["device_ops"][0][0] == "fusion.1"
    assert r["device_ops"][0][1] == pytest.approx(0.014)
    assert "while.7" not in dict(r["device_ops"])
    # device 0's gaps: 8-12 under bench.fence, 14-16 under the second fence
    assert dict(r["idle_gaps"]) == pytest.approx({"bench.fence": 0.006})


def test_window_is_the_annotation_not_the_operations():
    """Under ``bench.traced_window`` the device's idle time before its first
    and after its last operation counts, and what ran outside is cut off."""
    ms = 1_000_000
    trace = hand_trace()
    for events in trace["devices"].values():
        events.append(["fusion.9", 28 * ms, 4 * ms])    # ends outside
    trace["host"].append([tr.WINDOW, -5 * ms, 35 * ms])  # -5 .. 30
    r = tr.reduce(trace)
    assert r["window_s"] == pytest.approx(0.035)
    # device 0: 14 ms + 2 of fusion.9's 4; device 1: 20 + 2
    assert r["busy_s"] == pytest.approx(0.019)
    assert r["idle_share"] == pytest.approx(1 - 19 / 35)
    assert dict(r["device_ops"])["fusion.9"] == pytest.approx(0.002)
    # device 0's gaps: -5..0 and 20..28 under no annotation but the
    # window's own, which names nothing
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.fence": 0.006, "(none)": 0.013})


def test_no_device_operation_reduces_to_nothing():
    assert tr.reduce({"devices": {}, "host": []}) is None
    assert tr.reduce({"devices": {"/device:TPU:0": [["while.1", 0, 5]]},
                      "host": []}) is None


def brute_force_busy(events, step):
    t0 = min(s for _, s, d in events)
    t1 = max(s + d for _, s, d in events)
    busy = 0
    for t in range(t0, t1, step):
        busy += any(s <= t < s + d for _, s, d in events)
    return busy * step, t1 - t0


def test_recorded_trace():
    with open(DATA) as f:
        trace = json.load(f)
    r = tr.reduce(trace)
    assert r is not None and 0 < r["busy_s"] <= r["window_s"]
    events = [e for evs in trace["devices"].values() for e in evs
              if e[2] > 0 and not tr.CONTAINERS.match(e[0])]
    assert len(trace["devices"]) == 1
    busy, window = brute_force_busy(events, step=max(1, int(
        r["window_s"] * 1e9 / 20000)))
    assert r["window_s"] == pytest.approx(window / 1e9)
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=0.02)
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
