"""The train step's named scalars ride the trace a fence late
(``runtime/engine.py _drain_counters``): queued on the device while something
listens, fetched by a later call once the device has finished them, published
as a ``counters`` span (``ds.counters`` on the profiler's clock), a ring event
and registry gauges. With nothing listening a step costs what it cost."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.monitor import tracing


class Named(nn.Module):
    """A loss beside two named scalars that tell the steps apart."""

    @nn.compact
    def __call__(self, x):
        loss = jnp.mean(nn.Dense(1)(x) ** 2)
        return loss, {"probe_first_feature": jnp.mean(x[:, 0]),
                      "probe_rows": jnp.float32(x.shape[0])}


def batch(step, rows=8):
    return {"x": np.full((rows, 4), float(step), np.float32)}


def engine_of(gas=1, **config):
    engine, *_ = ds.initialize(
        model=Named(), example_batch={"x": batch(0)["x"][:1]},
        config={"train_batch_size": 8 * gas, "steps_per_print": 0,
                "gradient_accumulation_steps": gas,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                **config})
    return engine


def counters(engine):
    return [e for e in engine.tracer.events() if e["name"] == "counters"]


@pytest.fixture
def fetches(monkeypatch):
    """Every ``jax.device_get`` call's argument."""
    seen, real = [], jax.device_get

    def spy(x):
        seen.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    return seen


def test_nothing_listening_nothing_queued_nothing_fetched(fetches):
    """Ring off, no monitor, no progress line, profiler not recording: after
    the compile-carrying first call (which publishes as it always did) a
    call queues nothing and fetches nothing."""
    engine = engine_of()
    assert not engine.tracer.enabled and not tracing.profiler_recording()
    engine.train_batch(batch=batch(7))
    assert engine.registry.snapshot()["probe_first_feature"] == 7.0
    assert not engine._counter_queue
    del fetches[:]
    for step in range(1, 5):
        engine.train_batch(batch=batch(step))
        assert not engine._counter_queue
    assert fetches == []
    # the gauges still say what the first call published
    assert engine.registry.snapshot()["probe_first_feature"] == 7.0
    assert "train_tflops_per_chip" not in engine.registry.snapshot()


def test_a_step_is_published_no_later_than_two_calls_on():
    """Ring on: step n's scalars come out as a ``counters`` event that
    carries ``step == n`` once the device has finished it; the gauges hold
    the newest published step's."""
    engine = engine_of(tracing={"enabled": True})
    for step in range(6):
        loss = engine.train_batch(batch=batch(step))
        jax.block_until_ready(loss)     # the device is done with step n
        seen = {e["args"]["step"]: e["args"] for e in counters(engine)}
        # n is fetched by the next call at the latest (this one has queued
        # it after its dispatch and may already have found it ready)
        assert set(range(step)) <= set(seen) <= set(range(step + 1))
    engine.train_batch(batch=batch(6))
    seen = {e["args"]["step"]: e["args"] for e in counters(engine)}
    assert set(range(6)) <= set(seen)
    for step in range(6):
        assert seen[step]["probe_first_feature"] == float(step)
        assert seen[step]["probe_rows"] == 8.0
        assert "dropped" not in seen[step]
    newest = max(seen)
    assert engine.registry.snapshot()["probe_first_feature"] == float(newest)
    assert len(engine._counter_queue) <= 1


def test_one_fetch_publishes_a_whole_fenced_group(fetches, monkeypatch):
    """The benchmark's pattern: calls dispatched back to back, one fence;
    the next call finds every queued step finished and fetches them in ONE
    ``device_get``, each under its own span. The group's own calls drain
    nothing here, as on a device still busy with it: this tiny step can
    finish before its own call looks, and the group then reaches the fence
    already published (the test failed one run in four on that race)."""
    engine = engine_of(tracing={"enabled": True})
    engine.train_batch(batch=batch(0))
    with monkeypatch.context() as busy:
        busy.setattr(engine, "_drain_counters", lambda wait=False: None)
        out = [engine.train_batch(batch=batch(step)) for step in range(1, 6)]
    jax.block_until_ready(out)
    before = len(counters(engine))
    del fetches[:]
    engine.train_batch(batch=batch(6))
    assert len(fetches) == 1
    steps = [e["args"]["step"] for e in counters(engine)]
    assert steps == sorted(set(steps))          # each step once, in order
    assert set(range(6)) <= set(steps) and len(steps) > before


def test_the_queue_is_bounded_and_counts_what_it_drops(monkeypatch):
    engine = engine_of(tracing={"enabled": True})
    monkeypatch.setattr(type(engine), "COUNTER_QUEUE_BOUND", 3)
    # a device that never finishes: nothing drains without a wait
    monkeypatch.setattr(engine, "_drain_counters",
                        lambda wait=False: None)
    for step in range(8):
        engine.train_batch(batch=batch(step))
    assert [s for s, _, _ in engine._counter_queue] == [5, 6, 7]
    assert engine._counters_dropped == 5
    monkeypatch.undo()
    engine._drain_counters(wait=True)
    found = counters(engine)
    assert [e["args"]["step"] for e in found] == [5, 6, 7]
    assert found[0]["args"]["dropped"] == 5     # said once, on the next span
    assert all("dropped" not in e["args"] for e in found[1:])
    assert engine._counters_dropped == 0 and not engine._counter_queue


@pytest.mark.parametrize("gas", [1, 2])
def test_micro_batches_publish_their_mean(gas):
    """``gas > 1``: a step's scalar is the mean over its micro-batches, in
    the span and in the gauge alike."""
    engine = engine_of(gas=gas, tracing={"enabled": True})
    x = np.arange(32 * gas, dtype=np.float32).reshape(8 * gas, 4)
    engine.train_batch(batch={"x": x})
    (event,) = counters(engine)
    assert event["args"]["step"] == 0
    assert event["args"]["probe_first_feature"] == pytest.approx(
        x[:, 0].mean())
    assert engine.registry.snapshot()["probe_first_feature"] == \
        pytest.approx(x[:, 0].mean())


@pytest.mark.parametrize("sink", ["ring", "progress_line", "profiler"])
def test_each_sink_alone_turns_the_queue_on(sink, monkeypatch):
    engine = engine_of(**({"tracing": {"enabled": True}} if sink == "ring"
                          else {"steps_per_print": 1}
                          if sink == "progress_line" else {}))
    engine.train_batch(batch=batch(0))
    if sink == "profiler":
        # the one wrapper of TraceAnnotation.is_enabled
        monkeypatch.setattr("deepspeed_tpu.monitor.tracing.TraceAnnotation"
                            ".is_enabled", staticmethod(lambda: True))
    jax.block_until_ready(engine.train_batch(batch=batch(3)))
    engine.train_batch(batch=batch(4))
    # the progress line waits for its own step; the others are a call late
    assert engine.registry.snapshot()["probe_first_feature"] in (3.0, 4.0)


def test_step_rate_gauges_follow_publications_not_calls():
    """``train_tflops_per_chip`` is the cost model over the host time
    between two publications per step published: with a fence between the
    groups that is the device's pace, however short a call is."""
    engine = engine_of(tracing={"enabled": True})
    engine.train_batch(batch=batch(0))          # starts the clock
    assert "train_tflops_per_chip" not in engine.registry.snapshot()
    out = [engine.train_batch(batch=batch(s)) for s in range(1, 4)]
    jax.block_until_ready(out)
    engine.train_batch(batch=batch(4))          # publishes 1..3
    assert engine._published[1] >= 3
    rate = engine.registry.snapshot()["train_tflops_per_chip"]
    assert rate > 0
    # the gauge is the cost model over the step time handed to perf
    assert engine.perf.last["train_step"]["flops_per_sec"] == \
        pytest.approx(rate * 1e12 * engine.perf.n_devices)
