"""Per-collective comm observability (``comm/comm.py``): every verb
emits a ``comm:<op>`` span + a labeled ``comm_op_s`` histogram when
armed, nothing at all when disarmed, and the disabled guard costs the
hot trace path nothing measurable. ``trace_view --summary`` must
aggregate the spans into the per-op comm table."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.monitor.registry import MetricsRegistry
from deepspeed_tpu.monitor.tracing import Tracer
from deepspeed_tpu.parallel import build_mesh


@pytest.fixture()
def observer():
    """Arm a fresh tracer+registry; always disarm (module-global)."""
    tr = Tracer(capacity=1024)
    reg = MetricsRegistry()
    comm.configure_comm_tracing(tracer=tr, registry=reg)
    yield tr, reg
    comm.disable_comm_tracing()


def _mesh():
    return build_mesh(data=8)


def _run(body, x):
    mesh = _mesh()
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")))(x)


def test_every_collective_emits_span_and_histogram(observer):
    tr, reg = observer

    def body(v):
        r = comm.all_reduce(v, group="data")
        g = comm.all_gather(v, group="data", tiled=True)
        s = comm.reduce_scatter(g, group="data")
        b = comm.broadcast(v, src=0, group="data")
        p = comm.send_recv_next(v, group="data")
        a = comm.all_to_all_single(jnp.tile(v, 8), group="data")[:1]
        comm.barrier("data")
        return r + s + b + p + a

    out = _run(body, jnp.arange(8.0))
    assert np.isfinite(np.asarray(out)).all()
    spans = [e for e in tr.events() if e.get("cat") == "comm"]
    ops = {e["args"]["op"] for e in spans}
    assert ops == {"all_reduce", "all_gather", "reduce_scatter",
                   "broadcast", "ppermute", "all_to_all_single", "barrier"}
    for e in spans:
        assert e["ph"] == "X" and e["name"] == f"comm:{e['args']['op']}"
        assert "bytes" in e["args"] and "dtype" in e["args"]
    # histograms: one per (op, dtype, bytes_bucket), counted
    keys = [k for k, _ in reg.items()]
    assert any(k.startswith("comm_op_s{") and "op=all_reduce" in k
               for k in keys)
    for k, h in reg.items():
        assert h.count >= 1, k
    # labels carry the pow2 size class (a float32[1] payload is <=4B)
    assert any("bytes_bucket=<=4B" in k and "dtype=float32" in k
               for k in keys)


def test_tpot_style_byte_buckets():
    from deepspeed_tpu.comm.comm import _bytes_bucket

    assert _bytes_bucket(0) == "0B"
    assert _bytes_bucket(3) == "<=4B"
    assert _bytes_bucket(4) == "<=4B"
    assert _bytes_bucket(5000) == "<=8KiB"
    assert _bytes_bucket(1 << 20) == "<=1MiB"
    assert _bytes_bucket((1 << 30) + 1) == "<=2GiB"


def test_disabled_observer_emits_nothing(observer):
    tr, reg = observer
    comm.disable_comm_tracing()
    _run(lambda v: comm.all_reduce(v, group="data"), jnp.arange(8.0))
    assert [e for e in tr.events() if e.get("cat") == "comm"] == []
    assert [k for k, _ in reg.items()] == []


def test_overhead_disabled_vs_enabled(observer):
    """The satellite bar: comm-span overhead measured disabled vs
    enabled. Emission happens at TRACE time, so the honest comparison is
    trace cost: stage a 24-collective body repeatedly via make_jaxpr
    (never cached) both ways. The bound is deliberately loose — jax
    tracing dominates by orders of magnitude; this guards against an
    accidentally quadratic emit, not microseconds."""
    def body(v):
        for _ in range(24):
            v = comm.all_reduce(v, group="data")
        return v

    mesh = _mesh()
    wrapped = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                            out_specs=P("data"))
    x = jnp.arange(8.0)

    def trace_once():
        t0 = time.perf_counter()
        jax.make_jaxpr(wrapped)(x)
        return time.perf_counter() - t0

    samples = {False: [], True: []}
    trace_once()  # warm imports/caches out of the comparison
    for _ in range(5):
        for enabled in (False, True):
            comm.comm_observer.enabled = enabled
            samples[enabled].append(trace_once())
    comm.comm_observer.enabled = True  # fixture disarms
    off = sorted(samples[False])[len(samples[False]) // 2]
    on = sorted(samples[True])[len(samples[True]) // 2]
    assert on < off * 2.0, (off, on)


def test_dead_sinks_disarm_observer():
    """The observer is process-global, its sinks are engine-owned: when
    the arming engine's tracer + registry are garbage-collected, the
    next emit disarms the observer instead of pinning dead sinks (and
    untraced engines stop paying)."""
    import gc

    tr = Tracer(capacity=16)
    reg = MetricsRegistry()
    comm.configure_comm_tracing(tracer=tr, registry=reg)
    try:
        comm.comm_observer.emit("all_reduce", None, "data",
                                time.perf_counter())
        assert comm.comm_observer.enabled
        del tr, reg
        gc.collect()
        assert comm.comm_observer.tracer is None
        assert comm.comm_observer.registry is None
        comm.comm_observer.emit("all_reduce", None, "data",
                                time.perf_counter())
        assert not comm.comm_observer.enabled
        assert comm.comm_observer._hists == {}
    finally:
        comm.disable_comm_tracing()


def test_trace_view_summary_comm_table(observer, tmp_path):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools"))
    import trace_view

    tr, _ = observer

    def body(v):
        return comm.all_reduce(v, group="data") + \
            comm.all_gather(v, group="data", tiled=True).sum()

    _run(body, jnp.arange(8.0))
    path = tr.dump(str(tmp_path / "comm_trace.json"))
    s = trace_view.summarize([path])
    assert set(s["comm_spans"]) == {"all_reduce", "all_gather"}
    rec = s["comm_spans"]["all_reduce"]
    assert rec["count"] == 1 and rec["bytes"] > 0
    shares = [r["share"] for r in s["comm_spans"].values()]
    assert all(sh is not None for sh in shares)
    assert abs(sum(shares) - 1.0) < 1e-6
