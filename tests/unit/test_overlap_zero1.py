"""Bitwise parity grid for the bucketed-overlap / ZeRO-1 explicit lane.

The contract under test (``runtime/zero/overlap.py``): for a fixed
(zero stage, grad-accum, precision) configuration, every lane variant —
overlap on/off, any ``reduce_bucket_size`` — produces BITWISE identical
parameters and losses over N steps. This holds because all arithmetic
runs in one barrier-fenced canonical flat pipeline and the variants
differ only in collective grouping, which XLA's collectives are exactly
invariant to (reduce-scatter of a concatenation == concatenation of
reduce-scatters, element for element).

Also covered here:

- bucket-composition-is-DATA: changing ``reduce_bucket_size`` changes
  which leaves share a reduce-scatter but NOT the compiled step's
  interface — the recompile sentinel stays silent and the resident
  ``train_step`` fingerprint is identical across bucket sizes;
- ONE resident compile per engine across all steps;
- the lane agrees with the fused dense engine to float32 roundoff
  (1 ulp — the fused step fuses the update differently, so bitwise
  equality is deliberately NOT claimed across engines).
"""

import jax
import numpy as np
import pytest

import deepspeed_tpu as ds
from tests.unit.simple_model import SimpleModel, batch_of

STEPS = 3

#: engine-run cache: the grid shares baselines (each kill-switch engine
#: anchors several overlap cells), so runs are memoized by config key.
_CACHE = {}


def _cfg(stage, gas, fp16, overlap_comm, bucket, lane=True):
    cfg = {
        "train_batch_size": 16 * gas,
        "gradient_accumulation_steps": gas,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage,
                              "overlap_grad_sync": lane,
                              "overlap_comm": overlap_comm,
                              "reduce_bucket_size": bucket},
    }
    if fp16:
        cfg["fp16"] = {"enabled": True, "initial_scale_power": 8}
    return cfg


def _run(stage, gas, fp16, overlap, bucket=4096, lane=True):
    """Train a fresh engine for STEPS steps; return (param leaves, losses,
    compiles, recompiles, fingerprint) — memoized per config."""
    key = (stage, gas, fp16, overlap, bucket, lane)
    if key not in _CACHE:
        e = ds.initialize(model=SimpleModel(),
                          config=_cfg(stage, gas, fp16, overlap, bucket, lane),
                          example_batch=batch_of(2),
                          rng=jax.random.PRNGKey(0))[0]
        losses = []
        for i in range(STEPS):
            loss = e.train_batch(batch=batch_of(16 * gas, seed=i))
            losses.append(np.asarray(loss))
        leaves = [np.asarray(x)
                  for x in jax.tree_util.tree_leaves(e.state.params)]
        prog = e.perf.programs.program("train_step")
        _CACHE[key] = (leaves, losses, prog.compiles, prog.recompiles,
                       dict(prog.fingerprint))
    return _CACHE[key]


def _assert_bitwise(a, b, what):
    la, losses_a = a[0], a[1]
    lb, losses_b = b[0], b[1]
    for s, (x, y) in enumerate(zip(losses_a, losses_b)):
        assert x.tobytes() == y.tobytes(), \
            f"{what}: loss diverged at step {s}: {x} vs {y}"
    for i, (x, y) in enumerate(zip(la, lb)):
        bad = int(np.sum(x.view(np.uint8) != y.view(np.uint8)))
        assert bad == 0, \
            f"{what}: param leaf {i} differs in {bad} bytes after {STEPS} steps"


@pytest.mark.parametrize("stage", [0, 1], ids=["stage0", "zero1"])
@pytest.mark.parametrize("gas", [1, 4], ids=["gas1", "gas4"])
@pytest.mark.parametrize("fp16", [False, True], ids=["fp32", "fp16"])
def test_overlap_bitwise_vs_monolithic(stage, gas, fp16):
    """Bucketed-overlap engine == kill-switch (monolithic sync exchange)
    engine, bitwise, params AND losses, for every grid cell."""
    overlap_on = _run(stage, gas, fp16, overlap=True)
    kill_switch = _run(stage, gas, fp16, overlap=False)
    _assert_bitwise(overlap_on, kill_switch,
                    f"stage{stage}/gas{gas}/{'fp16' if fp16 else 'fp32'}")


def test_bucket_size_bitwise_and_zero_recompiles():
    """reduce_bucket_size is bucket POLICY, not program structure: a 8x
    smaller bucket (more reduce-scatters per step) yields bitwise
    identical training and an identical resident-program fingerprint —
    the sentinel stays silent because the compiled interface never saw
    the change."""
    big = _run(1, 1, False, overlap=True, bucket=4096)
    small = _run(1, 1, False, overlap=True, bucket=512)
    _assert_bitwise(big, small, "bucket4096-vs-bucket512")
    # identical fingerprints: bucket composition is invisible to the
    # compiled step's argument spec
    assert big[4] == small[4]


def test_one_resident_compile_and_silent_sentinel():
    """Every grid engine compiles its train_step exactly once and the
    recompile sentinel never fires across steps."""
    for key, (_, _, compiles, recompiles, _) in sorted(
            _CACHE.items(), key=repr):
        assert compiles == 1, f"{key}: {compiles} compiles (want 1)"
        assert recompiles == 0, f"{key}: sentinel fired {recompiles}x"
    # the grid tests populate the cache first in suite order, but keep
    # this self-sufficient under -k selection
    if not _CACHE:
        _run(1, 1, False, overlap=True)
        test_one_resident_compile_and_silent_sentinel()


def test_lane_matches_fused_engine_to_roundoff():
    """The explicit lane and the fused dense step agree to float32
    roundoff (~1 ulp): same math, different fusion — allclose, not
    bitwise (XLA re-associates compute per program; see the module
    docstring of runtime/zero/overlap.py)."""
    lane = _run(0, 1, False, overlap=True)
    fused = _run(0, 1, False, overlap=True, lane=False)
    for i, (x, y) in enumerate(zip(lane[0], fused[0])):
        np.testing.assert_allclose(
            x, y, rtol=0, atol=2e-7,
            err_msg=f"lane vs fused diverged beyond roundoff at leaf {i}")
    np.testing.assert_allclose(np.asarray(lane[1]), np.asarray(fused[1]),
                               rtol=1e-6)


def test_overlap_trace_evidence_is_balanced():
    """The comm spans the lane's resident ``train_step`` stages (trace
    time: once per compile) show every per-bucket async start matched by
    exactly one done, under ONE compile and a silent sentinel."""
    cfg = _cfg(1, 1, False, True, 512)    # three buckets
    # armed BEFORE the first train_batch: the spans ride the one compile
    cfg["tracing"] = {"enabled": True, "comm": True}
    e = ds.initialize(model=SimpleModel(), config=cfg,
                      example_batch=batch_of(2),
                      rng=jax.random.PRNGKey(0))[0]
    e.train_batch(batch=batch_of(16))
    pairs = {}
    for ev in e.tracer.events():
        a = ev.get("args", {})
        op, _, side = a.get("op", "").rpartition("_")
        if a.get("tag") and side in ("start", "done"):
            ent = pairs.setdefault(f"{op}:{a['tag']}", {"start": 0, "done": 0})
            ent[side] += 1
    assert sum(k.startswith("reduce_scatter:grad_bucket") for k in pairs) > 1, \
        pairs
    for key, ent in pairs.items():
        assert ent["start"] == ent["done"] == 1, (key, ent)
    prog = e.perf.programs.program("train_step")
    assert prog.compiles == 1 and prog.recompiles == 0
