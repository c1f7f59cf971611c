"""The bench harnesses must always emit one parseable JSON summary line on
stdout — the round-2 perf evidence was lost to an rc=124 timeout kill with
nothing emitted (VERDICT r2 weak #1) — and exit 0 only with a measurement:
a null ``value`` is a failed run, reported as one."""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _last_json(stdout: str) -> dict:
    lines = [ln for ln in stdout.splitlines() if ln.strip().startswith("{")]
    assert lines, f"no JSON line in stdout: {stdout!r}"
    return json.loads(lines[-1])


@pytest.mark.slow
def test_bench_tiny_emits_json():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env={**os.environ, "DS_BENCH_TINY": "1"},
        capture_output=True, text=True, timeout=540, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = _last_json(r.stdout)
    assert rec["metric"] == "llama400m_train_tflops_per_chip"
    assert rec["value"] is not None and rec["value"] > 0


def test_bench_aborts_on_stray_bench_process():
    """Pre-flight stray guard: with another live 'bench.py' process on
    the box (here: a sleep wearing bench.py as argv[0] — the shape the
    PR 8 leaked-grandchild incident had), bench.py must refuse to time
    anything and emit an error JSON naming the PID, instead of silently
    producing contended numbers. DS_BENCH_IGNORE_STRAYS=1 overrides."""
    stray = subprocess.Popen(["bench.py", "60"], executable="/bin/sleep")
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env={**os.environ, "DS_BENCH_TINY": "1"},
            capture_output=True, text=True, timeout=120, cwd=REPO)
        assert r.returncode != 0, "a null value must not exit 0"
        rec = _last_json(r.stdout)
        assert rec["value"] is None
        assert "stray" in rec["error"] and str(stray.pid) in rec["error"]
        assert "ladder" not in (rec.get("detail") or {}), \
            "no candidate may run once the guard fired"
    finally:
        stray.kill()
        stray.wait()


def test_stray_scan_detects_strays_not_self_or_editors(monkeypatch, tmp_path):
    monkeypatch.syspath_prepend(REPO)
    import bench

    me, parent = os.getpid(), os.getppid()
    # an idle "editor" whose cmdline merely NAMES bench.py (argv0 vim,
    # bench.py a later arg — the sh $0 slot) is NOT contention
    editor = subprocess.Popen(["vim", "-c", "sleep 600", "bench.py"],
                              executable="/bin/sh")
    # a real leaked shape: a python interpreter EXECUTING a bench.py
    fake = tmp_path / "bench.py"
    fake.write_text("import time; time.sleep(600)\n")
    stray = subprocess.Popen([sys.executable, str(fake)])
    try:
        # wait out the fork->exec window: until exec lands, the child's
        # /proc cmdline does not yet carry bench.py
        deadline = time.time() + 10
        pids = set()
        while time.time() < deadline and stray.pid not in pids:
            pids = {pid for pid, _ in bench.stray_bench_processes()}
            if stray.pid not in pids:
                time.sleep(0.05)
        assert stray.pid in pids, "an executing bench.py must be detected"
        assert editor.pid not in pids, \
            "an editor merely naming bench.py must not abort timing runs"
        assert me not in pids and parent not in pids, \
            "the scan must exclude the calling process and its ancestors"
    finally:
        for p in (editor, stray):
            p.kill()
            p.wait()


@pytest.mark.slow
def test_bench_decode_tiny_emits_json():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_decode.py"),
         "--tiny"],
        capture_output=True, text=True, timeout=540, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = _last_json(r.stdout)
    assert rec["metric"] == "llama400m_decode"
    assert len(rec["points"]) == 2
    assert all(p["ttft_ms"] > 0 for p in rec["points"])


def test_bench_unreachable_backend_still_emits_json():
    # force the probe at a backend name that CANNOT exist on ANY host
    # (jax rejects unknown platform names at init): the parent must still
    # print a JSON record carrying an explicit error and a null value, and
    # exit non-zero — no earlier run's number rides on this one. NOT the
    # tier-1 cpu value, and not "tpu" either (a real
    # TPU VM would initialize it): under JAX_PLATFORMS=cpu a warm jax
    # import occasionally beat the 1s probe deadline, bench.py then
    # launched a REAL candidate subprocess, this test's timeout killed
    # only the bench.py parent, and the candidate grandchild survived as
    # a 400s 100%-CPU stray that poisoned every timing run after it.
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env={**os.environ, "DS_BENCH_PROBE_S": "5",
             "JAX_PLATFORMS": "ds_bench_test_unreachable"},
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode != 0, "a null value must not exit 0"
    rec = _last_json(r.stdout)
    assert "backend unavailable" in rec["error"]
    assert rec["value"] is None
    assert "detail" not in rec


def test_attack_axis_order_ranks_by_cost_model():
    """attack_mfu's in-axis ordering: with >=6 measured results the ridge
    model must rank the known-better value first; with fewer, declaration
    order is kept (current value always first either way)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import attack_mfu

    def rec(batch, gas, policy, tflops):
        return {"tflops": tflops,
                "spec": {"tag": "t", "batch": batch, "gas": gas,
                         "policy": policy, "fq": 512, "fk": 512,
                         "lchunk": 0, "padam": False, "attn": "flash"}}

    cur = dict(attack_mfu.DEFAULT)
    # 6 measurements with a clean monotone signal: bigger batch*gas wins
    state = {"results": {
        f"k{i}": rec(b, g, "dots", 10.0 * b * g)
        for i, (b, g) in enumerate(
            [(8, 8), (16, 4), (16, 8), (32, 4), (8, 16), (8, 4)])}}
    order = attack_mfu.axis_order(state, cur, "bg",
                                  attack_mfu.AXES["bg"])
    assert order[0] == cur["bg"]            # incumbent always first
    # the clearly-worst value (b*g = 64, every other rest value is 128)
    # must be ranked last by the fitted model
    assert order[-1] == (16, 4)
    # sparse state: declaration order preserved
    order2 = attack_mfu.axis_order({"results": {}}, cur, "bg",
                                   attack_mfu.AXES["bg"])
    assert order2 == [cur["bg"]] + [v for v in attack_mfu.AXES["bg"]
                                    if v != cur["bg"]]


def test_attack_resumes_walk_from_persisted_best():
    """A resumed attack window must restart the descent AT the best
    persisted config, not at DEFAULT (else every window re-probes
    single-lever neighbors of DEFAULT and the search stalls)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import attack_mfu

    spec = {"tag": "t", "batch": 16, "gas": 8, "policy": "nothing",
            "fq": 1024, "fk": 512, "lchunk": 4096, "padam": True,
            "attn": "xla"}
    cfg = attack_mfu.cfg_from_spec(spec)
    assert cfg == {"bg": (16, 8), "policy": "nothing", "fq": 1024,
                   "fk": 512, "lchunk": 4096, "padam": True, "attn": "xla"}
    # round trip through spec_of: the persisted form reconstructs exactly
    assert attack_mfu.cfg_from_spec(attack_mfu.spec_of(cfg)) == cfg
