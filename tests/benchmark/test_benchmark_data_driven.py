"""A configuration, a traffic mix, a cell and a per-layer metric dropped in
as NEW files in a copy of the benchmark are found and run by the harness,
with no existing file edited (BENCHMARK.json gains entries, as it must)."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVE_CELL = "mistral-7b.serve.chat"


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = digest(root)
    b = root / "benchmark"
    # a new configuration: its sizes, its reference by name
    cfg = json.load(open(b / "configs" / "mistral-7b.json"))
    cfg.update(name="newmodel", source="https://example.org/newmodel")
    cfg["tiny"] = {**cfg["tiny"], "hidden_size": 32, "sliding_window": 48}
    json.dump(cfg, open(b / "configs" / "newmodel.json", "w"))
    # a new traffic mix: parameters only
    json.dump({"kind": "packed", "seq_len": 8192, "sequences_per_chip": 1},
              open(b / "traffic" / "train.new.json", "w"))
    # a new cell
    wl = json.load(open(b / "workloads" / "mistral-7b.train.8k.json"))
    wl.update(config="newmodel", traffic="train.new")
    wl["tiny"]["tiny_mix"] = {"seq_len": 96}
    json.dump(wl, open(b / "workloads" / "newmodel.train.new.json", "w"))
    # a new per-layer metric: a reader of its own
    (b / "layer_metrics" / "train.steps_counted.py").write_text(
        "def read(run):\n    return float(run['observed']['steps'])\n")
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({
        "name": "newmodel", "source": "https://example.org/newmodel",
        "file": "benchmark/configs/newmodel.json",
        "reduced": ["num_hidden_layers"], "why": "added as files"})
    bench["workloads"].append({
        "name": "newmodel.train.new", "config": "newmodel",
        "traffic": "train.new", "chips": 1, "why": "added as files"})
    bench["per_layer"].append({
        "name": "train.steps_counted", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["newmodel.train.new"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("newmodel.train.new")
    for m in bench["per_layer"]:
        if m["name"] in ("train.step_ms_p50", "train.mfu"):
            m["workloads"].append("newmodel.train.new")
    # the serving cell this benchmark keeps as files only (PERF.md section 7)
    # and its metrics: entries alone
    bench["workloads"].append({
        "name": SERVE_CELL, "config": "mistral-7b", "traffic": "serve.chat",
        "chips": 1, "why": "entered as a later PR would"})
    bench["end_to_end"] += [
        {"name": n, "unit": u, "better": better, "bound": 0.1,
         "source": "host_clock", "workloads": [SERVE_CELL]}
        for n, u, better in (("ttft_p95_ms", "ms", "lower"),
                             ("tpot_p95_ms", "ms", "lower"),
                             ("serve_tokens_per_s", "tokens/s", "higher"))]
    bench["per_layer"] += [
        {"name": n, "unit": u, "better": "lower", "source": "host_clock",
         "layer": "serving host loop", "moves": moves,
         "workloads": [SERVE_CELL]}
        for n, u, moves in (("serve.step_ms_p50", "ms", "tpot_p95_ms"),
                            ("serve.ttft_p50_ms", "ms", "ttft_p95_ms"),
                            ("device.idle_share.serve", "%", "tpot_p95_ms"))]
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    assert {k: v for k, v in digest(root).items() if k in before} == before
    return root


def run(root, *args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_added_files_are_found_and_run(copy):
    out = run(copy, "--workload", "newmodel.train.new", "--seed",
              str(2 ** 31 + 3), "--seconds", "1", "--trace", "1",
              "--rehearse-cpu")
    assert out.returncode == 1, out.stderr[-2000:]   # a rehearsal never 0
    line = last_json(out.stdout)
    assert line["rehearsal"] == "passed" and "correct" not in line
    metrics = line["would_print"]["metrics"]
    assert metrics["train.steps_counted"]["value"] >= 1
    assert metrics["train.steps_counted"]["unit"] == "steps"
    assert "train.step_ms_p50" in metrics
    # trace-sourced metrics have nothing to read on a CPU: left out
    assert "device.idle_share.train" not in metrics
    # the clocked window's fenced groups, on a line before the result's, in
    # traced runs as in untraced ones
    lines = out.stdout.splitlines()
    at = next(i for i, l in enumerate(lines)
              if l.startswith('{"phase": "window"'))
    window = json.loads(lines[at])
    assert at < len(lines) - 1 and window["groups"] >= 1
    assert window["fence_ms_max"] >= window["fence_ms_p50"] > 0
    assert list(window) == ["phase", "groups", "fence_ms_p50", "fence_ms_max"]
    # what `correct` compared, each number beside its limit: the result's
    # last key, and lines of standard error after everything the run says
    result = line["would_print"]
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"loss_gap", "logit_rel_l2"}
    said = [l for l in out.stderr.splitlines()
            if l.startswith("benchmark: compared ")]
    assert len(said) == 2
    for name, c in result["compared"].items():
        assert 0 <= c["value"] <= c["limit"], (name, c)
        assert any(l.split()[2] == name and float(l.split()[-1]) == c["limit"]
                   for l in said)


@pytest.mark.parametrize("trace", [0, 1])
def test_serving_cell_kept_as_files_runs_once_entered(copy, trace):
    """The open loop end to end at tiny size: a traced run clocks the part
    of its window before the profiler starts, and reports from that."""
    out = run(copy, "--workload", SERVE_CELL, "--seed", str(2 ** 31 + 3),
              "--seconds", "6", "--trace", str(trace), "--rehearse-cpu")
    assert out.returncode == 1, out.stderr[-2000:]
    line = last_json(out.stdout)
    assert line["rehearsal"] == "passed"
    result = line["would_print"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    want = ({"serve.step_ms_p50", "serve.ttft_p50_ms"} if trace else
            {"setup_s", "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s"})
    assert set(result["metrics"]) == want
    window = next(json.loads(l) for l in out.stdout.splitlines()
                  if l.startswith('{"phase": "window"'))
    assert window["w1"] - window["w0"] == pytest.approx(
        3.0 if trace else 6.0, abs=0.5)
    assert window["counters"]["compiles"] == 0


def test_no_tpu_no_result(copy):
    out = run(copy, "--workload", "newmodel.train.new", "--seed", "1",
              "--seconds", "1", "--trace", "0")
    assert out.returncode == 2
    assert "needs 1 TPU chip" in out.stderr
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert not any("correct" in json.loads(l) for l in lines)


def test_benchmark_json_names_only_files_that_exist():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    b = os.path.join(ROOT, "benchmark")
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(b, "workloads", w["name"] + ".json"))
        assert os.path.exists(os.path.join(b, "traffic", w["traffic"] + ".json"))
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(b, "layer_metrics",
                                           m["name"] + ".py"))
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells))
