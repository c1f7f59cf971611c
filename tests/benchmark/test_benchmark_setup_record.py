"""The six ``setup.*`` per-layer metrics (PR 54) read the engine's set-up
record off the traced run's ``ds.setup`` host event
(``benchmark/setup_record.py``): on a cut of a real chip trace, on the same
cut without the event (the parent of that PR publishes none: None, no
exception), and end to end in a CPU rehearsal of ``olmoe-1b-7b.train.4k``
under a declaration that lists them (BENCHMARK.json does not yet: the entries
wait in ``data/setup_per_layer_entries.json``)."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import common, setup_record

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDING = "scope_trace_setup_olmoe_4k.json"
ENTRIES = "setup_per_layer_entries.json"
CELLS = ["mistral-7b.train.8k", "mixtral-8x7b.train.ep4",
         "olmoe-1b-7b.train.4k", "kimi-vl-a3b.train.8k"]
#: each metric, and what it is of the record
METRICS = {
    "setup.import_s": lambda r: r["import_s"],
    "setup.engine_init_s": lambda r: r["init_s"],
    "setup.first_step_s": lambda r: r["first_step_s"],
    "setup.trace_lower_s": lambda r: r["trace_s"] + r["lower_s"],
    "setup.compile_or_cache_s": lambda r: r["backend_s"],
    "setup.cache_hit_share": lambda r: 100.0
    * (r["cache_hits"] + r["outside_cache_hits"])
    / (r["cache_hits"] + r["outside_cache_hits"]
       + r["cache_misses"] + r["outside_cache_misses"]),
}


def recording():
    with open(os.path.join(DATA, RECORDING)) as f:
        return json.load(f)


def without_setup(trace):
    out = copy.deepcopy(trace)
    out["host"] = [e for e in out["host"] if e[0] != setup_record.SPAN]
    return out


def run_of(trace, kind="train", setup_s=24.0):
    return {"observed": {"kind": kind}, "scope_trace": trace,
            "end_to_end": {"setup_s": setup_s}}


def reader(name):
    return common.load_file_module("layer_metrics", name)


def stats():
    (event,) = [e for e in recording()["host"] if e[0] == setup_record.SPAN]
    return {k: float(v) for k, v in event[3].items()}


def test_recording_is_the_traced_windows_first_steps():
    """The warm traced run of olmoe 4k on the v5e at PR 54: one ``ds.setup``
    event, inside the window's first ``ds.train_batch``, carrying the whole
    record — a warm reading (every compile a hit), published after the
    clocked part of the window."""
    host = recording()["host"]
    (event,) = [e for e in host if e[0] == setup_record.SPAN]
    steps = sorted(e for e in host if e[0] == "ds.train_batch")
    assert len(steps) >= 2
    first = steps[0]
    assert first[1] <= event[1] and event[1] + event[2] <= first[1] + first[2]
    rec = stats()
    assert len(rec) == 26 and all(v >= 0 for v in rec.values())
    assert rec["steps_before"] == first[3]["step"] > 100
    assert rec["init_s"] >= sum(rec[f"init_{k}_s"] for k in (
        "shapes", "params", "opt_state", "step"))
    assert rec["first_step_s"] >= rec["first_dispatch_s"] + rec["first_wait_s"]
    assert rec["cache_misses"] == rec["outside_cache_misses"] == 0
    assert rec["cache_hits"] >= 3 and rec["outside_cache_hits"] >= 3
    assert rec["backend_s"] >= rec["cache_read_s"] > 0


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_reads_the_record(metric):
    got = reader(metric).read(run_of(recording()))
    assert got == pytest.approx(METRICS[metric](stats()))
    assert got > 0


def test_readers_values_on_the_recording():
    """The numbers by hand, from the event's stats as recorded."""
    read = {m: reader(m).read(run_of(recording())) for m in METRICS}
    assert read["setup.cache_hit_share"] == 100.0
    assert 1.0 < read["setup.import_s"] < 10.0
    assert 1.0 < read["setup.engine_init_s"] < 10.0
    assert 1.0 < read["setup.first_step_s"] < 10.0
    assert read["setup.trace_lower_s"] < read["setup.engine_init_s"] \
        + read["setup.first_step_s"]
    assert read["setup.compile_or_cache_s"] < 5.0      # reads, no compile


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_reader_finds_nothing_in_a_trace_without_the_event(metric):
    """The parent publishes no ``ds.setup``: None, and no exception — in
    the same trace less the event, in PR 50's recording of the cell, in a
    serving run, and with no trace at all."""
    read = reader(metric).read
    assert read(run_of(without_setup(recording()))) is None
    with open(os.path.join(DATA, "scope_trace_train_olmoe_4k_gmm.json")) as f:
        assert read(run_of(json.load(f))) is None
    assert read(run_of(recording(), kind="serve")) is None
    assert read(run_of(None)) is None


def test_the_last_event_of_a_trace_is_the_record():
    trace = recording()
    (event,) = [e for e in trace["host"] if e[0] == setup_record.SPAN]
    later = copy.deepcopy(event)
    later[1] += 10 ** 9
    later[3]["steps_before"] = event[3]["steps_before"] + 9
    later[3]["note"] = "not a number"
    trace["host"].append(later)
    rec = setup_record.record(run_of(trace))
    assert rec["steps_before"] == float(event[3]["steps_before"]) + 9
    assert "note" not in rec


def test_cache_hit_share_needs_a_cache_that_was_asked():
    trace = recording()
    for e in trace["host"]:
        if e[0] == setup_record.SPAN:
            for k in ("cache_hits", "cache_misses", "outside_cache_hits",
                      "outside_cache_misses"):
                e[3][k] = 0
    assert reader("setup.cache_hit_share").read(run_of(trace)) is None
    assert reader("setup.import_s").read(run_of(trace)) > 0
    trace = recording()
    for e in trace["host"]:
        if e[0] == setup_record.SPAN:
            e[3].update(cache_hits=3, outside_cache_hits=1,
                        cache_misses=0, outside_cache_misses=4)
    assert reader("setup.cache_hit_share").read(run_of(trace)) == 50.0
    trace = recording()
    for e in trace["host"]:
        if e[0] == setup_record.SPAN:
            del e[3]["lower_s"]
    assert reader("setup.trace_lower_s").read(run_of(trace)) is None


def test_observation_line_splits_setup_s_once_a_trace(capsys):
    trace, rec = recording(), stats()
    run = run_of(trace, setup_s=30.0)
    for metric in METRICS:
        reader(metric).read(run)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    (line,) = [l for l in lines if l.get("observation") == "setup"]
    assert line["record"] == rec and line["setup_s"] == 30.0
    program = rec["import_s"] + rec["init_s"] + rec["first_step_s"] \
        + rec["cost_capture_s"]
    outside = rec["outside_trace_s"] + rec["outside_lower_s"] \
        + rec["outside_backend_s"]
    assert line["program_s"] == pytest.approx(program)
    assert line["outside_compile_s"] == pytest.approx(outside)
    assert line["pre_init_s"] == rec["pre_init_s"] > 0
    assert line["remainder_s"] == pytest.approx(
        30.0 - program - outside - rec["pre_init_s"])
    assert line["remainder_s"] > 0
    # a trace without the event prints nothing
    reader("setup.import_s").read(run_of(without_setup(trace)))
    assert "observation" not in capsys.readouterr().out


def entries():
    with open(os.path.join(DATA, ENTRIES)) as f:
        return json.load(f)["per_layer"]


def test_the_six_entries_wait_as_data_for_a_benchmark_pr():
    """BENCHMARK.json does not list the six: two standing tests hold every
    per-layer metric that lists a cell to the cell's RATE
    (``test_every_cell_reports_one_rate_and_its_layers_move_it``) and to a
    share read off PR 24's recordings (``test_new_reader_on_a_real_recording``),
    and neither can take a metric that moves ``setup_s`` without an edit.
    The entries are kept as data, in the form the declaration asks for, each
    with a reader beside it and on cells that report ``setup_s``."""
    bench = common.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    (setup,) = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert [m["name"] for m in entries()] == [
        "setup.import_s", "setup.engine_init_s", "setup.first_step_s",
        "setup.trace_lower_s", "setup.compile_or_cache_s",
        "setup.cache_hit_share"]
    for m in entries():
        assert sorted(m) == sorted(["name", "unit", "better", "source",
                                    "layer", "moves", "workloads"])
        assert (m["moves"], m["source"], m["layer"]) == (
            "setup_s", "program_counter", "set-up")
        assert m["workloads"] == CELLS and set(CELLS) <= cells
        assert all(c in setup.get("workloads", CELLS) for c in CELLS)
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"].endswith("share") else ("s", "lower"))
        assert hasattr(reader(m["name"]), "read")
    assert set(METRICS) == {m["name"] for m in entries()}


def test_rehearsal_of_olmoe_4k_prints_the_six_once_they_are_listed(tmp_path):
    """A checkout whose BENCHMARK.json has the six entries appended, and
    nothing else changed: ``run.py`` finds the readers by name."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = common.load_benchmark()
    bench["per_layer"] += entries()
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "olmoe-1b-7b.train.4k", "--seed", str(2 ** 31 + 5), "--seconds", "4",
         "--trace", "1", "--rehearse-cpu"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 1, out.stderr[-2000:]       # a rehearsal never 0
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["rehearsal"] == "passed"
    metrics = line["would_print"]["metrics"]
    assert set(METRICS) <= set(metrics)
    assert all(metrics[m]["value"] > 0 for m in METRICS)
    assert 0 < metrics["setup.cache_hit_share"]["value"] <= 100
    seen = [json.loads(l) for l in lines if l.startswith('{"observation"')]
    (obs,) = [o for o in seen if o["observation"] == "setup"]
    rec = obs["record"]
    assert obs["setup_s"] >= obs["program_s"] > 0
    assert metrics["setup.engine_init_s"]["value"] == rec["init_s"]
    assert rec["steps_before"] >= 4      # the check's and the warm-up's steps
