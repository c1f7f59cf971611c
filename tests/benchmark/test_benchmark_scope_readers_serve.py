"""The serving readers this benchmark keeps as files in no cell
(``serve.plan_ms_p50``, ``serve.pack_ms_p50``, ``serve.fetch_wait_ms_p50``,
``serve.harvest_ms_p50``, ``serve.attention_share``,
``kernel.ragged.roofline_share``): each reads a number off a serving trace
and nothing off a training one, and entered in a copy beside the chat cell,
as a later PR would enter them, the harness finds and runs them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVE_CELL = "mistral-7b.serve.chat"
READERS = {"serve.plan_ms_p50": "ms", "serve.pack_ms_p50": "ms",
           "serve.fetch_wait_ms_p50": "ms", "serve.harvest_ms_p50": "ms",
           "serve.attention_share": "%", "kernel.ragged.roofline_share": "%"}

MIXED = "jit(mixed_step)/ds.mixed_step/LlamaForCausalLM/model/while/body/"


def serving_trace():
    """Two steps of a serving loop, by hand: the resident program's
    operations under their scopes, the engine's spans around them."""
    device, host = [], [["bench.traced_window", 0, 200_000, {}, "python"]]
    for k, t in enumerate((10_000, 110_000)):
        device += [
            ["fusion.4", t + 20_000, 5_000, MIXED + "layers/block/self_attn/ds.kv_append/scatter"],
            ["ds_ragged_paged_attention.2", t + 25_000, 40_000,
             MIXED + "layers/block/self_attn/ds.attention/ds_ragged_paged_attention/pallas_call"],
            ["fusion.9", t + 65_000, 10_000, MIXED + "layers/block/mlp/ds.mlp/dot_general"],
            ["fusion.11", t + 75_000, 1_000, "jit(mixed_step)/ds.mixed_step/ds.sample/argmax"],
        ]
        step = {"step": k}
        host += [
            ["bench.srv_step", t, 90_000, {}, "python"],
            ["ds.step", t + 1_000, 88_000, {**step, "step_num": k}, "python"],
            ["ds.plan", t + 2_000, 3_000 + 1_000 * k, step, "python"],
            ["ds.pack", t + 6_000, 8_000, step, "python"],
            ["ds.dispatch", t + 15_000, 4_000,
             {**step, "decode_tokens": 7, "verify_tokens": 0,
              "prefill_tokens": 9, "width": 17, "rows": 8,
              "context_tokens": 2400}, "python"],
            ["ds.fetch", t + 19_000, 58_000, step, "python"],
            ["ds.harvest", t + 77_000, 6_000, step, "python"],
        ]
    return {"devices": {"/device:TPU:0": device}, "host": host}


@pytest.fixture(scope="module")
def serve_cell_entered():
    """``kernel_costs.cell_files`` finds the chat cell's files through
    BENCHMARK.json: enter it in memory, as the copy below does on disk."""
    bench = common.load_benchmark()
    bench["workloads"].append({"name": SERVE_CELL, "config": "mistral-7b",
                               "traffic": "serve.chat", "chips": 1,
                               "why": "entered by the tests"})
    original, common.load_benchmark = common.load_benchmark, lambda: bench
    yield
    common.load_benchmark = original


@pytest.mark.parametrize("metric,want", [
    ("serve.plan_ms_p50", 0.0035), ("serve.pack_ms_p50", 0.008),
    ("serve.fetch_wait_ms_p50", 0.058), ("serve.harvest_ms_p50", 0.006),
    ("serve.attention_share", 100 * 40 / 56),
    ("kernel.ragged.roofline_share", None),
])
def test_serving_reader_on_a_serving_trace(serve_cell_entered, metric, want):
    run = {"cell": SERVE_CELL, "observed": {"kind": "serve"},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "scope_trace": serving_trace()}
    reader = common.load_file_module("layer_metrics", metric)
    value = reader.read(run)
    if want is None:
        # 2,400 context tokens x 8 kv heads x 128 x 2 (k, v) x 2 B = 9.8 MB
        # at 819 GB/s is 12 us of the kernel's 40 us a call
        bytes_needed = 2 * 2 * 8 * 128 * 2400 + 2 * 2 * 32 * 128 * 16
        want = 100 * (bytes_needed / 819e9) / 40e-6
    assert value == pytest.approx(want, rel=1e-6)
    assert reader.read({**run, "observed": {"kind": "train"}}) is None


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
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
        {"name": "serve.step_ms_p50", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "serving host loop",
         "moves": "tpot_p95_ms", "workloads": [SERVE_CELL]}] + [
        {"name": n, "unit": u, "better": "higher" if "roofline" in n
         else "lower", "source": "program_span" if u == "ms"
         else "device_trace", "layer": "serving host loop" if u == "ms"
         else "kernels", "moves": "tpot_p95_ms", "workloads": [SERVE_CELL]}
        for n, u in READERS.items()]
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return root


def test_serving_readers_run_once_entered(copy):
    """A traced rehearsal on the CPU: the readers are found and called; a
    CPU trace has no device operation, so they leave their metrics out and
    the run still passes."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", SERVE_CELL,
         "--seed", str(2 ** 31 + 5), "--seconds", "6", "--trace", "1",
         "--rehearse-cpu"], cwd=copy, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 1, out.stderr[-2000:]   # a rehearsal never 0
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
    assert set(line["would_print"]["metrics"]) == {"serve.step_ms_p50"}
