"""The readers PR 37 brings: device time under the names the train step gained
(``ds.param_cast``, ``ds.layer_stack``, ``ds.norm``, ``ds.residual``), what no
name reaches (``train.unnamed_share``), and the step's named scalars off the
``ds.counters`` host events (``benchmark/counters.py``). Fed a hand-made trace
and the cut of zaya 8k's chip trace with its paths re-labelled as the new
program labels them; the recordings themselves are the parent's program."""

import json
import os
import re

import pytest

from benchmark import common, counters, scope_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CELL = "zaya1-8b.train.8k"
SHARES = ("train.param_cast_share", "train.layer_stack_share",
          "train.norm_residual_share")
COUNTERS = {"moe.compact_hit_share": ("moe_compact_hit_share", 100.0),
            "moe.rows_max_over_mean": ("moe_rows_max_over_mean", 1.0),
            "moe.held_rows_over_expected":
                ("moe_held_rows_over_expected", 1.0)}
RECORDINGS = {"mistral-7b.train.8k": "scope_trace_train_8k.json",
              "mixtral-8x7b.train.ep4": "scope_trace_train_ep4.json",
              "olmoe-1b-7b.train.4k": "scope_trace_train_olmoe_4k.json",
              "kimi-vl-a3b.train.8k": "scope_trace_train_kimi_8k.json",
              CELL: "scope_trace_train_zaya1_8k.json"}


def reader(name):
    return common.load_file_module("layer_metrics", name)


def run_of(trace, kind="train", cell=CELL):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind}}


def relabel(op_name):
    """A path of the parent's program as the program with the names spells
    it (tests/unit/test_trace_names.py holds the real step to them)."""
    op_name = op_name.replace("/model/while", "/model/ds.layer_stack/while")
    op_name = re.sub(r"/block/((?:input|post_attention)_layernorm)/",
                     r"/block/ds.norm/\1/", op_name)
    op_name = re.sub(r"/block/((?:attn|mlp)_residual)/",
                     r"/block/ds.residual/\1/", op_name)
    return re.sub(r"(ds\.loss_and_grad/(?:transpose\()?jvp\()(\)+/convert)",
                  r"\1ds.param_cast\2", op_name)


def recording(name=RECORDINGS[CELL], labelled=False, host=()):
    rec = json.load(open(os.path.join(DATA, name)))
    ops = [relabel(op) if labelled else op for op in rec["op_names"]]
    return {"devices": {p: [[n, s, d, ops[i]] for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"] + list(host)}


def counter_events(values, name="moe_rows_max_over_mean", start=1000,
                   **more):
    """One ``ds.counters`` event a value, as ``scope_reduce.load`` keeps a
    host event: [name, start_ns, duration_ns, stats, thread]."""
    return [["ds.counters", start + 100 * i, 40,
             {"step": 7 + i, name: v, **{k: w[i] for k, w in more.items()}},
             "python3"] for i, v in enumerate(values)]


# -- the names, on a hand-made trace -----------------------------------------

STEP = "jit(ds_train_step_n2)/ds.loss_and_grad/"
FWD = STEP + "jvp(M)/model/ds.layer_stack/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["convert.1", 0, 300, STEP + "jvp(ds.param_cast)/convert_element_type"],
        ["fusion.1", 1000, 500, FWD + "dynamic_slice"],
        ["fusion.2", 2000, 100,
         FWD + "closed_call/layers/block/ds.norm/input_layernorm/mul"],
        ["fusion.3", 3000, 60,
         FWD + "closed_call/layers/block/ds.residual/add"],
        ["fusion.4", 4000, 1000,
         FWD + "closed_call/layers/block/self_attn/ds.attn_proj/dot"],
        ["fusion.5", 5000, 40, STEP + "jvp(M)/mul"],
        ["copy.7", 6000, 200, ""],
        ["fusion.6", 6500, 50, ""],
        ["ragged-dot-none.3", 7000, 700, ""],
        ["fusion.8", 8000, 50, "jit(_threefry_split)/add"],
        ["convert.2", 9000, 200,
         STEP + "transpose(jvp(ds.param_cast))/convert_element_type"],
        ["fusion.9", 9500, 1800, "jit(ds_train_step_n2)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"]],
}       # busy: 5,000 ns


@pytest.mark.parametrize("metric,want", [
    ("train.param_cast_share", 10.0), ("train.layer_stack_share", 10.0),
    ("train.norm_residual_share", 3.2),
    ("train.unnamed_share", 100 * (40 + 200 + 50 + 50) / 5000)])
def test_share_readers_on_a_hand_made_trace(metric, want, capsys):
    assert reader(metric).read(run_of(HAND)) == pytest.approx(want)
    assert reader(metric).read(run_of(HAND, kind="serve")) is None
    assert reader(metric).read(run_of(None)) is None
    if metric == "train.unnamed_share":
        lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
        (line,) = [x for x in lines
                   if x["observation"] == "train.unnamed_share"]
        assert line["share_pct"] == {"bare": 0.8, "compiler_copies": 4.0,
                                     "no_op_name": 1.0, "other_paths": 1.0}


def test_a_norm_inside_another_scope_stays_that_scopes():
    """The q/k norms under ``ds.attn_proj`` and the final norm under
    ``ds.lm_head_loss`` are opened inside those scopes: the innermost name
    wins, so ``ds.norm`` takes only the block's own."""
    inner = FWD + "closed_call/layers/block/self_attn/ds.attn_proj/q_norm/mul"
    head = STEP + "jvp(M)/model/ds.lm_head_loss/norm/mul"
    assert scope_reduce.scope_of(inner) == "ds.attn_proj"
    assert scope_reduce.scope_of(head) == "ds.lm_head_loss"
    assert scope_reduce.scope_of(
        FWD + "closed_call/layers/block/ds.norm/input_layernorm/mul") == \
        "ds.norm"
    assert scope_reduce.scope_of(FWD + "squeeze") == "ds.layer_stack"


# -- on the cut of zaya 8k's chip trace, re-labelled -------------------------

@pytest.mark.parametrize("metric,low,high", [
    ("train.param_cast_share", 3.0, 3.8),       # 6.4 ms of a 188.7 ms step
    ("train.layer_stack_share", 6.9, 7.9),      # 13.9 + 0.2
    ("train.norm_residual_share", 1.0, 1.6)])   # 2.4
def test_named_shares_of_the_relabelled_recording(metric, low, high):
    """ISSUE 37's table, read by name: what was bare ``ds.loss_and_grad`` in
    PR 35's trace is the cast, the loop's own copies and the norms."""
    assert low < reader(metric).read(run_of(recording(labelled=True))) < high
    # the parent's program has no such name
    assert reader(metric).read(run_of(recording())) is None


def test_the_names_move_time_and_neither_make_nor_lose_any():
    before = scope_reduce.reduce(recording())
    after = scope_reduce.reduce(recording(labelled=True))
    assert after["busy_s"] == before["busy_s"]
    new = ("ds.param_cast", "ds.layer_stack", "ds.norm", "ds.residual")
    moved = sum(after["by_scope"][k] for k in new)
    bare = "ds.loss_and_grad"
    assert moved + after["by_scope"].get(bare, 0.0) == pytest.approx(
        before["by_scope"][bare], rel=1e-9)
    # bare held an eighth of the step; what stays is under half a per cent
    assert before["by_scope"][bare] / before["busy_s"] > 0.11
    assert after["by_scope"].get(bare, 0.0) / after["busy_s"] < 0.005
    for scope, seconds in before["by_scope"].items():
        if scope != bare:
            assert after["by_scope"][scope] == pytest.approx(seconds,
                                                             rel=1e-12)


def cell_metrics():
    old = {m["name"] for m in common.load_benchmark()["per_layer"]
           if CELL in m["workloads"] and m["source"] == "device_trace"}
    return sorted(old - {"device.idle_share.train",
                         "train.host_gap_ms_per_step"})


@pytest.mark.parametrize("metric", cell_metrics())
def test_every_older_metric_reads_what_it_read(metric):
    """``moe.expert_share``, ``train.attn_proj_share``,
    ``train.head_loss_share`` and the rest: the same number with and without
    the new names in the paths."""
    value = reader(metric).read(run_of(recording()))
    assert value is not None
    assert reader(metric).read(run_of(recording(labelled=True))) == \
        pytest.approx(value, rel=1e-12)


def test_unnamed_share_falls_by_what_the_names_took():
    before = reader("train.unnamed_share").read(run_of(recording()))
    after = reader("train.unnamed_share").read(
        run_of(recording(labelled=True)))
    taken = sum(reader(m).read(run_of(recording(labelled=True)))
                for m in SHARES)
    assert before - after == pytest.approx(taken, rel=1e-9)
    assert 18 < before < 21 and 6 < after < 9       # ISSUE 37: 19.4 -> 7.3


@pytest.mark.parametrize("cell", sorted(RECORDINGS))
def test_unnamed_share_of_every_recording(cell):
    """Readable in the parent's program too: bare + unscoped less the
    grouped products ``moe.grouped_matmul_share`` names."""
    run = run_of(recording(RECORDINGS[cell]), cell=cell)
    r = scope_reduce.reduced(run)
    gmm = reader("moe.grouped_matmul_share").read(run) or 0.0
    want = 100 * (r["by_scope"].get("ds.loss_and_grad", 0.0)
                  + r["by_scope"].get(scope_reduce.UNSCOPED, 0.0)) \
        / r["busy_s"] - gmm
    assert reader("train.unnamed_share").read(run) == pytest.approx(want)
    assert 0 < want < 100


def test_a_program_that_names_nothing_reads_as_nothing():
    bare = {"devices": {p: [[n, s, d, ""] for n, s, d, _ in events]
                        for p, events in HAND["devices"].items()},
            "host": HAND["host"]}
    for metric in SHARES + ("train.unnamed_share",) + tuple(COUNTERS):
        assert reader(metric).read(run_of(bare)) is None, metric


# -- the counters ------------------------------------------------------------

@pytest.mark.parametrize("metric", sorted(COUNTERS))
def test_counter_reader_is_the_mean_over_the_windows_events(metric, capsys):
    name, scale = COUNTERS[metric]
    values = [1.0, 0.8, 1.25, 0.95, 1.0]
    host = counter_events(values, name, moe_skip_share=[0.05] * 5) + \
        counter_events([9.0], name, start=10 ** 12)    # after the window
    run = run_of(recording(labelled=True, host=host))
    assert reader(metric).read(run) == pytest.approx(scale * 1.0)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    (line,) = [x for x in lines if x["observation"] == "counters"]
    assert line["events"] == 5 and line["steps"] == [7, 11]
    assert line["distinct_steps"] == 5
    assert line["span_total_ms"] == pytest.approx(5 * 40 / 1e6)
    assert line["scalars"][name] == {"n": 5, "mean": 1.0, "min": 0.8,
                                     "max": 1.25}
    assert line["scalars"]["moe_skip_share"]["mean"] == pytest.approx(0.05)
    assert reader(metric).read({**run, "observed": {"kind": "serve"}}) is None


@pytest.mark.parametrize("metric", sorted(COUNTERS))
@pytest.mark.parametrize("events", [0, 3])
def test_counter_reader_needs_four_events(metric, events):
    host = counter_events([1.0] * events, COUNTERS[metric][0])
    assert reader(metric).read(
        run_of(recording(labelled=True, host=host))) is None
    assert reader(metric).read(run_of(None)) is None


def test_counter_stats_may_come_back_as_text():
    """The profiler hands a span's metadata back as it parsed it: a number
    where it could, else the text."""
    host = counter_events(["1.5", 0.5, "2", 0.0])
    assert counters.mean(run_of(recording(host=host)),
                         "moe_rows_max_over_mean") == pytest.approx(1.0)
    assert counters.numbers([{"a": "x"}, {"a": None}, {}], "a") == []


@pytest.mark.parametrize("cell", sorted(RECORDINGS))
@pytest.mark.parametrize("metric", sorted(COUNTERS))
def test_counter_readers_find_nothing_in_the_parents_recordings(cell, metric):
    """The parent publishes no ``ds.counters`` event: None, no exception."""
    assert reader(metric).read(
        run_of(recording(RECORDINGS[cell]), cell=cell)) is None


def test_benchmark_lists_what_the_frozen_tests_let_it():
    """The four entries PR 37 could add without an edit to a test that is
    there (PERF.md section 7): no metric may list zaya 8k
    (test_benchmark_zaya1.py pins its list) and a trace-sourced one must
    read the older recordings."""
    listed = {m["name"]: m for m in common.load_benchmark()["per_layer"]}
    assert set(listed["train.unnamed_share"]["workloads"]) == \
        set(RECORDINGS) - {CELL}
    for metric in COUNTERS:
        assert listed[metric]["source"] == "program_counter"
        assert CELL not in listed[metric]["workloads"]
    assert not set(SHARES) & set(listed)
    for name in SHARES + tuple(COUNTERS) + ("train.unnamed_share",):
        assert callable(reader(name).read)
