"""``benchmark/scope_reduce.py``: device time by the program's own names.

A hand-made trace pins each rule (innermost scope wins, the phase marks,
exposed collectives by the scope that caused them, idle gaps to the
program's spans, self time, the window clipped as ``trace_reduce`` clips
it); cuts of real chip traces of both training cells, kept as JSON beside
``trace_small.json``, show every new reader a number."""

import json
import os

import pytest

from benchmark import common, scope_reduce, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

FWD = "jit(train_step)/ds.loss_and_grad/jvp(M)/model/while/body/closed_call/"
BWD = ("jit(train_step)/ds.loss_and_grad/transpose(jvp(M))/model/while/body/"
       "closed_call/checkpoint/")
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 900, 300, FWD + "layers/block/self_attn/ds.attn_proj/q_proj/dot_general"],
        ["ds_flash_fwd.3", 1200, 800, FWD + "layers/block/self_attn/ds.attention/ds_flash_fwd/pallas_call"],
        ["while.2", 1000, 5000, FWD[:-1]],
        ["fusion.7", 2000, 1000, BWD + "rematted_computation/layers/block/mlp/ds.mlp/up_proj/dot_general"],
        ["fusion.8", 3000, 1000, BWD + "layers/block/mlp/ds.mlp/up_proj/dot_general"],
        ["all-reduce.1", 4000, 1000, BWD + "layers/block/block_sparse_moe/ds.moe_experts/dot_general"],
        ["fusion.9", 4000, 400, "jit(train_step)/ds.optimizer/mul"],
        ["copy.5", 6000, 500, ""],
    ]},
    "host": [
        ["bench.traced_window", 1000, 10000, {}, "python"],
        ["ds.train_batch", 4900, 1300, {"step": 7, "step_num": 7}, "python"],
        ["ds.shape_batch", 4950, 900, {"step": 7}, "python"],
        ["ds.dispatch", 5900, 200, {"step": 7, "program": "train_step"}, "python"],
        ["bench.fence", 6400, 4700, {}, "python"],
        ["ds.report", 6450, 100, {"step": 7}, "other-thread"],
    ],
}


@pytest.fixture(scope="module")
def hand():
    return scope_reduce.reduce(HAND)


def ns(seconds):
    return round(seconds * 1e9)


@pytest.mark.parametrize("table,key,want", [
    ("by_scope", "ds.attn_proj", 200),      # clipped to the window's start
    ("by_scope", "ds.attention", 800),      # innermost of loss_and_grad/...
    ("by_scope", "ds.mlp", 2000),
    ("by_scope", "ds.moe_experts", 600),    # the part fusion.9 left it
    ("by_scope", "ds.optimizer", 400),
    ("by_scope", "(unscoped)", 500),
    ("by_phase", "forward", 1000),
    ("by_phase", "recompute", 1000),
    ("by_phase", "backward", 1600),
    ("by_phase", "optimizer", 400),
    ("by_phase", "other", 500),
    ("exposed_by_scope", "ds.moe_experts", 600),
    ("idle_gaps", "ds.shape_batch", 1000),  # covers most of [5000, 6000)
    ("idle_gaps", "bench.fence", 4500),     # no ds.* span on [6500, 11000)
])
def test_hand_made_trace(hand, table, key, want):
    assert ns(hand[table][key]) == want


def test_hand_made_totals(hand):
    assert ns(hand["busy_s"]) == 4500 and ns(hand["window_s"]) == 10000
    assert ns(sum(hand["by_phase"].values())) == ns(hand["busy_s"])
    assert ns(sum(hand["by_scope"].values())) == ns(hand["busy_s"])
    assert hand["steps"] == 1
    assert hand["by_kernel"] == {"ds_flash_fwd": {"s": 800e-9, "calls": 1}}
    assert hand["dispatch_args"] == [{"step": 7, "program": "train_step"}]
    assert set(hand["exposed_by_scope"]) == {"ds.moe_experts"}


@pytest.mark.parametrize("span,count,self_ns", [
    ("ds.train_batch", 1, 200),     # 1300 less its children's 900 + 200
    ("ds.shape_batch", 1, 900),
    ("ds.dispatch", 1, 200),
    ("ds.report", 1, 100),          # another thread: nobody's child
    ("bench.fence", 1, 4600),       # clipped to the window's end
])
def test_self_time_is_duration_less_children(hand, span, count, self_ns):
    row = hand["spans"][span]
    assert row["count"] == count and ns(row["self_s"]) == self_ns
    assert row["self_ms_p50"] == pytest.approx(self_ns / 1e6)


def expand(name):
    """A recording (op_names interned) as ``scope_reduce.reduce`` takes it."""
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


RECORDINGS = {"mistral-7b.train.8k": "scope_trace_train_8k.json",
              "mixtral-8x7b.train.ep4": "scope_trace_train_ep4.json"}


@pytest.mark.parametrize("trace", [HAND] + [expand(f)
                                            for f in RECORDINGS.values()])
def test_window_and_busy_equal_trace_reduce(trace):
    """The same events through ``trace_reduce.reduce``: one window, one busy
    time, one idle share, one exposed collective time."""
    old = trace_reduce.reduce({
        "devices": {p: [ev[:3] for ev in events]
                    for p, events in trace["devices"].items()},
        "host": [ev[:3] for ev in trace["host"]]})
    new = scope_reduce.reduce(trace)
    assert new["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert new["busy_s"] == pytest.approx(old["busy_s"], rel=1e-9)
    assert new["devices"] == old["devices"]
    assert sum(new["exposed_by_scope"].values()) == pytest.approx(
        old["collective_exposed_s"], rel=1e-9, abs=1e-12)
    assert sum(new["by_phase"].values()) == pytest.approx(new["busy_s"],
                                                         rel=1e-9)


def run_of(cell, kind="train"):
    return {"cell": cell, "device": TPU, "observed": {"kind": kind},
            "scope_trace": expand(RECORDINGS[cell])}


def new_train_metrics(cell):
    bench = common.load_benchmark()
    old = {"train.step_ms_p50", "train.mfu", "collective.exposed_share",
           "device.idle_share.train"}
    return [m["name"] for m in bench["per_layer"]
            if m["name"] not in old and cell in m["workloads"]]


@pytest.mark.parametrize("cell,metric", [
    (c, m) for c in RECORDINGS for m in new_train_metrics(c)])
def test_new_reader_on_a_real_recording(cell, metric):
    reader = common.load_file_module("layer_metrics", metric)
    value = reader.read(run_of(cell))
    assert value is not None and 0 < value < 100
    assert reader.read(run_of(cell, kind="serve")) is None


def test_recorded_shares_are_the_cells_shape():
    """What PERF.md section 5 says of each cell, from the recordings."""
    dense = scope_reduce.reduce(expand(RECORDINGS["mistral-7b.train.8k"]))
    moe = scope_reduce.reduce(expand(RECORDINGS["mixtral-8x7b.train.ep4"]))
    assert dense["unscoped_share"] < 0.05 and moe["unscoped_share"] < 0.05
    assert set(dense["by_kernel"]) == {"ds_flash_fwd", "ds_flash_bwd_dq",
                                       "ds_flash_bwd_dkv"}
    assert not moe["by_kernel"]             # no Pallas kernel under a mesh
    top = lambda r: max(r["by_scope"], key=r["by_scope"].get)
    assert top(dense) == "ds.mlp" and top(moe) == "ds.moe_experts"
    assert moe["devices"] > 1 and moe["exposed_by_scope"]


def test_a_program_that_names_nothing_reads_as_nothing():
    """The parent commit's trace under this benchmark: no ``ds.`` scope, no
    ``ds.*`` span, no ``ds_*`` kernel: every reader returns None and
    raises nothing."""
    bare = {"devices": {p: [[n.replace("ds_", "self_attn_"), s, d, ""]
                            for n, s, d, _ in events]
                        for p, events in HAND["devices"].items()},
            "host": [ev for ev in HAND["host"]
                     if not ev[0].startswith("ds.")]}
    run = {"cell": "mistral-7b.train.8k", "device": TPU,
           "observed": {"kind": "train"}, "scope_trace": bare}
    for metric in new_train_metrics("mistral-7b.train.8k") + \
            new_train_metrics("mixtral-8x7b.train.ep4"):
        reader = common.load_file_module("layer_metrics", metric)
        assert reader.read(run) is None, metric


# -- the file's wire format --------------------------------------------------

def varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def test_op_names_come_out_of_the_metadata_plane():
    instruction = lambda name, op_name: field(2, field(1, name) + field(
        2, "fusion") + field(7, field(1, "mul") + field(2, op_name)))
    hlo = field(1, field(1, "jit_train_step") + field(3, field(
        1, "main") + instruction("fusion.3", "jit(train_step)/ds.mlp/mul")
        + instruction("copy.1", "")))
    stat_meta = field(5, field(1, 9) + field(2, field(1, 9)
                                             + field(2, "Hlo Proto")))
    event_meta = field(4, field(1, 4) + field(2, field(1, 4) + field(
        2, "jit_train_step(77)") + field(5, field(1, 9) + field(6, hlo))))
    space = field(1, field(2, "/device:TPU:0")) + field(
        1, field(1, 2) + field(2, "/host:metadata") + stat_meta + event_meta)
    assert scope_reduce.module_op_names(space) == {
        "jit_train_step(77)": {"fusion.3": "jit(train_step)/ds.mlp/mul",
                               "copy.1": ""}}


@pytest.mark.parametrize("op_name,scope,phase", [
    ("jit(train_step)/ds.loss_and_grad/jvp(M)/model/ds.embed/embed_tokens/gather",
     "ds.embed", "forward"),
    (BWD + "layers/block/self_attn/ds.attention/ds_flash_bwd_dq/pallas_call",
     "ds.attention", "backward"),
    (BWD + "rematted_computation/layers/block/self_attn/ds.attn_proj/mul",
     "ds.attn_proj", "recompute"),
    ("jit(train_step)/ds.optimizer/sqrt", "ds.optimizer", "optimizer"),
    ("jit(mixed_step)/ds.mixed_step/M/model/layers/block/self_attn/ds.kv_append/scatter",
     "ds.kv_append", "other"),
    ("", "(unscoped)", "other"),
])
def test_scope_and_phase_of_a_path(op_name, scope, phase):
    assert scope_reduce.scope_of(op_name) == scope
    assert scope_reduce.phase_of(op_name) == phase


@pytest.mark.parametrize("name,phase", [
    ("multiply_add_fusion.2.remat2", "recompute"),   # the compiler's clone
    ("fusion.347.remat", "recompute"),
    ("fusion.92.remat3.clone", "recompute"),
    ("fusion.347", "forward"),
    ("remat_fusion.3", "forward"),                   # not the clone's suffix
])
def test_compiler_clones_count_as_recomputation(name, phase):
    path = FWD + "layers/block/block_sparse_moe/ds.moe_experts/dot_general"
    assert scope_reduce.phase_of(path, name) == phase
