"""The two readers PR 50 brings, of the small-group grouped-matmul kernels'
names alone (``ds_moe_gmm.N``, ``ds_moe_gmm_t.N``): ``moe.gmm_kernel_share``
and ``kernel.ds_moe_gmm.roofline_share`` -- files that no ``per_layer`` entry
names yet (see the first test). Fed a hand-made trace, the older
recordings (the parent's programs: nothing to read, no exception) and a cut
of olmoe 4k's chip trace with the kernels in."""

import json
import os

import pytest

from benchmark import common, instruction_times, scope_reduce

READ_PR50_KERNELS = ("moe.gmm_kernel_share",
                     "kernel.ds_moe_gmm.roofline_share")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CELL = "olmoe-1b-7b.train.4k"
CELLS = ("olmoe-1b-7b.train.4k", "kimi-vl-a3b.train.8k", "zaya1-8b.train.8k",
         "mellum2-12b-a2.5b.train.8k")
OLDER = ("scope_trace_train_8k.json", "scope_trace_train_ep4.json",
         "scope_trace_train_olmoe_4k.json", "scope_trace_train_kimi_8k.json",
         "scope_trace_train_zaya1_8k.json")

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/while/body/"
GMM = FWD + "block_sparse_moe/ds.moe_experts/moe_gmm/gmm/ds_moe_gmm/pallas_call"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.2", 1000, 1000, FWD + "block_sparse_moe/ds.moe_experts/moe_dispatch/gather"],
        ["ds_moe_gmm.3", 2000, 1000, GMM],
        ["ds_moe_gmm.4", 3000, 1000, GMM],
        ["ds_moe_gmm.3", 4000, 1000, GMM],
        ["ds_moe_gmm_t.1", 5000, 3000, GMM.replace("gmm/ds_moe_gmm", "tgmm/ds_moe_gmm_t")],
        ["while.2", 1000, 8000, FWD[:-1]],
        ["ds_moe_gmm.3", 9100, 5000, GMM],                    # clipped
        ["ragged-dot-none.7", 8200, 500, "ragged-dot-none"],  # not theirs
        ["fusion.9", 9000, 100, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 11100, {}, "python"],
             ["ds.train_batch", 100, 50, {"step": 7}, "python"]],
}


def reader(name):
    return common.load_file_module("layer_metrics", name)


def run_of(trace, kind="train", cell=CELL):
    return {"cell": cell, "device": TPU, "observed": {"kind": kind},
            "scope_trace": trace}


def recording(name):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


def test_the_two_readers_are_files_and_the_older_entries_stand():
    """PR 50 brings the readers as files: the kernels' share of the busy
    time (for the four one-chip MoE cells whose rate is the shared metric)
    and their roofline share where the row count is exact (olmoe 4k). Their
    ``per_layer`` entries are the next ``benchmark`` PR's, because the
    accepted tests pin the cells' lists of readers (PERF.md section 7, 33);
    the entries of XLA's kernel stand as they stood."""
    for name in READ_PR50_KERNELS:
        assert callable(reader(name).read)
    old = {m["name"]: m["workloads"]
           for m in common.load_benchmark()["per_layer"]}
    assert old["moe.grouped_matmul_share"] == list(CELLS)
    assert old["kernel.moe_gmm.roofline_share"] == [CELL]


def test_gmm_kernel_share_counts_both_kernels_and_nothing_else():
    """Busy 1000 + 3 x 1000 + 3000 + 500 + 100 + 2000 = 9600 ns (the
    container is not work, the last product is clipped to the window); the
    instructions named ds_moe_gmm* hold 8000 of them, XLA's kernel none."""
    ops = instruction_times.by_instruction(run_of(HAND), "ds_moe_gmm")
    assert {k: (round(v["s"] * 1e9), v["calls"]) for k, v in ops.items()} \
        == {"ds_moe_gmm.3": (4000, 3), "ds_moe_gmm.4": (1000, 1),
            "ds_moe_gmm_t.1": (3000, 1)}
    share = reader("moe.gmm_kernel_share")
    assert share.read(run_of(HAND)) == pytest.approx(100 * 8000 / 9600)
    assert share.read(run_of(HAND, kind="serve")) is None
    assert reader("moe.grouped_matmul_share").read(run_of(HAND)) == \
        pytest.approx(100 * 500 / 9600)


def test_ds_moe_gmm_roofline_charges_what_the_xla_kernels_metric_charges():
    """Five calls of mean 8000 / 5 ns against the least time of one of the
    cell's products on the v5e, ``kernel.moe_gmm.roofline_share``'s own
    count: 2 x 65,536 rows x 2048 x 1024 / 197e12 = 1.395 ms."""
    roof = reader("kernel.ds_moe_gmm.roofline_share")
    xla = reader("kernel.moe_gmm.roofline_share")
    assert roof.grouped_matmul(65536, 2048, 1024, 64) == \
        xla.grouped_matmul(65536, 2048, 1024, 64)
    least = 2 * 65536 * 2048 * 1024 / 197e12
    assert roof.read(run_of(HAND)) == pytest.approx(
        100 * least / (8000e-9 / 5))
    assert roof.read(run_of(HAND, kind="serve")) is None
    assert roof.read({**run_of(HAND), "device": {"platform": "cpu"}}) is None


@pytest.mark.parametrize("name", OLDER)
@pytest.mark.parametrize("metric", READ_PR50_KERNELS)
def test_a_program_without_the_kernels_reads_nothing(metric, name):
    """The parent's program in every cell (the recordings all predate the
    kernels), and a run with no trace: None, no exception -- what the driver
    sees when it lays these readers over the parent's checkout."""
    assert reader(metric).read(run_of(recording(name))) is None
    assert reader(metric).read(run_of(None)) is None


# -- a cut of a real chip trace of olmoe 4k with the kernels in ---------------

RECORDING = "scope_trace_train_olmoe_4k_gmm.json"


def test_recording_is_one_step_of_the_cell_with_the_kernels_in():
    """130 ms of olmoe 4k on the v5e at PR 50 (one step of 115.7 ms and the
    start of the next): the layer's products are ``ds_moe_gmm`` (eight whole
    calls: three forward, two replayed, three dx; two more of the next
    step's forward) and ``ds_moe_gmm_t`` (three), none is XLA's
    ``ragged-dot``, and they lie under ``ds.moe_experts`` -- a Pallas call
    keeps its path -- so ``moe.expert_share`` holds the whole layer where
    the older recording's (PR 27) held it less the products."""
    run = run_of(recording(RECORDING))
    r = scope_reduce.reduce(run["scope_trace"])
    assert {k: v["calls"] for k, v in r["by_kernel"].items()} == {
        "ds_flash_fwd": 2, "ds_flash_bwd_dq": 1, "ds_flash_bwd_dkv": 1,
        "ds_moe_gmm": 10, "ds_moe_gmm_t": 3}
    assert not instruction_times.by_instruction(run, "ragged-dot")
    gmm = r["by_kernel"]["ds_moe_gmm"]
    gmm_t = r["by_kernel"]["ds_moe_gmm_t"]
    assert 1.7 < 1e3 * gmm["s"] / gmm["calls"] < 1.9      # least: 1.395 ms
    assert 2.0 < 1e3 * gmm_t["s"] / gmm_t["calls"] < 2.1
    old = run_of(recording("scope_trace_train_olmoe_4k.json"))
    assert 8 < reader("moe.expert_share").read(old) < 12
    assert 27 < reader("moe.expert_share").read(run) < 30
    assert reader("train.unnamed_share").read(run) < 2 < \
        reader("train.unnamed_share").read(old)


def test_the_new_readers_on_the_recording_and_the_older_ones_silent():
    """The kernels' share of the busy time and of their roofline on one step
    of the chip's, and the two metrics of XLA's kernel reading nothing there
    (a traced run of the cell with the two entries laid over the benchmark
    read ``kernel.ds_moe_gmm.roofline_share`` 74.0 over a 5 s window where
    ``kernel.moe_gmm.roofline_share`` read 45.07 at the parent: my chip
    run, PR 50)."""
    run = run_of(recording(RECORDING))
    assert 18 < reader("moe.gmm_kernel_share").read(run) < 20
    assert 73 < reader("kernel.ds_moe_gmm.roofline_share").read(run) < 78
    assert reader("moe.grouped_matmul_share").read(run) is None
    assert reader("kernel.moe_gmm.roofline_share").read(run) is None


@pytest.mark.parametrize("metric", [
    m["name"] for m in common.load_benchmark()["per_layer"]
    if CELL in m["workloads"] and m["source"] == "device_trace"
    and m["name"] not in ("device.idle_share.train",
                          "train.host_gap_ms_per_step",
                          "moe.grouped_matmul_share",
                          "kernel.moe_gmm.roofline_share")]
    + list(READ_PR50_KERNELS))
def test_every_other_trace_reader_of_the_cell_reads_the_new_recording(metric):
    """The cell's trace-sourced metrics but the two that fell silent, and
    the two readers that wait for their entries: each finds something in a step with the kernels in, a share of at most 100."""
    value = reader(metric).read(run_of(recording(RECORDING)))
    assert value is not None and 0 <= value <= 100, (metric, value)
