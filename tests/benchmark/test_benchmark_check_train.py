"""The train cells' correctness check at tiny size on the CPU: the engine
against the plain reference passes on 16 seeds, and each deliberately wrong
computation fails it — on the statistic that concentrates (relative L2 of
late-position logits), whatever the signed loss gap happens to be."""

import os

import pytest

from bench_helpers import train_check

DENSE, MOE = "mistral-7b.train.8k", "mixtral-8x7b.train.ep4"


@pytest.mark.parametrize("seed", [2 ** 31 + 11] + list(range(40, 55)))
def test_dense_engine_matches_reference(seed):
    ok, stats = train_check(DENSE, seed)
    assert ok, stats
    # measured over these seeds: loss_gap <= 3.9e-4, logit_rel_l2 <= 0.016
    assert stats["logit_rel_l2"] < 0.025 and stats["loss_gap"] < 1e-3


@pytest.mark.parametrize("seed", [40, 41, 42])
def test_window_mask_off_fails(seed):
    ok, stats = train_check(DENSE, seed, control="window_off")
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert stats["logit_rel_l2"] > 0.3          # ~0.7 against ~0.016


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42])
def test_moe_engine_matches_reference_on_four_devices(seed):
    ok, stats = train_check(MOE, seed)
    assert ok, stats
    # float32 at tiny size: the reference's routing, renormalised weights
    # and load-balancing loss are the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-4 and stats["loss_gap"] < 1e-5


@pytest.mark.parametrize("seed", [40, 41])
def test_top1_routing_fails(seed):
    ok, stats = train_check(MOE, seed, control="top1_routing")
    assert not ok and stats["logit_rel_l2"] > 0.05


# -- whose weights a cell is measured on (``weight_seed``, PR 46) -------------

KEYE = "keye-vl2-30b-a3b.train.16k"


def cells():
    from benchmark import common

    return [w["name"] for w in common.load_benchmark()["workloads"]]


def engine_and_batches(seed, weight_seed):
    """The dense cell's tiny engine for ``--seed seed`` with the workload
    file's copy stating ``weight_seed`` (None: not stated), the check's
    batch and the window's first."""
    import jax
    import numpy as np

    from bench_helpers import tiny_context
    from benchmark.traffic import generator

    ctx, kind = tiny_context(DENSE, seed)
    assert "weight_seed" not in ctx["workload"]
    if weight_seed is not None:
        ctx["workload"] = {**ctx["workload"], "weight_seed": weight_seed}
    engine = kind.build_engine(ctx, ctx["sizes"])
    leaves = [np.asarray(x) for x in
              jax.tree_util.tree_leaves(engine.state.params)]
    batches = [generator.packed_batch(
        ctx["mix"], ctx["seed"], step, ctx["sizes"]["vocab_size"],
        ctx["cell"]["chips"])["input_ids"] for step in (-1, 0)]
    return leaves, batches


@pytest.mark.parametrize("weight_seed,same", [(7, True), (2 ** 31 + 5, True),
                                              (None, False)])
def test_weight_seed_fixes_the_weights_and_leaves_the_data(weight_seed, same):
    """With the key two runs of different ``--seed`` start from bit-equal
    parameters and draw different batches; without it the parameters follow
    ``--seed`` as they always did."""
    import numpy as np

    a, batches_a = engine_and_batches(40, weight_seed)
    b, batches_b = engine_and_batches(41, weight_seed)
    equal = all(np.array_equal(x, y) for x, y in zip(a, b))
    assert len(a) == len(b) and equal == same
    for x, y in zip(batches_a, batches_b):
        assert x.shape == y.shape and not np.array_equal(x, y)


def test_weight_seed_is_the_files_value_whatever_the_run_seed():
    """At the cell's own size the engine's seed is the file's, for every
    ``--seed``; the key is a whole number the engine's 31 bits hold."""
    from benchmark import common, run as bench_run

    kind = common.load_file_module("kinds", "train")
    bench = common.load_benchmark()
    stated = common.load_json("workloads", f"{KEYE}.json")["weight_seed"]
    assert isinstance(stated, int) and 0 <= stated < 2 ** 31
    for seed in (0, 7, 2 ** 31 + 11):
        assert kind.weight_seed(bench_run.context(bench, KEYE, seed)) == stated


def test_only_keye_16k_states_a_weight_seed():
    """The key is data of the one cell whose step follows a frozen seeded
    softmax router's load; no other workload file states it, at its own
    size or under ``tiny``."""
    import glob
    import json
    import os

    from benchmark import common

    stating = []
    for path in sorted(glob.glob(os.path.join(common.HERE, "workloads",
                                              "*.json"))):
        wl = json.load(open(path))
        if "weight_seed" in wl:
            stating.append(os.path.basename(path))
        if os.path.basename(path) != f"{KEYE}.json":
            assert "weight_seed" not in wl.get("tiny", {}), path
    assert stating == [f"{KEYE}.json"]
    assert common.load_json("workloads", f"{KEYE}.json")["tiny"][
        "weight_seed"] is None


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("seed", [41, 2 ** 31 + 11])
def test_every_tiny_context_draws_its_weights_from_the_run_seed(cell, seed):
    """Under ``tiny`` (every CPU test and rehearsal) "passes over seeds"
    keeps meaning weights as well as data, in every cell of BENCHMARK.json."""
    from bench_helpers import tiny_context

    ctx, kind = tiny_context(cell, seed)
    assert kind.weight_seed(ctx) == seed


# -- under which name a cell reports its rate (``rate_metric``, PR 46) --------

RATE = "train_tokens_per_s_per_chip"


def split_readers():
    from benchmark import common

    return [m["name"] for m in common.load_benchmark()["per_layer"]
            if m["name"].endswith(".trajectory")]


def test_only_keye_16k_states_a_rate_metric():
    """A bound belongs to a metric, not a cell: the one cell whose rate
    follows its own training trajectory reports the same quantity under a
    name with a bound of its own; the six others stay under the 1% bound."""
    import glob
    import json
    import os

    from benchmark import common

    kind = common.load_file_module("kinds", "train")
    assert kind.rate_metric({"workload": {}}) == RATE
    stating = {os.path.basename(p): json.load(open(p)).get("rate_metric")
               for p in glob.glob(os.path.join(common.HERE, "workloads",
                                               "*.json"))}
    assert {k: v for k, v in stating.items() if v} == {
        f"{KEYE}.json": f"{RATE}.trajectory"}
    e2e = {m["name"]: m for m in common.load_benchmark()["end_to_end"]}
    assert e2e[f"{RATE}.trajectory"]["workloads"] == [KEYE]
    assert e2e[RATE]["bound"] == 0.01 and KEYE not in e2e[RATE]["workloads"]
    assert 0.05 < e2e[f"{RATE}.trajectory"]["bound"] <= 0.1
    same = ("unit", "better", "source")
    assert [e2e[RATE][k] for k in same] == \
        [e2e[f"{RATE}.trajectory"][k] for k in same]


@pytest.mark.parametrize("cell", cells())
def test_every_cell_reports_one_rate_and_its_layers_move_it(cell):
    """Besides ``setup_s`` a training cell is listed by exactly one
    end-to-end metric, the one its runner reports its rate under (at its own
    size and under ``tiny``), and every per-layer metric that lists the cell
    moves that one."""
    from bench_helpers import tiny_context
    from benchmark import common, run as bench_run

    bench = common.load_benchmark()
    listing = [m["name"] for m in bench["end_to_end"]
               if m["name"] != "setup_s" and cell in m.get("workloads", [])]
    ctx, kind = tiny_context(cell, 41)
    assert listing == [kind.rate_metric(ctx)]
    assert listing == [kind.rate_metric(bench_run.context(bench, cell, 41))]
    moved = {m["moves"] for m in bench["per_layer"]
             if cell in m.get("workloads", [])}
    assert moved == set(listing)


@pytest.mark.parametrize("name", split_readers())
def test_a_split_reader_is_the_shared_reader_under_another_name(name):
    """``<metric>.trajectory`` runs ``<metric>``'s own ``read`` (no second
    arithmetic), its entry differs from the shared one in its name, what it
    moves and its one cell, and the shared entry no longer lists that cell."""
    from benchmark import common

    base = name[:-len(".trajectory")]
    split = common.load_file_module("layer_metrics", name)
    assert split.read.__code__.co_filename.endswith(
        os.path.join("layer_metrics", f"{base}.py"))
    listed = {m["name"]: m for m in common.load_benchmark()["per_layer"]}
    keys = ("unit", "better", "source", "layer")
    assert [listed[name][k] for k in keys] == [listed[base][k] for k in keys]
    assert listed[name]["moves"] == f"{RATE}.trajectory"
    assert listed[name]["workloads"] == [KEYE]
    assert KEYE not in listed[base]["workloads"]
    run = {"observed": {"kind": "train", "fence_ms": [3.0, 1.0, 2.0]},
           "cell": {"name": KEYE}, "trace": None, "scope_trace": None,
           "counters": None}
    if base == "train.step_ms_p50":
        assert split.read(run) == 2.0
