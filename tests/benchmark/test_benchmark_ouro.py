"""The configuration ``ouro-2.6b`` and its cell ``ouro-2.6b.train.8k``: what
``BENCHMARK.json`` gained for them (found by name, never by position), the
file against the catalog row, parameters and required operations by hand, the
cell's correctness check at tiny size on one CPU device (passes over seeds;
every wrong computation of ``ouro_wrong.py`` fails it, the one that is no
wrong computation passes it), and the five readers the cell brings, on a
hand-made trace, on the cell's own recorded steps and on other programs'
recordings."""

import json
import os
import subprocess
import sys

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, ouro_costs
import ouro_wrong

CELL = "ouro-2.6b.train.8k"
NAME = "ouro-2.6b"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.looped", "train.loop_stack_share", "train.exit_gate_share",
       "loop.exit_step_mean", "loop.loss_last_over_first")
#: the readers other cells have too, which READ something on this one
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step",
          "kernel.flash_fwd.roofline_share")
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", f"{NAME}.json")


def sizes(**over):
    return {**common.sizes_of(config(), "train"), **over}


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_five_metrics():
    """One configuration, one cell on one chip under the traffic that
    stands, five per-layer metrics that list the cell alone, and the cell's
    name in ten lists that stood: the rate's and the nine shared readers'.
    ``train.mfu`` counts ONE walk down the stack and one head
    (``benchmark/flops.py``), ``kernel.flash_bwd.roofline_share`` reads
    nothing on any cell since PR 51 and ``train.unnamed_share``'s list is
    pinned by ``test_benchmark_step_names.py``: none lists the cell. Nothing
    here says the entries are the file's LAST: a later cell is appended
    behind them."""
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train.8k", 1)
    assert all(1 <= len(x["why"]) <= 200 for x in (entry, cell))
    assert len(bench["configs"]) >= 10 and len(bench["workloads"]) >= 10
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "train_tokens_per_s_per_chip"
    assert {n: by_name[n]["source"] for n in NEW} == {
        "train.mfu.looped": "host_clock",
        "train.loop_stack_share": "device_trace",
        "train.exit_gate_share": "device_trace",
        "loop.exit_step_mean": "program_counter",
        "loop.loss_last_over_first": "program_counter"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers
    lists = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert lists == set(NEW + SHARED + ("train_tokens_per_s_per_chip",))
    workload = common.load_json("workloads", f"{CELL}.json")
    assert "rate_metric" not in workload and "weight_seed" not in workload


def test_benchmark_names_only_files_that_exist():
    bench = common.load_benchmark()
    here = os.path.dirname(os.path.abspath(common.__file__))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert os.path.exists(os.path.join(common.ROOT, entry["file"]))
    for parts in (("workloads", f"{CELL}.json"), ("configs", f"{NAME}.json"),
                  ("traffic", "train.8k.json"), ("kinds", "train.py"),
                  ("reference", f"{config()['reference']}.py")):
        assert os.path.exists(os.path.join(here, *parts)), parts
    for name in NEW + SHARED:
        assert os.path.exists(os.path.join(here, "layer_metrics",
                                           f"{name}.py")), name


def test_the_reference_imports_nothing_of_the_models():
    """``benchmark/reference/ouro.py`` is independent of the code under
    test: plain ``jax.numpy`` and ``reference/dense.py``'s primitives."""
    here = os.path.dirname(os.path.abspath(common.__file__))
    for name in ("ouro.py", "dense.py"):
        text = open(os.path.join(here, "reference", name)).read()
        assert "deepspeed_tpu" not in text, name


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key; the depth alone
    differs, is listed with its arithmetic and its reading, and the
    published count stands beside it."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == sorted(file["reduced"]) == ["num_hidden_layers"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == differ
    assert entry["source"] == file["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert file["published"] == {"num_hidden_layers": 48}
    depth = file["num_hidden_layers"]
    assert depth["published"] == 48 and depth["train"] in (6, 8, 9)
    assert "memory_analysis()" in file["reduced"]["num_hidden_layers"]
    for key in ("sandwich_norms", "state_between_passes", "exit_gate",
                "exit_distribution", "exit_entropy_coef",
                "per_token_weighting", "rotary_positions", "head_dim",
                "weights", "report_loop"):
        assert key in file["assumed"], key
    assert "arXiv:2510.25741" in json.dumps(file["assumed"])
    assert "one TPU v5e chip" in file["deployment"]
    assert "pipeline" in file["deployment"]
    tiny = file["tiny"]
    assert tiny["total_ut_steps"] == 4
    assert tiny["num_hidden_layers"]["train"] >= 2


def test_model_is_built_from_the_file_and_the_workload():
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "OuroForCausalLM"
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps, cfg.tie_word_embeddings,
            cfg.sliding_window, cfg.max_position_embeddings,
            cfg.total_ut_steps, cfg.early_exit_threshold,
            cfg.exit_entropy_coef, cfg.report_loop, cfg.attention_impl,
            cfg.remat, cfg.remat_policy, cfg.scan_layers) == \
        (2048, 5632, 16, 16, 128, 49152, 1000000, 1e-6, False, None, 65536,
         4, 1, 0.1, True, "flash", True, "nothing", True)
    assert cfg.loss_chunk > 0
    assert cfg.num_hidden_layers == file["num_hidden_layers"]["train"]
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert wl["engine"]["zero_optimization"] == {"stage": 0}
    assert wl["engine"]["gradient_accumulation_steps"] == 1
    assert (wl["warmup_steps"], wl["kind"], wl["chips"], wl["depth"]) == \
        (3, "train", 1, "train")
    # steps of more than a second: a traced tail of 10 s holds the four
    # ``ds.counters`` events a gauge's mean needs
    assert wl["trace_seconds"] == 10


def parameters(**over):
    import jax
    import jax.numpy as jnp

    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(config(), sizes(**over), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_parameters_by_hand():
    """A layer 51.39 M, the two whole tables 201.3 M, the final norm and the
    gate's 2,049: at 18 B a parameter (fp32 master, two Adam moments, fp32
    gradient, the bf16 copy) 11.0 GB at eight layers and 11.95 at nine
    (ISSUE 56)."""
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    rest = 2 * 49152 * 2048 + 2048 + 2049
    want = lambda depth: depth * layer + rest
    assert [parameters(num_hidden_layers=d) for d in (6, 8, 9)] == \
        [want(6), want(8), want(9)] == [509661185, 612438017, 663826433]
    assert [round(want(d) * 18 / 1e9, 2) for d in (8, 9)] == [11.02, 11.95]
    assert parameters() == want(config()["num_hidden_layers"]["train"])
    # the passes share every weight: their number moves no count
    assert parameters(total_ut_steps=1) == parameters()


def test_a_token_needs_four_walks_and_four_heads():
    """ISSUE 56's arithmetic at eight layers: a layer application 102.8
    MFLOP of products and 33.6 of attention core at a mean of 4,096.5 keys;
    four passes of eight layers and four heads of 201.3: 5.17 GFLOP forward,
    15.5 forward and backward, the cores 21% and the heads 16% of it."""
    s = sizes(num_hidden_layers=8)
    parts = ouro_costs.forward_parts(s, 8192)
    layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    core = 2 * 2 * 16 * 128 * 4096.5
    want = {"layer_products": 4 * 8 * layer, "attention": 4 * 8 * core,
            "head": 4 * 2 * 2048 * 49152, "gate": 4 * 2 * 2048}
    assert parts == pytest.approx(want)
    assert flops.mean_attended_keys(8192) == 4096.5
    assert [round(x / 1e6, 1) for x in (layer, core)] == [102.8, 33.6]
    total = sum(parts.values())
    assert round(total / 1e9, 2) == 5.17
    assert ouro_costs.train_flops_per_token(s, 8192) == \
        pytest.approx(3 * total)
    assert round(3 * total / 1e9, 1) == 15.5
    assert round(100 * parts["attention"] / total) == 21
    assert round(100 * parts["head"] / total) == 16
    # one pass is the plain decoder ``flops.py`` counts, plus its gate
    one = ouro_costs.forward_parts({**s, "total_ut_steps": 1}, 8192)
    assert sum(one.values()) - one["gate"] == pytest.approx(
        flops.forward_flops_per_token(s, 8192))
    # nine layers: 17.1 GFLOP (ISSUE 56)
    assert round(ouro_costs.train_flops_per_token(
        sizes(num_hidden_layers=9), 8192) / 1e9, 1) == 17.1


def test_cost_readers_know_their_own_cells():
    assert ouro_costs.is_looped(sizes())
    for c in common.load_benchmark()["configs"]:
        if c["name"] != NAME:
            assert not ouro_costs.is_looped(common.sizes_of(
                common.load_json("configs", f"{c['name']}.json"), "train"))


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, four passes of two layers: the engine is the
    # reference to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-6


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, or the reference from float8."""
    ctx, kind = tiny_context(CELL, seed)
    how = ouro_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else ouro_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    *ouro_wrong.WRONG, "reference_fp8_e4m3", "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the loop left out or replaced, and the reference one
    precision down, is far outside a tolerance: what changes the states by
    the last pass's logits AND the loss, what changes the mixing of the
    passes' losses by the loss alone (the logits are the sound ones)."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    # the float8 references' loss is a signed mean of small errors (44 and
    # more times the limit at this size); every wrong formula moves it whole
    assert stats["loss_gap"] > (20 if name.startswith("reference_fp8")
                                else 50) * tol["loss_gap_tol"]
    if name in ouro_wrong.LOSS_ONLY:
        assert stats["verdicts"]["logit_rel_l2"]
    else:
        assert stats["logit_rel_l2"] > 50 * tol["logit_rel_l2_tol"]


@pytest.mark.parametrize("seed", [40, 41])
def test_positions_that_advance_by_pass_are_no_wrong_computation(seed):
    """ISSUE 56 lists it; rotary attention reads the DIFFERENCE of two
    positions, so a shift common to a pass's positions moves nothing but
    rounding: the check passes it, as it must."""
    assert set(ouro_wrong.NOT_WRONG) == {"positions_advance_by_pass"}
    ok, stats = wrong_check(seed, "positions_advance_by_pass")
    assert ok, stats
    assert stats["logit_rel_l2"] < 1e-4


@pytest.mark.parametrize("limit, sound, wrong", [
    # largest of 21 sound sets / uniform weights for the gate's, the nearest
    # of the four wrong computations that only the loss sees
    ("loss_gap_tol", 5.0347e-05, 3.3880e-03),
    # largest of 21 sound sets / rotation left out after the first pass, the
    # nearest of those that change a state (the float8 e5m2 reference: 0.575)
    ("logit_rel_l2_tol", 0.031078, 0.25938)])
def test_each_limit_of_the_timed_size_lies_between_its_two_chip_readings(
        limit, sound, wrong):
    """The cell file's ``check.why`` has where each reading came from (my
    chip runs, PR 56, calls 1 and 3): room on both sides, more above the
    sound readings than a fresh seed has ever taken."""
    tol = common.load_json("workloads", f"{CELL}.json")["check"][limit]
    assert 1.5 * sound < tol < wrong / 4


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(ouro_wrong.WRONG) == {
        "three_passes_for_four", "post_sublayer_norms_left_out",
        "state_not_normed_between_passes",
        "rotation_left_out_after_first_pass", "last_pass_loss_alone",
        "uniform_exit_weights", "entropy_left_out",
        "remainder_not_on_last_pass"}
    assert set(ouro_wrong.LOSS_ONLY) < set(ouro_wrong.WRONG)
    assert callable(ouro_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.ouro as ouro

    names = ("_run_passes", "_post_norm", "expected_loss",
             "exit_log_distribution")
    before = [ouro.__dict__[k] for k in names]
    for name in [*ouro_wrong.WRONG, *ouro_wrong.NOT_WRONG]:
        with ouro_wrong.wrong(name):
            assert sum(ouro.__dict__[k] is not v
                       for k, v in zip(names, before)) == 1, name
    assert all(ouro.__dict__[k] is v for k, v in zip(names, before))


def test_the_cells_rehearsal_on_the_cpu_passes():
    """``benchmark/run.py --rehearse-cpu`` end to end: exit code 1 (a
    rehearsal never 0), ``"rehearsal": "passed"``, the gauges' two metrics
    read from the ``ds.counters`` events, no device metric on a CPU."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=common.ROOT)
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 5), "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], cwd=common.ROOT, env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 1, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] == "passed"
    result = line["would_print"]
    assert result["correct"] and result["failed"] == 0
    metrics = result["metrics"]
    assert 1 < metrics["loop.exit_step_mean"]["value"] < 4
    assert 0.5 < metrics["loop.loss_last_over_first"]["value"] < 1.5
    assert "train.step_ms_p50" in metrics
    for name in ("train.mfu.looped", "train.loop_stack_share",
                 "train.exit_gate_share", "device.idle_share.train"):
        assert name not in metrics


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(OuroForCausalLM)/" \
    "ds.loop_stack/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(OuroForCausalLM))/" \
    "ds.loop_stack/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 500, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(OuroForCausalLM)/ds.embed/gather"],
        ["fusion.1", 1000, 1000, FWD + "loop/ds.layer_stack/layers/while/"
         "body/block/self_attn/ds.attn_proj/dot_general"],
        ["ds_flash_fwd", 2000, 3000, FWD + "loop/ds.layer_stack/layers/"
         "while/body/block/self_attn/ds.attention/pallas_call"],
        ["fusion.2", 5000, 2000,
         FWD + "loop/ds.lm_head_loss/while/body/checkpoint/dot_general"],
        ["fusion.3", 7000, 300, FWD + "loop/ds.exit_gate/dot_general"],
        ["fusion.4", 7500, 200, FWD + "stack"],
        ["fusion.5", 8000, 100, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(OuroForCausalLM)/ds.exit_gate/mul"],
        ["fusion.6", 9000, 400, BWD + "add_any"],
        ["fusion.7", 10000, 5000, BWD + "loop/ds.layer_stack/layers/while/"
         "body/checkpoint/rematted_computation/block/mlp/ds.mlp/dot"],
        ["fusion.9", 16000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.4", 30000, 1000, FWD + "stack"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"],
             *[["ds.counters", 1000 + 100 * i, 10,
                {"step": 10 + i, "loop_exit_step_mean": 2.0 + 0.1 * i,
                 "loop_exit_entropy": 1.0, "loop_loss_first": 11.0,
                 "loop_loss_last": 10.0 + 0.5 * i}, "python"]
               for i in range(5)]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_shares_of_the_loop_and_the_gate_on_a_hand_made_trace():
    """Busy 13,500 ns. ``ds.loop_stack`` is the INNERMOST scope of the
    stacking (200) and the gradients' sum (400) alone: everything a pass
    runs has a scope of its own inside it. ``ds.exit_gate``: the passes'
    gates (300) and the mixing outside the loop (100)."""
    run = run_of(HAND)
    assert reader("train.loop_stack_share").read(run) == \
        pytest.approx(100 * 600 / 13500)
    assert reader("train.exit_gate_share").read(run) == \
        pytest.approx(100 * 400 / 13500)
    assert reader("train.head_loss_share").read(run) == \
        pytest.approx(100 * 2000 / 13500)
    assert reader("loop.exit_step_mean").read(run) == pytest.approx(2.2)
    assert reader("loop.loss_last_over_first").read(run) == \
        pytest.approx(11.0 / 11.0)
    for name in NEW:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_gauges_need_four_events():
    few = {**HAND, "host": HAND["host"][:4]}
    run = run_of(few)
    assert reader("loop.exit_step_mean").read(run) is None
    assert reader("loop.loss_last_over_first").read(run) is None


def test_mfu_reader_counts_every_pass():
    run = run_of(None, tokens_per_s=6800.0, chips=1)
    per_token = ouro_costs.train_flops_per_token(sizes(), 8192)
    want = 100 * per_token * 6800.0 / 197e12
    assert reader("train.mfu.looped").read(run) == pytest.approx(want)
    assert 0 < want < 100
    # ``train.mfu``'s count is one walk and one head: a quarter of it
    assert per_token > 3.9 * flops.train_flops_per_token(sizes(), 8192) \
        * (1 - 0.01)
    assert reader("train.mfu.looped").read(
        {**run, "device": {"platform": "cpu"}}) is None


def recording(name):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other,fixture", [
    ("mistral-7b.train.8k", "scope_trace_train_8k.json"),
    ("olmoe-1b-7b.train.4k", "scope_trace_train_olmoe_4k.json"),
    ("kimi-vl-a3b.train.8k", "scope_trace_train_kimi_8k.json")])
def test_new_readers_find_nothing_in_another_program(name, other, fixture):
    """A program without a loop over passes (the other cells' recorded
    traces, as the parent commit runs them): None, no exception."""
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """Another program's trace under this cell's own name (the driver lays
    the benchmark's files over the parent's checkout): no ``ds.loop_stack``
    or ``ds.exit_gate`` scope and no ``loop_*`` counter, so those read
    None."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    for name in ("train.loop_stack_share", "train.exit_gate_share",
                 "loop.exit_step_mean", "loop.loss_last_over_first"):
        assert reader(name).read(run) is None


def test_flash_forward_roofline_counts_sixteen_heads_and_no_window():
    """The standing reader takes the cell unedited: 16 / 16 heads of 128,
    the whole causal triangle (``sliding_window`` null), one packed
    sequence; bound by operations."""
    s = sizes()
    cost = kernel_costs.flash_fwd(1, 8192, s["num_attention_heads"],
                                  s["num_key_value_heads"], s["head_dim"],
                                  s.get("sliding_window"))
    assert cost["flops"] == 4 * 128 * 16 * 8192 * 4096.5
    assert cost["bytes"] == 2 * 8192 * 128 * (2 * 16 + 2 * 16) + 4 * 16 * 8192
    assert kernel_costs.least_seconds(cost, TPU["kind"]) == \
        (pytest.approx(1.3955e-3, rel=1e-4), "flops")


def test_every_new_reader_reads_the_cells_own_recorded_steps():
    """A cut of the cell's traced run on the v5e from the committed files
    (PR 56, call 4: 5.2 s, four steps and parts of two more, five
    ``ds.counters`` events): nothing stands under ``ds.loop_stack`` but the
    readings' stacking -- the shared weights' gradient sums ride the
    optimizer's fusions -- and the gate is a hundredth of a percent."""
    run = run_of(recording("scope_trace_train_ouro_8k.json"),
                 tokens_per_s=6817.45, chips=1)
    got = {name: reader(name).read(run) for name in NEW}
    assert got["train.mfu.looped"] == pytest.approx(53.65, abs=0.01)
    assert got["train.loop_stack_share"] == pytest.approx(3.3e-4, rel=0.05)
    assert got["train.exit_gate_share"] == pytest.approx(0.0145, rel=0.02)
    assert got["loop.exit_step_mean"] == pytest.approx(2.672, abs=0.001)
    assert got["loop.loss_last_over_first"] == pytest.approx(0.9958,
                                                             abs=1e-4)
    # the shared readers the cell is listed under read it too
    for name, about in (("train.attention_share", 23.4),
                        ("train.attn_proj_share", 18.9),
                        ("train.head_loss_share", 14.6),
                        ("train.optimizer_share", 2.37),
                        ("train.recompute_share", 16.6),
                        ("train.host_gap_ms_per_step", 4.13),
                        ("kernel.flash_fwd.roofline_share", 46.87),
                        # read, not listed (its list is pinned elsewhere)
                        ("train.unnamed_share", 2.04)):
        assert reader(name).read(run) == pytest.approx(about, rel=0.02), name
    from benchmark import counters, scope_reduce
    assert [e["step"] for e in counters.events(run)] == [43, 44, 45, 46, 47]
    # four passes of eight layers: 32 forward calls a step
    r = scope_reduce.reduced(run)
    assert r["steps"] == 5 and r["by_kernel"]["ds_flash_fwd"]["calls"] == 160
