"""The configuration ``sdar-30b-a3b`` and its cell ``sdar-30b-a3b.train.8k``:
what ``BENCHMARK.json`` gained for them (found by name, never by position),
the file against the catalog row, parameters and required operations by hand
and pair by pair, the cell's correctness check at tiny size on one CPU device
(passes over seeds; every wrong computation of ``sdar_wrong.py`` fails it),
and the six readers the cell brings, on a hand-made trace, on the cell's own
recorded steps and on other programs' recordings."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, kernel_costs, sdar_costs
import sdar_wrong

CELL = "sdar-30b-a3b.train.8k"
NAME = "sdar-30b-a3b"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.bd_moe", "kernel.flash_bd_fwd.roofline_share",
       "kernel.flash_bd_bwd.roofline_share", "train.bd_noise_share",
       "bd.kept_tile_share", "bd.masked_share")
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", f"{NAME}.json")


def workload():
    return common.load_json("workloads", f"{CELL}.json")


def sizes(**over):
    return {**common.sizes_of(config(), "train"), **over}


def rate():
    """The end-to-end name the cell's rate is reported under."""
    return workload().get("rate_metric", "train_tokens_per_s_per_chip")


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_six_metrics():
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["source"], entry["file"], entry["reduced"]) == (
        SOURCE, f"benchmark/configs/{NAME}.json",
        ["num_hidden_layers", "num_experts", "vocab_size"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train.8k", 1)
    assert all(1 <= len(x["why"]) <= 200 for x in (entry, cell))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
    assert {n: by_name[n]["source"] for n in NEW} == {
        "train.mfu.bd_moe": "host_clock",
        "kernel.flash_bd_fwd.roofline_share": "device_trace",
        "kernel.flash_bd_bwd.roofline_share": "device_trace",
        "train.bd_noise_share": "device_trace",
        "bd.kept_tile_share": "program_counter",
        "bd.masked_share": "program_counter"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers


def test_every_metric_that_lists_the_cell_moves_the_rate_it_reports():
    """The cell reports ONE rate metric (its file's ``rate_metric``, else the
    plain rate): the end-to-end list it stands in is that metric's, and every
    per-layer entry that lists it ``moves`` that metric -- the dead readers
    and the lists a test pins to its recordings do not list it."""
    bench = common.load_benchmark()
    ends = [m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", ())]
    assert ends == [rate()]
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert set(NEW) < {m["name"] for m in listed}
    assert len(listed) >= len(NEW) + 10
    for m in listed:
        assert m["moves"] == rate(), m["name"]
    assert not {m["name"] for m in listed} & {
        "kernel.flash_bwd.roofline_share", "moe.grouped_matmul_share",
        "moe.grouped_matmul_share.trajectory", "kernel.moe_gmm.roofline_share",
        "train.unnamed_share", "train.mfu"}


def twins():
    return [m["name"] for m in common.load_benchmark()["per_layer"]
            if m["name"].endswith(".trajectory")]


@pytest.mark.parametrize("name", twins())
def test_a_twin_is_the_shared_reader_for_the_cells_that_state_its_rate(name):
    """What ``test_benchmark_check_train.py``'s standing test of the twins
    holds them to, with the part this cell ended -- a twin's list is keye 16k
    ALONE -- asserted by its meaning: ``<metric>.trajectory`` runs
    ``<metric>``'s own ``read`` (no second arithmetic); its entry is the
    shared one's but for its name, what it moves and its list; its list holds
    cells whose files state ``rate_metric`` and no other, and the shared
    entry lists none of them. The standing test is an expected failure for
    the twelve twins this cell joined (``tests/conftest.py``): these cases
    keep its other assertions on, for keye 16k and for this cell."""
    base = name[:-len(".trajectory")]
    split = reader(name)
    assert split.read.__code__.co_filename.endswith(
        os.path.join("layer_metrics", f"{base}.py"))
    listed = {m["name"]: m for m in common.load_benchmark()["per_layer"]}
    keys = ("unit", "better", "source", "layer")
    assert [listed[name][k] for k in keys] == [listed[base][k] for k in keys]
    stating = [w["name"] for w in common.load_benchmark()["workloads"]
               if common.load_json("workloads", f"{w['name']}.json").get(
                   "rate_metric") == "train_tokens_per_s_per_chip.trajectory"]
    assert stating == ["keye-vl2-30b-a3b.train.16k", CELL]
    assert listed[name]["moves"] == "train_tokens_per_s_per_chip.trajectory"
    # the dead reader's twin reads nothing and stays keye's (ISSUE 58)
    dead = name == "moe.grouped_matmul_share.trajectory"
    assert listed[name]["workloads"] == stating[:1 if dead else 2]
    assert not set(stating) & set(listed[base]["workloads"])
    for cell in listed[name]["workloads"]:
        run = {"observed": {"kind": "train", "fence_ms": [3.0, 1.0, 2.0]},
               "cell": {"name": cell}, "trace": None, "scope_trace": None,
               "counters": None}
        if base == "train.step_ms_p50":
            assert split.read(run) == 2.0
        else:
            assert split.read(run) is None


def test_benchmark_names_only_files_that_exist():
    bench = common.load_benchmark()
    here = os.path.dirname(os.path.abspath(common.__file__))
    for parts in (("workloads", f"{CELL}.json"), ("configs", f"{NAME}.json"),
                  ("traffic", "train.8k.json"), ("kinds", "train.py"),
                  ("reference", f"{config()['reference']}.py")):
        assert os.path.exists(os.path.join(here, *parts)), parts
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.exists(os.path.join(
                here, "layer_metrics", f"{m['name']}.py")), m["name"]


def test_the_reference_imports_nothing_of_the_models():
    here = os.path.dirname(os.path.abspath(common.__file__))
    for name in ("sdar.py", "keye_vl2.py", "dense.py"):
        text = open(os.path.join(here, "reference", name)).read()
        assert "deepspeed_tpu" not in text, name


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(file["reduced"]) == differ
    assert file["published"] == {k: published[k] for k in differ}
    assert file["source"] == SOURCE
    assert file["num_hidden_layers"]["train"] >= 5
    assert (file["num_experts"], file["num_local_experts"],
            file["router_experts"], file["first_expert"]) == (16, 16, 128, 0)
    assert file["vocab_size"] == 151936 // 8
    # one value in use is no option: the model has a field for none of them
    assert not {"mask_token_id", "noise_eps", "noise_seed"} & set(file)
    assert file["router_trainable"] is False
    for key in ("block_length", "noise_schedule", "noise_key",
                "mask_token_id", "loss_normalisation", "no_shift",
                "training_pass", "router_trainable", "label_free_forward"):
        assert len(file["assumed"][key]) > 40, key
    assert "eight" in file["deployment"]


def test_model_is_built_from_the_file_and_the_workload():
    file, wl = config(), workload()
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "SdarForCausalLM"
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.expert_width,
            cfg.num_local_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.norm_topk_prob, cfg.vocab_size,
            cfg.rope_theta, cfg.rms_norm_eps, cfg.qk_norm_per_head,
            cfg.router_trainable, cfg.router_aux_loss_coef,
            cfg.block_length, cfg.attention_impl, cfg.report_expert_load,
            cfg.sliding_window, cfg.tie_word_embeddings) == \
        (2048, 32, 4, 128, 768, 16, 128, 0, 8, True, 18992, 1000000, 1e-6,
         True, False, 0.0, 4, "flash", True, None, False)
    assert cfg.loss_chunk > 0
    import dataclasses
    from deepspeed_tpu.models.mixtral import MixtralConfig
    assert {f.name for f in dataclasses.fields(cfg)} - {
        f.name for f in dataclasses.fields(MixtralConfig)} == {"block_length"}
    assert cfg.num_hidden_layers == file["num_hidden_layers"]["train"]
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert wl["engine"]["zero_optimization"] == {"stage": 0}
    # eight steps on the check's repeated batch, not the other cells' four:
    # a batch ONE token of weight 1 / t near 1,000 dominates spends its
    # first steps on that token (PERF.md section 6, PR 58)
    assert (wl["warmup_steps"], wl["kind"], wl["chips"], wl["depth"]) == \
        (7, "train", 1, "train")
    assert isinstance(wl["weight_seed"], int)
    assert wl["tiny"]["weight_seed"] is None


def test_parameters_are_the_issues_count():
    """A layer 94.6 M (attention 18.87 M, router 0.26 M, two norms, 16 held
    experts of 4.72 M), the two sliced tables 77.8 M: 645.6 M at six layers,
    11.6 GB at 18 B a parameter (ISSUE 58)."""
    import jax
    import jax.numpy as jnp

    _, model = common.build_model(config(), sizes(num_hidden_layers=6),
                                  **workload()["model"])
    ids = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, labels=ids))["params"]
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2048 * 128 \
        + 2 * 2048 + 16 * 3 * 2048 * 768
    assert count == 6 * layer + 2 * 18992 * 2048 + 2048 == 645623296
    assert round(layer / 1e6, 1) == 94.6 and round(count / 1e6, 1) == 645.6
    assert round(count * 18 / 1e9, 1) == 11.6


def test_required_operations_are_the_issues_and_the_pairs_counted_one_by_one():
    """ISSUE 58's arithmetic at six layers, a TRAINED token, forward: a
    layer's projections 2 x 37.7 MFLOP, the core 2 x 67.1 (a mean of 4,098
    kept keys a position), router and one held expert 2 x 9.96; six layers
    1,378, the head once 77.8: attention 55%."""
    s = sizes(num_hidden_layers=6)
    parts = sdar_costs.forward_parts(s, 8192)
    proj = 2 * 2048 * 128 * (32 + 4 + 4 + 32)
    core = 2 * 2 * 32 * 128 * 4098
    moe = 2 * 2048 * 128 + 3 * 2 * 2048 * 768
    assert parts == pytest.approx({
        "attn_proj": 6 * 2 * proj, "attention": 6 * 2 * core,
        "router": 6 * 2 * 2 * 2048 * 128,
        "held_experts": 6 * 2 * 3 * 2 * 2048 * 768,
        "head": 2 * 2048 * 18992})
    assert [round(x / 1e6, 1) for x in (proj, core, moe)] == [37.7, 67.1, 10.0]
    total = sum(parts.values())
    assert round((total - parts["head"]) / 1e6) == 1378
    assert round(parts["head"] / 1e6, 1) == 77.8
    assert round(100 * parts["attention"] / total) == 55
    assert sdar_costs.train_flops_per_token(s, 8192) == pytest.approx(
        3 * total)
    # the kept pairs against the rule as the reference writes it, pair by
    # pair, at sizes a CPU counts
    ref = common.load_file_module("reference", "sdar")
    for length, block in ((64, 4), (96, 8), (512, 4), (640, 128)):
        q = np.arange(2 * length)
        seen = ref.sees(q[:, None], q[None, :], length, block)
        assert int(seen.sum()) == sdar_costs.kept_pairs(length, block)
    assert sdar_costs.kept_pairs(8192, 4) == 67141632
    fwd = sdar_costs.flash_bd_fwd(1, 8192, 32, 4, 128, 4)
    assert fwd["flops"] == 4 * 128 * 32 * 67141632
    assert fwd["bytes"] == 2 * 16384 * 128 * (64 + 8) + 4 * 32 * 16384
    assert kernel_costs.least_seconds(fwd, TPU["kind"]) == \
        (pytest.approx(5.584e-3, rel=1e-3), "flops")
    bwd = sdar_costs.flash_bd_bwd(1, 8192, 32, 4, 128, 4)
    assert bwd["flops"] == 2.5 * fwd["flops"]


def test_cost_readers_know_their_own_cells():
    assert sdar_costs.is_bd(sizes())
    for c in common.load_benchmark()["configs"]:
        if c["name"] != NAME:
            assert not sdar_costs.is_bd(common.sizes_of(
                common.load_json("configs", f"{c['name']}.json"), "train"))


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-6


def wrong_check(seed, name):
    ctx, kind = tiny_context(CELL, seed)
    how = sdar_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else sdar_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    *sdar_wrong.WRONG, "reference_fp8_e4m3", "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the training pass left out or replaced, and the
    reference one precision down, is outside a tolerance: the label-free
    logits are the training pass's own, so they see the rule, the positions
    and the stack; the loss's two wrong weightings show in the loss alone."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    assert stats["loss_gap"] > 10 * tol["loss_gap_tol"]
    if name in sdar_wrong.LOSS_ONLY:
        assert stats["verdicts"]["logit_rel_l2"]
    else:
        assert stats["logit_rel_l2"] > 20 * tol["logit_rel_l2_tol"]


@pytest.mark.parametrize("limit, sound, wrong", [
    # largest of 48 sound readings / 1 / t left out, the nearer of the two
    # wrong weightings of the loss, which only this limit refuses
    ("loss_gap_tol", 1.288e-04, 0.4865),
    # largest of 16 sound readings over all 8,192 rows / the q/k norm left
    # out, the nearest wrong computation the limit refuses on every batch
    # (the leak reads 0.0193 to 0.0304: the nearest miss, check.why)
    ("logit_rel_l2_tol", 0.01603, 0.0681)])
def test_each_limit_of_the_timed_size_lies_between_its_two_chip_readings(
        limit, sound, wrong):
    """The cell file's ``check.why`` has where each reading came from (my
    chip runs, PR 58, calls 1 to 7) and which wrong computation no limit
    with room refuses at seeded weights: room on both sides."""
    tol = workload()["check"][limit]
    assert 1.5 * sound < tol < wrong / 1.5
    assert workload()["check"]["probe_positions"] == 8192   # every row


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(sdar_wrong.WRONG) == {
        "noised_block_sees_its_clean_block", "causal_inside_a_block",
        "positions_run_on_to_2L", "one_over_t_left_out",
        "loss_over_all_noised_rows", "head_norm_left_out"}
    assert set(sdar_wrong.LOSS_ONLY) < set(sdar_wrong.WRONG)
    assert callable(sdar_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.llama as llama
    import deepspeed_tpu.models.sdar as sdar

    from deepspeed_tpu.ops.pallas.flash_attention import BlockDiffusion

    names = [(BlockDiffusion, "sees"), (sdar, "doubled_positions"),
             (sdar, "loss_weights"), (llama, "RMSNorm")]
    before = [m.__dict__[k] for m, k in names]
    for name in sdar_wrong.WRONG:
        with sdar_wrong.wrong(name):
            assert sum(m.__dict__[k] is not v
                       for (m, k), v in zip(names, before)) == 1, name
    assert all(m.__dict__[k] is v for (m, k), v in zip(names, before))
    assert BlockDiffusion.tiles is not sdar_wrong._tiles_of_sees


def test_a_wrong_rules_table_is_its_own_answer_tile_by_tile():
    """The leak keeps one more diagonal of tiles in the noised-clean
    quadrant cut, nothing else moves: 2 x 1,024 positions in tiles of 512."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _CUT, _INSIDE, BlockDiffusion, _tile_table)

    rule = BlockDiffusion(1024, 4)
    sound = _tile_table(2048, 2048, 512, 512, True, rule)
    q = np.arange(2048)
    with sdar_wrong.wrong("noised_block_sees_its_clean_block"):
        leak = _tile_table(2048, 2048, 512, 512, True, rule)
        pairs = int(rule.sees(q[:, None], q[None]).sum())
    assert sound.shape == leak.shape == (3, 8)
    np.testing.assert_array_equal(sound, leak)      # the same tiles, cut
    assert pairs == sdar_costs.kept_pairs(1024, 4) + 1024 * 4
    assert int(((sound[2] & _INSIDE) != 0).sum()) == 2
    assert int(((sound[2] & _CUT) != 0).sum()) == 6


def test_the_cells_rehearsal_on_the_cpu_passes():
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
    assert 40 < metrics["bd.masked_share"]["value"] < 60
    assert metrics["bd.kept_tile_share"]["value"] == 100.0  # one tile of 128
    for name in ("train.mfu.bd_moe", "kernel.flash_bd_fwd.roofline_share",
                 "train.bd_noise_share", "device.idle_share.train"):
        assert name not in metrics


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(SdarForCausalLM)/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(SdarForCausalLM))/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 300, FWD + "ds.bd_noise/threefry2x32"],
        ["fusion.1", 400, 100, FWD + "ds.bd_noise/concatenate"],
        ["fusion.2", 1000, 500, FWD + "model/ds.embed/gather"],
        ["ds_flash_fwd", 2000, 3000, FWD + "model/ds.layer_stack/layers/"
         "while/body/block/self_attn/ds.attention/pallas_call"],
        ["fusion.3", 5000, 50, FWD + "ds.bd_gather/slice"],
        ["fusion.4", 6000, 2000, FWD + "ds.lm_head_loss/while/body/"
         "checkpoint/dot_general"],
        ["fusion.5", 8000, 150, BWD + "ds.bd_gather/pad"],
        ["ds_flash_bwd", 9000, 6000, BWD + "model/ds.layer_stack/layers/"
         "while/body/block/self_attn/ds.attention/pallas_call"],
        ["fusion.9", 16000, 900, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.0", 30000, 1000, FWD + "ds.bd_noise/threefry2x32"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"],
             *[["ds.counters", 1000 + 100 * i, 10,
                {"step": 10 + i, "bd_masked_share": 0.5 + 0.01 * i,
                 "bd_kept_tile_share": 0.28125, "bd_loss_weight_mean": 1.0},
                "python"] for i in range(5)]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_readers_on_a_hand_made_trace():
    """Busy 13,000 ns; ``ds.bd_noise`` 400 and ``ds.bd_gather`` 200 of it.
    The kernels' shares: the least time of the KEPT pairs over the time a
    call took (3 us and 6 us here, so far over 100: the count, not the
    reading, is what is checked)."""
    run = run_of(HAND)
    assert reader("train.bd_noise_share").read(run) == \
        pytest.approx(100 * 600 / 13000)
    assert reader("bd.masked_share").read(run) == pytest.approx(52.0)
    assert reader("bd.kept_tile_share").read(run) == pytest.approx(28.125)
    least = 4 * 128 * 32 * 67141632 / 197e12
    assert reader("kernel.flash_bd_fwd.roofline_share").read(run) == \
        pytest.approx(100 * least / 3e-6, rel=1e-6)
    assert reader("kernel.flash_bd_bwd.roofline_share").read(run) == \
        pytest.approx(100 * 2.5 * least / 6e-6, rel=1e-6)
    for name in NEW:
        assert reader(name).read(run_of(HAND, kind="serve")) is None
    few = {**HAND, "host": HAND["host"][:4]}
    assert reader("bd.masked_share").read(run_of(few)) is None


def test_mfu_reader_counts_a_trained_token():
    run = run_of(None, tokens_per_s=10000.0, chips=1)
    per_token = sdar_costs.train_flops_per_token(sizes(), 8192)
    want = 100 * per_token * 10000.0 / 197e12
    assert reader("train.mfu.bd_moe").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("train.mfu.bd_moe").read(
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
    ("keye-vl2-30b-a3b.train.16k", "scope_trace_train_keye_16k.json")])
def test_new_readers_find_nothing_in_another_program(name, other, fixture):
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """Another program's trace under this cell's own name (the driver lays
    the benchmark's files over the parent's checkout): no ``ds.bd_*`` scope
    and no ``bd_*`` counter, so those read None and nothing raises."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    for name in ("train.bd_noise_share", "bd.kept_tile_share",
                 "bd.masked_share"):
        assert reader(name).read(run) is None


def test_every_new_reader_reads_the_cells_own_recorded_steps():
    """A cut of the cell's traced run on the v5e (PR 58, call 1, weight seed
    5: 3.2 s, five steps and parts of two more, four ``ds.counters``
    events): the two kernels under the rule stand far under the causal
    cells' shares -- a cut tile's mask divides two 512 x 512 iotas by the
    block length (PERF.md section 7) -- and the noise is nothing."""
    run = run_of(recording("scope_trace_train_sdar_8k.json"),
                 tokens_per_s=13215.0, chips=1)
    got = {name: reader(name).read(run) for name in NEW}
    assert got["train.mfu.bd_moe"] == pytest.approx(29.30, abs=0.01)
    assert got["kernel.flash_bd_fwd.roofline_share"] == \
        pytest.approx(38.69, abs=0.01)
    assert got["kernel.flash_bd_bwd.roofline_share"] == \
        pytest.approx(60.62, abs=0.01)
    assert got["train.bd_noise_share"] == pytest.approx(0.0034, rel=0.05)
    assert got["bd.kept_tile_share"] == 28.125
    assert got["bd.masked_share"] == pytest.approx(49.43, abs=0.01)
    # the shared readers the cell is listed under read it too
    for name, about in (("train.attention_share", 40.4),
                        ("train.attn_proj_share", 18.9),
                        ("train.head_loss_share", 2.65),
                        ("train.optimizer_share", 3.89),
                        ("train.recompute_share", 9.84),
                        ("train.host_gap_ms_per_step", 2.43),
                        ("moe.expert_share", 23.7),
                        ("moe.router_share", 1.06),
                        ("moe.compact_hit_share", 100.0),
                        ("moe.rows_max_over_mean", 4.27),
                        ("moe.held_rows_over_expected", 0.797)):
        assert reader(name).read(run) == pytest.approx(about, rel=0.02), name
    from benchmark import counters, scope_reduce
    assert [e["step"] for e in counters.events(run)] == [76, 77, 78, 79]
    # one forward and one fused backward call a layer: no ``_dq`` / ``_dkv``
    kernels = scope_reduce.reduced(run)["by_kernel"]
    assert (kernels["ds_flash_fwd"]["calls"],
            kernels["ds_flash_bwd"]["calls"]) == (35, 30)
    assert not {"ds_flash_bwd_dq", "ds_flash_bwd_dkv"} & set(kernels)
