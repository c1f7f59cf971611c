"""The configuration ``kimi-vl-a3b`` and its cell ``kimi-vl-a3b.train.8k``:
the file against the catalog row, required operations and the kernels'
costs by hand, the cell's correctness check at tiny size on one CPU device
(passes over seeds; every wrong computation PERF.md lists fails it), and
the readers the cell brings, on a hand-made trace and on a cut of a real
chip trace of the cell."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, mla_costs, scope_reduce
import kimi_vl_wrong

CELL = "kimi-vl-a3b.train.8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.mla_moe", "kernel.flash_mla_fwd.roofline_share",
       "kernel.flash_mla_bwd.roofline_share", "moe.shared_expert_share",
       "train.attn_proj_share")


def reader(name):
    return common.load_file_module("layer_metrics", name)


def sizes():
    return common.sizes_of(common.load_json("configs", "kimi-vl-a3b.json"),
                           "train")


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config`` (the ``text_config`` of the source's
    ``config.json``), each under its own key; the three cuts differ, are
    listed with their arithmetic, and the published counts stand beside."""
    published = {
        "vocab_size": 163840, "max_position_embeddings": 131072,
        "hidden_size": 2048, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "num_hidden_layers": 27,
        "num_attention_heads": 16, "n_shared_experts": 2,
        "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
        "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
        "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "seq_aux": True,
        "num_key_value_heads": 16, "hidden_act": "silu",
        "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False}
    config = common.load_json("configs", "kimi-vl-a3b.json")
    differ = sorted(k for k, v in published.items()
                    if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == "kimi-vl-a3b")
    assert sorted(entry["reduced"]) == differ
    assert config["published"] == {k: published[k] for k in differ}
    assert config["num_hidden_layers"] == {"published": 27, "train": 6}
    assert (config["n_routed_experts"], config["router_experts"],
            config["vocab_size"] * 8) == (8, 64, 163840)
    cfg, _ = common.build_model(config, common.sizes_of(config, "train"))
    assert (cfg.n_routed_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.scoring_func, cfg.topk_method,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.first_k_dense_replace,
            cfg.num_hidden_layers, cfg.report_expert_load,
            cfg.router_trainable, cfg.router_bias_update_rate) == \
        (8, 64, 0, 6, "sigmoid", "noaux_tc", 192, 128, 1, 6, True, False,
         0.03)


def test_parameters_are_669_million():
    """83.0 M dense layer + 5 x (13.8 + 17.3 + 0.13 + 8 x 8.65 M) + 2 x
    41.9 M, as the file's ``reduced`` says: 10.7 GB at 16 B each."""
    import jax
    import jax.numpy as jnp

    config = common.load_json("configs", "kimi-vl-a3b.json")
    _, model = common.build_model(config, common.sizes_of(config, "train"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    attn = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    layer = attn + 2 * 2048
    dense = layer + 3 * 2048 * 11264
    moe = layer + 3 * 2048 * 2816 + 2048 * 64 + 64 + 8 * 3 * 2048 * 1408
    want = dense + 5 * moe + 2 * 20480 * 2048 + 2048
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == want
    assert round(want / 1e6) == 669 and round(want * 16 / 1e9, 1) == 10.7


def test_a_token_needs_878_mflop_forward_and_where():
    parts = mla_costs.forward_parts(sizes(), 8192)
    keys = (8192 + 1) / 2
    want = {
        "attn_proj": 6 * 2 * (2048 * 3072 + 2048 * 576 + 512 * 4096
                              + 2048 * 2048),
        "attention": 6 * 2 * 16 * (192 + 128) * keys,
        "dense_mlp": 3 * 2 * 2048 * 11264,
        "router": 5 * 2 * 2048 * 64,
        "shared_experts": 5 * 3 * 2 * 2048 * 2816,
        "held_experts": 5 * (6 * 8 / 64) * 3 * 2 * 2048 * 1408,
        "head": 2 * 2048 * 20480}
    assert parts == pytest.approx(want)
    total = sum(parts.values())
    assert round(total / 1e6) == 878
    assert {k: round(v / 1e6) for k, v in parts.items()} == {
        "attn_proj": 165, "attention": 252, "dense_mlp": 138, "router": 1,
        "shared_experts": 173, "held_experts": 65, "head": 84}
    assert mla_costs.train_flops_per_token(sizes(), 8192) == \
        pytest.approx(3 * total)
    # what benchmark/flops.py would count of this file (a GQA layer, no
    # expert) is read by no metric the cell lists
    assert flops.forward_flops_per_token(sizes(), 8192) != \
        pytest.approx(total, rel=0.05)
    listed = [m["name"] for m in common.load_benchmark()["per_layer"]
              if CELL in m["workloads"]]
    assert "train.mfu" not in listed and "train.mfu.mla_moe" in listed
    assert not mla_costs.is_mla(common.sizes_of(
        common.load_json("configs", "olmoe-1b-7b.json"), "train"))


def test_flash_costs_with_two_widths_by_hand():
    """One 8192-token sequence, 16 heads, queries and keys 192 wide, values
    128: 16 x 8192 x 4096.5 pairs; forward 2 x (192 + 128) operations a
    pair, backward's five products 2 x (3 x 192 + 2 x 128)."""
    pairs = 16 * 8192 * 4096.5
    fwd = mla_costs.flash_mla_fwd(1, 8192, 16, 192, 128)
    bwd = mla_costs.flash_mla_bwd(1, 8192, 16, 192, 128)
    assert fwd["flops"] == 2 * 320 * pairs == pytest.approx(343.6e9, rel=1e-3)
    assert bwd["flops"] == 2 * 832 * pairs == pytest.approx(893.4e9, rel=1e-3)
    rows = 8192 * 16
    assert fwd["bytes"] == 2 * rows * (192 + 192 + 128 + 128) + 4 * rows
    assert bwd["bytes"] == 2 * rows * (4 * 192 + 3 * 128) + 8 * rows
    # equal widths: benchmark/kernel_costs.py's count of the other cells
    from benchmark import kernel_costs
    assert mla_costs.flash_mla_fwd(1, 8192, 16, 128, 128) == \
        kernel_costs.flash_fwd(1, 8192, 16, 16, 128)
    assert mla_costs.flash_mla_bwd(1, 8192, 16, 128, 128) == \
        pytest.approx(kernel_costs.flash_bwd(1, 8192, 16, 16, 128))


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, 4 of the router's 16 experts held: the
    # reference's share is the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, or the reference from float8 weights."""
    ctx, kind = tiny_context(CELL, seed)
    how = kimi_vl_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else kimi_vl_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name,least", [
    ("rope_off_shared_key", 0.3), ("kv_norm_left_out", 0.2),
    ("bias_left_out_of_selection", 0.1), ("softmax_for_sigmoid", 0.1),
    ("scale_left_out", 0.1), ("normalised_over_held_only", 0.2),
    ("shared_expert_zeroed", 0.3), ("held_experts_zeroed", 0.15),
    ("reference_fp8_e4m3", 0.1), ("reference_fp8_e5m2", 0.15)])
def test_a_wrong_computation_fails_the_check(seed, name, least):
    """Each thing of the published forward pass left out or replaced, and
    the reference one precision down, is far outside the tolerance."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert stats["logit_rel_l2"] > least > tol["logit_rel_l2_tol"]


@pytest.mark.parametrize("seed", [40, 41])
def test_top1_routing_fails_the_check(seed):
    ok, stats = train_check(CELL, seed, control="top1_routing")
    assert not ok and not stats["verdicts"]["logit_rel_l2"]


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.deepseek_v3 as dsv3

    before = {k: getattr(dsv3, k) for k in
              ("route", "_rotate", "_kv_norm", "_shared_experts",
               "_routed_experts")}
    for name in kimi_vl_wrong.WRONG:
        with kimi_vl_wrong.wrong(name):
            assert any(getattr(dsv3, k) is not v for k, v in before.items())
    assert all(getattr(dsv3, k) is v for k, v in before.items())
    load = common.load_file_module
    with kimi_vl_wrong.reference_from_float8():
        assert common.load_file_module is not load
    assert common.load_file_module is load


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/layers/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 1000, FWD + "block/self_attn/ds.attn_proj/dot"],
        ["ds_flash_fwd", 1000, 4000,
         FWD + "block/self_attn/ds.attention/pallas_call"],
        ["fusion.2", 5000, 2000,
         FWD + "block/mlp/shared_experts/ds.moe_shared/dot"],
        ["ds_flash_bwd_dq", 7000, 3000, "jit(ds_train_step)/ds.loss_and_grad/"
         "transpose(jvp(M))/ds.attention/pallas_call"],
        ["ds_flash_bwd_dkv", 10000, 5000, "jit(ds_train_step)/"
         "ds.loss_and_grad/transpose(jvp(M))/ds.attention/pallas_call"],
        ["fusion.9", 15000, 5000, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_share_readers_on_a_hand_made_trace():
    run = run_of(HAND)
    assert reader("train.attn_proj_share").read(run) == pytest.approx(5.0)
    assert reader("moe.shared_expert_share").read(run) == pytest.approx(10.0)
    for name in ("train.attn_proj_share", "moe.shared_expert_share"):
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_mla_rooflines_are_least_time_over_the_time_of_a_call():
    """One forward call of 4000 ns, one backward call of 3000 + 5000 ns;
    the least times are bound by operations: 343.6 and 893.4 GFLOP at 197
    TFLOP/s."""
    run = run_of(HAND)
    fwd = mla_costs.flash_mla_fwd(1, 8192, 16, 192, 128)
    bwd = mla_costs.flash_mla_bwd(1, 8192, 16, 192, 128)
    assert fwd["flops"] / 197e12 > fwd["bytes"] / 819e9
    assert reader("kernel.flash_mla_fwd.roofline_share").read(run) == \
        pytest.approx(100 * fwd["flops"] / 197e12 / 4000e-9)
    assert reader("kernel.flash_mla_bwd.roofline_share").read(run) == \
        pytest.approx(100 * bwd["flops"] / 197e12 / 8000e-9)


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=20000.0, chips=1)
    want = 100 * 3 * sum(mla_costs.forward_parts(
        sizes(), 8192).values()) * 20000.0 / 197e12
    assert reader("train.mfu.mla_moe").read(run) == pytest.approx(want)
    assert 26 < want < 27
    assert reader("train.mfu.mla_moe").read(
        {**run, "device": {"platform": "cpu"}}) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["olmoe-1b-7b.train.4k",
                                   "mistral-7b.train.8k"])
def test_new_readers_find_nothing_in_another_program(name, other):
    """A program without latent attention and shared experts (the other
    cells' recorded traces, as the parent commit runs them): None, no
    exception."""
    fixture = {"olmoe-1b-7b.train.4k": "scope_trace_train_olmoe_4k.json",
               "mistral-7b.train.8k": "scope_trace_train_8k.json"}[other]
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    if name == "train.attn_proj_share":     # every model has projections
        assert 0 < reader(name).read(run) < 100
    else:
        assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


# -- a cut of a real chip trace of the cell ----------------------------------

def recording(name="scope_trace_train_kimi_8k.json"):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


def cell_metrics():
    """The cell's readers of the scope trace (the idle share reads
    ``trace_reduce``'s numbers, the host gap needs whole ``train_batch``
    spans: neither is a share of this cut)."""
    return [m["name"] for m in common.load_benchmark()["per_layer"]
            if CELL in m["workloads"] and m["source"] == "device_trace"
            and m["name"] not in ("device.idle_share.train",
                                  "train.host_gap_ms_per_step")]


@pytest.mark.parametrize("metric", cell_metrics())
def test_trace_reader_of_the_cell_on_its_recording(metric):
    """Every trace-sourced metric the cell lists finds something to read in
    a cut of the cell's trace on the v5e, a share of at most 100."""
    value = reader(metric).read(run_of(recording()))
    assert value is not None and 0 <= value <= 100, (metric, value)


def test_recording_is_the_cells_shape():
    """What PERF.md section 5 says of the cell, from the recording."""
    run = run_of(recording())
    r = scope_reduce.reduce(run["scope_trace"])
    assert set(r["by_kernel"]) == {"ds_flash_fwd", "ds_flash_bwd_dq",
                                   "ds_flash_bwd_dkv"}
    assert {"ds.attention", "ds.attn_proj", "ds.mlp", "ds.moe_router",
            "ds.moe_experts", "ds.moe_shared", "ds.lm_head_loss",
            "ds.optimizer", "ds.embed"} <= set(r["by_scope"])
    assert max(r["by_scope"], key=r["by_scope"].get) == "ds.attention"
    assert reader("kernel.flash_mla_fwd.roofline_share").read(run) < 100
    assert reader("kernel.flash_mla_bwd.roofline_share").read(run) < 100
