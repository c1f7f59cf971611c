"""The configuration ``zaya1-8b`` and its cell ``zaya1-8b.train.8k``: the
file against the catalog row, parameters and required operations by hand,
the cell's correctness check at tiny size on one CPU device (passes over
seeds; every wrong computation ISSUE 35 lists fails it), and the readers the
cell brings, on a hand-made trace and on a cut of a real chip trace of the
cell."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import cca_costs, common, flops, kernel_costs, scope_reduce
import zaya1_wrong

CELL = "zaya1-8b.train.8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.cca_moe", "train.cca_mix_share", "moe.router_share",
       "kernel.flash_cca_fwd.roofline_share",
       "kernel.flash_cca_bwd.roofline_share")
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step",
          "moe.expert_share", "moe.grouped_matmul_share")


def reader(name):
    return common.load_file_module("layer_metrics", name)


def sizes():
    return common.sizes_of(common.load_json("configs", "zaya1-8b.json"),
                           "train")


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key, nested groups
    whole; the three cuts differ, are listed with their arithmetic, and the
    published counts stand beside."""
    rope = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"}
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": rope, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 262272}
    config = common.load_json("configs", "zaya1-8b.json")
    differ = sorted(k for k, v in published.items()
                    if config.get(k, "absent") != v)
    assert differ == sorted(config["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == "zaya1-8b")
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == config["source"]
    assert config["published"] == {k: published[k] for k in differ}
    assert config["num_hidden_layers"] == {"published": 40, "train": 6}
    # the row's name of the count and the package's: one number
    assert config["num_experts"] == config["n_routed_experts"] == 8
    assert config["tiny"]["num_experts"] == \
        config["tiny"]["n_routed_experts"]
    assert (config["router_experts"], config["vocab_size"] * 8) == \
        (16, 262272)
    assert config["rope_theta"] == rope["hybrid"]["rope_theta"]
    cfg, _ = common.build_model(config, common.sizes_of(config, "train"))
    assert (cfg.n_routed_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.head_dim,
            cfg.rotary_dim, cfg.rope_theta, cfg.cca_time0, cfg.cca_time1,
            cfg.router_hidden_size, cfg.moe_intermediate_size,
            cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.num_hidden_layers, cfg.tie_word_embeddings,
            cfg.report_expert_load, cfg.router_trainable) == \
        (8, 17, 0, 1, 128, 64, 5e6, 2, 2, 256, 2048, 8, 2, 6, True,
         True, False)
    assert cfg.router_bias_update_rate > 0 and cfg.router_bias_init > 0
    for item in ("cca_qk_mean_grouping", "cca_temperature_on_the_key",
                 "cca_conv_biases", "softmax_scale",
                 "router_reads_the_normed_input", "skip_expert_output",
                 "residual_scaling", "gelu", "router_bias_update_rate",
                 "seeded_init", "intermediate_size"):
        assert len(config["assumed"][item]) > 40, item


def test_parameters_are_708_7_million():
    """6 x 106.9 M + the 67.1 M table, as the file's ``reduced`` says:
    11.34 GB at 16 B each."""
    import jax
    import jax.numpy as jnp

    config = common.load_json("configs", "zaya1-8b.json")
    _, model = common.build_model(config, common.sizes_of(config, "train"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    cca = 2048 * (1024 + 256 + 128 + 128) + 1024 * 2048 \
        + (2 * 1280 + 1280) + (2 * 10 * 128 * 128 + 1280) + 2
    router = (2048 * 256 + 256) + 256 + 256 + 2 * (256 * 256 + 256) \
        + 256 * 17 + 17
    layer = cca + router + 2 * 2048 + 8 * 2048 + 8 * 3 * 2048 * 2048
    want = 6 * layer + 32784 * 2048 + 2048
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == want
    assert round(layer / 1e6, 1) == 106.9
    assert round(want / 1e6, 1) == 708.7
    assert round(want * 16 / 1e9, 2) == 11.34


def test_a_token_needs_381_mflop_forward_and_where():
    parts = cca_costs.forward_parts(sizes(), 8192)
    keys = (8192 + 1) / 2
    want = {
        "attn_proj": 6 * 2 * 5_242_880,
        "cca_conv": 6 * 2 * (2 * 1280 + 2 * 10 * 128 * 128),
        "attention": 6 * 2 * 2 * 8 * 128 * keys,
        "router": 6 * 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 17),
        "held_experts": 6 * (8 / 17) * 3 * 2 * 2048 * 2048,
        "head": 2 * 2048 * 32784}
    assert parts == pytest.approx(want)
    total = sum(parts.values())
    assert round(total / 1e6) == 381
    assert {k: round(v / 1e6) for k, v in parts.items()} == {
        "attn_proj": 63, "cca_conv": 4, "attention": 101, "router": 8,
        "held_experts": 71, "head": 134}
    assert cca_costs.train_flops_per_token(sizes(), 8192) == \
        pytest.approx(3 * total)
    # what benchmark/flops.py would count of this file (256-wide heads, a
    # dense feed-forward, no router) is read by no metric the cell lists
    assert flops.forward_flops_per_token(sizes(), 8192) != \
        pytest.approx(total, rel=0.05)
    listed = [m["name"] for m in common.load_benchmark()["per_layer"]
              if CELL in m["workloads"]]
    assert sorted(listed) == sorted(NEW + SHARED)
    for name in ("train.mfu", "kernel.flash_fwd.roofline_share",
                 "kernel.flash_bwd.roofline_share",
                 "kernel.moe_gmm.roofline_share"):
        assert name not in listed
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b"):
        assert not cca_costs.is_cca(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))


def test_flash_costs_read_the_models_own_head_width():
    """``common.sizes_of`` calls ``head_dim`` what is hidden / heads = 256;
    the kernels run at ``head_dim_override`` = 128: 8 x 8192 x 4096.5 pairs
    of 4 x 128 operations forward, 2.5 times that backward."""
    s = sizes()
    assert (s["head_dim"], s["head_dim_override"]) == (256, 128)
    pairs = 8 * 8192 * 4096.5
    fwd = kernel_costs.flash_fwd(1, 8192, 8, 2, 128)
    bwd = kernel_costs.flash_bwd(1, 8192, 8, 2, 128)
    assert fwd["flops"] == 4 * 128 * pairs == pytest.approx(137.4e9,
                                                           rel=1e-3)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"])
    assert fwd["bytes"] == 2 * 8192 * 128 * (2 * 8 + 2 * 2) + 4 * 8 * 8192


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, 4 of the router's 8 experts held: the
    # reference's share is the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, or the reference from float8 weights."""
    ctx, kind = tiny_context(CELL, seed)
    if name in zaya1_wrong.WRONG_CONFIG:
        ctx = zaya1_wrong.wrong_context(ctx, name)
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])
    how = zaya1_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else zaya1_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    *zaya1_wrong.WRONG, *zaya1_wrong.WRONG_CONFIG, "reference_fp8_e4m3",
    "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the layer left out or replaced, and the reference one
    precision down, is far outside the tolerance."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert stats["logit_rel_l2"] > 10 * tol["logit_rel_l2_tol"]


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(zaya1_wrong.WRONG) | set(zaya1_wrong.WRONG_CONFIG) == {
        "conv_a_left_out", "conv_b_left_out", "qk_mean_left_out",
        "value_from_current_token", "temperature_left_out",
        "unit_norm_left_out", "rotary_on_all_columns",
        "router_state_left_out", "skip_expert_returns_zero",
        "bias_left_out_of_choice", "top2", "residual_scaling_left_out",
        "softmax_scale_256"}


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.zaya as zaya

    names = ("causal_conv", "_qk_mean", "_shifted_value", "_temperature",
             "_unit_length", "_rotary", "_carry_state", "_skip_expert",
             "route", "_residual", "_softmax_scale")
    before = {k: getattr(zaya, k) for k in names}
    for name in zaya1_wrong.WRONG:
        with zaya1_wrong.wrong(name):
            assert sum(getattr(zaya, k) is not v
                       for k, v in before.items()) == 1
    assert all(getattr(zaya, k) is v for k, v in before.items())


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/layers/while/body/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 1000, FWD + "block/self_attn/ds.attn_proj/dot"],
        ["fusion.2", 1000, 600, FWD + "block/self_attn/ds.cca_mix/mul"],
        ["ds_flash_fwd", 2000, 2000,
         FWD + "block/self_attn/ds.attention/pallas_call"],
        ["fusion.3", 4000, 400, FWD + "block/mlp/ds.moe_router/router/dot"],
        ["fusion.4", 4400, 200, FWD + "block/mlp/ds.moe_skip/mul"],
        ["ds_flash_bwd_dq", 7000, 2000, BWD + "ds.attention/pallas_call"],
        ["ds_flash_bwd_dkv", 10000, 3000, BWD + "ds.attention/pallas_call"],
        ["fusion.9", 15000, 800, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_share_readers_on_a_hand_made_trace():
    run = run_of(HAND)        # busy: 10,000 ns
    assert reader("train.cca_mix_share").read(run) == pytest.approx(6.0)
    assert reader("moe.router_share").read(run) == pytest.approx(4.0)
    assert scope_reduce.share(run, "train", "ds.moe_skip") == \
        pytest.approx(2.0)
    for name in ("train.cca_mix_share", "moe.router_share"):
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_cca_rooflines_are_least_time_over_the_time_of_a_call():
    """One forward call of 2000 ns, one backward call of 2000 + 3000 ns; the
    least times are bound by operations: 137.4 and 343.6 GFLOP at 197
    TFLOP/s."""
    run = run_of(HAND)
    fwd = kernel_costs.flash_fwd(1, 8192, 8, 2, 128)
    bwd = kernel_costs.flash_bwd(1, 8192, 8, 2, 128)
    assert fwd["flops"] / 197e12 > fwd["bytes"] / 819e9
    assert reader("kernel.flash_cca_fwd.roofline_share").read(run) == \
        pytest.approx(100 * fwd["flops"] / 197e12 / 2000e-9)
    assert reader("kernel.flash_cca_bwd.roofline_share").read(run) == \
        pytest.approx(100 * bwd["flops"] / 197e12 / 5000e-9)


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=40000.0, chips=1)
    want = 100 * 3 * sum(cca_costs.forward_parts(
        sizes(), 8192).values()) * 40000.0 / 197e12
    assert reader("train.mfu.cca_moe").read(run) == pytest.approx(want)
    assert 23 < want < 24
    assert reader("train.mfu.cca_moe").read(
        {**run, "device": {"platform": "cpu"}}) is None


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["olmoe-1b-7b.train.4k",
                                   "mistral-7b.train.8k",
                                   "kimi-vl-a3b.train.8k"])
def test_new_readers_find_nothing_in_another_program(name, other):
    """A program without compressed attention (the other cells' recorded
    traces, as the parent commit runs them): None, no exception; a router
    has its scope in every expert model."""
    fixture = {"olmoe-1b-7b.train.4k": "scope_trace_train_olmoe_4k.json",
               "mistral-7b.train.8k": "scope_trace_train_8k.json",
               "kimi-vl-a3b.train.8k": "scope_trace_train_kimi_8k.json"}[
                   other]
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    if name == "moe.router_share" and not other.startswith("mistral"):
        assert 0 < reader(name).read(run) < 100
    else:
        assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def recording(name="scope_trace_train_zaya1_8k.json"):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


# -- a cut of a real chip trace of the cell ----------------------------------

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
    assert {"ds.attention", "ds.attn_proj", "ds.cca_mix", "ds.moe_router",
            "ds.moe_experts", "ds.moe_skip", "ds.lm_head_loss",
            "ds.optimizer", "ds.embed"} <= set(r["by_scope"])
    assert max((s for s in r["by_scope"] if s.startswith("ds.")),
               key=r["by_scope"].get) == "ds.attention"
    # what CCA adds is a few per cent of the step, the skip expert nothing
    assert 1 < reader("train.cca_mix_share").read(run) < 10
    assert scope_reduce.share(run, "train", "ds.moe_skip") < 0.5
    assert reader("kernel.flash_cca_fwd.roofline_share").read(run) < 100
    assert reader("kernel.flash_cca_bwd.roofline_share").read(run) < 100
