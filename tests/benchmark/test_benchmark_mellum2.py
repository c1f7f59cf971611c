"""The configuration ``mellum2-12b-a2.5b`` and its cell
``mellum2-12b-a2.5b.train.8k``: what ``BENCHMARK.json`` gained for them, the
file against the catalog row, parameters
and required operations by hand, the cell's correctness check at tiny size on
one CPU device (passes over seeds; every wrong computation ISSUE 49 lists
fails it), and the five readers the cell brings, on a hand-made trace and on
other programs' recordings."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, swa_costs
import mellum2_wrong

CELL = "mellum2-12b-a2.5b.train.8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.swa_moe", "kernel.flash_swa_fwd.roofline_share",
       "kernel.flash_swa_bwd.roofline_share", "train.full_layer_share",
       "train.window_layer_share")
#: the readers other cells have too, under the names keye 16k's twins carry
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step",
          "moe.expert_share", "moe.grouped_matmul_share",
          "moe.compact_hit_share", "moe.rows_max_over_mean",
          "moe.held_rows_over_expected")
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", "mellum2-12b-a2.5b.json")


def sizes(depth=None):
    s = common.sizes_of(config(), "train")
    return s if depth is None else {**s, "num_hidden_layers": depth}


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_five_metrics():
    """One configuration, one cell on one chip under the traffic that
    stands, five per-layer metrics that list the cell alone, and the cell's
    name at the end of fourteen lists: the rate's and the thirteen shared
    readers'. The lists that count one window or every expert do not gain
    it."""
    bench = common.load_benchmark()
    assert bench["configs"][-1]["name"] == "mellum2-12b-a2.5b"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        (CELL, "mellum2-12b-a2.5b", "train.8k", 1)
    assert all(len(x["why"]) <= 200
               for x in (bench["configs"][-1], cell))
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    for m in bench["per_layer"][-5:]:
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
    lists = {m["name"]: m["workloads"]
             for m in bench["end_to_end"] + bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert sorted(lists) == sorted(
        NEW + SHARED + ("train_tokens_per_s_per_chip",))
    assert all(cells[-1] == CELL for cells in lists.values())
    workload = common.load_json("workloads", f"{CELL}.json")
    assert "rate_metric" not in workload and "weight_seed" not in workload


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key, nested groups
    whole; the depth and the vocabulary differ and the experts held are
    stated beside ``num_experts``; the cuts are listed with their arithmetic
    and the published counts stand beside."""
    pattern = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": pattern * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "num_local_experts": 64, "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": YARN,
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == sorted(file["reduced"]) == \
        ["num_hidden_layers", "num_local_experts", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == file["source"] == \
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/" \
        "blob/main/config.json"
    assert entry["file"] == "benchmark/configs/mellum2-12b-a2.5b.json"
    assert file["published"] == {k: published[k] for k in differ}
    assert file["num_hidden_layers"]["published"] == 28
    # a whole number of periods, the floor of experts and of the vocabulary
    assert file["num_hidden_layers"]["train"] % 4 == 0
    assert (file["num_local_experts"] * 8, file["vocab_size"] * 8) == \
        (64, 98304)
    for text in file["reduced"].values():
        assert len(text) > 200
    assert "memory_analysis()" in file["reduced"]["num_hidden_layers"]
    assert "eight TPU v5e chips share each layer" in file["deployment"]
    for item in ("qk_norm_per_head", "router_aux_loss_coef", "no_mtp_head",
                 "router_trainable", "full_attention_period",
                 "sliding_window", "rope_theta", "yarn_factor",
                 "yarn_original_max_position_embeddings", "yarn_beta_fast",
                 "yarn_beta_slow", "yarn_attention_factor",
                 "head_dim_override", "per_expert_init"):
        assert len(file["assumed"][item]) > 40, item


def test_flat_numbers_say_what_the_nested_keys_say():
    """``common.sizes_of`` hands on top-level numbers only: each flat copy
    is the nested key's value, and is listed as derived from it."""
    file = config()
    full = file["rope_parameters"]["full_attention"]
    assert full == YARN
    assert (file["rope_theta"], file["yarn_factor"],
            file["yarn_original_max_position_embeddings"],
            file["yarn_beta_fast"], file["yarn_beta_slow"],
            file["yarn_attention_factor"]) == tuple(
        full[k] for k in ("rope_theta", "factor",
                          "original_max_position_embeddings", "beta_fast",
                          "beta_slow", "attention_factor"))
    assert file["rope_parameters"]["sliding_attention"]["rope_theta"] == \
        file["rope_theta"]
    period = file["full_attention_period"]
    assert file["layer_types"] == [
        "full_attention" if (l + 1) % period == 0 else "sliding_attention"
        for l in range(28)]
    assert file["head_dim_override"] == file["head_dim"] == 128
    assert file["router_experts"] == file["num_experts"] == 64
    for key in ("yarn_factor", "yarn_original_max_position_embeddings",
                "yarn_beta_fast", "yarn_beta_slow", "yarn_attention_factor",
                "rope_theta", "full_attention_period", "head_dim_override"):
        assert "derived from" in file["assumed"][key], key


def test_model_is_built_from_the_file_and_the_workload():
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "MellumForCausalLM"
    assert (cfg.num_local_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.head_dim, cfg.expert_width,
            cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.tie_word_embeddings, cfg.norm_topk_prob, cfg.qk_norm,
            cfg.qk_norm_per_head, cfg.router_trainable,
            cfg.router_aux_loss_coef, cfg.report_expert_load,
            cfg.attention_impl, cfg.sliding_window,
            cfg.full_attention_period,
            cfg.yarn_factor, cfg.yarn_original_max_position_embeddings,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow,
            cfg.yarn_attention_factor, cfg.remat, cfg.remat_policy,
            cfg.scan_layers, cfg.embed_init_std, cfg.head_init_std) == \
        (8, 64, 0, 8, 128, 896, 7168, 32, 4, 500000, 1e-6, False, True,
         False, True, False, 0.0, True, "flash", 1024, 4, 16, 8192, 32,
         1, 1.2772588722239782, True, "nothing", True, 1.0, 0.0002)
    # the two tables' SEEDED scales are numbers of the benchmark's weights,
    # not of the row: each stands under `assumed` with its reason
    assert {"embed_init_std", "head_init_std"} <= set(file["assumed"])
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert (wl["warmup_steps"], wl["check"]["probe_positions"]) == (3, 256)
    assert "rate_metric" not in wl          # the 1% bound's name


def parameters(depth):
    import jax
    import jax.numpy as jnp

    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(config(), sizes(depth), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_parameters_by_hand():
    """A layer is 21.23 M of attention, 0.147 M of router and 8 held experts
    of 6.19 M; the sliced table and head 56.6 M: 624 M at two periods (what
    ISSUE 49 sized), 340 M at the one the memory rule left."""
    attention = 2304 * (4096 + 512 + 512) + 4096 * 2304 + 2 * 128
    layer = attention + 2304 * 64 + 2 * 2304 + 8 * 3 * 2304 * 896
    assert (round(attention / 1e6, 2), round(layer / 1e6, 2)) == (21.23,
                                                                  70.93)
    want = lambda depth: depth * layer + 2 * 12288 * 2304 + 2304
    assert parameters(8) == want(8) == 624075008
    assert round(want(8) * 16 / 1e9, 2) == 9.99
    depth = config()["num_hidden_layers"]["train"]
    assert parameters(depth) == want(depth)
    assert depth == 4 and round(want(4) / 1e6, 2) == 340.35
    assert round(want(4) * 16 / 1e9, 2) == 5.45


def test_a_token_needs_726_mflop_forward_at_two_periods_and_where():
    """ISSUE 49's arithmetic at depth 8, and the same at the depth run."""
    parts = swa_costs.forward_parts(sizes(8), 8192)
    window = (1024 * 1025 / 2 + (8192 - 1024) * 1024) / 8192
    assert window == pytest.approx(960.06, abs=0.01)
    assert flops.mean_attended_keys(8192) == 4096.5
    want = {
        "attn_proj": 8 * 2 * 2304 * 128 * (32 + 4 + 4 + 32),
        "attention_window": 6 * 2 * 2 * 32 * 128 * window,
        "attention_full": 2 * 2 * 2 * 32 * 128 * 4096.5,
        "router": 8 * 2 * 2304 * 64,
        "held_experts": 8 * (8 * 8 / 64) * 3 * 2 * 2304 * 896,
        "head": 2 * 2304 * 12288}
    assert parts == pytest.approx(want)
    assert round(sum(parts.values()) / 1e6) == 726     # ISSUE 49: "about 727"
    layer = {k: v / 8 for k, v in parts.items()
             if k not in ("head", "attention_window", "attention_full")}
    other = sum(layer.values())                 # projections, router, experts
    sliding = other + parts["attention_window"] / 6
    full = other + parts["attention_full"] / 2
    assert (round(6 * sliding / 1e6), round(2 * full / 1e6)) == (425, 245)
    assert round(full / sliding, 1) == 1.7
    assert round(parts["attention_full"] / 2
                 / (parts["attention_window"] / 6), 1) == 4.3
    assert round(100 * parts["held_experts"] / sum(parts.values())) == 14
    # as run: one period
    run = swa_costs.forward_parts(sizes(), 8192)
    assert run == pytest.approx({
        k: want[k] / 2 for k in want if k != "head"} | {"head": want["head"]})
    assert round(sum(run.values()) / 1e6, 1) == 391.5
    assert swa_costs.train_flops_per_token(sizes(), 8192) == \
        pytest.approx(3 * sum(run.values()))
    assert swa_costs.layer_counts(sizes(8)) == {1024: 6, None: 2}
    assert swa_costs.layer_counts(sizes()) == {1024: 3, None: 1}


def test_cost_readers_know_their_own_cells():
    assert swa_costs.is_swa_moe(sizes())
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b",
                  "keye-vl2-30b-a3b", "phi4-mini-flash", "mixtral-8x7b"):
        assert not swa_costs.is_swa_moe(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))
    listed = [m["name"] for m in common.load_benchmark()["per_layer"]
              if CELL in m["workloads"]]
    assert sorted(listed) == sorted(NEW + SHARED)
    rate = next(m for m in common.load_benchmark()["end_to_end"]
                if m["name"] == "train_tokens_per_s_per_chip")
    assert rate["workloads"][-1] == CELL
    for m in common.load_benchmark()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s_per_chip"


def test_kernel_costs_count_each_kinds_pairs():
    """512 operations a (query, attended key) pair a head forward, 2.5
    times that backward, at the heads' own width (not ``hidden / heads``);
    a window call needs 23% of a full call's."""
    s = sizes()
    assert (s["head_dim"], s["head_dim_override"]) == (72, 128)
    full = swa_costs.flash_swa_fwd(s, 1, 8192, None)
    window = swa_costs.flash_swa_fwd(s, 1, 8192, 1024)
    assert full == kernel_costs.flash_fwd(1, 8192, 32, 4, 128)
    assert full["flops"] == 512 * 32 * 8192 * 4096.5
    assert window["flops"] / full["flops"] == pytest.approx(0.2344, abs=1e-4)
    assert window["bytes"] == full["bytes"]
    for w in (None, 1024):
        assert swa_costs.flash_swa_bwd(s, 1, 8192, w)["flops"] == \
            pytest.approx(2.5 * swa_costs.flash_swa_fwd(s, 1, 8192,
                                                        w)["flops"])


def test_benchmark_names_only_files_that_exist():
    bench = common.load_benchmark()
    here = os.path.dirname(os.path.abspath(common.__file__))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"])), c
    for w in bench["workloads"]:
        for parts in (("workloads", f"{w['name']}.json"),
                      ("configs", f"{w['config']}.json"),
                      ("traffic", f"{w['traffic']}.json")):
            assert os.path.exists(os.path.join(here, *parts)), parts
        wl = common.load_json("workloads", f"{w['name']}.json")
        file = common.load_json("configs", f"{w['config']}.json")
        assert os.path.exists(os.path.join(here, "kinds",
                                           f"{wl['kind']}.py"))
        assert os.path.exists(os.path.join(here, "reference",
                                           f"{file['reference']}.py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "layer_metrics",
                                           f"{m['name']}.py")), m["name"]


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, two periods, 2 of the router's 8 experts held:
    # the reference's share is the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, a harness control, or the reference from float8."""
    if name in ("window_off", "top1_routing"):
        return train_check(CELL, seed, name)
    ctx, kind = tiny_context(CELL, seed)
    how = mellum2_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else mellum2_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    "window_off", "top1_routing", *mellum2_wrong.WRONG,
    "reference_fp8_e4m3", "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the pattern, the tables, the router or the norms left
    out or replaced, and the reference one precision down, is far outside
    the tolerance."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    assert stats["logit_rel_l2"] > 10 * tol["logit_rel_l2_tol"]


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(mellum2_wrong.WRONG) == {
        "all_layers_window", "full_layer_first_in_period", "yarn_left_off",
        "yarn_on_window_layers", "attention_factor_left_out",
        "topk_not_renormalised", "qk_norm_left_out"}
    assert callable(mellum2_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.llama as llama
    import deepspeed_tpu.models.mellum as mellum

    names = [(mellum, "period_kinds"), (mellum, "rope_tables"),
             (mellum, "kind_config"), (llama, "RMSNorm")]
    before = [getattr(m, k) for m, k in names]
    for name in mellum2_wrong.WRONG:
        with mellum2_wrong.wrong(name):
            assert sum(getattr(m, k) is not v
                       for (m, k), v in zip(names, before)) == 1
    assert all(getattr(m, k) is v for (m, k), v in zip(names, before))


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/ds.layer_stack/" \
    "periods/while/body/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/model/" \
    "ds.layer_stack/periods/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 500, "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/"
         "model/ds.rope_tables/cos"],
        ["fusion.1", 1000, 1000,
         FWD + "ds.layer_window/block_0/self_attn/ds.attn_proj/dot"],
        ["ds_flash_fwd", 2000, 1000,
         FWD + "ds.layer_window/block_0/self_attn/ds.attention/pallas_call"],
        ["ragged-dot-none.1", 3000, 1500,
         FWD + "ds.layer_window/block_1/block_sparse_moe/ds.moe_experts/"
         "moe_gmm/ragged_dot"],
        ["ds_flash_fwd", 5000, 3000,
         FWD + "ds.layer_full/block_3/self_attn/ds.attention/pallas_call"],
        ["fusion.2", 8000, 500, FWD + "ds.layer_full/block_3/ds.norm/mul"],
        ["ds_flash_bwd_dq", 10000, 2000, BWD + "ds.layer_full/"
         "ds.layer_full/checkpoint/block_3/self_attn/ds.attention/"
         "pallas_call"],
        ["ds_flash_bwd_dkv", 12000, 3000, BWD + "ds.layer_full/"
         "ds.layer_full/checkpoint/block_3/self_attn/ds.attention/"
         "pallas_call"],
        ["fusion.3", 15000, 1000, BWD + "ds.layer_window/ds.layer_window/"
         "checkpoint/rematted_computation/block_2/self_attn/ds.attn_proj/"
         "dot"],
        ["fusion.8", 16000, 1500, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(M)/ds.lm_head_loss/dot"],
        ["fusion.9", 18000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.1", 30000, 1000,
         FWD + "ds.layer_full/block_3/self_attn/ds.attn_proj/dot"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_layer_kind_shares_read_the_path_not_the_innermost_scope():
    """Busy 16,000 ns: the full layer's 3,000 + 500 + 2,000 + 3,000, the
    window layers' 1,000 + 1,000 + 1,500 + 1,000; the tables, the head and
    the optimizer are neither's. ``by_scope`` files the same operations
    under their innermost names."""
    from benchmark import scope_reduce

    run = run_of(HAND)
    assert reader("train.full_layer_share").read(run) == \
        pytest.approx(100 * 8500 / 16000)
    assert reader("train.window_layer_share").read(run) == \
        pytest.approx(100 * 4500 / 16000)
    by_scope = scope_reduce.reduced(run)["by_scope"]
    assert "ds.layer_full" not in by_scope and "ds.attention" in by_scope
    assert swa_costs.path_share(run, "ds.rope_tables") == \
        pytest.approx(100 * 500 / 16000)
    # a scope the program never names; another kind of run
    assert swa_costs.path_share(run, "ds.layer_cross") is None
    for name in NEW[3:]:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_swa_rooflines_sum_a_steps_calls():
    """A step calls the forward kernel three times at the window and once
    full (depth 4); the trace's two forward calls average 2000 ns, the
    backward's two kernels 2000 + 3000: least times summed over four calls'
    time. Both kinds are bound by operations."""
    run = run_of(HAND)
    s = sizes()
    for w in (1024, None):
        cost = swa_costs.flash_swa_fwd(s, 1, 8192, w)
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
    least = lambda fn, w: kernel_costs.least_seconds(
        fn(s, 1, 8192, w), TPU["kind"])[0]
    want = 3 * least(swa_costs.flash_swa_fwd, 1024) \
        + least(swa_costs.flash_swa_fwd, None)
    assert reader("kernel.flash_swa_fwd.roofline_share").read(run) == \
        pytest.approx(100 * want / (4 * 2000e-9))
    want = 3 * least(swa_costs.flash_swa_bwd, 1024) \
        + least(swa_costs.flash_swa_bwd, None)
    assert reader("kernel.flash_swa_bwd.roofline_share").read(run) == \
        pytest.approx(100 * want / (4 * 5000e-9))
    cpu = {**run, "device": {"platform": "cpu"}}
    assert reader("kernel.flash_swa_fwd.roofline_share").read(cpu) is None


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=40000.0, chips=1)
    want = 100 * 3 * sum(swa_costs.forward_parts(
        sizes(), 8192).values()) * 40000.0 / 197e12
    assert reader("train.mfu.swa_moe").read(run) == pytest.approx(want)
    assert 23 < want < 25
    assert reader("train.mfu.swa_moe").read(
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
    """A program without a pattern of layer kinds (the other cells' recorded
    traces, as the parent commit runs them): None, no exception."""
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """Another program's trace under this cell's own name (the driver lays
    the benchmark's files over the parent's checkout): no ``ds.layer_*``
    scope, so the two layer shares read None; the flash kernels are there."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    assert reader("train.full_layer_share").read(run) is None
    assert reader("train.window_layer_share").read(run) is None
    assert reader("kernel.flash_swa_fwd.roofline_share").read(run) > 0
