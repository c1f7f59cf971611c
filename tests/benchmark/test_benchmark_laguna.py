"""The configuration ``laguna-xs.2`` and its cell ``laguna-xs.2.train.8k``:
what ``BENCHMARK.json`` gained for them (entries found by NAME: a later cell
is appended behind them), the file against the catalog row, parameters and
required operations by hand, the kept pairs counted pair by pair, the cell's
correctness check at tiny size on one CPU device (passes over seeds; every
wrong computation ISSUE 63 lists fails it), and the six readers the cell
brings, on a hand-made trace, on the cell's own recorded step and on other
programs' recordings."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, laguna_costs
import laguna_wrong

CELL = "laguna-xs.2.train.8k"
NAME = "laguna-xs.2"
RATE = "train_tokens_per_s_per_chip"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.lg_moe", "kernel.flash_lg_fwd.roofline_share",
       "kernel.flash_lg_bwd.roofline_share", "train.dense_layer_share",
       "train.attn_gate_share", "attn.gate_mean")
#: the readers other cells have too, which READ something on this one
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step",
          "moe.expert_share", "moe.router_share", "moe.shared_expert_share",
          "moe.compact_hit_share", "moe.rows_max_over_mean",
          "moe.held_rows_over_expected", "train.window_layer_share",
          "train.full_layer_share")
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", f"{NAME}.json")


def sizes(**over):
    return {**common.sizes_of(config(), "train"), **over}


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_six_metrics():
    """One configuration, one cell on one chip under the traffic that
    stands, six per-layer metrics that list the cell, and the cell's name in
    the rate's list and the sixteen shared readers' that read it
    (``train.unnamed_share`` reads the cell too and does not list it:
    ``test_benchmark_step_names.py`` pins that list; the dead readers --
    ``kernel.flash_bwd.*``, ``moe.grouped_matmul_share``,
    ``kernel.moe_gmm.*`` -- stay off). Every entry that lists the cell
    ``moves`` the rate metric the cell reports."""
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train.8k", 1)
    assert all(1 <= len(x["why"]) <= 200 for x in (entry, cell))
    assert len(bench["configs"]) >= 12 and len(bench["workloads"]) >= 12
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"][0] == CELL
    assert {n: by_name[n]["source"] for n in NEW} == {
        "train.mfu.lg_moe": "host_clock",
        "kernel.flash_lg_fwd.roofline_share": "device_trace",
        "kernel.flash_lg_bwd.roofline_share": "device_trace",
        "train.dense_layer_share": "device_trace",
        "train.attn_gate_share": "device_trace",
        "attn.gate_mean": "program_counter"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers
    listing = [m for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", ())]
    assert {m["name"] for m in listing} == set(NEW + SHARED + (RATE,))
    workload = common.load_json("workloads", f"{CELL}.json")
    assert workload.get("rate_metric", RATE) == RATE
    assert all(m.get("moves", RATE) == RATE for m in listing)
    assert "weight_seed" not in workload


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


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key, nested groups
    whole; the depth, the experts and the vocabulary differ, are listed with
    their arithmetic, and the published counts stand beside them."""
    heads = [48, 64, 64, 64] * 10
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention"] * 10,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": heads}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == sorted(file["reduced"]) == \
        ["num_experts", "num_hidden_layers", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == file["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert file["published"] == {k: published[k] for k in differ}
    # the dense layer and one whole period, an eighth of the experts (at
    # least 8) and of the rows
    assert file["num_hidden_layers"] == {"published": 40, "train": 5}
    assert file["num_experts"] == file["num_local_experts"] >= 8
    assert file["router_experts"] == 256 and file["first_expert"] == 0
    assert file["num_experts"] * 8 == 256 and file["vocab_size"] * 8 == 100352
    # what the model class reads, derived from the nested groups
    full = published["rope_parameters"]["full_attention"]
    assert (file["head_dim_override"], file["sliding_num_attention_heads"],
            file["full_attention_period"], file["first_k_dense"],
            file["rope_theta"], file["sliding_rope_theta"],
            file["yarn_factor"], file["yarn_original_max_position_embeddings"],
            file["yarn_beta_fast"], file["yarn_beta_slow"],
            file["yarn_attention_factor"], file["routed_scaling_factor"],
            file["attn_head_gate"], file["router_scoring"]) == \
        (128, 64, 4, 1, full["rope_theta"], 10000, full["factor"],
         full["original_max_position_embeddings"], full["beta_fast"],
         full["beta_slow"], full["attention_factor"], 2.5, True, "sigmoid")
    assert set(heads[l] for l in range(40) if l % 4 == 0) == {48}
    assert set(heads[l] for l in range(40) if l % 4) == {64}
    for key in ("attn_head_gate", "no_qk_norm", "router_scoring",
                "no_selection_bias_no_aux_loss", "shared_expert_ungated",
                "hidden_act", "yarn_attention_factor", "router_trainable",
                "embed_init_std", "head_init_std", "head_dim_override",
                "sliding_num_attention_heads", "full_attention_period",
                "first_k_dense"):
        assert key in file["assumed"], key
    assert "eight TPU v5e chips" in file["deployment"]
    assert "memory_analysis()" in file["reduced"]["num_experts"]


def test_model_is_built_from_the_file_and_the_workload():
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "LagunaForCausalLM"
    assert (cfg.num_hidden_layers, cfg.first_k_dense,
            cfg.full_attention_period, cfg.num_attention_heads,
            cfg.sliding_num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.sliding_window, cfg.partial_rotary_factor,
            cfg.rope_theta, cfg.sliding_rope_theta, cfg.yarn_factor,
            cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow, cfg.yarn_attention_factor,
            cfg.attn_head_gate, cfg.intermediate_size, cfg.expert_width,
            cfg.shared_expert_intermediate_size, cfg.router_width,
            cfg.num_local_experts, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.router_scoring,
            cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.router_trainable, cfg.router_aux_loss_coef,
            cfg.report_expert_load, cfg.rms_norm_eps,
            cfg.tie_word_embeddings, cfg.qk_norm, cfg.qk_norm_per_head,
            cfg.attention_impl, cfg.flash_block_q, cfg.flash_block_k,
            cfg.remat, cfg.remat_policy, cfg.scan_layers,
            cfg.embed_init_std, cfg.head_init_std) == \
        (5, 1, 4, 48, 64, 8, 128, 512, 0.5, 500000, 10000, 64, 4096, 64, 1,
         1.4158883083359672, True, 8192, 512, 512, 256, 32, 0, 8, "sigmoid",
         2.5, True, False, 0.0, True, 1e-6, False, False, False, "flash",
         512, 512, True, "nothing", True, 1.0, 0.0002)
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert (wl["warmup_steps"], wl["check"]["probe_positions"]) == (3, 8192)
    # the keys benchmark/flops.py reads stand in the file
    assert flops.train_flops_per_token(sizes(), 8192) > 0


def parameters(**over):
    import jax
    import jax.numpy as jnp

    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(config(), sizes(**over), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_parameters_by_hand():
    """ISSUE 63's count: the dense layer 79.79 M (attention 29.46 + MLP
    50.33); a sliding expert layer 41.55 M outside its experts + 32 x 3.146
    M; the full expert layer 33.13 + 100.66; the sliced tables 51.4 M: 691.6
    M = 11.07 GB at 16 B, 12.45 with the bf16 copy; 490.3 M with 16 held.
    The whole model by the same function: 33.44 B, the published 33.4B."""
    attn = lambda heads: 2048 * 128 * (2 * heads + 16) + 2048 * heads
    norms, expert = 2 * 2048, 3 * 2048 * 512
    dense = attn(48) + 3 * 2048 * 8192 + norms
    outside = lambda heads: attn(heads) + norms + 2048 * 256 + expert
    assert [round(x / 1e6, 2) for x in (
        attn(48), 3 * 2048 * 8192, dense, outside(64), outside(48), expert)] \
        == [29.46, 50.33, 79.79, 41.55, 33.13, 3.15]
    want = lambda held: dense + 3 * outside(64) + outside(48) \
        + 4 * held * expert + 2 * 12544 * 2048 + 2048
    assert parameters() == want(32) == 691623936
    assert parameters(num_local_experts=16) == want(16) == 490297344
    assert [round(want(h) * 16 / 1e9, 2) for h in (32, 16)] == [11.07, 7.84]
    assert round(want(32) * 18 / 1e9, 2) == 12.45
    # 1 + 9 x 4 layers are built; the published 40 end in three more
    # sliding layers
    built = parameters(num_hidden_layers=37, num_local_experts=256,
                       router_experts=None, report_expert_load=False,
                       vocab_size=100352)
    whole = built + 3 * (outside(64) + 256 * expert)
    assert round(whole / 1e9, 2) == 33.44
    active = whole - 39 * 248 * expert        # 8 of 256 experts a token
    assert round(active / 1e9, 2) == 3.02


def test_a_token_needs_802_mflop_forward_and_where():
    """ISSUE 63's arithmetic: the dense layer 260.3 (projections 58.9, core
    100.7 at a mean of 4,096.5 keys, MLP 100.7), a sliding layer 105.6
    (projections 75.8, core 16.3 at a mean of 496 keys, router 1.05, shared
    6.3, one held expert a token 6.3), the full expert layer 173.2, head
    51.4: 801.8, the attention of both kinds 74%."""
    parts = laguna_costs.forward_parts(sizes(), 8192)
    proj = lambda heads: 2 * 2048 * (128 * (2 * heads + 16) + heads)
    want = {
        "dense_mlp": 3 * 2 * 2048 * 8192,
        "router": 4 * 2 * 2048 * 256,
        "shared_expert": 4 * 3 * 2 * 2048 * 512,
        "held_experts": 4 * (8 * 32 / 256) * 3 * 2 * 2048 * 512,
        "head": 2 * 2048 * 12544,
        "attn_proj_full": 2 * proj(48), "attn_proj_window": 3 * proj(64),
        "attention_full": 2 * 4 * 48 * 128 * 4096.5,
        "attention_window": 3 * 4 * 64 * 128
        * (512 * 513 / 2 + (8192 - 512) * 512) / 8192}
    assert parts == pytest.approx(want)
    assert flops.mean_attended_keys(8192, 512) == pytest.approx(496.03, 1e-5)
    mflop = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mflop == {"dense_mlp": 100.7, "router": 4.2, "shared_expert": 25.2,
                     "held_experts": 25.2, "head": 51.4,
                     "attn_proj_full": 117.8, "attn_proj_window": 227.3,
                     "attention_full": 201.4, "attention_window": 48.8}
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 801.8
    layer = lambda kind, n: (parts[f"attn_proj_{kind}"]
                             + parts[f"attention_{kind}"]) / n
    sparse = (parts["router"] + parts["shared_expert"]
              + parts["held_experts"]) / 4
    assert [round(x / 1e6, 1) for x in (
        layer("full", 2) + parts["dense_mlp"], layer("window", 3) + sparse,
        layer("full", 2) + sparse)] == [260.3, 105.6, 173.2]
    attention = sum(v for k, v in parts.items() if k.startswith("att"))
    assert round(100 * attention / total) == 74
    assert laguna_costs.train_flops_per_token(sizes(), 8192) == \
        pytest.approx(3 * total)
    assert laguna_costs.kinds(sizes()) == {
        "full": (2, 48, None), "window": (3, 64, 512)}
    assert laguna_costs.kinds(sizes(num_hidden_layers=37)) == {
        "full": (10, 48, None), "window": (27, 64, 512)}


def test_kept_pairs_and_tiles_pair_by_pair():
    """The pairs ``laguna_costs`` charges a kind are the pairs its mask
    keeps, counted one by one at a tiny size; at L = 8,192 under the 512
    window a 512-row query tile meets two key tiles and both are cut: 31
    tiles a head, none inside, 4,063,488 kept pairs of the 8,126,464 they
    compute (the full triangle: 136 tiles, 120 inside)."""
    T, W = 96, 16
    kept = sum(1 for i in range(T) for j in range(T) if 0 <= i - j < W)
    assert kept == T * flops.mean_attended_keys(T, W)
    assert sum(1 for i in range(T) for j in range(T) if j <= i) \
        == T * flops.mean_attended_keys(T)
    tiny = sizes(sliding_window=W)
    fwd = laguna_costs.flash_lg_fwd(tiny, 1, T, "window")
    assert fwd["flops"] == 4 * 128 * 64 * kept
    assert laguna_costs.flash_lg_fwd(tiny, 1, T, "full")["flops"] \
        == 4 * 128 * 48 * T * (T + 1) / 2

    def tiles(L, window, tile=512):
        """(tiles a head that hold a kept pair, tiles wholly kept): over a
        tile of queries ``q0..q1`` and keys ``k0..k1`` the distance ``i -
        j`` runs from ``q0 - k1`` to ``q1 - k0``, and a pair is kept where
        it lies in ``0..window - 1``."""
        last = float("inf") if window is None else window - 1
        met = inside = 0
        for q0 in range(0, L, tile):
            for k0 in range(0, L, tile):
                near, far = q0 - (k0 + tile - 1), q0 + tile - 1 - k0
                met += far >= 0 and near <= last
                inside += near >= 0 and far <= last
        return met, inside

    assert tiles(8192, 512) == (31, 0)
    assert tiles(8192, None) == (136, 120)
    assert tiles(8192, 1024) == (45, 15)        # mellum2's window
    kept = 8192 * flops.mean_attended_keys(8192, 512)
    assert (kept, 31 * 512 * 512) == (4063488, 8126464)
    s = sizes()
    window, full = (laguna_costs.flash_lg_fwd(s, 1, 8192, k)
                    for k in ("window", "full"))
    assert window == kernel_costs.flash_fwd(1, 8192, 64, 8, 128, 512)
    assert full == kernel_costs.flash_fwd(1, 8192, 48, 8, 128)
    assert window["flops"] == 512 * 64 * 4063488
    assert full["flops"] == 512 * 48 * 8192 * 4096.5
    assert (s["head_dim"], s["head_dim_override"]) == (42, 128)
    for kind in ("window", "full"):
        bwd = laguna_costs.flash_lg_bwd(s, 1, 8192, kind)
        assert bwd["flops"] == pytest.approx(
            2.5 * laguna_costs.flash_lg_fwd(s, 1, 8192, kind)["flops"])
    # the full calls are bound by operations, the window calls by bytes
    peaks = common.load_json("peaks.json")[TPU["kind"]]
    bound = lambda c: kernel_costs.least_seconds(c, TPU["kind"])[1]
    assert (bound(full), bound(window)) == ("flops", "flops")
    assert peaks["bf16_flops_per_s"] == 197e12


def test_cost_readers_know_their_own_cells():
    assert laguna_costs.is_laguna(sizes())
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b",
                  "keye-vl2-30b-a3b", "phi4-mini-flash", "mixtral-8x7b",
                  "mellum2-12b-a2.5b", "qwen3-next-80b-a3b", "ouro-2.6b",
                  "sdar-30b-a3b"):
        assert not laguna_costs.is_laguna(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))


# -- the check ---------------------------------------------------------------

@pytest.fixture(scope="module")
def engine40():
    """The cell's context, kind and ONE engine at the tiny size, seed 40:
    the sound check builds and compiles it, and every wrong computation is
    checked on it -- ``kinds/train.py model_logits`` traces the model anew
    at every call, so a patch in force shows in the logits the check
    compares, while the compiled train step stays the sound one."""
    ctx, kind = tiny_context(CELL, 40)
    return ctx, kind, kind.build_engine(ctx, ctx["sizes"])


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40])
def test_engine_matches_reference_on_one_device(engine40, seed):
    ctx, kind, engine = engine40
    ok, stats = kind.check(ctx, engine, ctx["sizes"]) if seed == 40 \
        else train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, a dense layer and two periods of two head
    # counts, 2 of the router's 8 experts held from the third on
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5
    assert (ctx["sizes"]["num_hidden_layers"], ctx["sizes"]["first_expert"],
            ctx["sizes"]["num_attention_heads"],
            ctx["sizes"]["sliding_num_attention_heads"]) == (9, 2, 4, 6)


@pytest.mark.parametrize("name", [
    "window_off", "top1_routing", *laguna_wrong.WRONG, "reference_fp8_e4m3",
    "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(engine40, name):
    """Each thing of the gate, the head counts, the windows, the rotations,
    the router or the two feed-forwards left out or replaced, and the
    reference one precision down, is far outside the tolerance of the
    cell's own check."""
    ctx, kind, engine = engine40
    if name in ("window_off", "top1_routing"):    # the harness's controls
        ok, stats = train_check(CELL, 40, name)
    else:
        how = laguna_wrong.reference_from_float8(
            *((4, 3) if name.endswith("e4m3") else (5, 2))) \
            if name.startswith("reference_fp8") else laguna_wrong.wrong(name)
        with how:
            ok, stats = kind.check(ctx, engine, ctx["sizes"])
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert stats["logit_rel_l2"] > 50 * ctx["workload"]["check"][
        "logit_rel_l2_tol"]


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(laguna_wrong.WRONG) == {
        "gate_left_out", "full_grouping_on_window_layers",
        "all_layers_window", "all_columns_rotated",
        "full_table_on_window_layers", "attention_factor_left_out",
        "softmax_scores", "routed_scale_left_out", "topk_not_normalised",
        "shared_expert_left_out", "dense_layer_at_an_experts_width"}
    assert callable(laguna_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.laguna as laguna
    import deepspeed_tpu.models.llama as llama

    names = [(llama, "_head_gate"), (llama, "repeat_kv"),
             (laguna, "kind_config"), (laguna, "rope_tables"),
             (laguna, "_shared_expert"), (laguna, "_SwiGLU")]
    before = [m.__dict__[k] for m, k in names]
    for name in laguna_wrong.WRONG:
        with laguna_wrong.wrong(name):
            assert sum(m.__dict__[k] is not v
                       for (m, k), v in zip(names, before)) == 1, name
    assert all(m.__dict__[k] is v for (m, k), v in zip(names, before))


# -- the readers -------------------------------------------------------------

LEAD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/ds.layer_stack/" \
    "leading/ds.layer_dense/"
FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/ds.layer_stack/" \
    "periods/while/body/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/model/" \
    "ds.layer_stack/periods/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 500, "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/"
         "model/ds.embed/gather"],
        ["fusion.1", 1000, 1000,
         LEAD + "checkpoint/block_0/self_attn/ds.attn_proj/dot"],
        ["ds_flash_fwd", 2000, 3000,
         LEAD + "checkpoint/block_0/self_attn/ds.attention/pallas_call"],
        ["fusion.2", 5000, 200,
         LEAD + "checkpoint/block_0/self_attn/ds.attn_gate/mul"],
        ["fusion.3", 5500, 1500, LEAD + "checkpoint/block_0/mlp/ds.mlp/dot"],
        ["ds_flash_fwd", 7000, 1000, FWD + "ds.layer_window/checkpoint/"
         "block_0/self_attn/ds.attention/pallas_call"],
        ["fusion.4", 8000, 300, FWD + "ds.layer_window/checkpoint/block_0/"
         "self_attn/ds.attn_gate/mul"],
        ["fusion.5", 8500, 500, FWD + "ds.layer_window/checkpoint/block_1/"
         "shared_expert/ds.moe_shared/dot"],
        ["ds_flash_bwd", 10000, 5000, BWD + "ds.layer_full/checkpoint/"
         "block_3/self_attn/ds.attention/pallas_call"],
        ["ds_flash_bwd", 15000, 2000, BWD + "ds.layer_window/checkpoint/"
         "block_2/self_attn/ds.attention/pallas_call"],
        ["fusion.8", 17000, 1500, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(M)/ds.lm_head_loss/dot"],
        ["fusion.9", 19000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.1", 30000, 1000,
         LEAD + "checkpoint/block_0/self_attn/ds.attn_proj/dot"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"],
             *[["ds.counters", 1000 + 100 * i, 10,
                {"step": 10 + i, "attn_gate_mean": 0.5 + 0.001 * i,
                 "moe_held_rows_over_expected": 1.0}, "python"]
               for i in range(5)]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_shares_of_the_dense_layer_and_the_gate_on_a_hand_made_trace():
    """Busy 17,500 ns: the dense layer's 1,000 + 3,000 + 200 + 1,500 by
    path; under ``ds.attn_gate`` 200 + 300 (innermost scope); the window
    layers' 1,000 + 300 + 500 + 2,000; the full layer's 5,000."""
    run = run_of(HAND)
    assert reader("train.dense_layer_share").read(run) == \
        pytest.approx(100 * 5700 / 17500)
    assert reader("train.attn_gate_share").read(run) == \
        pytest.approx(100 * 500 / 17500)
    assert reader("train.window_layer_share").read(run) == \
        pytest.approx(100 * 3800 / 17500)
    assert reader("train.full_layer_share").read(run) == \
        pytest.approx(100 * 5000 / 17500)
    assert reader("moe.shared_expert_share").read(run) == \
        pytest.approx(100 * 500 / 17500)
    assert reader("attn.gate_mean").read(run) == pytest.approx(0.502)
    for name in NEW:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_lg_rooflines_sum_the_kinds_calls():
    """A step's five forward calls -- two of 48 heads under the causal
    table, three of 64 under the window's -- against five times the trace's
    mean call; the backward alike."""
    run = run_of(HAND)
    s = sizes()
    least = lambda fn: sum(n * kernel_costs.least_seconds(
        fn(s, 1, 8192, kind), TPU["kind"])[0]
        for kind, (n, _, _) in laguna_costs.kinds(s).items())
    assert reader("kernel.flash_lg_fwd.roofline_share").read(run) == \
        pytest.approx(100 * least(laguna_costs.flash_lg_fwd)
                      / (5 * 2000e-9))
    assert reader("kernel.flash_lg_bwd.roofline_share").read(run) == \
        pytest.approx(100 * least(laguna_costs.flash_lg_bwd)
                      / (5 * 3500e-9))
    cpu = {**run, "device": {"platform": "cpu"}}
    assert reader("kernel.flash_lg_fwd.roofline_share").read(cpu) is None


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=30000.0, chips=1)
    want = 100 * laguna_costs.train_flops_per_token(sizes(), 8192) * 30000.0 \
        / 197e12
    assert reader("train.mfu.lg_moe").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("train.mfu.lg_moe").read(
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
    ("kimi-vl-a3b.train.8k", "scope_trace_train_kimi_8k.json")])
def test_new_readers_find_nothing_in_another_program(name, other, fixture):
    """A program without these layers (the other cells' recorded traces, as
    the parent commit runs them): None, no exception."""
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """Another program's trace under this cell's own name (the driver lays
    the benchmark's files over the parent's checkout): no
    ``ds.layer_dense``, ``ds.attn_gate`` or counter, so those read None; the
    flash forward kernel is there."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    for name in ("train.dense_layer_share", "train.attn_gate_share",
                 "attn.gate_mean"):
        assert reader(name).read(run) is None
    assert reader("kernel.flash_lg_fwd.roofline_share").read(run) > 0


def test_every_new_reader_reads_the_cells_own_recorded_steps():
    """A cut of the cell's traced run on the v5e (PR 63 call 1: 750 ms, two
    steps and parts of their neighbours): the window layers two fifths of
    busy time by path, the dense layer and the full layer a fifth each; the
    flash forward a third of its roofline on the kept pairs; no
    ``ds.counters`` event falls inside so short a cut, so the gauges read
    None here."""
    run = run_of(recording("scope_trace_train_laguna_8k.json"),
                 tokens_per_s=30530.0, chips=1)
    got = {name: reader(name).read(run) for name in NEW}
    assert got["attn.gate_mean"] is None
    assert got["train.dense_layer_share"] == pytest.approx(17.68, abs=0.01)
    assert got["train.attn_gate_share"] == pytest.approx(2.03, abs=0.01)
    assert got["kernel.flash_lg_fwd.roofline_share"] == \
        pytest.approx(35.86, abs=0.01)
    assert got["kernel.flash_lg_bwd.roofline_share"] == \
        pytest.approx(56.46, abs=0.01)
    assert got["train.mfu.lg_moe"] == pytest.approx(37.28, abs=0.01)
    # the shared readers the cell is listed under read it too
    for name, about in (("train.window_layer_share", 41.96),
                        ("train.full_layer_share", 19.31),
                        ("train.attention_share", 30.57),
                        ("train.attn_proj_share", 24.71),
                        ("train.head_loss_share", 4.32),
                        ("train.optimizer_share", 8.91),
                        ("train.recompute_share", 2.2),
                        ("moe.expert_share", 10.95),
                        ("moe.router_share", 1.455),
                        ("moe.shared_expert_share", 1.56),
                        # read, not listed (see the first test)
                        ("train.unnamed_share", 3.92)):
        assert reader(name).read(run) == pytest.approx(about, rel=0.01), name


def test_the_recorded_flash_calls_are_of_two_kinds():
    """The step's flash calls by the outer scope of their path: under
    ``ds.layer_window`` (64 heads, the window's tile table: 31 tiles a head,
    every one cut) a forward call takes 3.6 ms, under ``ds.layer_full`` and
    ``ds.layer_dense`` (48 heads, the causal table: 136 tiles a head, 120
    inside) 9.1 ms -- 1.83 and 1.39 microseconds a tile."""
    trace = recording("scope_trace_train_laguna_8k.json")
    calls = {}
    for name, _, ns, op in trace["devices"]["/device:TPU:0"]:
        if name.startswith("ds_flash_fwd"):
            kind = next(k for k in ("window", "full", "dense")
                        if f"ds.layer_{k}" in op)
            calls.setdefault(kind, []).append(ns / 1e6)
    assert {k: len(v) for k, v in calls.items()} == {
        "dense": 3, "window": 9, "full": 3}
    mean = lambda v: sum(v) / len(v)
    assert mean(calls["window"]) == pytest.approx(3.62, abs=0.02)
    assert mean(calls["full"]) == pytest.approx(9.06, abs=0.03)
    assert mean(calls["dense"]) == pytest.approx(9.06, abs=0.03)
    assert 1e3 * mean(calls["window"]) / (64 * 31) == \
        pytest.approx(1.83, abs=0.02)
    assert 1e3 * mean(calls["full"]) / (48 * 136) == \
        pytest.approx(1.39, abs=0.02)


@pytest.mark.parametrize("limit, sound, wrong", [
    # largest of 15 sound sets / the harness's accepted limit for a loss
    # that the layers hardly move: the broken-outright reading of layer 0
    # at an expert's width on one seed
    ("loss_gap_tol", 6.0634e-07, 2.2737e-05),
    # largest of 15 sound sets / YaRN's factor left off cos and sin, the
    # nearest wrong computation
    ("logit_rel_l2_tol", 0.04127, 0.0984)])
def test_each_limit_of_the_timed_size_lies_between_its_two_chip_readings(
        limit, sound, wrong):
    """The cell file's ``check.why`` has where each reading came from (my
    chip runs, PR 63, call 1)."""
    tol = common.load_json("workloads", f"{CELL}.json")["check"][limit]
    assert 1.4 * sound < tol < wrong / 1.4
    why = common.load_json("workloads", f"{CELL}.json")["check"]["why"]
    for name in ("factor left off", "window_off", "softmax scores",
                 "grouping", "under the window", "2.5 left out",
                 "all 128 columns", "gate left out", "e5m2", "e4m3",
                 "full layers' table", "top1_routing", "expert's width",
                 "shared expert left out", "not normalised"):
        assert name in why, name
