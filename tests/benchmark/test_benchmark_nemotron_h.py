"""The configuration ``nemotron-3-nano-30b-a3b`` and its cell
``nemotron-3-nano-30b-a3b.train.8k``: what ``BENCHMARK.json`` gained for them
(entries found by NAME: a later cell is appended behind them), the file
against the catalog row, parameters and required operations term by term, the
cell's correctness check at tiny size on one CPU device (passes over seeds;
every wrong computation ISSUE 66 lists fails it), and the five readers the
cell brings, on a hand-made trace, on the cell's own recorded step and on
other programs' recordings."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, ssd_costs
import nemotron_h_wrong

CELL = "nemotron-3-nano-30b-a3b.train.8k"
NAME = "nemotron-3-nano-30b-a3b"
RATE = "train_tokens_per_s_per_chip.trajectory"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.ssd_moe", "kernel.flash_nh_fwd.roofline_share",
       "kernel.flash_nh_bwd.roofline_share", "train.mamba_layer_share",
       "train.moe_layer_share")
#: the shared readers' twins under the rate metric this cell reports (its
#: step follows what a seeded, frozen router sends its held experts: two
#: sets of six runs spread 2.5% and 1.8%, PERF.md section 6), each the
#: shared reader's own ``read``
SHARED = tuple(f"{name}.trajectory" for name in (
    "train.step_ms_p50", "device.idle_share.train", "train.attention_share",
    "train.head_loss_share", "train.optimizer_share",
    "train.recompute_share", "moe.expert_share",
    "train.host_gap_ms_per_step", "train.attn_proj_share",
    "moe.compact_hit_share", "moe.rows_max_over_mean",
    "moe.held_rows_over_expected"))
#: shared readers that READ the cell and have no twin, so list it nowhere
UNLISTED = ("train.full_layer_share", "train.ssm_scan_share",
            "train.ssm_mix_share", "ssm.chunk_decay_max", "moe.router_share",
            "moe.shared_expert_share")
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/" \
    "blob/main/config.json"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", f"{NAME}.json")


def sizes(**over):
    return {**common.sizes_of(config(), "train"), **over}


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_five_metrics():
    """One configuration, one cell on one chip under the traffic that
    stands, five per-layer metrics that list the cell, and the cell's name
    in the ``.trajectory`` rate's list and the twelve live twins' (the rule
    of ISSUE 58 that ISSUE 66 restates: its two sets of six runs spread over
    0.5%, so the file states ``weight_seed`` and ``rate_metric``; a shared
    reader without a twin -- ``UNLISTED`` -- stays off; so do
    ``train.unnamed_share``, whose list ``test_benchmark_step_names.py``
    pins, the dead readers -- ``kernel.flash_bwd.*``,
    ``moe.grouped_matmul_share`` and its twin, ``kernel.moe_gmm.*`` -- and
    Mamba-1's ``kernel.ssm_scan_*``). Every entry that lists the cell
    ``moves`` the rate metric the cell reports."""
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train.8k", 1)
    assert all(1 <= len(x["why"]) <= 200 for x in (entry, cell))
    assert len(bench["configs"]) >= 13 and len(bench["workloads"]) >= 13
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"][0] == CELL
    assert {n: by_name[n]["source"] for n in NEW} == {
        "train.mfu.ssd_moe": "host_clock",
        "kernel.flash_nh_fwd.roofline_share": "device_trace",
        "kernel.flash_nh_bwd.roofline_share": "device_trace",
        "train.mamba_layer_share": "device_trace",
        "train.moe_layer_share": "device_trace"}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} <= layers
    listing = [m for m in bench["end_to_end"] + bench["per_layer"]
               if CELL in m.get("workloads", ())]
    workload = common.load_json("workloads", f"{CELL}.json")
    assert workload["rate_metric"] == RATE
    assert workload["weight_seed"] in range(6)
    assert {m["name"] for m in listing} == set(NEW + SHARED + (RATE,))
    assert all(m.get("moves", RATE) == RATE for m in listing)


STATING = ["keye-vl2-30b-a3b.train.16k", "sdar-30b-a3b.train.8k", CELL]


@pytest.mark.parametrize("name", [
    *SHARED, "moe.grouped_matmul_share.trajectory"])
def test_a_twin_is_the_shared_reader_for_the_three_cells_that_state_its_rate(
        name):
    """``test_benchmark_sdar.py``'s test of the twins, with the part this
    cell ended -- the cells that state the rate are keye 16k and sdar 8k
    ALONE -- asserted by its meaning: ``<metric>.trajectory`` runs
    ``<metric>``'s own ``read``; its entry is the shared one's but for its
    name, what it moves and its list; its list holds the cells whose files
    state ``rate_metric``, and the shared entry lists none of them."""
    base = name[:-len(".trajectory")]
    assert reader(name).read.__code__.co_filename.endswith(
        os.path.join("layer_metrics", f"{base}.py"))
    bench = common.load_benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    keys = ("unit", "better", "source", "layer")
    assert [listed[name][k] for k in keys] == [listed[base][k] for k in keys]
    stating = [w["name"] for w in bench["workloads"]
               if common.load_json("workloads", f"{w['name']}.json").get(
                   "rate_metric") == RATE]
    assert stating == STATING
    assert listed[name]["moves"] == RATE
    # the dead reader's twin reads nothing and stays keye's (ISSUE 58)
    dead = name == "moe.grouped_matmul_share.trajectory"
    assert listed[name]["workloads"] == stating[:1 if dead else 3]
    assert not set(stating) & set(listed[base]["workloads"])
    run = {"observed": {"kind": "train", "fence_ms": [3.0, 1.0, 2.0]},
           "cell": {"name": CELL}, "trace": None, "scope_trace": None,
           "counters": None}
    assert reader(name).read(run) == (
        2.0 if base == "train.step_ms_p50" else None)


def test_benchmark_names_only_files_that_exist():
    bench = common.load_benchmark()
    here = os.path.dirname(os.path.abspath(common.__file__))
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert os.path.exists(os.path.join(common.ROOT, entry["file"]))
    for parts in (("workloads", f"{CELL}.json"), ("configs", f"{NAME}.json"),
                  ("traffic", "train.8k.json"), ("kinds", "train.py"),
                  ("reference", f"{config()['reference']}.py")):
        assert os.path.exists(os.path.join(here, *parts)), parts
    for name in NEW + SHARED + UNLISTED:
        assert os.path.exists(os.path.join(here, "layer_metrics",
                                           f"{name}.py")), name


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key; the depth, the
    held experts and the vocabulary differ, are listed with their
    arithmetic, and the published counts stand beside them."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == sorted(file["reduced"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == NAME)
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == file["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert file["published"] == {k: published[k] for k in differ}
    # published layers 0-8, a sixteenth of the experts (the floor of 8), an
    # eighth of the rows
    assert file["num_hidden_layers"] == {"published": 52, "train": 9}
    assert file["first_layer"] == 0
    assert PATTERN[:9] == "MEMEM*EME" and len(PATTERN) == 52
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == \
        (23, 23, 6)
    assert file["n_routed_experts"] == file["num_local_experts"] == 8
    assert file["router_experts"] == 128 and file["first_expert"] == 0
    assert file["vocab_size"] * 8 == 131072
    assert (file["head_dim_override"], file["rms_norm_eps"]) == \
        (file["head_dim"], file["norm_eps"])
    for key in ("split_order", "norm_after_gate_a_group", "no_dt_clamp",
                "seeding", "no_positional_encoding", "no_selection_bias",
                "no_router_loss", "intermediate_size", "ungated_experts",
                "head_dim_override", "first_layer", "mamba_width",
                "router_trainable"):
        assert key in file["assumed"], key
    assert "sixteen TPU v5e chips" in file["deployment"]
    assert "memory_analysis()" in file["reduced"]["num_hidden_layers"]


def test_the_pattern_is_stated_alike_everywhere():
    """The file's string for the record, the class default the model reads
    (a configuration hands on numbers alone), the reference's and the cost
    functions' own copies."""
    from deepspeed_tpu.models import nemotron_h

    ref = common.load_file_module("reference", "nemotron_h")
    assert config()["hybrid_override_pattern"] == PATTERN \
        == nemotron_h.PUBLISHED_PATTERN == ref.PATTERN == ssd_costs.PATTERN
    assert nemotron_h.NemotronHConfig().hybrid_override_pattern == PATTERN


def test_model_is_built_from_the_file_and_the_workload():
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "NemotronHForCausalLM"
    assert (cfg.pattern, cfg.first_layer, cfg.num_hidden_layers,
            cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel,
            cfg.chunk_size, cfg.time_step_min, cfg.time_step_max,
            cfg.time_step_floor, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.rotary_dim,
            cfg.sliding_window, cfg.expert_width,
            cfg.moe_shared_expert_intermediate_size, cfg.router_width,
            cfg.num_local_experts, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.router_scoring,
            cfg.routed_scaling_factor, cfg.norm_topk_prob,
            cfg.expert_activation, cfg.router_trainable,
            cfg.router_aux_loss_coef, cfg.report_expert_load,
            cfg.rms_norm_eps, cfg.tie_word_embeddings, cfg.attention_impl,
            cfg.flash_block_q, cfg.flash_block_k, cfg.remat,
            cfg.remat_policy, cfg.scan_layers, cfg.embed_init_std,
            cfg.head_init_std, cfg.vocab_size) == \
        ("MEMEM*EME", 0, 9, 2688, 64, 64, 128, 8, 4, 128, 0.001, 0.1, 0.0001,
         32, 2, 128, 0, None, 1856, 3712, 128, 8, 0, 6, "sigmoid", 2.5, True,
         "relu2", False, 0.0, True, 1e-5, False, "flash", 512, 512, True,
         "nothing", True, 1.0, 0.0002, 16384)
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert (wl["warmup_steps"], wl["check"]["probe_positions"]) == (3, 8192)
    # the keys benchmark/flops.py reads stand in the file
    assert flops.train_flops_per_token(sizes(), 8192) > 0
    # the tiny preset: every kind twice, two groups, a grouping of 4, a
    # share that starts past expert 0
    tiny, _ = common.build_model(file, common.sizes_of(file, "train", True))
    assert (tiny.pattern, tiny.n_groups, tiny.num_attention_heads
            // tiny.num_key_value_heads, tiny.first_expert) == \
        ("EM*EMEMEM*", 2, 4, 2)


def parameters(**over):
    import jax
    import jax.numpy as jnp

    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(config(), sizes(**over), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_parameters_term_by_term():
    """ISSUE 66's count: a Mamba layer 38.74 M (in 2688 x 10,304 = 27.70,
    out 4096 x 2688 = 11.01, taps, biases, A_log, D, dt_bias, two norms);
    the attention layer 23.40 M (q 11.01, k + v 1.38, o 11.01); an expert
    layer 8 x 9.978 + 19.96 + 0.34 = 100.1 M; tables 88.1 M: 667.0 M = 10.67
    GB at 16 B, 12.0 with the bf16 copy; 528.1 M at depth 7; 986 M with 16
    held. The model's own shapes say the same, and the whole model by the
    same function is 31.58 B, 3.23 B active: the published 31.6B-A3.2B."""
    conv = 4096 + 2 * 8 * 128
    mamba = 2688 * (4096 + conv + 64) + 4096 * 2688 + 5 * conv + 3 * 64 \
        + 4096 + 2688
    attn = 2688 * 128 * (32 + 2 + 2) + 32 * 128 * 2688 + 2688
    expert, shared, router = 2 * 2688 * 1856, 2 * 2688 * 3712, 2688 * 128
    moe = lambda held: held * expert + shared + router + 2688
    assert [round(x / 1e6, 2) for x in (mamba, attn, expert, shared, router,
                                        moe(8))] == \
        [38.74, 23.4, 9.98, 19.96, 0.34, 100.13]
    tables = 2 * 16384 * 2688 + 2688
    want = lambda held: 4 * mamba + attn + 4 * moe(held) + tables
    assert ssd_costs.parameters(sizes()) == want(8) == parameters() \
        == 666962944
    assert ssd_costs.layer_parameters(sizes(), 8) == {
        "M": mamba, "*": attn, "E": moe(8)}
    assert [round(want(h) * 16 / 1e9, 2) for h in (8, 16)] == [10.67, 15.78]
    assert round(want(8) * 18 / 1e9, 1) == 12.0
    assert round(want(16) / 1e6) == 986 and want(16) * 18 > 16.91e9
    assert ssd_costs.parameters(sizes(num_hidden_layers=7)) == \
        3 * mamba + attn + 3 * moe(8) + tables == 528092736
    whole = sizes(num_hidden_layers=52, num_local_experts=128,
                  vocab_size=131072)
    assert round(ssd_costs.parameters(whole) / 1e9, 2) == 31.58
    assert ssd_costs.parameters(whole) == \
        23 * mamba + 6 * attn + 23 * moe(128) + 2 * 131072 * 2688 + 2688
    assert round(ssd_costs.parameters(whole, active=True) / 1e9, 2) == 3.23


def test_a_token_needs_715_mflop_forward_and_where():
    """ISSUE 66's arithmetic: a Mamba layer 80.1 (in 55.4, out 22.0, the
    recurrence 2.6 at 5 P N a head whatever the chunk, the convolution
    0.05); an expert layer 48.1 (router 0.7, shared 39.9, 0.375 held experts
    a token 7.5); the attention layer 113.9 (projections 46.8, core 67.1 at
    a mean of 4,096.5 keys); head 88.1: 714.7 -- the mixers 45%, the expert
    layers 27 (the shared expert 22), attention 16, the head 12."""
    parts = ssd_costs.forward_parts(sizes(), 8192)
    mflop = {k: round(v / 1e6, 2) for k, v in parts.items()}
    assert mflop == {
        "ssm_in_proj": 221.58, "ssm_out_proj": 88.08, "ssm_conv": 0.2,
        "ssm_recurrence": 10.49, "attn_proj": 46.79, "attention": 67.12,
        "router": 2.75, "shared_expert": 159.65, "held_experts": 29.93,
        "head": 88.08}
    # written out term by term
    assert parts["ssm_in_proj"] == 4 * 2 * 2688 * (4096 + 6144 + 64)
    assert parts["ssm_recurrence"] == 4 * 5 * 64 * 64 * 128
    assert parts["ssm_conv"] == 4 * 2 * 4 * 6144
    assert parts["attention"] == 2 * 2 * 32 * 128 * 4096.5
    assert parts["held_experts"] == 4 * (6 * 8 / 128) * 2 * 2 * 2688 * 1856
    assert parts["shared_expert"] == 4 * 2 * 2 * 2688 * 3712
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 714.7
    assert ssd_costs.train_flops_per_token(sizes(), 8192) == 3 * total
    share = lambda *keys: round(100 * sum(parts[k] for k in keys) / total)
    assert share("ssm_in_proj", "ssm_out_proj", "ssm_conv",
                 "ssm_recurrence") == 45
    assert share("router", "shared_expert", "held_experts") == 27
    assert (share("shared_expert"), share("attn_proj", "attention"),
            share("head")) == (22, 16, 12)
    # at a tiny size, every term by hand
    tiny = dict(hidden_size=8, mamba_num_heads=2, mamba_head_dim=4,
                n_groups=1, ssm_state_size=3, conv_kernel=4,
                num_attention_heads=4, num_key_value_heads=2,
                head_dim_override=2, num_local_experts=2, router_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=5,
                moe_shared_expert_intermediate_size=6, vocab_size=10,
                num_hidden_layers=3, first_layer=4)      # "M*E"
    assert ssd_costs.kinds(tiny) == {"M": 1, "*": 1, "E": 1}
    assert ssd_costs.forward_parts(tiny, 4) == {
        "ssm_in_proj": 2 * 8 * (8 + 14 + 2), "ssm_out_proj": 2 * 8 * 8,
        "ssm_conv": 2 * 4 * 14, "ssm_recurrence": 5 * 2 * 4 * 3,
        "attn_proj": 2 * 8 * 2 * (4 + 2 + 2 + 4),
        "attention": 2 * 2 * 4 * 2 * 2.5, "router": 2 * 8 * 4,
        "shared_expert": 2 * 2 * 8 * 6, "held_experts": 1.0 * 2 * 2 * 8 * 5,
        "head": 2 * 8 * 10}
    assert ssd_costs.parameters(tiny) == (
        8 * 24 + 8 * 8 + 5 * 14 + 3 * 2 + 8 + 8) + (
        8 * 2 * 8 + 8 * 8 + 8) + (8 * 4 + 2 * 2 * 8 * 5 + 2 * 8 * 6 + 8) \
        + 2 * 10 * 8 + 8


def test_cost_readers_know_their_own_cells():
    assert ssd_costs.is_nemotron_h(sizes())
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b",
                  "keye-vl2-30b-a3b", "phi4-mini-flash", "mixtral-8x7b",
                  "mellum2-12b-a2.5b", "qwen3-next-80b-a3b", "ouro-2.6b",
                  "sdar-30b-a3b", "laguna-xs.2"):
        assert not ssd_costs.is_nemotron_h(common.sizes_of(
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
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5
    assert (ctx["sizes"]["num_hidden_layers"], ctx["sizes"]["first_layer"],
            ctx["sizes"]["first_expert"], ctx["sizes"]["n_groups"]) == \
        (10, 3, 2, 2)


@pytest.mark.parametrize("name", [
    "top1_routing", *nemotron_h_wrong.WRONG, "other_pattern",
    "reference_fp8_e4m3", "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(engine40, name):
    """Each thing of the mixer, the attention, the router or the experts
    left out or replaced, the layers in another pattern's order, and the
    reference one precision down, is far outside the tolerance of the
    cell's own check (a step size without its softplus is refused for what
    it makes of the recurrence: nothing finite)."""
    ctx, kind, engine = engine40
    if name == "top1_routing":                   # the harness's control
        ok, stats = train_check(CELL, 40, name)
    else:
        how = nemotron_h_wrong.reference_from_float8(
            *((4, 3) if name.endswith("e4m3") else (5, 2))) \
            if name.startswith("reference_fp8") \
            else nemotron_h_wrong.reference_under_pattern() \
            if name == "other_pattern" else nemotron_h_wrong.wrong(name)
        with how:
            ok, stats = kind.check(ctx, engine, ctx["sizes"])
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert not stats["logit_rel_l2"] <= 20 * ctx["workload"]["check"][
        "logit_rel_l2_tol"]


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(nemotron_h_wrong.WRONG) == {
        "norm_before_gate", "one_norm_over_all_columns",
        "every_head_reads_group_0", "skip_left_out", "dt_bias_left_out",
        "softplus_left_out", "conv_bias_left_out", "conv_silu_left_out",
        "split_xbc_first", "relu_for_relu2", "silu_for_relu2",
        "softmax_scores", "routed_scale_left_out", "topk_not_normalised",
        "shared_expert_left_out", "attention_rotated", "kv_head_by_modulo"}
    assert nemotron_h_wrong.OTHER_PATTERN["MEMEM*EME"] == "MEM*EMEME"
    for own, other in nemotron_h_wrong.OTHER_PATTERN.items():
        assert sorted(own) == sorted(other) and own != other
    assert callable(nemotron_h_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.llama as llama
    import deepspeed_tpu.models.mixtral as mixtral
    import deepspeed_tpu.models.nemotron_h as nemotron_h

    names = [(llama, "repeat_kv"), (mixtral, "_ACTIVATIONS"),
             *((nemotron_h, k) for k in (
                 "_gated_norm", "_groups", "_skip", "_step_size",
                 "_conv_act", "_split", "MixtralSparseMoeBlock",
                 "SharedExpert", "LlamaAttention"))]
    before = [m.__dict__[k] for m, k in names]
    for name in nemotron_h_wrong.WRONG:
        with nemotron_h_wrong.wrong(name):
            assert sum(m.__dict__[k] is not v
                       for (m, k), v in zip(names, before)) == 1, name
    assert all(m.__dict__[k] is v for (m, k), v in zip(names, before))
    load = common.load_file_module
    with nemotron_h_wrong.reference_under_pattern():
        assert common.load_file_module is not load
    assert common.load_file_module is load


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/run_1/" \
    "ds.layer_stack/periods/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/model/run_1/" \
    "ds.layer_stack/periods/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 500, "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/"
         "model/ds.embed/gather"],
        ["fusion.1", 1000, 1000,
         FWD + "ds.layer_mamba/checkpoint/block_0/mixer/ds.ssm_mix/dot"],
        ["fusion.2", 2000, 600,
         FWD + "ds.layer_mamba/checkpoint/block_0/mixer/ds.ssm_scan/dot"],
        ["ds_flash_fwd", 3000, 2000, FWD + "ds.layer_full/checkpoint/"
         "block_1/self_attn/ds.attention/pallas_call"],
        ["fusion.3", 5000, 400, FWD + "ds.layer_moe/checkpoint/block_2/"
         "shared_expert/ds.moe_shared/dot"],
        ["fusion.4", 5500, 300, FWD + "ds.layer_moe/checkpoint/block_2/"
         "block_sparse_moe/ds.moe_experts/moe_gmm/ragged_dot"],
        ["ds_flash_bwd", 6000, 5000, BWD + "ds.layer_full/checkpoint/"
         "block_1/self_attn/ds.attention/pallas_call"],
        ["fusion.5", 11000, 1400, BWD + "ds.layer_mamba/checkpoint/"
         "rematted_computation/block_0/mixer/ds.ssm_scan/dot"],
        ["fusion.8", 13000, 1500, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(M)/ds.lm_head_loss/dot"],
        ["fusion.9", 15000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.1", 30000, 1000,
         FWD + "ds.layer_mamba/checkpoint/block_0/mixer/ds.ssm_mix/dot"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"],
             *[["ds.counters", 1000 + 100 * i, 10,
                {"step": 10 + i, "ssm_chunk_decay_max": 20.0 + i,
                 "moe_held_rows_over_expected": 1.0}, "python"]
               for i in range(5)]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_shares_of_the_layer_kinds_on_a_hand_made_trace():
    """Busy 13,700 ns: the Mamba layers' 1,000 + 600 + 1,400 by path; the
    expert layers' 400 + 300; the attention layer's 2,000 + 5,000; under
    ``ds.ssm_scan`` 600 + 1,400 (innermost scope)."""
    run = run_of(HAND)
    assert reader("train.mamba_layer_share").read(run) == \
        pytest.approx(100 * 3000 / 13700)
    assert reader("train.moe_layer_share").read(run) == \
        pytest.approx(100 * 700 / 13700)
    assert reader("train.full_layer_share").read(run) == \
        pytest.approx(100 * 7000 / 13700)
    assert reader("train.ssm_scan_share").read(run) == \
        pytest.approx(100 * 2000 / 13700)
    assert reader("train.ssm_mix_share").read(run) == \
        pytest.approx(100 * 1000 / 13700)
    assert reader("moe.shared_expert_share").read(run) == \
        pytest.approx(100 * 400 / 13700)
    assert reader("ssm.chunk_decay_max").read(run) == pytest.approx(22.0)
    for name in NEW:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_nh_rooflines_are_a_calls_least_time_over_its_time():
    """One forward call of 32 / 2 heads of 128 over the causal triangle at
    8,192 against the trace's call; the backward alike."""
    run = run_of(HAND)
    s = sizes()
    fwd, bwd = (kernel_costs.least_seconds(fn(s, 1, 8192), TPU["kind"])[0]
                for fn in (ssd_costs.flash_nh_fwd, ssd_costs.flash_nh_bwd))
    assert ssd_costs.flash_nh_fwd(s, 1, 8192) == kernel_costs.flash_fwd(
        1, 8192, 32, 2, 128)
    assert ssd_costs.flash_nh_fwd(s, 1, 8192)["flops"] == \
        4 * 128 * 32 * 8192 * 4096.5
    assert reader("kernel.flash_nh_fwd.roofline_share").read(run) == \
        pytest.approx(100 * fwd / 2000e-9)
    assert reader("kernel.flash_nh_bwd.roofline_share").read(run) == \
        pytest.approx(100 * bwd / 5000e-9)
    cpu = {**run, "device": {"platform": "cpu"}}
    assert reader("kernel.flash_nh_fwd.roofline_share").read(cpu) is None


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=30000.0, chips=1)
    want = 100 * ssd_costs.train_flops_per_token(sizes(), 8192) * 30000.0 \
        / 197e12
    assert reader("train.mfu.ssd_moe").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("train.mfu.ssd_moe").read(
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
    ("laguna-xs.2.train.8k", "scope_trace_train_laguna_8k.json")])
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
    ``ds.layer_mamba`` or ``ds.layer_moe``, so those read None; the flash
    forward kernel is there."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    for name in ("train.mamba_layer_share", "train.moe_layer_share"):
        assert reader(name).read(run) is None
    assert reader("kernel.flash_nh_fwd.roofline_share").read(run) > 0


def test_every_new_reader_reads_the_cells_own_recorded_steps():
    """A cut of the cell's traced run on the v5e (PR 66 call A: 600 ms, one
    whole step and parts of its neighbours): the Mamba layers two fifths of
    busy time by path, a third of that under ``ds.ssm_scan``; the flash
    forward 45% of its roofline at 32 / 2 heads; no ``ds.counters`` event
    falls inside so short a cut, so the gauges read None here."""
    run = run_of(recording("scope_trace_train_nemotron_h_8k.json"),
                 tokens_per_s=24758.0, chips=1)
    got = {name: reader(name).read(run) for name in NEW}
    assert got["train.mamba_layer_share"] == pytest.approx(40.83, abs=0.01)
    assert got["train.moe_layer_share"] == pytest.approx(16.02, abs=0.01)
    assert got["kernel.flash_nh_fwd.roofline_share"] == \
        pytest.approx(45.16, abs=0.01)
    assert got["kernel.flash_nh_bwd.roofline_share"] == \
        pytest.approx(68.98, abs=0.01)
    assert got["train.mfu.ssd_moe"] == pytest.approx(26.94, abs=0.01)
    # the shared readers read it too (under a twin's name where they have
    # one, in no list where they have none)
    for name, about in (("train.ssm_scan_share", 13.71),
                        ("train.ssm_mix_share", 26.90),
                        ("train.full_layer_share", 8.50),
                        ("train.attention_share", 5.76),
                        ("train.attn_proj_share", 2.45),
                        ("train.head_loss_share", 5.29),
                        ("train.optimizer_share", 4.12),
                        ("train.recompute_share", 8.22),
                        ("moe.expert_share", 5.96),
                        ("moe.router_share", 0.865),
                        ("moe.shared_expert_share", 8.89)):
        assert reader(name).read(run) == pytest.approx(about, rel=0.01), name
    for name in ("ssm.chunk_decay_max", "moe.compact_hit_share",
                 "moe.rows_max_over_mean", "moe.held_rows_over_expected"):
        assert reader(name).read(run) is None


def test_the_recorded_experts_products_carry_no_scope():
    """1856 columns are 14.5 lanes, so ``grouped_matmul.plan`` leaves the
    held experts' products to ``jax.lax.ragged_dot``, and XLA:TPU's expansion
    of it keeps no ``op_name``: a fifth of the recorded busy time stands under
    no ``ds.*`` scope (``ragged-dot-none`` and the ``conditional`` around the
    compact buffer), which ``moe.expert_share`` and ``train.moe_layer_share``
    therefore do not see (PERF.md section 7)."""
    from benchmark import scope_reduce
    from deepspeed_tpu.ops.pallas import grouped_matmul

    assert grouped_matmul.plan("tpu", 1, 6144, 2688, 1856, 8) is None
    assert grouped_matmul.plan("tpu", 1, 6144, 2688, 1920, 8) is not None
    trace = recording("scope_trace_train_nemotron_h_8k.json")
    bare = {}
    for name, _, ns, op in trace["devices"]["/device:TPU:0"]:
        if scope_reduce.scope_of(op) == scope_reduce.UNSCOPED:
            kind = name.split(".")[0]
            bare[kind] = bare.get(kind, 0) + ns
    top = sorted(bare, key=bare.get, reverse=True)[:2]
    assert set(top) == {"ragged-dot-none", "conditional"}
    reduced = scope_reduce.reduce(trace)
    assert 100 * reduced["unscoped_share"] == pytest.approx(21.1, abs=0.1)


@pytest.mark.parametrize("limit, sound, wrong", [
    # largest of 33 sound sets / the harness's accepted limit for a loss
    # that the layers hardly move: the broken-outright readings (a SiLU for
    # relu^2 and the e4m3 reference, each on one seed)
    ("loss_gap_tol", 9.83e-07, 1.44e-05),
    # largest of 33 sound sets / softmax scores for sigmoid, the nearest
    # wrong computation the floor lets a limit refuse
    ("logit_rel_l2_tol", 0.04510, 0.1159)])
def test_each_limit_of_the_timed_size_lies_between_its_two_chip_readings(
        limit, sound, wrong):
    """The cell file's ``check.why`` has where each reading came from (my
    chip runs, PR 66, calls A and B), and says which wrong computation the
    floor hides: the one attention layer rotated reads 0.047-0.049 where
    sound weights read up to 0.045."""
    check = common.load_json("workloads", f"{CELL}.json")["check"]
    assert 1.4 * sound < check[limit] < wrong / 1.4
    for name in ("ROTATED", "NOT refused", "softmax scores", "i mod 2",
                 "group 0", "2.5 left out", "MEM*EMEME", "e5m2", "e4m3",
                 "all 4096 columns", "top1_routing", "norm before the gate",
                 "relu for relu^2", "dt_bias left out", "SiLU for relu^2",
                 "bias left out", "not normalised", "D x left out",
                 "shared expert left out", "[xBC ; z ; dt]",
                 "softplus left out"):
        assert name in check["why"], name
