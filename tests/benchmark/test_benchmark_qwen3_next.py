"""The configuration ``qwen3-next-80b-a3b`` and its cell
``qwen3-next-80b-a3b.train.8k``: what ``BENCHMARK.json`` gained for them, the
file against the catalog row, parameters and required operations by hand, the
cell's correctness check at tiny size on one CPU device (passes over seeds;
every wrong computation ISSUE 52 lists fails it), and the seven readers the
cell brings, on a hand-made trace, on the cell's own recorded step and on
other programs' recordings."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, gdn_costs, kernel_costs
import qwen3_next_wrong

CELL = "qwen3-next-80b-a3b.train.8k"
NAME = "qwen3-next-80b-a3b"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.gdn_moe", "train.gdn_layer_share", "train.gdn_rule_share",
       "train.gdn_mix_share", "gdn.chunk_decay_max",
       "kernel.flash_ga_fwd.roofline_share",
       "kernel.flash_ga_bwd.roofline_share")
#: the readers other cells have too, which READ something on this one
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step",
          "moe.expert_share", "moe.shared_expert_share",
          "moe.compact_hit_share", "moe.rows_max_over_mean",
          "moe.held_rows_over_expected", "train.full_layer_share",
          "moe.router_share")
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/" \
    "main/config.json"


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", f"{NAME}.json")


def sizes(**over):
    return {**common.sizes_of(config(), "train"), **over}


# -- what BENCHMARK.json gained ---------------------------------------------

def test_the_benchmark_gained_one_configuration_one_cell_and_seven_metrics():
    """One configuration, one cell on one chip under the traffic that
    stands, seven per-layer metrics that list the cell alone, and the cell's
    name in sixteen lists that stood: the rate's and the fifteen shared
    readers' (``train.unnamed_share`` reads the cell too and does not list
    it: ``test_benchmark_step_names.py`` pins that list). Nothing here says the entries are the file's LAST: a later
    cell is appended behind them."""
    bench = common.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "train.8k", 1)
    assert all(len(x["why"]) <= 200 for x in (entry, cell))
    assert len(bench["configs"]) >= 9 and len(bench["workloads"]) >= 9
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["workloads"][0] == CELL
        assert m["moves"] == "train_tokens_per_s_per_chip"
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


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key; the depth, the
    experts and the vocabulary differ, are listed with their arithmetic, and
    the published counts stand beside them."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
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
    # one whole period, at least 8 experts, at least an eighth of the rows
    assert file["num_hidden_layers"] == {"published": 48, "train": 4}
    assert file["num_experts"] == file["num_local_experts"] >= 8
    assert file["router_experts"] == 512 and 512 % file["num_experts"] == 0
    assert file["vocab_size"] * 8 == 151936
    assert file["head_dim_override"] == file["head_dim"]
    for key in ("no_mtp_head", "router_aux_loss_coef", "A_log_and_dt_bias",
                "conv_bias", "router_trainable", "embed_init_std",
                "head_init_std", "num_local_experts", "head_dim_override",
                "gdn_chunk"):
        assert key in file["assumed"], key
    chips = 512 // file["num_experts"]
    assert f"{chips} TPU v5e chips" in file["deployment"] or \
        {16: "sixteen", 32: "thirty-two"}[chips] in file["deployment"]
    assert "memory_analysis()" in file["reduced"]["num_experts"]


def test_model_is_built_from_the_file_and_the_workload():
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    cfg, model = common.build_model(file, sizes(), **wl["model"])
    assert type(model).__name__ == "Qwen3NextForCausalLM"
    assert (cfg.router_width, cfg.first_expert, cfg.num_experts_per_tok,
            cfg.head_dim, cfg.rotary_dim, cfg.expert_width,
            cfg.shared_expert_intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.linear_num_key_heads,
            cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim,
            cfg.full_attention_interval, cfg.gdn_chunk, cfg.rope_theta,
            cfg.rms_norm_eps, cfg.tie_word_embeddings, cfg.norm_topk_prob,
            cfg.router_trainable, cfg.router_aux_loss_coef,
            cfg.report_expert_load, cfg.attention_impl, cfg.sliding_window,
            cfg.remat, cfg.remat_policy, cfg.scan_layers,
            cfg.embed_init_std, cfg.head_init_std) == \
        (512, 0, 10, 256, 64, 512, 512, 16, 2, 16, 32, 128, 128, 4, 4, 64,
         10000000, 1e-6, False, True, False, 0.0, True, "flash", None, True,
         "nothing", True, 1.0, 0.0002)
    assert cfg.num_local_experts == file["num_experts"]
    mix = common.load_json("traffic", "train.8k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 8192, 1)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    # every position is probed: the full layer's part of a logit falls with
    # the keys a query attends, and the last 256 alone hide a wrong rotation
    assert (wl["warmup_steps"], wl["check"]["probe_positions"]) == (3, 8192)


def parameters(**over):
    import jax
    import jax.numpy as jnp

    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(config(), sizes(**over), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def test_parameters_by_hand():
    """A delta-rule mixer 33.7 M, a full mixer 27.3 M, router and shared
    expert 4.2 M a layer, an expert 3.15 M, the sliced tables 77.8 M: 626 M
    = 10.0 GB at 16 B with 32 held (ISSUE 52), 425 M = 6.8 GB with 16."""
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    shared = 3 * 2048 * 512 + 2048
    expert = 3 * 2048 * 512
    assert [round(x / 1e6, 2) for x in (gdn, full, 2048 * 512 + shared,
                                        expert)] == [33.72, 27.26, 4.2, 3.15]
    want = lambda held: 3 * gdn + full + 4 * (
        2 * 2048 + 2048 * 512 + shared + held * expert) \
        + 2 * 18992 * 2048 + 2048
    assert parameters(num_local_experts=32) == want(32) == 625667136
    assert parameters(num_local_experts=16) == want(16) == 424340544
    assert [round(want(h) * 16 / 1e9, 1) for h in (32, 16)] == [10.0, 6.8]
    assert parameters() == want(config()["num_experts"])


def test_a_token_needs_460_mflop_forward_and_where():
    """ISSUE 52's arithmetic (469, with the rule's chunks counted as whole
    products: about 6 a layer) with the rule charged what the RECURRENCE
    needs, 6 dk dv a value head: 3.15 MFLOP a layer whatever the chunk. The
    chunked form the program runs spends 4.45 at chunk 64, the pairs its
    causal masks leave: observed, charged to nothing."""
    parts = gdn_costs.forward_parts(sizes(num_local_experts=32), 8192)
    rule = 32 * 6 * 128 * 128
    assert gdn_costs.rule_per_token(sizes()) == rule
    assert gdn_costs.rule_per_token(sizes(gdn_chunk=128)) == rule
    assert round(rule / 1e6, 2) == 3.15
    chunked = 32 * 2 * (31.5 * 128 + 31.5 * 256 + 32.5 * 128 + 32.5 * 128
                        + 3 * 128 * 128)
    assert gdn_costs.chunked_rule_per_token(sizes(), 64) == chunked
    assert round(chunked / 1e6, 2) == 4.45
    want = {
        "gdn_proj": 3 * 2 * 2048 * (12288 + 64 + 4096),
        "gdn_rule": 3 * rule,
        "attn_proj": 2 * 2048 * 256 * (2 * 16 + 2 + 2 + 16),
        "attention": 2 * 2 * 16 * 256 * 4096.5,
        "router": 4 * 2 * 2048 * 512,
        "shared_expert": 4 * (3 * 2 * 2048 * 512 + 2 * 2048),
        "held_experts": 4 * (10 * 32 / 512) * 3 * 2 * 2048 * 512,
        "head": 2 * 2048 * 18992}
    assert parts == pytest.approx(want)
    assert flops.mean_attended_keys(8192) == 4096.5
    mflop = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mflop == {"gdn_proj": 202.1, "gdn_rule": 9.4, "attn_proj": 54.5,
                     "attention": 67.1, "router": 8.4, "shared_expert": 25.2,
                     "held_experts": 15.7, "head": 77.8}
    total = sum(parts.values())
    assert round(total / 1e6, 1) == 460.3
    # the delta-rule MIXERS (projections and rule) against the whole token
    assert round(100 * (parts["gdn_proj"] + parts["gdn_rule"]) / total,
                 1) == 46.0
    # a token meets 10 x 32 / 512 = 0.625 held experts a layer
    assert parts["held_experts"] / 4 / (3 * 2 * 2048 * 512) == 0.625
    assert gdn_costs.train_flops_per_token(sizes(num_local_experts=32),
                                           8192) == pytest.approx(3 * total)
    assert gdn_costs.layer_counts(sizes()) == (3, 1)
    assert gdn_costs.layer_counts(sizes(num_hidden_layers=48)) == (36, 12)
    # a longer chunk's own pairs cost more, the boundary products the same
    assert gdn_costs.chunked_rule_per_token(sizes(), 128) > chunked


def test_cost_readers_know_their_own_cells():
    assert gdn_costs.is_gdn_moe(sizes())
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b",
                  "keye-vl2-30b-a3b", "phi4-mini-flash", "mixtral-8x7b",
                  "mellum2-12b-a2.5b"):
        assert not gdn_costs.is_gdn_moe(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))


def test_kernel_costs_count_256_wide_heads():
    """1,024 operations a (query, attended key) pair a head forward, 2.5
    times that backward (five products to two), at the heads' own width
    (not ``hidden / heads``); k and v move once a key/value head."""
    s = sizes()
    assert (s["head_dim"], s["head_dim_override"]) == (128, 256)
    fwd = gdn_costs.flash_ga_fwd(s, 1, 8192)
    assert fwd == kernel_costs.flash_fwd(1, 8192, 16, 2, 256)
    assert fwd["flops"] == 1024 * 16 * 8192 * 4096.5
    assert fwd["bytes"] == 2 * 8192 * 256 * (2 * 16 + 2 * 2) + 4 * 16 * 8192
    assert gdn_costs.flash_ga_bwd(s, 1, 8192)["flops"] == \
        pytest.approx(2.5 * fwd["flops"])
    for cost in (fwd, gdn_costs.flash_ga_bwd(s, 1, 8192)):
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, two periods, 2 of the router's 8 experts held:
    # the reference's token-by-token rule is the engine's chunked one to
    # rounding
    assert stats["logit_rel_l2"] < 1e-4 and stats["loss_gap"] < 1e-5


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, a harness control, or the reference from float8."""
    if name == "top1_routing":
        return train_check(CELL, seed, name)
    ctx, kind = tiny_context(CELL, seed)
    how = qwen3_next_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else qwen3_next_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    "top1_routing", *qwen3_next_wrong.WRONG, "reference_fp8_e4m3",
    "reference_fp8_e5m2"])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the delta rule, the gates, the rotation, the router or
    the convolution left out or replaced, and the reference one precision
    down, is far outside the tolerance."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    assert stats["logit_rel_l2"] > 50 * tol["logit_rel_l2_tol"]


@pytest.mark.parametrize("limit, sound, wrong", [
    # largest of 38 sound sets / smallest of six float8 e5m2 references
    ("loss_gap_tol", 2.9041e-07, 1.1616e-06),
    # largest of 28 sound sets / all 256 columns rotated, the nearest
    ("logit_rel_l2_tol", 0.01452, 0.0200)])
def test_each_limit_of_the_timed_size_lies_between_its_two_chip_readings(
        limit, sound, wrong):
    """The cell file's ``check.why`` has where each reading came from (my
    chip runs, PR 52). The loss comes in whole float32 ulps of itself: its
    limit falls between two of them, not on one."""
    tol = common.load_json("workloads", f"{CELL}.json")["check"][limit]
    assert 1.15 * sound < tol < wrong / 1.15
    if limit == "loss_gap_tol":
        ulps = tol / 9.68e-08
        assert 0.25 < ulps - int(ulps) < 0.75


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(qwen3_next_wrong.WRONG) == {
        "beta_taken_as_one", "decay_left_out", "correction_left_out",
        "qk_unit_length_left_out", "attention_gate_left_out",
        "shared_gate_left_out", "all_columns_rotated",
        "weights_over_held_only", "conv_silu_left_out"}
    assert callable(qwen3_next_wrong.reference_from_float8)


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.mixtral as mixtral
    import deepspeed_tpu.models.qwen3_next as qn

    names = [(qn, "_beta"), (qn, "_log_decay"), (qn, "_unit_lower_solve"),
             (qn, "_unit_length"), (qn, "_attn_gate"), (qn, "_shared_gate"),
             (qn, "_conv_act"), (mixtral, "_expert_mlp"),
             (qn.Qwen3NextConfig, "rotary_dim")]
    before = [m.__dict__[k] for m, k in names]
    for name in qwen3_next_wrong.WRONG:
        with qwen3_next_wrong.wrong(name):
            assert sum(m.__dict__[k] is not v
                       for (m, k), v in zip(names, before)) == 1, name
    assert all(m.__dict__[k] is v for (m, k), v in zip(names, before))
    assert qn.Qwen3NextConfig.tiny().rotary_dim == 4


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/ds.layer_stack/" \
    "periods/while/body/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/model/" \
    "ds.layer_stack/periods/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.0", 0, 500, "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/"
         "model/ds.embed/gather"],
        ["fusion.1", 1000, 1000,
         FWD + "ds.layer_gdn/block_0/linear_attn/ds.attn_proj/dot"],
        ["fusion.2", 2000, 600,
         FWD + "ds.layer_gdn/block_0/linear_attn/ds.gdn_mix/mul"],
        ["fusion.3", 3000, 1400, FWD + "ds.layer_gdn/block_1/linear_attn/"
         "ds.gdn_rule/while/body/checkpoint/dot_general"],
        ["fusion.4", 4500, 500, FWD + "ds.layer_gdn/block_1/shared_expert/"
         "ds.moe_shared/dot"],
        ["ds_flash_fwd", 5000, 3000,
         FWD + "ds.layer_full/block_3/self_attn/ds.attention/pallas_call"],
        ["fusion.5", 8000, 500,
         FWD + "ds.layer_full/block_3/self_attn/ds.attn_gate/mul"],
        ["ds_flash_bwd", 10000, 5000, BWD + "ds.layer_full/"
         "ds.layer_full/checkpoint/block_3/self_attn/ds.attention/"
         "pallas_call"],
        ["fusion.6", 15000, 1000, BWD + "ds.layer_gdn/ds.layer_gdn/"
         "checkpoint/rematted_computation/block_2/linear_attn/ds.gdn_rule/"
         "while/body/dot_general"],
        ["fusion.8", 16000, 1500, "jit(ds_train_step)/ds.loss_and_grad/"
         "jvp(M)/ds.lm_head_loss/dot"],
        ["fusion.9", 18000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
        # outside the window: never counted
        ["fusion.1", 30000, 1000,
         FWD + "ds.layer_gdn/block_0/linear_attn/ds.attn_proj/dot"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"],
             *[["ds.counters", 1000 + 100 * i, 10,
                {"step": 10 + i, "gdn_chunk_decay_max": 1000.0 + 10 * i,
                 "moe_held_rows_over_expected": 1.0}, "python"]
               for i in range(5)]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_shares_of_the_delta_rule_layers_on_a_hand_made_trace():
    """Busy 16,000 ns: the delta-rule layers' 1,000 + 600 + 1,400 + 500 +
    1,000 by path; under ``ds.gdn_rule`` 1,400 + 1,000, under ``ds.gdn_mix``
    600 (innermost scopes); the full layer's 3,000 + 500 + 5,000."""
    run = run_of(HAND)
    assert reader("train.gdn_layer_share").read(run) == \
        pytest.approx(100 * 4500 / 16000)
    assert reader("train.gdn_rule_share").read(run) == \
        pytest.approx(100 * 2400 / 16000)
    assert reader("train.gdn_mix_share").read(run) == \
        pytest.approx(100 * 600 / 16000)
    assert reader("train.full_layer_share").read(run) == \
        pytest.approx(100 * 8500 / 16000)
    assert reader("moe.shared_expert_share").read(run) == \
        pytest.approx(100 * 500 / 16000)
    assert reader("gdn.chunk_decay_max").read(run) == pytest.approx(1020.0)
    for name in NEW:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_flash_ga_rooflines_are_per_call():
    run = run_of(HAND)
    s = sizes()
    least = lambda fn: kernel_costs.least_seconds(fn(s, 1, 8192),
                                                  TPU["kind"])[0]
    assert reader("kernel.flash_ga_fwd.roofline_share").read(run) == \
        pytest.approx(100 * least(gdn_costs.flash_ga_fwd) / 3000e-9)
    assert reader("kernel.flash_ga_bwd.roofline_share").read(run) == \
        pytest.approx(100 * least(gdn_costs.flash_ga_bwd) / 5000e-9)
    cpu = {**run, "device": {"platform": "cpu"}}
    assert reader("kernel.flash_ga_fwd.roofline_share").read(cpu) is None


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=30000.0, chips=1)
    want = 100 * gdn_costs.train_flops_per_token(sizes(), 8192) * 30000.0 \
        / 197e12
    assert reader("train.mfu.gdn_moe").read(run) == pytest.approx(want)
    assert 0 < want < 100
    assert reader("train.mfu.gdn_moe").read(
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
    """A program without delta-rule layers (the other cells' recorded
    traces, as the parent commit runs them): None, no exception."""
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """Another program's trace under this cell's own name (the driver lays
    the benchmark's files over the parent's checkout): no ``ds.layer_gdn``,
    ``ds.gdn_*`` scope or counter, so those read None; the flash forward
    kernel is there."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"),
                 tokens_per_s=1.0, chips=1)
    for name in ("train.gdn_layer_share", "train.gdn_rule_share",
                 "train.gdn_mix_share", "gdn.chunk_decay_max"):
        assert reader(name).read(run) is None
    assert reader("kernel.flash_ga_fwd.roofline_share").read(run) > 0


def test_every_new_reader_reads_the_cells_own_recorded_steps():
    """A cut of the cell's traced run on the v5e (PR 52: 700 ms, two steps
    and a few of their neighbours' operations, the delta rule's scan bodies
    merged an instruction a run): the delta-rule layers two thirds of busy
    time by path, their rule a quarter, the flash kernels bound by
    operations; three ``ds.counters`` events are under the four a mean
    needs, so the gauge reads None on this cut."""
    run = run_of(recording("scope_trace_train_qwen3_next_8k.json"),
                 tokens_per_s=24000.0, chips=1)
    got = {name: reader(name).read(run) for name in NEW}
    assert got["gdn.chunk_decay_max"] is None
    assert got["train.gdn_layer_share"] == pytest.approx(67.1, abs=0.1)
    assert got["train.gdn_rule_share"] == pytest.approx(25.7, abs=0.1)
    assert got["train.gdn_mix_share"] == pytest.approx(18.7, abs=0.1)
    assert got["kernel.flash_ga_fwd.roofline_share"] == \
        pytest.approx(61.0, abs=0.1)
    assert got["kernel.flash_ga_bwd.roofline_share"] == \
        pytest.approx(81.8, abs=0.1)
    assert got["train.mfu.gdn_moe"] == pytest.approx(16.82, abs=0.01)
    # the shared readers the cell is listed under read it too
    for name, about in (("train.full_layer_share", 11.6),
                        ("moe.shared_expert_share", 1.46),
                        ("train.attention_share", 4.08),
                        ("train.attn_proj_share", 16.5),
                        ("moe.expert_share", 9.4),
                        ("moe.router_share", 2.21),
                        ("train.recompute_share", 20.9),
                        # read, not listed (see SHARED)
                        ("train.unnamed_share", 6.34)):
        assert reader(name).read(run) == pytest.approx(about, rel=0.02), name
    from benchmark import counters
    assert [e["step"] for e in counters.events(run)] == [143, 144, 145]
