"""The configuration ``keye-vl2-30b-a3b`` and its cell
``keye-vl2-30b-a3b.train.16k``: the file against the catalog row, parameters
and required operations by hand, the cell's correctness check at tiny size on
one CPU device (passes over seeds; every wrong computation ISSUE 39 lists
fails it), and the readers the cell brings, on a hand-made trace and on a cut
of a real chip trace of the cell."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, sa_costs, scope_reduce
import keye_vl2_wrong

CELL = "keye-vl2-30b-a3b.train.16k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.sa_moe", "train.sa_index_share", "train.sa_select_share",
       "train.sa_loss_share", "kernel.flash_sa_fwd.roofline_share",
       "kernel.flash_sa_bwd.roofline_share", "kernel.sa_probs.roofline_share",
       "sa.kept_tile_share")
#: the readers other cells have too, here under the names that move the
#: cell's own rate metric (``train_tokens_per_s_per_chip.trajectory``)
SHARED = tuple(f"{name}.trajectory" for name in (
    "train.step_ms_p50", "device.idle_share.train",
    "train.attention_share", "train.attn_proj_share",
    "train.head_loss_share", "train.optimizer_share",
    "train.recompute_share", "train.host_gap_ms_per_step",
    "moe.expert_share", "moe.grouped_matmul_share",
    "moe.compact_hit_share", "moe.rows_max_over_mean",
    "moe.held_rows_over_expected"))
SA = {"indexer_head_dim": 64, "indexer_num_heads": 16,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": 2048}


def reader(name):
    return common.load_file_module("layer_metrics", name)


def config():
    return common.load_json("configs", "keye-vl2-30b-a3b.json")


def sizes():
    return common.sizes_of(config(), "train")


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    """The catalog row's ``config``, each under its own key, nested groups
    whole; the three cuts differ, are listed with their arithmetic, and the
    published counts stand beside."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "sa_config": SA, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    file = config()
    differ = sorted(k for k, v in published.items()
                    if file.get(k, "absent") != v)
    assert differ == sorted(file["reduced"]) == \
        ["num_hidden_layers", "num_local_experts", "vocab_size"]
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == "keye-vl2-30b-a3b")
    assert sorted(entry["reduced"]) == differ
    assert entry["source"] == file["source"]
    assert file["published"] == {k: published[k] for k in differ}
    assert file["num_hidden_layers"]["published"] == 48
    assert file["num_hidden_layers"] == {"published": 48, "train": 4}
    assert (file["num_local_experts"] * 8, file["vocab_size"] * 8) == \
        (128, 151936)
    for text in file["reduced"].values():
        assert len(text) > 200
    for item in ("vision_tower", "mrope_section", "qk_norm_per_head",
                 "indexer_rotary_and_key_norm", "indexer_score_scale",
                 "chunk_sizes_are_tile_sizes", "hadamard_and_float8",
                 "router_aux_loss_coef", "router_trainable",
                 "indexer_loss_coefficient", "seeded_init",
                 "reference_from_the_description"):
        assert len(file["assumed"][item]) > 40, item


def test_model_is_built_from_the_file_and_the_workload():
    """``common.sizes_of`` hands on numbers only: the workload's ``model``
    carries the nested ``sa_config``, the file's flat ``sa_*`` keys carry it
    to the reference, and the three say the same."""
    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    assert wl["model"]["sa_config"] == file["sa_config"] == SA
    assert (file["sa_topk"], file["sa_indexer_num_heads"],
            file["sa_indexer_head_dim"]) == (2048, 16, 64)
    tiny_sa = wl["tiny"]["model"]["sa_config"]
    assert (tiny_sa["topk"], tiny_sa["indexer_num_heads"],
            tiny_sa["indexer_head_dim"]) == tuple(
        file["tiny"][k] for k in ("sa_topk", "sa_indexer_num_heads",
                                  "sa_indexer_head_dim"))
    cfg, _ = common.build_model(file, sizes(), **wl["model"])
    assert (cfg.num_local_experts, cfg.router_width, cfg.first_expert,
            cfg.num_experts_per_tok, cfg.head_dim, cfg.expert_width,
            cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.rope_theta, cfg.rms_norm_eps,
            cfg.tie_word_embeddings, cfg.norm_topk_prob, cfg.qk_norm,
            cfg.qk_norm_per_head, cfg.router_trainable,
            cfg.router_aux_loss_coef, cfg.report_expert_load,
            cfg.attention_impl, cfg.sa_config.topk) == \
        (16, 128, 0, 8, 128, 768, 6144, 32, 4, 1e7, 1e-6, False, True, False,
         True, False, 0.0, True, "flash", 2048)
    # the published router and the package's one buffer: no knob of the cell
    assert not {"router_bias_update_rate", "compact_margin"} & \
        (set(wl["model"]) | set(file))
    # a row a step: the traffic the issue gives
    mix = common.load_json("traffic", "train.16k.json")
    assert (mix["kind"], mix["seq_len"], mix["sequences_per_chip"]) == \
        ("packed", 16384, 1)


def test_cell_states_its_weights_and_nothing_else_moved():
    """PR 46: the cell's file states whose weights it is measured on, and
    that alone: the optimizer, the traffic, the limits of `correct` and the
    warm-up are the ones PR 39 set; under ``tiny`` the key is null."""
    wl = common.load_json("workloads", f"{CELL}.json")
    mix = common.load_json("traffic", "train.16k.json")
    assert isinstance(wl["weight_seed"], int)
    assert wl["engine"]["optimizer"] == {"type": "AdamW",
                                         "params": {"lr": 1e-4}}
    assert "scheduler" not in wl["engine"]
    assert set(mix) == {"kind", "what", "seq_len", "sequences_per_chip"}
    assert wl["tiny"]["weight_seed"] is None
    assert wl["tiny"]["engine"]["optimizer"]["params"]["lr"] == 1e-3
    assert (wl["check"]["loss_gap_tol"], wl["check"]["logit_rel_l2_tol"],
            wl["check"]["probe_positions"], wl["warmup_steps"]) == \
        (3e-4, 0.13, 256, 3)


def test_parameters_by_hand():
    """A layer is 18.9 M of attention, 2.26 M of indexer, 0.26 M of router
    and 16 held experts of 4.7 M; the sliced table and head 77.8 M."""
    import jax
    import jax.numpy as jnp

    file = config()
    wl = common.load_json("workloads", f"{CELL}.json")
    _, model = common.build_model(file, sizes(), **wl["model"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    attention = 2048 * (4096 + 512 + 512) + 4096 * 2048 + 2 * 128
    indexer = 2048 * (1024 + 64 + 16) + 2 * 64
    router = 2048 * 128               # the gate alone: no bias on the choice
    layer = attention + indexer + router + 2 * 2048 \
        + 16 * 3 * 2048 * 768
    depth = file["num_hidden_layers"]["train"]
    want = depth * layer + 2 * 18992 * 2048 + 2048
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == want
    assert (round(attention / 1e6, 1), round(indexer / 1e6, 2),
            round(layer / 1e6, 1)) == (18.9, 2.26, 96.9)
    assert depth == 4 and round(want / 1e6, 1) == 465.4
    assert round(want * 16 / 1e9, 2) == 7.45


def test_a_token_needs_480_mflop_forward_and_where():
    parts = sa_costs.forward_parts(sizes(), 16384)
    selected = (2048 * 2049 / 2 + (16384 - 2048) * 2048) / 16384
    assert selected == pytest.approx(1920, abs=0.1)
    want = {
        "attn_proj": 4 * 2 * 2048 * 128 * (32 + 4 + 4 + 32),
        "sa_index_proj": 4 * 2 * 2048 * (1024 + 64 + 16),
        "sa_index_scores": 4 * 2 * 16 * 64 * 8192.5,
        "attention": 4 * 2 * 2 * 32 * 128 * selected,
        "router": 4 * 2 * 2048 * 128,
        "held_experts": 4 * (8 * 16 / 128) * 3 * 2 * 2048 * 768,
        "head": 2 * 2048 * 18992}
    assert parts == pytest.approx(want)
    assert round(sum(parts.values()) / 1e6) == 480
    # the selection path: the indexer and the core over the selected pairs
    layer = {k: v / 4 for k, v in parts.items() if k != "head"}
    path = layer["sa_index_proj"] + layer["sa_index_scores"] \
        + layer["attention"]
    assert round(100 * path / sum(layer.values())) == 53
    assert sa_costs.train_flops_per_token(sizes(), 16384) == pytest.approx(
        3 * sum(sa_costs.forward_parts(sizes(), 16384).values()))
    listed = [m["name"] for m in common.load_benchmark()["per_layer"]
              if CELL in m["workloads"]]
    assert sorted(listed) == sorted(NEW + SHARED)
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b"):
        assert not sa_costs.is_sa(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))


def test_kernel_costs_count_selected_pairs():
    """512 operations a selected pair a head forward, 2.5 times that
    backward, the scores alone for the head-mean; the mask at a bit a pair;
    the heads' own width, not ``hidden / heads``."""
    s = sizes()
    assert (s["head_dim"], s["head_dim_override"]) == (64, 128)
    pairs = 32 * 16384 * flops.mean_attended_keys(16384, 2048)
    fwd = sa_costs.flash_sa_fwd(1, 16384, 32, 4, 128, 2048)
    bwd = sa_costs.flash_sa_bwd(1, 16384, 32, 4, 128, 2048)
    probs = sa_costs.sa_probs(1, 16384, 32, 4, 128, 2048)
    assert fwd["flops"] == 512 * pairs == pytest.approx(515.4e9, rel=1e-3)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"])
    assert probs["flops"] == pytest.approx(fwd["flops"] / 2)
    dense = kernel_costs.flash_fwd(1, 16384, 32, 4, 128)
    assert fwd["flops"] / dense["flops"] == pytest.approx(0.234, abs=1e-3)
    assert fwd["bytes"] == dense["bytes"] + 16384 * 16384 // 8


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size, 2 of the router's 16 experts held: the
    # reference's share and both its loss terms are the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


#: what the flash branch of ``indexed_attention`` takes from a kernel's
#: module, not from its own file: (wrong computation, module, attribute)
FLASH_ENTRIES = (("relu_left_out", "sa_index", "index_scores"),
                 ("head_weights_left_out", "sa_index", "index_scores"),
                 ("index_loss_left_out", "sa_probs", "index_kl"))


def flash_context(seed):
    """The tiny cell on the branch the chip times: ``attention_impl``
    "flash", which off a TPU runs the kernels' modules' reference math."""
    ctx, kind = tiny_context(CELL, seed)
    ctx["workload"] = {**ctx["workload"], "model": {
        **ctx["workload"]["model"], "attention_impl": "flash"}}
    return ctx, kind


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, or the reference from float8 weights; ``name@flash``:
    on the flash branch of ``indexed_attention``."""
    name, _, impl = name.partition("@")
    ctx, kind = flash_context(seed) if impl else tiny_context(CELL, seed)
    how = keye_vl2_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else keye_vl2_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("name", [
    *keye_vl2_wrong.WRONG, "reference_fp8_e4m3", "reference_fp8_e5m2",
    *(f"{name}@flash" for name, _, _ in FLASH_ENTRIES)])
def test_a_wrong_computation_fails_the_check(seed, name):
    """Each thing of the layer or of the loss left out or replaced, and the
    reference one precision down, is far outside the tolerance: the logits
    refuse what changes the forward pass, the first loss what changes the
    indexer's term alone. The three the flash branch takes from a kernel's
    module are refused on that branch too (at PR 44's commit the script
    patched ``indexed_attention`` alone, and the branch the chip times ran
    the index scores right under ``relu_left_out``)."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    if name.startswith("index_loss_left_out"):
        assert stats["verdicts"]["logit_rel_l2"]
        assert stats["loss_gap"] > 100 * tol["loss_gap_tol"]
    else:
        assert stats["logit_rel_l2"] > 10 * tol["logit_rel_l2_tol"]


@pytest.mark.parametrize("seed", [40, 41])
def test_flash_branch_matches_reference_on_one_device(seed):
    """Unpatched, the branch the chip times passes the same check."""
    ctx, kind = flash_context(seed)
    assert ctx["workload"]["model"]["attention_impl"] == "flash"
    ok, stats = kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                           ctx["sizes"])
    assert ok, stats
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


@pytest.mark.parametrize("name,module,attribute", FLASH_ENTRIES)
def test_wrong_computation_patches_what_the_flash_branch_calls(
        name, module, attribute):
    """By name: the kernel module's entry is among the computation's
    patches, the flash branch of ``indexed_attention`` is where it is read
    (at call time, so a patch reaches it), and the replacement takes the
    arguments that branch passes."""
    import importlib
    import inspect

    import deepspeed_tpu.models.indexed_attention as ia

    mod = importlib.import_module(f"deepspeed_tpu.ops.pallas.{module}")
    patched = {(m, k) for m, make in keye_vl2_wrong.WRONG[name]
               for k in make(m)}
    assert (mod, attribute) in patched
    assert any(m is ia for m, _ in patched)        # the XLA path's as well
    source = inspect.getsource(ia.indexed_attention)
    flash = source[source.index('attention_impl == "flash"'):]
    assert (f"{module}.{attribute}(" in flash
            or f"{module} import {attribute}" in flash)
    real = inspect.signature(getattr(mod, attribute))
    with keye_vl2_wrong.wrong(name):
        fake = inspect.signature(getattr(mod, attribute))
    call = {"index_scores": (("qi", "ki", "w"), ("block_q", "block_k")),
            "index_kl": (("q", "k", "lse", "scores", "mask"),
                         ("block_q", "block_k", "tiles"))}[attribute]
    for sig in (real, fake):
        sig.bind(*call[0], **{k: 0 for k in call[1]})


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(keye_vl2_wrong.WRONG) == {
        "selection_left_out", "last_keys_for_top_keys", "relu_left_out",
        "head_weights_left_out", "top_half_of_topk", "index_loss_left_out",
        "head_norm_left_out", "held_experts_zeroed"}


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.indexed_attention as ia
    import deepspeed_tpu.models.llama as llama
    import deepspeed_tpu.models.mixtral as mixtral

    import deepspeed_tpu.ops.pallas.sa_index as sa_index
    import deepspeed_tpu.ops.pallas.sa_probs as sa_probs

    names = [(ia, "select_mask"), (ia, "index_scores"),
             (ia, "index_loss"),
             (llama, "RMSNorm"), (mixtral, "_routed_experts"),
             (sa_index, "index_scores"), (sa_probs, "index_kl")]
    before = [getattr(m, k) for m, k in names]
    for name in keye_vl2_wrong.WRONG:
        with keye_vl2_wrong.wrong(name):
            # one thing of the model, on each path that computes it
            assert sum(getattr(m, k) is not v
                       for (m, k), v in zip(names, before)) == \
                len(keye_vl2_wrong.WRONG[name])
    assert all(getattr(m, k) is v for (m, k), v in zip(names, before))


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/layers/while/body/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 1000, FWD + "block/self_attn/ds.attn_proj/dot"],
        ["fusion.2", 1000, 600, FWD + "block/self_attn/ds.sa_index/dot"],
        ["fusion.3", 1600, 400, FWD + "block/self_attn/ds.sa_select/while"],
        ["ds_flash_fwd", 2000, 2000,
         FWD + "block/self_attn/ds.attention/pallas_call"],
        ["ds_sa_probs", 4000, 1000,
         FWD + "block/self_attn/ds.sa_loss/pallas_call"],
        ["fusion.4", 5000, 200, FWD + "block/self_attn/ds.sa_loss/reduce"],
        ["ds_flash_bwd_dq", 7000, 2000, BWD + "ds.attention/pallas_call"],
        ["ds_flash_bwd_dkv", 10000, 3000, BWD + "ds.attention/pallas_call"],
        ["fusion.8", 13000, 800, BWD + "ds.sa_index/dot"],
        ["fusion.9", 15000, 1000, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 20000, {}, "python"]] + [
        ["ds.counters", 1000 * i, 10, {"step": i, "sa_kept_tile_share": 0.8,
                                       "sa_index_loss": 0.1}, "python"]
        for i in range(1, 6)],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_share_readers_on_a_hand_made_trace():
    run = run_of(HAND)        # busy: 12,000 ns
    assert reader("train.sa_index_share").read(run) == \
        pytest.approx(100 * 1400 / 12000)
    assert reader("train.sa_select_share").read(run) == \
        pytest.approx(100 * 400 / 12000)
    assert reader("train.sa_loss_share").read(run) == \
        pytest.approx(100 * 1200 / 12000)
    assert reader("sa.kept_tile_share").read(run) == pytest.approx(80.0)
    for name in ("train.sa_index_share", "train.sa_select_share",
                 "train.sa_loss_share", "sa.kept_tile_share"):
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_rooflines_are_least_time_over_the_time_of_a_call():
    """One forward call of 2000 ns, one backward call of 2000 + 3000 ns, one
    head-mean call of 1000 ns; the least times are bound by operations."""
    run = run_of(HAND)
    args = (1, 16384, 32, 4, 128, 2048)
    for name, cost, ns in (
            ("kernel.flash_sa_fwd.roofline_share",
             sa_costs.flash_sa_fwd(*args), 2000),
            ("kernel.flash_sa_bwd.roofline_share",
             sa_costs.flash_sa_bwd(*args), 5000),
            ("kernel.sa_probs.roofline_share", sa_costs.sa_probs(*args),
             1000)):
        assert cost["flops"] / 197e12 > cost["bytes"] / 819e9
        assert reader(name).read(run) == pytest.approx(
            100 * cost["flops"] / 197e12 / (ns * 1e-9))


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=10000.0, chips=1)
    want = 100 * 3 * sum(sa_costs.forward_parts(
        sizes(), 16384).values()) * 10000.0 / 197e12
    assert reader("train.mfu.sa_moe").read(run) == pytest.approx(want)
    assert reader("train.mfu.sa_moe").read(
        {**run, "device": {"platform": "cpu"}}) is None


def recording(name="scope_trace_train_keye_16k.json"):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other", ["olmoe-1b-7b.train.4k",
                                   "mistral-7b.train.8k",
                                   "kimi-vl-a3b.train.8k",
                                   "zaya1-8b.train.8k"])
def test_new_readers_find_nothing_in_another_program(name, other):
    """A program without a learned selection (the other cells' recorded
    traces, as the parent commit runs them): None, no exception."""
    fixture = {"olmoe-1b-7b.train.4k": "scope_trace_train_olmoe_4k.json",
               "mistral-7b.train.8k": "scope_trace_train_8k.json",
               "kimi-vl-a3b.train.8k": "scope_trace_train_kimi_8k.json",
               "zaya1-8b.train.8k": "scope_trace_train_zaya1_8k.json"}[other]
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_in_the_cells_own_parent():
    """The parent commit asked for this cell's readers on ANOTHER program's
    trace under this cell's name (the driver lays the benchmark's files
    over the parent's checkout): the kernel and scope readers find no
    ``ds.sa_*`` scope and no ``ds_sa_probs`` and return None."""
    run = run_of(recording("scope_trace_train_olmoe_4k.json"), cell=CELL,
                 tokens_per_s=1.0, chips=1)
    for name in ("train.sa_index_share", "train.sa_select_share",
                 "train.sa_loss_share", "kernel.sa_probs.roofline_share",
                 "sa.kept_tile_share"):
        assert reader(name).read(run) is None


# -- a cut of a real chip trace of the cell ----------------------------------

def cell_metrics():
    """The cell's readers of the scope trace (the idle share reads
    ``trace_reduce``'s numbers, the host gap needs whole ``train_batch``
    spans: neither is a share of this cut)."""
    return [m["name"] for m in common.load_benchmark()["per_layer"]
            if CELL in m["workloads"] and m["source"] == "device_trace"
            and m["name"] not in ("device.idle_share.train.trajectory",
                                  "train.host_gap_ms_per_step.trajectory")]


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
                                   "ds_flash_bwd_dkv", "ds_sa_probs"}
    assert {"ds.attention", "ds.attn_proj", "ds.sa_index", "ds.sa_select",
            "ds.sa_loss", "ds.moe_router", "ds.moe_experts",
            "ds.lm_head_loss", "ds.optimizer", "ds.embed"} \
        <= set(r["by_scope"])
    # the selection path is most of the step
    path = sum(reader(n).read(run) for n in (
        "train.attention_share", "train.sa_index_share",
        "train.sa_select_share", "train.sa_loss_share"))
    assert 50 < path < 90
    # masked dense work: the kernels' time is the causal triangle's, their
    # required operations the selection's 23% of it
    assert 5 < reader("kernel.flash_sa_fwd.roofline_share").read(run) < 25
    assert 5 < reader("kernel.flash_sa_bwd.roofline_share").read(run) < 25
    assert 5 < reader("kernel.sa_probs.roofline_share").read(run) < 25
