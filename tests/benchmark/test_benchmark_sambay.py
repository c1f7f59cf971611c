"""The configuration ``phi4-mini-flash`` and its cell
``phi4-mini-flash.train.8k``: the file against the catalog row, parameters
and required operations by hand, the cell's correctness check at tiny size on
one CPU device (passes over seeds; every wrong computation ISSUE 41 lists
fails it), and the readers the cell brings, on a hand-made trace and on other
programs' recorded traces."""

import json
import os

import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, kernel_costs, scope_reduce, ssm_costs
import sambay_wrong

CELL = "phi4-mini-flash.train.8k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
NEW = ("train.mfu.ssm_hybrid", "train.ssm_scan_share", "train.ssm_mix_share",
       "train.gmu_share", "train.da_mix_share",
       "kernel.ssm_scan_fwd.roofline_share",
       "kernel.ssm_scan_bwd.roofline_share",
       "kernel.flash_da_fwd.roofline_share",
       "kernel.flash_da_bwd.roofline_share", "ssm.chunk_decay_max")
SHARED = ("train.step_ms_p50", "device.idle_share.train",
          "train.attention_share", "train.attn_proj_share",
          "train.head_loss_share", "train.optimizer_share",
          "train.recompute_share", "train.host_gap_ms_per_step")


def reader(name):
    return common.load_file_module("layer_metrics", name)


def sizes():
    return common.sizes_of(
        common.load_json("configs", "phi4-mini-flash.json"), "train")


# -- the configuration -------------------------------------------------------

def test_configuration_keeps_every_number_of_the_catalog_row():
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    config = common.load_json("configs", "phi4-mini-flash.json")
    differ = sorted(k for k, v in published.items()
                    if config.get(k, "absent") != v)
    assert differ == ["num_hidden_layers", "vocab_size"]
    # the self-decoder's depth is no key of the row (it is half the layers)
    assert sorted(config["reduced"]) == sorted(
        differ + ["self_decoder_layers"])
    entry = next(c for c in common.load_benchmark()["configs"]
                 if c["name"] == "phi4-mini-flash")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    assert config["published"] == {
        "num_hidden_layers": 32, "self_decoder_layers": 16,
        "vocab_size": 200064}
    assert config["num_hidden_layers"] == {"published": 32, "train": 6}
    assert (config["self_decoder_layers"],
            config["cross_decoder_first_index"],
            config["vocab_size"] * 8) == (2, 16, 200064)
    cfg, _ = common.build_model(config, common.sizes_of(config, "train"))
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.sliding_window,
            cfg.layer_norm_eps, cfg.mb_per_layer, cfg.num_hidden_layers,
            cfg.self_layers, cfg.d_inner, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.dt_rank, cfg.mamba_conv_bias,
            cfg.mamba_proj_bias, cfg.tie_word_embeddings,
            cfg.report_ssm_decay) == \
        (2560, 10240, 40, 20, 64, 512, 1e-5, 2, 6, 2, 5120, 16, 4, 160,
         True, False, True, True)
    for item in ("reference_from_the_description", "mamba_sizes",
                 "attention_biases", "differential_attention", "no_rotary",
                 "seeded_init", "cross_decoder_first_index"):
        assert len(config["assumed"][item]) > 40, item
    assert "eight" in config["deployment"] and "rows" in config["deployment"]


def test_parameters_are_697_1_million():
    """2 x 119.90 + 2 x 98.32 + 104.87 + 91.77 M + the 64.0 M table, as the
    file's ``reduced`` says: 11.15 GB at 16 B each."""
    import jax
    import jax.numpy as jnp

    config = common.load_json("configs", "phi4-mini-flash.json")
    _, model = common.build_model(config, common.sizes_of(config, "train"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    mlp = 2560 * 20480 + 10240 * 2560
    norms = 4 * 2560
    mamba = 2560 * 10240 + (4 * 5120 + 5120) + 5120 * 192 \
        + (160 * 5120 + 5120) + 5120 * 16 + 5120 + 5120 * 2560
    attn = (2560 * 5120 + 5120) + (2560 * 2560 + 2560) + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    want = 2 * mamba + 2 * attn + gmu + cross + 6 * (mlp + norms) \
        + 25008 * 2560 + 2 * 2560
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == want
    assert round(want / 1e6, 1) == 697.1
    assert round(want * 16 / 1e9, 2) == 11.15


def test_a_token_needs_1528_mflop_forward_and_where():
    parts = ssm_costs.forward_parts(sizes(), 8192)
    window = flops.mean_attended_keys(8192, 512)
    full = (8192 + 1) / 2
    want = {
        "mlp": 6 * 3 * 2 * 2560 * 10240,
        "ssm_proj": 2 * 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120
                             + 5120 * 2560 + 4 * 5120),
        "ssm_scan": 2 * 9 * 5120 * 16,
        "attn_proj": 2 * 2 * 2560 * (2 * 2560 + 2 * 1280)
        + 2 * 2560 * 2 * 2560,
        "attention": 40 * (2 * 64 + 2 * 128) * (window + 2 * full),
        "gmu": 2 * 2 * 2560 * 5120,
        "head": 2 * 2560 * 25008}
    assert parts == pytest.approx(want)
    assert {k: round(v / 1e6, 1) for k, v in parts.items()} == {
        "mlp": 943.7, "ssm_proj": 164.6, "ssm_scan": 1.5, "attn_proj": 104.9,
        "attention": 133.5, "gmu": 52.4, "head": 128.0}
    total = sum(parts.values())
    assert round(total / 1e6) == 1529
    assert ssm_costs.train_flops_per_token(sizes(), 8192) == \
        pytest.approx(3 * total)
    assert ssm_costs.layer_counts(sizes()) == {
        "mamba": 2, "window": 1, "full": 1, "gmu": 1, "cross": 1}
    listed = [m["name"] for m in common.load_benchmark()["per_layer"]
              if CELL in m["workloads"]]
    assert sorted(listed) == sorted(NEW + SHARED)
    # test_benchmark_step_names.py pins train.unnamed_share's list (PERF.md
    # section 7): the cell's share is read off the observation line
    assert "train.unnamed_share" not in listed
    for other in ("olmoe-1b-7b", "kimi-vl-a3b", "mistral-7b", "zaya1-8b"):
        assert not ssm_costs.is_ssm_hybrid(common.sizes_of(
            common.load_json("configs", f"{other}.json"), "train"))


def test_kernel_costs_by_hand():
    """The scan: 9 operations a (position, channel, state) forward, 27
    backward; ``u``, ``delta``, ``y`` and ``B``, ``C`` moved once at two
    bytes. The flash calls: 40 query heads of 64-wide keys and 128-wide
    values."""
    fwd = ssm_costs.selective_scan_fwd(1, 8192, 5120, 16)
    bwd = ssm_costs.selective_scan_bwd(1, 8192, 5120, 16)
    cells = 8192 * 5120
    assert fwd["flops"] == 9 * cells * 16
    assert fwd["bytes"] == 2 * (3 * cells + 2 * 8192 * 16) + 4 * 5120 * 17
    assert bwd["flops"] == 3 * fwd["flops"]
    assert bwd["bytes"] == 2 * (5 * cells + 4 * 8192 * 16) \
        + 2 * 4 * 5120 * 17
    assert fwd["flops"] / 197e12 < fwd["bytes"] / 819e9     # bytes bind
    da = ssm_costs.flash_da_fwd(sizes(), 1, 8192)
    assert da["flops"] == 8192 * 40 * 384 * 4096.5
    assert da["bytes"] == 2 * 8192 * 64 * (3 * 40 + 2 * 20) + 4 * 40 * 8192
    assert ssm_costs.flash_da_bwd(sizes(), 1, 8192, 512)["flops"] == \
        pytest.approx(2.5 * ssm_costs.flash_da_fwd(sizes(), 1, 8192,
                                                   512)["flops"])


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42])
def test_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    assert stats["logit_rel_l2"] < 1e-4 and stats["loss_gap"] < 1e-5


def wrong_check(seed, name):
    """(verdict, stats) of the cell's check with the system computing
    ``name`` wrongly, or the reference from float8 weights."""
    if name == "window_off":
        return train_check(CELL, seed, control="window_off")
    ctx, kind = tiny_context(CELL, seed)
    how = sambay_wrong.reference_from_float8(
        *((4, 3) if name.endswith("e4m3") else (5, 2))) \
        if name.startswith("reference_fp8") else sambay_wrong.wrong(name)
    with how:
        return kind.check(ctx, kind.build_engine(ctx, ctx["sizes"]),
                          ctx["sizes"])


#: the nearest ones (the chip's readings, workloads/...json check.why) twice
NEAREST = ("lambda_fixed_at_init", "conv_bias_left_out",
           "skip_connection_left_out", "memory_after_the_gate")


@pytest.mark.parametrize("name,seed", [
    *((name, 40) for name in (*sambay_wrong.WRONG, "window_off",
                              "reference_fp8_e4m3", "reference_fp8_e5m2")),
    *((name, 41) for name in NEAREST)])
def test_a_wrong_computation_fails_the_check(name, seed):
    """Each thing of the layers left out or replaced, and the reference one
    precision down, is refused: far outside the logits' tolerance, or not
    finite (a recurrence that grows)."""
    ok, stats = wrong_check(seed, name)
    tol = tiny_context(CELL, seed)[0]["workload"]["check"]
    assert not ok
    assert not stats["verdicts"]["finite"] or \
        stats["logit_rel_l2"] > 10 * tol["logit_rel_l2_tol"]


def test_every_wrong_computation_of_the_issue_is_there():
    assert set(sambay_wrong.WRONG) == {
        "memory_after_the_gate", "gmu_gate_left_out",
        "cross_attention_on_own_keys", "lambda_fixed_at_init",
        "pair_norm_left_out", "rescale_left_out",
        "skip_connection_left_out", "conv_bias_left_out",
        "softplus_left_out", "a_log_read_as_a", "positions_rotated"}


def test_wrong_computations_leave_the_model_as_it_was():
    import deepspeed_tpu.models.sambay as sambay

    names = ("_memory", "_gmu_gate", "_cross_kv", "_lambda", "_pair_norm",
             "_rescale", "_skip_weight", "causal_conv", "_step_size",
             "_decay_rate", "_positional")
    before = {k: getattr(sambay, k) for k in names}
    for name in sambay_wrong.WRONG:
        with sambay_wrong.wrong(name):
            assert sum(getattr(sambay, k) is not v
                       for k, v in before.items()) == 1
    assert all(getattr(sambay, k) is v for k, v in before.items())


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/ds.layer_stack/"
BWD = "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(M))/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.1", 0, 1000, FWD + "memory_layer/mixer/ds.ssm_mix/dot"],
        ["ds_ssm_scan_fwd", 1000, 2000,
         FWD + "memory_layer/mixer/ds.ssm_scan/pallas_call"],
        ["fusion.2", 3000, 500, FWD + "kv_layer/mixer/ds.attn_proj/dot"],
        ["ds_flash_fwd", 4000, 3000,
         FWD + "kv_layer/mixer/ds.attention/pallas_call"],
        ["ds_flash_fwd", 7000, 1000,
         FWD + "self_decoder/window/mixer/ds.attention/pallas_call"],
        ["fusion.3", 8000, 400, FWD + "kv_layer/mixer/ds.da_mix/mul"],
        ["fusion.4", 8400, 600, FWD + "cross_decoder/gmu/mixer/ds.gmu/dot"],
        ["ds_ssm_scan_bwd", 10000, 6000, BWD + "ds.ssm_scan/pallas_call"],
        ["ds_flash_bwd_dq", 16000, 2000, BWD + "ds.attention/pallas_call"],
        ["ds_flash_bwd_dkv", 18000, 3000, BWD + "ds.attention/pallas_call"],
        ["fusion.9", 21000, 500, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 25000, {}, "python"]],
}


def run_of(trace, kind="train", cell=CELL, **observed):
    return {"cell": cell, "device": TPU, "scope_trace": trace,
            "observed": {"kind": kind, **observed}}


def test_share_readers_on_a_hand_made_trace():
    run = run_of(HAND)        # busy: 20,000 ns
    assert reader("train.ssm_scan_share").read(run) == pytest.approx(40.0)
    assert reader("train.ssm_mix_share").read(run) == pytest.approx(5.0)
    assert reader("train.da_mix_share").read(run) == pytest.approx(2.0)
    assert reader("train.gmu_share").read(run) == pytest.approx(3.0)
    for name in NEW[1:5]:
        assert reader(name).read(run_of(HAND, kind="serve")) is None


def test_scan_rooflines_are_least_time_over_the_time_of_a_call():
    """One forward call of 2000 ns, one backward call of 6000 ns; both are
    bound by the bytes ANY implementation moves, at 819 GB/s."""
    run = run_of(HAND)
    fwd = ssm_costs.selective_scan_fwd(1, 8192, 5120, 16)
    bwd = ssm_costs.selective_scan_bwd(1, 8192, 5120, 16)
    assert reader("kernel.ssm_scan_fwd.roofline_share").read(run) == \
        pytest.approx(100 * fwd["bytes"] / 819e9 / 2000e-9)
    assert reader("kernel.ssm_scan_bwd.roofline_share").read(run) == \
        pytest.approx(100 * bwd["bytes"] / 819e9 / 6000e-9)


def test_flash_da_rooflines_sum_a_steps_calls():
    """A step calls the forward kernel once at window 512 and twice full;
    the trace's two forward calls average 2000 ns, the backward's two
    kernels 2000 + 3000: least times summed over three calls' time."""
    run = run_of(HAND)
    s = sizes()
    least = lambda fn, w: kernel_costs.least_seconds(
        fn(s, 1, 8192, w), TPU["kind"])[0]
    want = least(ssm_costs.flash_da_fwd, 512) \
        + 2 * least(ssm_costs.flash_da_fwd, None)
    assert reader("kernel.flash_da_fwd.roofline_share").read(run) == \
        pytest.approx(100 * want / (3 * 2000e-9))
    want = least(ssm_costs.flash_da_bwd, 512) \
        + 2 * least(ssm_costs.flash_da_bwd, None)
    assert reader("kernel.flash_da_bwd.roofline_share").read(run) == \
        pytest.approx(100 * want / (3 * 5000e-9))


def test_mfu_reader_counts_this_architecture():
    run = run_of(None, tokens_per_s=20000.0, chips=1)
    want = 100 * 3 * sum(ssm_costs.forward_parts(
        sizes(), 8192).values()) * 20000.0 / 197e12
    assert reader("train.mfu.ssm_hybrid").read(run) == pytest.approx(want)
    assert 46 < want < 47
    assert reader("train.mfu.ssm_hybrid").read(
        {**run, "device": {"platform": "cpu"}}) is None


def test_decay_counter_is_the_mean_of_the_windows_events():
    events = [["ds.counters", 1000 * i, 10,
               {"step": i, "ssm_chunk_decay_max": 400.0 + 10 * i}, "python"]
              for i in range(6)]
    trace = {"devices": HAND["devices"], "host": HAND["host"] + events}
    assert reader("ssm.chunk_decay_max").read(run_of(trace)) == \
        pytest.approx(425.0)
    # fewer than four events are no mean; another program publishes none
    few = {"devices": HAND["devices"], "host": HAND["host"] + events[:3]}
    assert reader("ssm.chunk_decay_max").read(run_of(few)) is None
    assert reader("ssm.chunk_decay_max").read(run_of(HAND)) is None


def recording(name):
    rec = json.load(open(os.path.join(DATA, name)))
    return {"devices": {p: [[n, s, d, rec["op_names"][i]]
                            for n, s, d, i in events]
                        for p, events in rec["devices"].items()},
            "host": rec["host"]}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("other,fixture", [
    ("mistral-7b.train.8k", "scope_trace_train_8k.json"),
    ("kimi-vl-a3b.train.8k", "scope_trace_train_kimi_8k.json"),
    ("zaya1-8b.train.8k", "scope_trace_train_zaya1_8k.json")])
def test_new_readers_find_nothing_in_another_program(name, other, fixture):
    """A program without state-space layers (the other cells' recorded
    traces, as the parent commit runs them): None, no exception."""
    run = run_of(recording(fixture), cell=other, tokens_per_s=1.0, chips=1)
    assert reader(name).read(run) is None
    assert reader(name).read(run_of(None, cell=other, tokens_per_s=1.0,
                                    chips=1)) is None


def test_new_readers_find_nothing_where_the_program_lacks_the_names():
    """The parent of this PR on the cell's own name (the driver lays the
    benchmark files over it): a trace without the scopes, kernels and
    counter reads None everywhere but the operations' share."""
    run = run_of(recording("scope_trace_train_8k.json"), tokens_per_s=1.0,
                 chips=1)
    for name in NEW[1:]:
        if "flash_da" not in name:      # the flash kernels are there
            assert reader(name).read(run) is None, name
