"""The OLMoE-1B-7B configuration and its cell ``olmoe-1b-7b.train.4k``:
required operations by hand, the cell's correctness check at tiny size on
one CPU device (passes over seeds; top-1 routing, renormalised top-k, a
left-out q/k norm, an expert layer that returns zero and expert products in
float8 each fail it), and the two readers the cell brings, on a hand-made
trace and on a cut of a real chip trace of the cell."""

import json
import os

import numpy as np
import pytest

from bench_helpers import tiny_context, train_check
from benchmark import common, flops, instruction_times, scope_reduce
from benchmark.traffic import generator

CELL = "olmoe-1b-7b.train.4k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def reader(name):
    return common.load_file_module("layer_metrics", name)


# -- the configuration -------------------------------------------------------

def test_olmoe_one_layer_at_4k_is_357_mflop_a_token():
    s = common.sizes_of(common.load_json("configs", "olmoe-1b-7b.json"),
                        "train")
    assert (s["num_hidden_layers"], s["head_dim"]) == (1, 128)
    qkvo = 4 * 2 * 2048 * 2048          # MHA: q, k, v, o all 2048 -> 2048
    attn = 2 * 2 * 16 * 128 * (4096 + 1) / 2
    experts = 8 * (3 * 2 * 2048 * 1024)  # top-8 of 64, each 2048 x 1024 x 3
    router = 2 * 2048 * 64
    head = 2 * 2048 * 50304
    want = qkvo + attn + experts + router + head
    assert flops.forward_flops_per_token(s, 4096) == pytest.approx(want)
    assert round(want / 1e6) == 357
    assert round(experts / 1e6) == 101 and round(head / 1e6) == 206
    assert head / want == pytest.approx(0.58, abs=0.005)
    # at the published depth the head is 8% and the experts 75%
    full = 16 * (qkvo + attn + experts + router) + head
    assert head / full == pytest.approx(0.08, abs=0.005)


def test_configuration_keeps_every_number_of_the_catalog_row():
    """The source's ``config.json`` as the model-configs catalog has it,
    each under its own key; only the depth differs, and is listed."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    config = common.load_json("configs", "olmoe-1b-7b.json")
    differ = [k for k, v in published.items() if config.get(k, "absent") != v]
    assert differ == ["num_hidden_layers"] == list(config["reduced"])
    assert config["num_hidden_layers"]["published"] == 16
    assert config["num_local_experts"] == config["num_experts"]
    cfg, _ = common.build_model(config, common.sizes_of(config, "train"))
    assert (cfg.qk_norm, cfg.norm_topk_prob, cfg.num_local_experts,
            cfg.router_aux_loss_coef, cfg.sliding_window,
            cfg.per_expert_init, cfg.report_expert_load) == \
        (True, False, 64, 0.01, None, True, True)


# -- the check ---------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42, 43])
def test_olmoe_engine_matches_reference_on_one_device(seed):
    ok, stats = train_check(CELL, seed)
    assert ok, stats
    # float32 at tiny size: the reference's q/k norms, un-normalised
    # routing and load-balancing loss are the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-5 and stats["loss_gap"] < 1e-5


@pytest.mark.parametrize("seed", [40, 41])
def test_top1_routing_fails_the_olmoe_check(seed):
    ok, stats = train_check(CELL, seed, control="top1_routing")
    assert not ok and not stats["verdicts"]["logit_rel_l2"]


def wrong_model(seed, patch=None, **over):
    """(the cell's tolerance, logit_rel_l2) of the engine built with a
    model override, or with ``patch`` ({name: replacement} in
    ``models/mixtral.py``) in place, against the reference of the published
    model. The reference reads the system's weights: where the override
    left the q/k norms out, it is given the unit scales they start from."""
    import jax.numpy as jnp

    import deepspeed_tpu.models.mixtral as mx

    ctx, kind = tiny_context(CELL, seed)
    ctx["workload"] = {**ctx["workload"],
                       "model": {**ctx["workload"]["model"], **over}}
    sizes = ctx["sizes"]
    engine = kind.build_engine(ctx, sizes)
    params = engine.state.params
    if over.get("qk_norm") is False:
        attn = dict(params["model"]["layers"]["block"]["self_attn"])
        for n in "qk":
            k = attn[f"{n}_proj"]["kernel"]
            attn[f"{n}_norm"] = {"scale": jnp.ones((k.shape[0],
                                                    k.shape[-1]))}
        block = {**params["model"]["layers"]["block"], "self_attn": attn}
        params = {**params, "model": {**params["model"],
                                      "layers": {"block": block}}}
    ref = common.load_file_module("reference", "olmoe")
    ids = generator.packed_batch(ctx["mix"], seed, -1, sizes["vocab_size"],
                                 1)["input_ids"]
    rows = ctx["workload"]["check"]["probe_positions"]
    hidden = ref.hidden_states(params, sizes, jnp.asarray(ids[0]))[0]
    want = np.asarray(ref.logits(params, hidden[-rows:]))
    with pytest.MonkeyPatch.context() as mp:
        for name, wrong in (patch(mx) if patch else {}).items():
            mp.setattr(mx, name, wrong)
        got = kind.model_logits(engine, ids, rows)[0]
    return (ctx["workload"]["check"]["logit_rel_l2_tol"],
            common.rel_l2(got, want))


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("over,least", [
    ({"norm_topk_prob": True}, 0.05),     # measured 0.104-0.117
    ({"qk_norm": False}, 0.1),             # measured 0.203-0.245
], ids=["topk_renormalised", "qk_norm_left_out"])
def test_ignoring_a_flag_fails_the_olmoe_check(seed, over, least):
    tol, got = wrong_model(seed, **over)
    assert got > least > tol


def experts_zeroed(mx):
    import jax.numpy as jnp

    return {"_expert_mlp": lambda cfg, x, *rest: (jnp.zeros_like(x), None)}


def expert_products_fp8(mx):
    """The grouped products alone one precision down: both operands of
    every ``_grouped_dot`` rounded to float8 (e4m3). ``reduce_precision``
    and not a pair of converts, which XLA:TPU simplifies away inside a
    jit (my chip run, PR 27)."""
    import jax

    dot = mx._grouped_dot
    low = lambda t: jax.lax.reduce_precision(t, exponent_bits=4,
                                             mantissa_bits=3)
    return {"_grouped_dot": lambda lhs, rhs, sizes:
            dot(low(lhs), low(rhs), sizes)}


@pytest.mark.parametrize("seed", [40, 41])
@pytest.mark.parametrize("patch,least", [
    (experts_zeroed, 0.3),                  # measured 0.579-0.663
    (expert_products_fp8, 0.02)],           # measured 0.037-0.042
    ids=["experts_zeroed", "expert_products_fp8"])
def test_a_wrong_expert_layer_fails_the_olmoe_check(seed, patch, least):
    """``correct`` is the expert layer's judge too: with each expert's
    kernels seeded over its own fan-in (``per_expert_init``) the layer's
    output is the larger part of the residual stream, so the logits tell an
    expert layer that returns nothing, or one whose products lost a
    precision, from the right one."""
    tol, got = wrong_model(seed, patch)
    assert got > least > tol


# -- the readers -------------------------------------------------------------

FWD = "jit(ds_train_step)/ds.loss_and_grad/jvp(M)/model/while/body/"
HAND = {
    "devices": {"/device:TPU:0": [
        ["fusion.2", 1000, 1000, FWD + "block_sparse_moe/ds.moe_experts/moe_dispatch/gather"],
        ["ragged-dot-metadata", 2000, 100, "ragged-dot-metadata"],
        ["ragged-dot-none", 2100, 3000, "ragged-dot-none"],
        ["ragged-dot-none.1", 5100, 3000, "ragged-dot-none"],
        ["while.2", 1000, 8000, FWD[:-1]],
        ["ragged-dot-none", 9100, 5000, "ragged-dot-none"],   # clipped
        ["fusion.9", 9000, 100, "jit(ds_train_step)/ds.optimizer/mul"],
    ]},
    "host": [["bench.traced_window", 0, 11100, {}, "python"],
             ["ds.train_batch", 100, 50, {"step": 7}, "python"]],
}


def run_of(trace, kind="train", cell=CELL):
    return {"cell": cell, "device": TPU, "observed": {"kind": kind},
            "scope_trace": trace}


def test_grouped_matmul_share_counts_every_ragged_dot_instruction():
    """Busy 1000 + 100 + 3000 + 3000 + 100 + 2000 = 9200 ns (the container
    is not work, the last product is clipped to the window); the
    instructions named ragged-dot* hold 8100 of them."""
    share = reader("moe.grouped_matmul_share")
    ops = instruction_times.by_instruction(run_of(HAND), "ragged-dot")
    assert {k: (round(v["s"] * 1e9), v["calls"]) for k, v in ops.items()} \
        == {"ragged-dot-metadata": (100, 1), "ragged-dot-none": (5000, 2),
            "ragged-dot-none.1": (3000, 1)}
    assert share.read(run_of(HAND)) == pytest.approx(100 * 8100 / 9200)
    assert share.read(run_of(HAND, kind="serve")) is None


def test_moe_gmm_roofline_is_least_time_over_the_mean_call():
    """Three ``ragged-dot-none`` calls of mean 8000 / 3 ns; the least time
    of one call of the cell's product on the v5e is bound by operations:
    2 x 65,536 rows x 2048 x 1024 / 197e12 = 1.395 ms."""
    roof = reader("kernel.moe_gmm.roofline_share")
    cost = roof.grouped_matmul(65536, 2048, 1024, 64)
    assert cost["flops"] == 2 * 65536 * 2048 * 1024
    assert cost["bytes"] == 2 * (65536 * 2048 + 65536 * 1024
                                 + 64 * 2048 * 1024)
    least = cost["flops"] / 197e12
    assert least > cost["bytes"] / 819e9
    assert roof.read(run_of(HAND)) == pytest.approx(
        100 * least / (8000e-9 / 3))
    assert roof.read(run_of(HAND, kind="serve")) is None
    assert roof.read({**run_of(HAND), "device": {"platform": "cpu"}}) is None


def test_readers_find_nothing_in_a_program_without_the_kernels():
    """Another program under this benchmark (the dense cell's recorded
    trace): None, no exception."""
    rec = json.load(open(os.path.join(DATA, "scope_trace_train_8k.json")))
    dense = {"devices": {p: [[n, s, d, rec["op_names"][i]]
                             for n, s, d, i in events]
                         for p, events in rec["devices"].items()},
             "host": rec["host"]}
    for name in ("moe.grouped_matmul_share", "kernel.moe_gmm.roofline_share"):
        assert reader(name).read(run_of(dense)) is None
        assert reader(name).read({**run_of(None)}) is None


# -- a cut of a real chip trace of the cell ----------------------------------

def recording():
    rec = json.load(open(os.path.join(DATA,
                                      "scope_trace_train_olmoe_4k.json")))
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
    """Every trace-sourced metric the cell lists finds something to read
    in 260 ms (two steps) of the cell on the v5e, a share of at most 100."""
    value = reader(metric).read(run_of(recording()))
    assert value is not None and 0 <= value <= 100, (metric, value)


def test_recording_is_the_cells_shape():
    """What PERF.md section 5 says of the cell, from the recording: the
    products are XLA's unscoped kernels, nine a step at 42-48% of their
    roofline, a fifth of the busy time; the head leads; the two shares
    together are the expert layer less its weights' copies."""
    run = run_of(recording())
    r = scope_reduce.reduce(run["scope_trace"])
    ops = instruction_times.by_instruction(run, "ragged-dot-none")
    assert len(ops) == 9 and all(v["calls"] == 2 for v in ops.values())
    per_call_ms = [1e3 * v["s"] / v["calls"] for v in ops.values()]
    assert 2.5 < min(per_call_ms) and max(per_call_ms) < 3.6
    assert 40 < reader("kernel.moe_gmm.roofline_share").read(run) < 50
    assert 19 < reader("moe.grouped_matmul_share").read(run) < 24
    assert 8 < reader("moe.expert_share").read(run) < 12
    assert max(r["by_scope"], key=r["by_scope"].get) == "ds.lm_head_loss"
    assert set(r["by_kernel"]) == {"ds_flash_fwd", "ds_flash_bwd_dq",
                                   "ds_flash_bwd_dkv"}
