"""The program's own count of its matrix work (``monitor/perf.py StepCost``)
read by the benchmark: ``benchmark/step_cost.py`` finds the ``ds.step_cost``
event of a traced run and the four ``*_mxu_share`` readers divide a scope's
operations by its device seconds and the MXU's peak -- on hand-made traces,
by hand. And the count held against the benchmark's own cost files
(``flops.py`` and the ``*_costs.py`` behind each cell's ``train.mfu*``) at the
tiny sizes, part by part: the plain products agree to the operation, and
every part that does not is written down beside its cause. Nothing here
compiles: a cell's tiny train step is TRACED (``jax.make_jaxpr`` over shapes)
and walked."""

import functools
import json

import jax
import jax.numpy as jnp
import pytest
from bench_helpers import tiny_context

from benchmark import common, flops, kernel_costs, scope_reduce, step_cost
from deepspeed_tpu.profiling.flops_profiler.profiler import walk_jaxpr

PEAK = 197e12
METRICS = {"train.mlp_mxu_share": "ds.mlp",
           "train.attn_proj_mxu_share": "ds.attn_proj",
           "train.head_loss_mxu_share": "ds.lm_head_loss",
           "moe.expert_mxu_share": "ds.moe_experts"}
OLMOE, KIMI = "olmoe-1b-7b.train.4k", "kimi-vl-a3b.train.8k"


# -- the readers on a hand-made trace ----------------------------------------

def trace(stats=None, devices=1, steps=4):
    """A window of ten seconds; a step: 100 ms under ``ds.mlp`` (60 forward,
    40 backward), 50 under ``ds.lm_head_loss``, 25 under ``ds.moe_experts``,
    nothing under ``ds.attn_proj``; ``steps`` ``ds.train_batch`` spans and,
    with ``stats``, one ``ds.step_cost`` event inside the first."""
    ms = 1_000_000
    ops = []
    for i in range(steps):
        t = (1000 + 1000 * i) * ms
        ops += [
            ["fusion.1", t, 60 * ms,
             "jit(ds_train_step)/ds.loss_and_grad/jvp(ds.mlp)/dot_general"],
            ["fusion.2", t + 60 * ms, 40 * ms,
             "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(ds.mlp))/"
             "dot_general"],
            ["fusion.3", t + 100 * ms, 50 * ms,
             "jit(ds_train_step)/ds.loss_and_grad/jvp(ds.lm_head_loss)/"
             "dot_general"],
            ["ds_moe_gmm.4", t + 150 * ms, 25 * ms,
             "jit(ds_train_step)/ds.loss_and_grad/jvp(ds.moe_experts)/"
             "ds_moe_gmm/pallas_call"]]
    host = [["bench.traced_window", 0, 10_000 * ms, {}, "main"]]
    host += [["ds.train_batch", (990 + 1000 * i) * ms, 5 * ms, {"step": i},
              "main"] for i in range(steps)]
    if stats is not None:
        host.append(["ds.step_cost", 992 * ms, 4000, stats, "main"])
    return {"devices": {f"/device:TPU:{d}": [list(op) for op in ops]
                        for d in range(devices)}, "host": host}


def run_of(scope_trace, kind="train", platform="tpu"):
    return {"observed": {"kind": kind, "tokens_per_s": 8192.0,
                         "window_s": 4.0, "steps": 4, "chips": 1},
            "device": {"platform": platform, "kind": "TPU v5 lite"},
            "scope_trace": scope_trace}


STATS = {"matmul_flops_mlp": 9.85e12, "replayed_flops_mlp": 1.0e12,
         "matmul_flops_lm_head_loss": 1.97e12,
         "replayed_flops_lm_head_loss": 0.0,
         "matmul_flops_moe_experts": 1.0e12,
         "replayed_flops_moe_experts": 0.0,
         "matmul_flops_attn_proj": 3.0e12, "replayed_flops_attn_proj": 0.0,
         "matmul_flops": 15.82e12, "replayed_flops": 1.0e12,
         "cond_spread_flops": 0.0, "uncounted_kernel_calls": 2.0,
         "uncounted_ds_ssm_scan_fwd": 2.0, "walk_s": 0.05}


def reader(name):
    return common.load_file_module("layer_metrics", name)


@pytest.mark.parametrize("metric,by_hand", [
    # 9.85e12 operations a step (1.0e12 of them a replay the device does
    # not show) x 4 steps over 0.4 s under the scope
    ("train.mlp_mxu_share", 100 * 8.85e12 * 4 / (0.4 * PEAK)),
    ("train.head_loss_mxu_share", 100 * 1.97e12 * 4 / (0.2 * PEAK)),
    ("moe.expert_mxu_share", 100 * 1.0e12 * 4 / (0.1 * PEAK)),
    # counted by the program, and no device time under the scope
    ("train.attn_proj_mxu_share", None)])
def test_a_share_is_the_scopes_operations_over_its_seconds_and_the_peak(
        metric, by_hand, capsys):
    got = reader(metric).read(run_of(trace(STATS)))
    assert got == (None if by_hand is None else pytest.approx(by_hand))
    assert by_hand is None or 0 < got < 100
    if by_hand is not None:
        assert got == pytest.approx({"train.mlp_mxu_share": 44.923858,
                                     "train.head_loss_mxu_share": 20.0,
                                     "moe.expert_mxu_share": 20.304568}[
                                         metric])


def test_replays_stay_out_on_both_sides():
    """``STATS`` holds 1.0e12 replayed operations under ``ds.mlp``; the trace
    above shows no operation under ``rematted_computation`` there (the
    compiler merged an unrolled block's replay with its forward pass). A
    replay the device does run adds its seconds to the scope's and to no
    share: the products the model asks for over the time THEY take."""
    by_hand = 100 * (9.85e12 - 1.0e12) * 4 / (0.4 * PEAK)
    assert step_cost.mxu_share(run_of(trace(STATS)), "ds.mlp") == \
        pytest.approx(by_hand)
    replayed = trace(STATS)
    for ops in replayed["devices"].values():
        ops.append(["fusion.9", 1_200_000_000, 10_000_000,
                    "jit(ds_train_step)/ds.loss_and_grad/transpose(jvp(ds."
                    "layer_stack))/rematted_computation/ds.mlp/dot_general"])
    run = run_of(replayed)
    assert scope_reduce.reduced(run)["by_scope"]["ds.mlp"] == \
        pytest.approx(0.41)
    assert step_cost.mxu_share(run, "ds.mlp") == pytest.approx(by_hand)


def test_two_devices_share_a_global_count():
    """``by_scope`` is a device's seconds (the mean over the devices that
    ran anything) and the count is global: the peak is both chips'."""
    one = step_cost.mxu_share(run_of(trace(STATS)), "ds.mlp")
    two = step_cost.mxu_share(run_of(trace(STATS, devices=2)), "ds.mlp")
    assert two == pytest.approx(one / 2)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_reader_finds_nothing_where_there_is_nothing(metric):
    """The parent of the PR that added the span publishes no event; a serve
    run is another kind; a CPU has no peak to be a share of."""
    read = reader(metric).read
    assert read(run_of(trace(None))) is None
    assert read(run_of(trace(STATS), kind="serve")) is None
    assert read(run_of(trace(STATS), platform="cpu")) is None
    assert read(run_of(None)) is None


def test_the_record_is_the_last_event_and_is_observed_once(capsys):
    both = trace(STATS)
    both["host"].append(["ds.step_cost", 5_000_000_000, 3000,
                         {**STATS, "matmul_flops_mlp": 1.0}, "main"])
    run = run_of(both)
    assert step_cost.record(run)["matmul_flops_mlp"] == 1.0
    assert step_cost.record(run)["walk_s"] == 0.05
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if '"step_cost"' in l]
    (line,) = lines
    assert line["events"] == 2 and line["span_us"] == 3.0
    # what the model asks for a token: the replays left out
    assert line["model_flops_per_token"] == pytest.approx(
        (15.82e12 - 1.0e12) / 8192.0)
    assert set(line["mxu_share_pct"]) == {"mlp", "lm_head_loss",
                                          "moe_experts", "attn_proj"}
    assert step_cost.record(run_of(trace(STATS), kind="serve")) is None


def test_the_four_entries_are_what_the_benchmark_gained():
    """Appended, each with the list of the cells whose standing tests let a
    new metric in (PERF.md section 7 names the tests that refuse the
    others), every one moving the cell's own rate. The experts' share lists
    no cell with a HELD share: the grouped products are counted on every row
    of their buffer, and kimi 8k's compact buffer holds twice a level load's
    pairs -- the count and the clock disagree there by construction."""
    bench = common.load_benchmark()
    entries = bench["per_layer"][-4:]
    assert [m["name"] for m in entries] == [
        "train.mlp_mxu_share", "train.attn_proj_mxu_share",
        "train.head_loss_mxu_share", "moe.expert_mxu_share"]
    layers = {m["layer"] for m in bench["per_layer"][:-4]}
    for m in entries:
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "higher", "program_counter", "train_tokens_per_s_per_chip")
        assert m["layer"] in layers
        assert reader(m["name"]) is not None
    assert [m["workloads"] for m in entries] == [
        [KIMI], [KIMI], [OLMOE, KIMI], [OLMOE]]


# -- the count against the benchmark's cost files ----------------------------

CELLS = [w["name"] for w in common.load_benchmark()["workloads"]]
#: parts of a cost file -> the scopes that run exactly those products
GROUPS = {
    # a linear-attention or windowed layer's projections stand under the
    # same scope as a full layer's
    "projections": (("attn_proj", "gdn_proj", "kda_proj", "attn_proj_full",
                     "attn_proj_window"), ("ds.attn_proj",)),
    "head": (("head",), ("ds.lm_head_loss",)),
    "router": (("router",), ("ds.moe_router",)),
    "mlp": (("dense_mlp", "mlp"), ("ds.mlp",)),
    "shared": (("shared_experts", "shared_expert"), ("ds.moe_shared",)),
    "experts": (("held_experts",), ("ds.moe_experts",)),
    # ouro's file keeps a layer's seven products in one part
    "layer": (("layer_products",), ("ds.attn_proj", "ds.mlp")),
    # a state-space mixer's two projections (nemotron), and with the scan's
    # own projections inside its scope (phi4)
    "ssm": (("ssm_in_proj", "ssm_out_proj"), ("ds.ssm_mix",)),
    "ssm_scan": (("ssm_proj",), ("ds.ssm_mix", "ds.ssm_scan")),
}


def cost_module(cell):
    """The ``*_costs`` module behind the cell's ``train.mfu*`` reader, or
    None where it is ``flops.py``'s count that ``kinds/train.py`` hands it."""
    bench = common.load_benchmark()
    name = next(m["name"] for m in bench["per_layer"]
                if m["name"].startswith("train.mfu")
                and cell in m["workloads"])
    mods = [v for v in vars(reader(name)).values()
            if hasattr(v, "train_flops_per_token") and v is not flops]
    return mods[0] if mods else None


def dense_parts(sizes, seq_len):
    """``flops.py``'s one formula, by part."""
    H, Hq, Hkv = (sizes["hidden_size"], sizes["num_attention_heads"],
                  sizes["num_key_value_heads"])
    D = sizes.get("head_dim") or H // Hq
    L, mlp = sizes["num_hidden_layers"], 6 * H * sizes["intermediate_size"]
    parts = {"attn_proj": L * (4 * H * Hq * D + 4 * H * Hkv * D),
             "attention": L * 4 * Hq * D * flops.mean_attended_keys(
                 seq_len, sizes.get("sliding_window")),
             "head": 2 * H * sizes["vocab_size"]}
    E = sizes.get("num_local_experts")
    if E:
        parts.update(held_experts=L * sizes["num_experts_per_tok"] * mlp,
                     router=L * 2 * H * E)
    else:
        parts["dense_mlp"] = L * mlp
    return parts


@functools.lru_cache(maxsize=None)
def counted(cell):
    """(the walk of the cell's tiny train step, tokens a step, the file's
    forward parts a token, its whole count a token): the model's own loss
    differentiated over shapes -- the engine's step adds the cast, the norm
    and AdamW, no product."""
    ctx, _ = tiny_context(cell, 0)
    sizes, wl, mix = ctx["sizes"], ctx["workload"], ctx["mix"]
    _, model = common.build_model(ctx["config"], sizes, **wl.get("model", {}))
    batch = mix["sequences_per_chip"] * ctx["cell"]["chips"]
    ids = jax.ShapeDtypeStruct((batch, mix["seq_len"]), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def loss(params, ids):
        out = model.apply({"params": params}, input_ids=ids, labels=ids)
        return out[0] if isinstance(out, tuple) else out

    walk = walk_jaxpr(jax.make_jaxpr(jax.grad(loss))(shapes, ids))
    module = cost_module(cell)
    if module is None:
        parts = dense_parts(sizes, mix["seq_len"])
        whole = flops.train_flops_per_token(sizes, mix["seq_len"])
    else:
        parts = module.forward_parts(sizes, mix["seq_len"])
        whole = module.train_flops_per_token(sizes, mix["seq_len"])
    return walk, batch * mix["seq_len"], parts, whole


@functools.lru_cache(maxsize=None)
def file_parts(cell):
    module = cost_module(cell)
    ctx, _ = tiny_context(cell, 0)
    seq_len = ctx["mix"]["seq_len"]
    return dense_parts(ctx["sizes"], seq_len) if module is None \
        else module.forward_parts(ctx["sizes"], seq_len)


@functools.lru_cache(maxsize=None)
def group_cases():
    return [(cell, group) for cell in CELLS
            for group, (parts, _) in GROUPS.items()
            if set(parts) & set(file_parts(cell))]


def model_flops(walk, scopes):
    rows = [walk.scopes.get(scope, {}) for scope in scopes]
    return sum(sum(row.values()) - row.get("replayed", 0) for row in rows)


#: (cell, group) -> (the program's count over 3 x the file's parts, why).
#: ONE cause, nine cells: the grouped products run on every row of the
#: buffer they are given -- at these sizes tokens x top-k, the held experts'
#: pairs or not -- and the file charges the held experts a LEVEL router's
#: pairs, tokens x top-k x held / routed. On the chip the buffer is the
#: compact one (a quarter of the rows, the cheaper branch of a ``cond``):
#: the count stays an upper bound of what runs there, and
#: ``moe.expert_mxu_share`` lists no cell with a held share for it
#: (PERF.md section 7).
LEVEL = "every row of the buffer against a level load of the held experts"
EXCEPTIONS = {(cell, "experts"): (ratio, LEVEL) for cell, ratio in {
    "kimi-vl-a3b.train.8k": 4.0, "zaya1-8b.train.8k": 2.25,
    "keye-vl2-30b-a3b.train.16k": 8.0, "mellum2-12b-a2.5b.train.8k": 4.0,
    "qwen3-next-80b-a3b.train.8k": 4.0, "sdar-30b-a3b.train.8k": 8.0,
    "laguna-xs.2.train.8k": 4.0, "nemotron-3-nano-30b-a3b.train.8k": 4.0,
    "kimi-linear-48b-a3b.train.8k": 4.0}.items()}


@pytest.mark.parametrize("cell,group", group_cases())
def test_plain_products_are_counted_as_the_cost_file_counts_them(cell, group):
    """``matmul_flops - replayed_flops`` of the scopes against 3 x the
    file's forward parts (the backward pass asks twice the forward's), to
    1%: ouro's head scope holds the exit gate's 512 operations a token
    beside the head's 98,304."""
    walk, tokens, parts, _ = counted(cell)
    names, scopes = GROUPS[group]
    asked = 3 * sum(parts[n] for n in names if n in parts) * tokens
    ratio = model_flops(walk, scopes) / asked
    expected, cause = EXCEPTIONS.get((cell, group), (1.0, None))
    assert ratio == pytest.approx(expected, rel=0.01), (ratio, cause)


@pytest.mark.parametrize("cell", CELLS)
def test_what_is_left_is_the_cores_and_the_program_runs_them_whole(cell):
    """Beside the plain products stand the attention cores, the rules and
    the scans. There the program counts what RUNS -- every pair of a tile
    the causal line cuts (at these sizes the one tile a sequence is), a
    chunk's whole table -- and the file what is NEEDED, the causal mean of
    attended keys: never less, and at 8,192 positions in tiles of 512 it is
    136 tiles for 128.03 tiles' worth. The whole count is the file's plus
    these two differences and nothing else."""
    walk, tokens, parts, whole = counted(cell)
    matched = [g for c, g in group_cases() if c == cell]
    names = {n for g in matched for n in GROUPS[g][0]}
    scopes = {s for g in matched for s in GROUPS[g][1]}
    left_program = model_flops(walk, set(walk.scopes) - scopes)
    left_file = 3 * sum(v for n, v in parts.items() if n not in names) * tokens
    assert left_program >= 0.999 * left_file > 0
    plain = sum(model_flops(walk, GROUPS[g][1]) for g in matched)
    assert plain + left_program == model_flops(walk, walk.scopes)
    experts = model_flops(walk, ("ds.moe_experts",)) \
        if (cell, "experts") in EXCEPTIONS else 0
    level = experts / EXCEPTIONS.get((cell, "experts"), (1.0, None))[0]
    assert plain - experts + level + left_file == pytest.approx(
        whole * tokens, rel=0.01)
