"""The train step counts its own matrix work (``monitor/perf.py StepCost``
over ``profiling/flops_profiler walk_jaxpr``): a scan's body times its
length, grouped products, a ``shard_map`` over its devices, a ``cond``'s
cheapest branch, a Pallas call by the table beside the kernels' names --
filed under the innermost ``ds.`` scope ``benchmark/scope_reduce`` gives a
device operation's time to, forward, backward and replayed apart. The engine
walks the jaxpr its lowering was made from, once, and publishes the record as
its registry row and as ``ds.step_cost``. CPU only and nothing here compiles
but the engine's own tiny step: the walker needs a jaxpr, never an
executable."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

import deepspeed_tpu as ds
from benchmark import scope_reduce
from deepspeed_tpu.monitor import perf
from deepspeed_tpu.monitor.export import step_cost_line
from deepspeed_tpu.ops import pallas as names
from deepspeed_tpu.ops.pallas import flash_attention, grouped_matmul
from deepspeed_tpu.profiling.flops_profiler import profiler
from deepspeed_tpu.profiling.flops_profiler.profiler import walk_jaxpr
from tests.unit.simple_model import SimpleModel, batch_of

D, ROWS = 64, 8


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def walked(fn, *args):
    return walk_jaxpr(jax.make_jaxpr(fn)(*args))


# -- (a) a scanned stack -----------------------------------------------------

def stack_loss(ws, x, remat=False):
    def layer(h, w):
        with jax.named_scope("ds.mlp"):
            return h + jnp.tanh(h @ w["up"]) @ w["down"]

    block = jax.checkpoint(layer) if remat else layer
    with jax.named_scope("ds.layer_stack"):
        h, _ = jax.lax.scan(lambda h, w: (block(h, w), None), x, ws)
    with jax.named_scope("ds.lm_head_loss"):
        return (h @ ws["up"][0]).sum()


def stack_args(layers):
    return ({"up": f32(layers, D, D), "down": f32(layers, D, D)},
            f32(ROWS, D))


@pytest.mark.parametrize("layers", [1, 4, 16])
def test_a_scanned_stack_counts_every_layer_where_xla_counts_one(layers):
    """WHY the train step left ``cost_analysis()``: XLA's cost analysis
    counts a ``while`` body ONCE, so a scanned stack of 1, 4 and 16 layers
    reads the same operations (to a loop counter's few), and every cell of
    the benchmark scans its layers -- ``train_mfu`` read one layer and the
    head. The walk multiplies the body by the scan's length."""
    step = jax.jit(jax.grad(stack_loss))
    traced = step.trace(*stack_args(layers))
    walk = walk_jaxpr(traced.jaxpr)
    product = 2 * ROWS * D * D
    # a layer: two products forward, four backward; the head: one and two
    assert walk.scopes["ds.mlp"] == {
        "forward": 2 * product * layers, "backward": 4 * product * layers,
        "replayed": 0}
    assert walk.scopes["ds.lm_head_loss"] == {
        "forward": product, "backward": 2 * product, "replayed": 0}
    assert walk.matmul_flops() == (6 * layers + 3) * product
    assert not walk.uncounted and not walk.cond_spread_flops
    xla = traced.lower().cost_analysis()["flops"]
    one = jax.jit(jax.grad(stack_loss)).lower(
        *stack_args(1)).cost_analysis()["flops"]
    assert xla == pytest.approx(one, rel=0.2)      # whatever the depth
    assert xla < 2 * 9 * product


# -- (b) grouped products ----------------------------------------------------

def test_grouped_products_count_a_row_against_one_group():
    """``ragged_dot`` and the three forms of ``ragged_dot_general`` its
    gradient takes: ``[M, K] x [G, K, N]``, the same against the transposed
    weight, and the weights' gradient ``[M, K] x [M, N] -> [G, K, N]``
    (ragged along the contraction) -- each ``2 M K N``."""
    M, K, N, G = 64, 32, 16, 4
    sizes = jnp.full((G,), M // G, jnp.int32)

    def loss(a, b):
        with jax.named_scope("ds.moe_experts"):
            return jax.lax.ragged_dot(a, b, sizes).sum()

    forward = walked(loss, f32(M, K), f32(G, K, N))
    assert forward.scopes == {"ds.moe_experts": {
        "forward": 2 * M * K * N, "backward": 0, "replayed": 0}}
    both = walked(jax.grad(loss, argnums=(0, 1)), f32(M, K), f32(G, K, N))
    assert both.scopes == {"ds.moe_experts": {
        "forward": 2 * M * K * N, "backward": 4 * M * K * N, "replayed": 0}}
    eqns = [e for e in jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        f32(M, K), f32(G, K, N)).jaxpr.eqns
        if e.primitive.name == "ragged_dot_general"]
    assert len(eqns) == 3
    assert {profiler._ragged_dot_flops(e) for e in eqns} == \
        {(2 * M * K * N, M * K * N)}
    # the printed tree holds them too (it counted 0 for them before)
    assert both.tree.total_macs() == 3 * M * K * N


# -- (c) shard_map -----------------------------------------------------------

@pytest.mark.parametrize("devices", [2, 4])
def test_a_shard_map_body_counts_once_a_device(devices):
    """A body's shapes are ONE device's: the count is global, so that a
    division by the devices gives a chip's."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:devices]), ("expert",))

    def body(x, w):
        with jax.named_scope("ds.moe_experts"):
            return jax.lax.psum(x @ w, "expert")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(None, "expert"),
                                                  P("expert", None)),
                       out_specs=P())
    walk = walked(fn, f32(ROWS, D), f32(D, D))
    per_device = 2 * ROWS * (D // devices) * D
    assert walk.scopes["ds.moe_experts"]["forward"] == devices * per_device \
        == 2 * ROWS * D * D


def test_a_shard_map_over_some_axes_counts_those_axes():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                             ("data", "expert"))
    fn = jax.shard_map(lambda x, w: x @ w, mesh=mesh, axis_names={"expert"},
                       in_specs=(P(), P(None, "expert")),
                       out_specs=P(None, "expert"))
    walk = walked(fn, f32(ROWS, D), f32(D, D))
    assert walk.matmul_flops() == 2 * ROWS * D * D


# -- (d) cond and while ------------------------------------------------------

def test_a_cond_counts_its_cheapest_branch_and_keeps_the_spread():
    """A lower bound (a share of the peak computed from it cannot pass 100)
    however many EQUATIONS the cheap branch holds; the distance to the
    largest branch is kept."""
    def cheap(x, w):
        y = x[:2] @ w                      # more equations, fewer products
        for _ in range(6):
            y = jnp.tanh(y) + 1.0
        return jnp.zeros_like(x).at[:2].set(y)

    def dear(x, w):
        return (x @ w) @ w

    def fn(flag, x, w):
        with jax.named_scope("ds.moe_experts"):
            return jax.lax.cond(flag, dear, cheap, x, w)

    walk = walked(fn, jax.ShapeDtypeStruct((), jnp.bool_), f32(ROWS, D),
                  f32(D, D))
    assert walk.scopes["ds.moe_experts"]["forward"] == 2 * 2 * D * D
    assert walk.cond_spread_flops == 2 * 2 * ROWS * D * D - 2 * 2 * D * D
    # in a scan: both by the length
    def loop(flag, x, w):
        return jax.lax.scan(lambda c, _: (fn(flag, c, w), None), x, None,
                            length=3)[0]
    walk3 = walked(loop, jax.ShapeDtypeStruct((), jnp.bool_), f32(ROWS, D),
                   f32(D, D))
    assert walk3.matmul_flops() == 3 * walk.matmul_flops()
    assert walk3.cond_spread_flops == 3 * walk.cond_spread_flops


def test_a_while_counts_nothing_and_is_named():
    def fn(x, w):
        return jax.lax.while_loop(lambda c: c[0].sum() < 1e9,
                                  lambda c: (c[0] @ w,), (x,))[0] @ w

    walk = walked(fn, f32(ROWS, D), f32(D, D))
    assert walk.matmul_flops() == 2 * ROWS * D * D     # the one outside
    assert walk.uncounted == {"while": 1}


# -- (e) a remat'ed block ----------------------------------------------------

def test_a_remated_block_keeps_forward_backward_and_replay_apart():
    """The replay stands under ``rematted_computation``, the backward pass
    under ``transpose(``, and the scope is the innermost ``ds.`` name: what
    ``scope_reduce.scope_of`` / ``phase_of`` read off the lowered step's
    ``op_name`` for the same products."""
    assert profiler.SCOPE.pattern == scope_reduce.SCOPE.pattern
    assert profiler.UNSCOPED == scope_reduce.UNSCOPED

    def step(ws, x):
        with jax.named_scope("ds.loss_and_grad"):
            return jax.grad(stack_loss)(ws, x, True)

    traced = jax.jit(step).trace(*stack_args(4))
    walk = walk_jaxpr(traced.jaxpr)
    product = 2 * ROWS * D * D
    # the replay runs ``up`` again (tanh's input), never ``down``
    assert walk.scopes["ds.mlp"] == {
        "forward": 2 * product * 4, "backward": 4 * product * 4,
        "replayed": product * 4}
    assert walk.scopes["ds.lm_head_loss"]["replayed"] == 0
    assert walk.matmul_flops("replayed") == product * 4
    # the lowered step's names for its products (a called function's are
    # relative to its call: XLA joins them where it inlines the call)
    text = traced.lower().as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/dot_general)"', text))
    assert {scope_reduce.scope_of(p) for p in paths} == set(walk.scopes) \
        == {"ds.mlp", "ds.lm_head_loss"}
    replayed = {p for p in paths if "rematted_computation" in p}
    assert {scope_reduce.scope_of(p) for p in replayed} == {"ds.mlp"}
    assert all(profiler.phase_of(p) == "replayed" for p in replayed)
    head = {p for p in paths if p.startswith("jit(")}
    assert {(profiler.phase_of(p), scope_reduce.phase_of(p)) for p in head} \
        <= {("forward", "forward"), ("backward", "backward")}
    assert any("transpose(" in p for p in head)


def test_products_under_no_scope_are_unscoped():
    walk = walked(lambda x, w: x @ w, f32(ROWS, D), f32(D, D))
    assert walk.scopes == {profiler.UNSCOPED: {
        "forward": 2 * ROWS * D * D, "backward": 0, "replayed": 0}}
    assert perf.StepCost.stat(profiler.UNSCOPED) == "unscoped"
    assert perf.StepCost.stat("ds.lm_head_loss") == "lm_head_loss"


# -- (f) Pallas calls --------------------------------------------------------

def test_a_kernel_without_an_entry_counts_nothing_and_is_named():
    """The kernel's body is one grid step's over blocks: it is not entered
    (its one product would count 128 x 128 x 128 for a call that runs four
    of them)."""
    def kernel(x_ref, y_ref, o_ref):
        o_ref[...] = jnp.dot(x_ref[...], y_ref[...])

    def fn(x, y):
        with jax.named_scope("ds.mlp"):
            return pl.pallas_call(
                kernel, out_shape=f32(256, 256), grid=(2, 2),
                in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, 0)),
                          pl.BlockSpec((128, 128), lambda i, j: (0, j))],
                out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, j)),
                interpret=True, name="ds_no_entry")(x, y) @ y.T

    walk = walked(fn, f32(256, 128), f32(128, 256))
    assert walk.uncounted == {"ds_no_entry": 1}
    assert walk.matmul_flops() == 2 * 256 * 256 * 128   # the XLA product


def test_the_grouped_kernels_count_their_operands():
    M, A, B, G = 256, 128, 256, 4
    sizes = jnp.full((G,), M // G, jnp.int32)

    def fn(lhs, rhs, cot):
        with jax.named_scope("ds.moe_experts"):
            out = grouped_matmul.gmm(lhs, rhs, sizes, rows=128, cols=128,
                                     interpret=True)
            back = grouped_matmul.gmm(cot, rhs, sizes, rows=128, cols=128,
                                      transpose_rhs=True, interpret=True)
            dw = grouped_matmul.tgmm(lhs, cot, sizes, rows=128, cols=128,
                                     interpret=True)
        return out, back, dw

    walk = walked(fn, f32(M, A), f32(G, A, B), f32(M, B))
    assert walk.scopes == {"ds.moe_experts": {
        "forward": 3 * 2 * M * A * B, "backward": 0, "replayed": 0}}
    assert not walk.uncounted


def test_the_flash_kernels_count_their_grid_steps():
    """Grid steps x the products one step runs on its tile, a cut tile
    whole: forward two products, the two backward kernels (this CPU walks
    ``_dq`` and ``_dkv``) three and four. 256 causal positions in tiles of
    128 keep three tiles of four."""
    Bt, H, T, Dk = 2, 2, 256, 64

    def loss(q, k, v):
        with jax.named_scope("ds.attention"):
            return flash_attention.flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                interpret=True).sum()

    args = (f32(Bt, T, H, Dk),) * 3
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    walk = walk_jaxpr(jaxpr)
    tile = 2 * 128 * 128 * Dk * Bt * H * 3
    assert walk.scopes["ds.attention"]["forward"] == 2 * tile
    assert walk.scopes["ds.attention"]["backward"] == (3 + 4) * tile
    assert not walk.uncounted
    fused = names.MATMUL_FLOPS[names.FLASH_BWD]
    blocks = [(1, 1, 128, Dk), (1, 1, 128, Dk), (1, 1, 128, Dk)]
    assert fused([], [], (Bt, H, 3), blocks) == 5 * tile
    # a grid whose length is data is left out, by name
    assert fused([], [], (Bt, H, object()), blocks) is None


def test_the_table_names_kernels_that_exist():
    constants = {v for k, v in vars(names).items()
                 if k.isupper() and isinstance(v, str)}
    assert set(names.MATMUL_FLOPS) <= constants
    assert {names.MOE_GMM, names.MOE_GMM_T, names.FLASH_FWD, names.FLASH_BWD,
            names.FLASH_BWD_DQ, names.FLASH_BWD_DKV} == set(names.MATMUL_FLOPS)


# -- the record --------------------------------------------------------------

def test_the_record_is_flat_numbers_by_scope():
    walk = walked(jax.grad(lambda ws, x: stack_loss(ws, x, True)),
                  *stack_args(4))
    walk.uncounted.update({"ds_ssm_scan_fwd": 2, "while": 1})
    cost = perf.StepCost(walk, 0.25)
    rec = cost.record()
    product = 2 * ROWS * D * D
    assert rec == {
        "matmul_flops_lm_head_loss": 3 * product,
        "replayed_flops_lm_head_loss": 0,
        "matmul_flops_mlp": 28 * product, "replayed_flops_mlp": 4 * product,
        "matmul_flops": 31 * product, "replayed_flops": 4 * product,
        "cond_spread_flops": 0, "uncounted_kernel_calls": 2, "walk_s": 0.25,
        "uncounted_ds_ssm_scan_fwd": 2, "uncounted_while": 1}
    assert cost.model_flops == 27 * product
    row = cost.row()
    assert row["scopes"]["ds.mlp"]["replayed"] == 4 * product
    assert row["uncounted"] == {"ds_ssm_scan_fwd": 2, "while": 1}
    line = step_cost_line({"name": "train/train_step", "step_cost": row})
    assert "ds.mlp" in line and "ds_ssm_scan_fwd x2" in line
    assert step_cost_line({"name": "mixed_step"}) is None


# -- the engine --------------------------------------------------------------

def tiny_engine(tracing=False):
    engine, _, _, _ = ds.initialize(
        model=SimpleModel(),
        config={"train_batch_size": 16,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "tracing": {"enabled": tracing}},
        example_batch=batch_of(2))
    return engine


def test_the_engine_walks_its_one_trace_once_and_nothing_in_a_warm_step(
        monkeypatch):
    """The jaxpr is the one the step's lowering was made from: the step is
    traced ONCE (``note_compile`` runs where it is traced), the walk runs
    inside ``cost_capture``, and a warm step walks nothing, holds no jaxpr
    and fires no listener."""
    walks = []
    real = profiler.walk_jaxpr
    monkeypatch.setattr(profiler, "walk_jaxpr",
                        lambda jaxpr: walks.append(1) or real(jaxpr))
    engine = tiny_engine(True)
    assert engine._step_jaxpr is None
    engine.train_batch(batch=batch_of(16))
    prog = engine.perf.programs.program("train_step")
    assert walks == [1] and engine._step_jaxpr is None
    assert prog.compiles == 1 and prog.cost_source == "jaxpr"
    assert prog.bytes_accessed is None
    assert prog.step_cost.walk_s > 0
    assert prog.flops == prog.step_cost.model_flops > 0
    ledger = perf.compile_ledger()
    calls = ledger.snapshot()["calls"]
    for _ in range(4):
        engine.train_batch(batch=batch_of(16))
    assert walks == [1] and prog.compiles == 1
    assert ledger.snapshot()["calls"] == calls
    events = engine.tracer.events()
    names_ = [e["name"] for e in events]
    assert names_.count("step_cost") == names_.count("setup") == 1
    by = {e["name"]: e for e in events
          if e["name"] in ("cost_capture", "setup", "step_cost")}
    assert by["cost_capture"]["ts"] <= by["setup"]["ts"] \
        <= by["step_cost"]["ts"]
    assert by["step_cost"]["args"] == prog.step_cost.record()
    assert by["cost_capture"]["dur"] / 1e6 >= prog.step_cost.walk_s
    # the row /statusz and ds_report print
    (row,) = engine.perf.programs.table()
    assert row["cost_source"] == "jaxpr"
    assert row["step_cost"]["matmul_flops"] == prog.step_cost.matmul_flops
    assert "matrix operations" in step_cost_line(row)


def test_a_scanned_models_row_and_gauge_scale_with_its_depth():
    """The acceptance test of the gauge's source: on a scanned stack the
    train step's registry row (what ``train_mfu`` divides) scales with the
    depth. Lowered only: the engine holds zeros of the shapes, the step is
    traced and walked, nothing is compiled."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    batch = {"input_ids": np.zeros((8, 32), np.int32),
             "labels": np.zeros((8, 32), np.int32)}
    example = {k: v[:1] for k, v in batch.items()}
    flops = {}
    for layers in (2, 4, 6):
        model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                **example)["params"]
        engine, *_ = ds.initialize(
            model=model, example_batch=example,
            model_parameters=jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), shapes),
            config={"train_batch_size": 8, "bf16": {"enabled": True},
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        traced = engine._train_step.trace(
            engine.state, engine._shape_batch(batch), jax.random.PRNGKey(0))
        cost = engine.perf.capture_step_cost("train_step", traced.jaxpr)
        flops[layers] = engine.perf.programs.program("train_step").flops
        assert flops[layers] == cost.model_flops
        assert cost.replayed_flops > 0          # the tiny model is remat'ed
    assert flops[4] - flops[2] == flops[6] - flops[4] > 0
    # the head and the embedding's part stay: less than proportional
    assert flops[6] < 3 * flops[2]


def step_cost_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    found = [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in ("ds.step_cost", "ds.setup", "ds.train_batch")]
    return {name: [f for f in found if f[0] == name]
            for name in ("ds.step_cost", "ds.setup", "ds.train_batch")}


def test_one_step_cost_event_a_profiler_session(tmp_path):
    """Published by the mechanism that publishes ``ds.setup``: once a
    profiler session, inside the session's first ``ds.train_batch``, one
    stat a number."""
    engine = tiny_engine()
    for _ in range(2):
        engine.train_batch(batch=batch_of(16))
    record = engine.perf.programs.program("train_step").step_cost.record()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    for session in ("one", "two"):
        jax.profiler.start_trace(str(tmp_path / session),
                                 profiler_options=opts)
        try:
            for _ in range(3):
                engine.train_batch(batch=batch_of(16))
        finally:
            jax.profiler.stop_trace()
        engine.train_batch(batch=batch_of(16))      # between the sessions
        found = step_cost_events(str(tmp_path / session))
        assert len(found["ds.train_batch"]) == 3
        (event,), (setup,) = found["ds.step_cost"], found["ds.setup"]
        _, stats, start, dur = event
        assert {k: float(v) for k, v in stats.items()
                if not k.startswith("_")} == pytest.approx(record)
        s0, d0 = min((s, d) for _, _, s, d in found["ds.train_batch"])
        assert s0 <= start and start + dur <= s0 + d0
        assert setup[2] <= start
