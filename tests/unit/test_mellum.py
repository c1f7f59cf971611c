"""Mellum2's decoder (``models/mellum.py``: the GQA / sparse-expert stack of
``models/mixtral.py`` under a pattern of layer kinds) at tiny sizes in
float32 on the CPU: the system against the benchmark's plain reference
(``benchmark/reference/mellum.py``, written from the catalog row, not from
the system) at ONE CHIP'S SHARE — logits, loss and the gradient of every
parameter; YaRN's table against numbers worked by hand; the pattern from
config data (a period of 4, of 2, and of 1, which is ``MixtralModel``); the
eight shares adding up to the uncut layer; the training path through
``deepspeed_tpu.initialize``; the names a trace shows; what is not built
raising."""

import dataclasses
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import common
from deepspeed_tpu.models import layers, mellum
from deepspeed_tpu.models.mellum import (FULL, WINDOW, MellumConfig,
                                         MellumForCausalLM)
from deepspeed_tpu.models.mixtral import (MixtralConfig, MixtralForCausalLM,
                                          MixtralSparseMoeBlock)
from deepspeed_tpu.parallel import build_mesh

REF = common.load_file_module("reference", "mellum")
#: experts 2..4 of the router's 8
SHARE = dict(num_local_experts=2, router_experts=8, first_expert=2)
#: 8 frequency pairs, low 1 and high 5: pair 0 below the ramp, 1..5 on it
YARN = dict(rope_theta=100.0, yarn_factor=4.0,
            yarn_original_max_position_embeddings=64, yarn_beta_fast=4.0,
            yarn_beta_slow=1.0, yarn_attention_factor=1.25)
T = 48
IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, T)))


def tiny(**over):
    return MellumConfig.tiny(**{**dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=16, intermediate_size=64, moe_intermediate_size=16,
        num_experts_per_tok=2, norm_topk_prob=True, router_aux_loss_coef=0.0,
        qk_norm_per_head=True, per_expert_init=True,
        max_position_embeddings=512, rms_norm_eps=1e-6, sliding_window=16,
        num_hidden_layers=8, full_attention_period=4,
        report_expert_load=True, **YARN, **SHARE),
        **over})


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers."""
    sizes = {k: v for k, v in dataclasses.asdict(cfg).items()
             if isinstance(v, (int, float, bool)) or v is None}
    return {**sizes, "head_dim": cfg.hidden_size // cfg.num_attention_heads}


def seeded(cfg, seed=3, ids=IDS):
    """(model, params): the model's own init with the norms' scales moved
    off one, so that leaving one out shows."""
    model = MellumForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed), ids)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 128))
    return model, jax.tree_util.tree_map_with_path(
        lambda kp, p: p + 0.3 * jax.random.normal(next(keys), p.shape)
        if str(getattr(kp[-1], "key", "")) == "scale" else p, params)


def paths(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from paths(v, f"{prefix}{k}/")
        else:
            yield f"{prefix}{k}"


def leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


@pytest.fixture(scope="module")
def share():
    cfg = tiny()
    model, params = seeded(cfg)
    sizes = sizes_of(cfg)
    # one program a side (run operation by operation a gradient was some
    # thousand one-operation programs, compiled by every worker that drew a
    # case of this file)
    (loss, named), grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS),
        has_aux=True))(params)
    ref_grads = jax.jit(jax.grad(
        lambda p: REF.loss(p, sizes, np.asarray(IDS))))(params)
    return dict(cfg=cfg, model=model, params=params, sizes=sizes, loss=loss,
                named=named, grads=grads, ref_grads=ref_grads)


# -- the system against the reference ----------------------------------------

def test_logits_match_the_reference(share):
    got = share["model"].apply({"params": share["params"]}, IDS)
    for b in range(IDS.shape[0]):
        hidden, _ = REF.hidden_states(share["params"], share["sizes"], IDS[b])
        np.testing.assert_allclose(
            got[b], REF.logits(share["params"], hidden), rtol=2e-5,
            atol=2e-5)


def test_loss_and_gauges_match_the_reference(share):
    np.testing.assert_allclose(
        share["loss"], REF.loss(share["params"], share["sizes"],
                                np.asarray(IDS)), rtol=1e-5)
    rows = sum(REF.hidden_states(share["params"], share["sizes"], IDS[b])[1]
               for b in range(IDS.shape[0]))
    # pairs routed to the held experts / tokens x top-k x held / routed,
    # summed over the 8 layers
    np.testing.assert_allclose(
        share["named"]["moe_held_rows_over_expected"],
        float(jnp.sum(rows)) / (8 * IDS.size * 2 * 2 / 8), rtol=1e-6)
    np.testing.assert_allclose(
        share["named"]["moe_rows_max_over_mean"],
        float(jnp.max(rows) / jnp.mean(rows)), rtol=1e-6)


def test_gradient_of_every_parameter_matches_the_reference(share):
    """Every parameter kind of every position of the period: the four
    blocks' attention (per-head norms among them), router and held experts,
    the block norms, the table, the final norm and the head."""
    names = sorted(paths(share["grads"]))
    assert len(names) == 4 * 12 + 3
    for name in names:
        want, got = leaf(share["ref_grads"], name), leaf(share["grads"], name)
        assert got.shape[0] == 2 or "periods" not in name   # two periods
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6,
                                   err_msg=name)


def test_the_two_kinds_differ_where_the_reference_says(share, monkeypatch):
    """The reference with every layer read as one kind, or the full layers'
    table without its blend or its factor, is another model: the window
    (T = 48 over a window of 16) and YaRN both show at this size."""
    params, sizes = share["params"], share["sizes"]
    want, _ = REF.hidden_states(params, sizes, IDS[0])
    differs = lambda s: float(jnp.abs(
        REF.hidden_states(params, s, IDS[0])[0] - want).max()) > 1e-2
    assert differs({**sizes, "yarn_factor": None})
    assert differs({**sizes, "yarn_attention_factor": 1.0})
    for every_layer in (True, False):
        monkeypatch.setattr(REF, "is_full", lambda s, l: every_layer)
        assert differs(sizes)


# -- YaRN by hand ------------------------------------------------------------

def test_yarn_corrected_range_at_the_published_numbers():
    """d 128, theta 500,000, original length 8,192: corr(32) = 18.08 and
    corr(1) = 34.98, so the ramp runs from pair 18 to pair 35."""
    corr = lambda n: 128 * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(500000))
    assert (round(corr(32), 2), round(corr(1), 2)) == (18.08, 34.98)
    inv_freq, (low, high) = layers.yarn_inv_freq(128, 500000.0, 16.0, 8192)
    assert (low, high) == (18, 35) and inv_freq.shape == (64,)
    extrap = lambda i: 500000.0 ** (-2 * i / 128)
    # below the ramp: the frequency as published
    assert inv_freq[10] == pytest.approx(extrap(10), rel=1e-12)
    assert inv_freq[18] == pytest.approx(extrap(18), rel=1e-12)
    # inside: pair 25 is 7 / 17 of the way to a sixteenth
    ramp = 7 / 17
    assert inv_freq[25] == pytest.approx(
        extrap(25) * (ramp / 16 + 1 - ramp), rel=1e-12)
    assert inv_freq[25] == pytest.approx(3.6474e-3 , rel=1e-4)
    # above: a sixteenth
    assert inv_freq[35] == pytest.approx(extrap(35) / 16, rel=1e-12)
    assert inv_freq[63] == pytest.approx(extrap(63) / 16, rel=1e-12)
    # the reference computes the same table on its own
    ref, factor = REF.inv_freq(
        {"head_dim_override": 128, "rope_theta": 500000, "yarn_factor": 16,
         "yarn_original_max_position_embeddings": 8192,
         "yarn_beta_fast": 32, "yarn_beta_slow": 1,
         "yarn_attention_factor": 1.2772588722239782}, True)
    np.testing.assert_allclose(ref, inv_freq, rtol=2e-6)
    assert factor == 1.2772588722239782


def test_yarn_table_carries_the_attention_factor_on_cos_and_sin():
    pos = jnp.arange(40)[None, :]
    plain = layers.rotary_embedding(pos, 128, 500000.0)
    cos, sin = layers.yarn_rotary_embedding(
        pos, 128, 500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert cos.shape == sin.shape == plain[0].shape == (1, 40, 64)
    # position 0: cos is the factor itself; pairs below the ramp: the plain
    # table times the factor
    np.testing.assert_allclose(cos[0, 0], 1.2772588722239782, rtol=1e-6)
    np.testing.assert_allclose(cos[..., :19],
                               1.2772588722239782 * plain[0][..., :19],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(sin[..., :19],
                               1.2772588722239782 * plain[1][..., :19],
                               rtol=1e-4, atol=1e-5)
    # above it: the angle of a sixteenth of the position
    inv = 500000.0 ** (-2 * 40 / 128) / 16
    np.testing.assert_allclose(sin[0, 39, 40],
                               1.2772588722239782 * math.sin(39 * inv),
                               rtol=1e-4)
    # no factor given: 0.1 ln(factor) + 1
    default, _ = layers.yarn_rotary_embedding(pos, 128, 500000.0, 16.0, 8192)
    np.testing.assert_allclose(default[0, 0], 0.1 * math.log(16) + 1,
                               rtol=1e-6)


def test_tiny_yarn_numbers_exercise_the_ramp():
    """The tests' and the tiny configuration's YaRN numbers put the ramp
    inside the table (8 pairs: low 1, high 5), and a rotated score carries
    the factor's square."""
    _, (low, high) = layers.yarn_inv_freq(16, 100.0, 4.0, 64, 4.0, 1.0)
    assert (low, high) == (1, 5)
    file = common.load_json("configs", "mellum2-12b-a2.5b.json")["tiny"]
    assert (file["rope_theta"], file["yarn_factor"],
            file["yarn_original_max_position_embeddings"],
            file["yarn_beta_fast"], file["yarn_beta_slow"],
            file["head_dim_override"]) == (100, 4, 64, 4, 1, 16)
    cfg = tiny()
    tables = mellum.rope_tables(cfg, jnp.arange(8)[None, :], jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 1, 16))
    full = layers.apply_rotary(q, *tables[FULL])
    np.testing.assert_allclose(
        jnp.sum(full * full, -1), 1.25 ** 2 * jnp.sum(q * q, -1), rtol=1e-5)
    window = layers.apply_rotary(q, *tables[WINDOW])
    np.testing.assert_allclose(jnp.sum(window * window, -1),
                               jnp.sum(q * q, -1), rtol=1e-5)


# -- the pattern is config data ----------------------------------------------

@pytest.mark.parametrize("period,want", [
    (4, (WINDOW, WINDOW, WINDOW, FULL)),
    (8, (WINDOW,) * 7 + (FULL,)),
    (2, (WINDOW, FULL)),
    (1, (FULL,)),
])
def test_period_kinds_follow_the_config(period, want):
    cfg = tiny(full_attention_period=period)
    assert mellum.period_kinds(cfg) == want
    assert mellum.kind_config(cfg, WINDOW).sliding_window == 16
    assert mellum.kind_config(cfg, FULL).sliding_window is None
    assert dataclasses.replace(mellum.kind_config(cfg, FULL),
                               sliding_window=16) == cfg
    shapes = jax.eval_shape(MellumForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), IDS)["params"]
    blocks = shapes["model"]["periods"]
    assert sorted(blocks) == [f"block_{i}" for i in range(period)]
    assert blocks["block_0"]["self_attn"]["q_proj"]["kernel"].shape == \
        (8 // period, 32, 64)


@pytest.mark.parametrize("period", [4, 2])
def test_a_period_of_any_length_matches_the_reference(period):
    cfg = tiny(full_attention_period=period)
    model, params = seeded(cfg)
    got = model.apply({"params": params}, IDS[:1])
    hidden, _ = REF.hidden_states(params, sizes_of(cfg), IDS[0])
    np.testing.assert_allclose(got[0], REF.logits(params, hidden),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("scan", [True, False])
def test_a_period_of_one_is_mixtral_on_the_same_weights(scan):
    """Every layer full and no YaRN: ``MixtralModel`` without a window, the
    periods' one block being its scanned layer."""
    cfg = tiny(full_attention_period=1, yarn_factor=None, scan_layers=scan)
    model, params = seeded(cfg)
    fields = {f.name for f in dataclasses.fields(MixtralConfig)}
    plain = MixtralConfig(**{k: v for k, v in dataclasses.asdict(cfg).items()
                             if k in fields and k != "sliding_window"})
    assert plain.sliding_window is None and plain.scan_layers == scan
    stack = params["model"]
    if scan:
        theirs = {"layers": {"block": stack["periods"]["block_0"]}}
    else:
        theirs = {f"layers_{p}": stack[f"periods_{p}"]["block_0"]
                  for p in range(8)}
    theirs = {**params, "model": {
        **{k: v for k, v in stack.items() if not k.startswith("periods")},
        **theirs}}
    got = model.apply({"params": params}, IDS, labels=IDS)
    want = MixtralForCausalLM(plain).apply({"params": theirs}, IDS,
                                           labels=IDS)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for name in ("moe_rows_max_over_mean", "moe_held_rows_over_expected"):
        np.testing.assert_allclose(got[1][name], want[1][name], rtol=1e-6)
    np.testing.assert_allclose(
        model.apply({"params": params}, IDS),
        MixtralForCausalLM(plain).apply({"params": theirs}, IDS),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("field,path,std,want", [
    ("embed_init_std", "model/embed_tokens/embedding", None, 512 ** -0.5),
    ("embed_init_std", "model/embed_tokens/embedding", 1.0, 1.0),
    ("embed_init_std", "model/embed_tokens/embedding", 0.25, 0.25),
    ("head_init_std", "lm_head/kernel", None, 512 ** -0.5),
    ("head_init_std", "lm_head/kernel", 0.0002, 0.0002)])
def test_a_table_is_seeded_at_the_stated_scale(field, path, std, want):
    """``embed_init_std`` / ``head_init_std`` is the standard deviation of
    the input table's / the head's seeded rows and of nothing else (None:
    flax's ``1 / sqrt(hidden)``); the same seed draws every other parameter
    alike, and the reference reads the tree whatever its scale."""
    cfg = tiny(hidden_size=512, num_attention_heads=32, vocab_size=512,
               num_hidden_layers=4, **{field: std})
    ids = IDS[:, :16]
    params = MellumForCausalLM(cfg).init(jax.random.PRNGKey(3),
                                         ids)["params"]
    table = leaf(params, path)
    assert table.shape == (512, 512)
    assert float(jnp.std(table)) == pytest.approx(want, rel=0.02)
    plain = MellumForCausalLM(dataclasses.replace(
        cfg, **{field: None})).init(jax.random.PRNGKey(3), ids)["params"]
    rest = [k for k in paths(params) if k != path]
    assert len(rest) == len(list(paths(plain))) - 1
    assert all(np.array_equal(leaf(plain, k), leaf(params, k)) for k in rest)


def test_unrolled_periods_are_the_scanned_ones():
    cfg = tiny()
    model, params = seeded(cfg)
    flat = dataclasses.replace(cfg, scan_layers=False)
    stack = params["model"]["periods"]
    theirs = {**params, "model": {
        **{k: v for k, v in params["model"].items() if k != "periods"},
        **{f"periods_{p}": jax.tree_util.tree_map(lambda a: a[p], stack)
           for p in range(2)}}}
    np.testing.assert_allclose(
        MellumForCausalLM(flat).apply({"params": theirs}, IDS),
        model.apply({"params": params}, IDS), rtol=1e-5, atol=1e-6)


#: (leaves; sha256[:16] of every leaf's "path shape dtype"; the sum of every
#: |weight|) and the second period's first router weights of ``block_1``, of
#: the tiny presets as two periods of two kinds at ``PRNGKey(11)``: recorded
#: on PR 61's first commit, when each file still scanned a ``_Period`` of
#: its own
TREES = {
    "mellum": (23, "5f7577acaaf180af", 12481.355006518836),
    "qwen3_next": (36, "46cd00115af43436", 12821.14223604188),
}
GATE = [-0.2554759979248047, 0.21863120794296265, 0.042051441967487335,
        0.08249568194150925]


@pytest.mark.parametrize("family", sorted(TREES))
def test_the_shared_scan_keeps_each_models_parameter_tree(family):
    """``layers.scan_periods`` lays the two stacks' parameters where their
    own scans did (``periods/block_<i>/...``, the periods stacked on axis 0:
    partition rules, frozen parameters, checkpoints and the cells' seeded
    weights go by these paths) and draws them from the same keys."""
    import hashlib

    from deepspeed_tpu.models import qwen3_next

    if family == "mellum":
        model = MellumForCausalLM(MellumConfig.tiny(
            embed_init_std=1.0, num_hidden_layers=4, full_attention_period=2))
    else:
        model = qwen3_next.Qwen3NextForCausalLM(qwen3_next.Qwen3NextConfig.tiny(
            embed_init_std=1.0, num_hidden_layers=4,
            full_attention_interval=2))
    params = jax.jit(model.init)(jax.random.PRNGKey(11),
                                 jnp.zeros((1, 16), jnp.int32))["params"]
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    lines = [f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
             for path, leaf in leaves]
    count, digest, total = TREES[family]
    assert len(lines) == count
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == digest
    assert sum(float(np.abs(np.asarray(leaf, np.float64)).sum())
               for _, leaf in leaves) == pytest.approx(total, rel=1e-6)
    gate = params["model"]["periods"]["block_1"]["block_sparse_moe"]["gate"]
    np.testing.assert_allclose(gate["kernel"][1, 0], GATE, rtol=1e-6)


@pytest.mark.parametrize("layers_,barriers", [(4, True), (8, False)])
def test_a_lone_period_keeps_its_replay(layers_, barriers):
    """A scan of one trip is no loop once XLA is done with it, and the
    blocks' replay is then merged with their forward pass unless
    ``jax.checkpoint`` fences it (``prevent_cse``): one period asks for the
    fence, two leave it to the loop. The gradients are those without remat
    either way."""
    cfg = tiny(num_hidden_layers=layers_, remat=True)
    model, params = seeded(cfg)
    loss = lambda m: lambda p: m.apply({"params": p}, IDS, labels=IDS)[0]
    grad = jax.jit(jax.grad(loss(model)))
    # one barrier is the loss's own: its rule holds the logits' cotangent
    fences = grad.lower(params).as_text().count("optimization_barrier") - 1
    assert (fences > 0) == barriers and fences >= 0
    plain = MellumForCausalLM(dataclasses.replace(cfg, remat=False))
    want = jax.grad(loss(plain))(params)
    got = grad(params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


# -- the share ---------------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of a share, at 8 experts a router and 1 held a
    share: the parts of the expert layer's result that the eight shares give
    add up to what the reference gives with all 8 held (attention and router
    are whole on every chip, so the shares differ in their experts alone)."""
    full = tiny(num_local_experts=8, router_experts=None, first_expert=0)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    block = MixtralSparseMoeBlock(full)
    p = block.init(jax.random.PRNGKey(2), h)["params"]
    held = lambda cfg, p: jnp.stack(
        [REF.held_experts(h[b], p, sizes_of(cfg))[0] for b in range(2)])
    whole = held(full, p)
    parts, rows = 0, []
    for s in range(8):
        cfg = tiny(num_local_experts=1, router_experts=8, first_expert=s)
        ps = {**p, **{w: p[w][s:s + 1] for w in ("w1", "w2", "w3")}}
        out, _, _, r = MixtralSparseMoeBlock(cfg).apply({"params": ps}, h)
        np.testing.assert_allclose(out, held(cfg, ps), rtol=1e-4, atol=1e-6)
        parts, rows = parts + out, rows + [r]
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-6)
    assert int(jnp.sum(jnp.concatenate(rows))) == 2 * T * 2
    assert float(jnp.abs(whole).max()) > 1e-3


def test_a_quarter_share_at_real_row_counts_takes_the_compact_buffer():
    """8 of 64 held at 8,192 tokens and top-8: 16,384 compact rows for
    65,536, and the gauge that says whether a layer's held pairs fitted."""
    from deepspeed_tpu.models.mixtral import _compact_rows, _extra_stats

    assert _compact_rows(8192 * 8, 8, 64) == 16384
    cfg = MellumConfig.mellum2_12b_a2_5b(
        num_local_experts=8, router_experts=64, num_hidden_layers=8)
    assert _extra_stats(cfg, 8192 * 8) == ["compact_hit"]
    assert (cfg.head_dim, cfg.expert_width, cfg.router_width) == (128, 896,
                                                                  64)


# -- through the engine ------------------------------------------------------

def test_trains_through_initialize_and_names_its_scopes():
    """``deepspeed_tpu.initialize`` -> ``train_batch``: the loss falls, the
    optimizer never moves the frozen router, one compile; the lowered step
    names the two kinds' outer scopes around every inner name, and the
    tables' own."""
    cfg = tiny(remat=True, router_trainable=False, report_expert_load=True)
    model = MellumForCausalLM(cfg)
    batch = {"input_ids": np.asarray(IDS), "labels": np.asarray(IDS)}
    # one device, as the benchmark's cell has it (PERF.md section 7: under
    # a data axis of 8 CPU devices this step's collectives rendezvous out
    # of order and XLA:CPU aborts; Mixtral's own step does not)
    engine, *_ = ds.initialize(
        mesh=build_mesh(devices=jax.devices()[:1]),
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        partition_rules=MellumForCausalLM.partition_rules(cfg),
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
    gate = lambda: np.asarray(engine.state.params["model"]["periods"][
        "block_3"]["block_sparse_moe"]["gate"]["kernel"])
    before = gate()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(gate(), before)
    assert engine.perf.programs.program("train_step").compiles == 1
    text = engine._train_step.lower(
        engine.state, engine._shape_batch(batch),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("ds.rope_tables", "ds.layer_stack", "ds.layer_window",
                  "ds.layer_full", "ds.attention", "ds.attn_proj",
                  "ds.moe_router", "ds.moe_experts", "ds.norm",
                  "ds.residual", "ds.lm_head_loss"):
        assert re.search(re.escape(scope) + r"\b", text), scope
    # an inner name under its kind's outer scope, in the forward pass and
    # in what the backward pass replays
    assert re.search(r"ds\.layer_full[^\"]*ds\.attention", text)
    assert re.search(r"ds\.layer_window[^\"]*ds\.moe_experts", text)
    assert re.search(
        r"ds\.layer_full[^\"]*rematted_computation[^\"]*ds\.attn_proj", text)


def test_partition_rules_and_frozen_parameters_reach_the_periods():
    cfg = tiny(router_trainable=False)
    rules = MellumForCausalLM.partition_rules(cfg)
    shapes = jax.eval_shape(MellumForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), IDS)["params"]
    names = list(paths(shapes))
    hit = lambda pattern: [n for n in names if re.search(pattern, n)]
    assert len(hit(rules[1][0])) == 4 * 3          # q, k, v of four blocks
    assert len(hit(r"block_sparse_moe/(w1|w3)")) == 8
    frozen = MellumForCausalLM.frozen_parameters(cfg)
    assert len(hit(frozen[0])) == 4
    assert MellumForCausalLM.frozen_parameters(tiny()) == []


# -- what is not built -------------------------------------------------------

def test_what_is_not_built_raises():
    """(Each raised before any arithmetic: parameters by shape.)"""
    model = MellumForCausalLM(tiny())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), IDS)["params"]
    with pytest.raises(NotImplementedError, match="training"):
        model.apply({"params": params}, IDS, cache={}, cache_index=0)
    init = lambda cfg: jax.eval_shape(MellumForCausalLM(cfg).init,
                                      jax.random.PRNGKey(0), IDS)
    with pytest.raises(ValueError, match="whole periods"):
        init(tiny(full_attention_period=3))
    with pytest.raises(NotImplementedError, match="selection"):
        init(tiny(sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                                 q_chunk_size=16, kv_chunk_size=16, topk=8)))
    with pytest.raises(NotImplementedError, match="held share"):
        init(tiny(report_expert_load=True, router_experts=None,
                  num_local_experts=4))
    with pytest.raises(ValueError, match="router"):
        init(tiny(first_expert=7))
    with pytest.raises(NotImplementedError, match="head_init_std"):
        init(tiny(head_init_std=0.001, loss_chunk=16))
