"""Nemotron-3-Nano-30B-A3B's decoder (``models/nemotron_h.py``: a stack whose
every layer is ONE residual branch -- a Mamba-2 mixer, an attention layer
without rotation or an ungated expert layer -- under a pattern string) at tiny
sizes in float32 on the CPU: the system against the benchmark's plain
reference (``benchmark/reference/nemotron_h.py``, the recurrence token by
token) at ONE CHIP'S SHARE -- logits, loss and the gradient of every
parameter, under ``attention_impl="xla"`` and under the flash kernels in
interpret mode; the duality form against the token-by-token recurrence,
values and gradients, at two chunk lengths, a ragged tail and a decay of
hundreds of nats a chunk; the ungated grouped experts' written-out backward
against autodiff of a loop over experts; the pattern cut into runs and the
published 52 layers built from shapes; the sixteen shares adding up to the
uncut layer; what the stack offers its remat policy; the training path through
``deepspeed_tpu.initialize``; what is not built raising."""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import common
from deepspeed_tpu.models import mixtral as mx
from deepspeed_tpu.models import layers, nemotron_h as nh
from deepspeed_tpu.models.nemotron_h import (FULL, MAMBA, MOE, NemotronHBlock,
                                             NemotronHConfig,
                                             NemotronHForCausalLM)
from deepspeed_tpu.ops.pallas import (REMAT_MOE_ROWS, REMAT_MOE_UP, REMAT_QKV,
                                      REMAT_SSM_IN)
from deepspeed_tpu.parallel import build_mesh

REF = common.load_file_module("reference", "nemotron_h")
#: experts 2..4 of the router's 8
SHARE = dict(num_local_experts=2, router_experts=8, first_expert=2)
T = 44          # no multiple of the chunk of 8: the last chunk is ragged
IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, T)))


def tiny(**over):
    """Published layers 3-12, ``EM*EMEMEM*``: 4 expert, 4 Mamba-2 (4 heads of
    8 over a state of 8, 2 groups, chunks of 8) and 2 attention layers (8
    query heads over 2 key-value heads of 8)."""
    return NemotronHConfig.tiny(**{**dict(report_expert_load=True, **SHARE),
                                   **over})


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers."""
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float, bool)) or v is None}


def seeded(cfg, seed=3, ids=IDS):
    """(model, params): the model's own init with the norms' scales and
    ``D`` moved off one, so that leaving one out shows."""
    model = NemotronHForCausalLM(cfg)

    @jax.jit        # one program, not an operation a leaf
    def make(key, jitter_key):
        params = model.init(key, ids)["params"]
        keys = iter(jax.random.split(jitter_key, 128))
        return jax.tree_util.tree_map_with_path(
            lambda kp, p: p + 0.3 * jax.random.normal(next(keys), p.shape)
            if str(getattr(kp[-1], "key", "")) in ("scale", "norm_scale", "D")
            else p, params)

    return model, make(jax.random.PRNGKey(seed),
                       jax.random.PRNGKey(seed + 100))


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


def loss_and_grads(model, params):
    """``((loss, (named scalars, logits)), gradients)``: ONE jitted
    ``value_and_grad``, not a forward, a loss and a gradient program."""
    def loss(p):
        loss, named = model.apply({"params": p}, IDS, labels=IDS)
        return loss, (named, model.apply({"params": p}, IDS))

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


@pytest.fixture(scope="module")
def share():
    cfg = tiny()
    model, params = seeded(cfg)
    sizes = sizes_of(cfg)
    (loss, (named, logits)), grads = loss_and_grads(model, params)

    def reference(p):
        hidden, rows = zip(*(REF.hidden_states(p, sizes, IDS[b])
                             for b in range(IDS.shape[0])))
        return REF.loss(p, sizes, np.asarray(IDS)), (
            [REF.logits(p, h) for h in hidden], sum(rows))

    # the reference's side of every comparison: ONE program (run operation
    # by operation it was some thousand, compiled by every worker that drew
    # a case of this file)
    (ref_loss, (ref_logits, ref_rows)), ref_grads = jax.jit(
        jax.value_and_grad(reference, has_aux=True))(params)
    return dict(cfg=cfg, model=model, params=params, sizes=sizes, loss=loss,
                named=named, logits=logits, grads=grads, ref_loss=ref_loss,
                ref_logits=ref_logits, ref_rows=ref_rows, ref_grads=ref_grads)


# -- the system against the reference ----------------------------------------

def test_logits_match_the_reference(share):
    for got, want in zip(share["logits"], share["ref_logits"]):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_loss_and_gauges_match_the_reference(share):
    np.testing.assert_allclose(share["loss"], share["ref_loss"], rtol=1e-5)
    rows = share["ref_rows"]
    # pairs routed to the held experts / tokens x top-k x held / routed,
    # summed over the 4 EXPERT layers (the other six route nothing)
    named = share["named"]
    np.testing.assert_allclose(
        named["moe_held_rows_over_expected"],
        float(jnp.sum(rows)) / (4 * IDS.size * 2 * 2 / 8), rtol=1e-6)
    np.testing.assert_allclose(
        named["moe_rows_max_over_mean"],
        float(jnp.max(rows) / jnp.mean(rows)), rtol=1e-6)
    assert sorted(named) == ["moe_held_rows_over_expected",
                             "moe_rows_max_over_mean", "ssm_chunk_decay_max"]
    # the largest sum of dt |A| over one chunk of 8, over the 4 Mamba
    # layers (test_duality_form_matches_the_recurrence holds its value)
    assert 0 < float(named["ssm_chunk_decay_max"]) < 100


def test_gradient_of_every_parameter_matches_the_reference(share):
    """Every parameter kind of every block of every run, by norm and by
    value: in_proj, taps and their bias, ``A_log``, ``D``, ``dt_bias``, the
    gated norm's scale and out_proj through the duality form; q, k, v, o;
    router, held experts (two matrices) and the shared expert; the block
    norms, the table, the final norm and the head."""
    names = sorted(paths(share["grads"]))
    # runs (EM*, 1), (EM, 3), (*, 1): blocks of 6, 9 and 5 leaves a kind
    assert len(names) == (6 + 9 + 5) + (6 + 9) + 5 + 3
    for name in names:
        want, got = leaf(share["ref_grads"], name), leaf(share["grads"], name)
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(
            jnp.linalg.norm(got), jnp.linalg.norm(want), rtol=1e-3,
            err_msg=name)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6,
                                   err_msg=name)


def test_the_reference_tells_the_equations_apart(share):
    """The reference without the routed scale, with another top-k, another
    share or another slice of the pattern is another model: each shows at
    this size."""
    params, sizes = share["params"], share["sizes"]
    want, _ = REF.hidden_states(params, sizes, IDS[0])
    differs = lambda s: float(jnp.abs(
        REF.hidden_states(params, s, IDS[0])[0] - want).max()) > 1e-2
    assert differs({**sizes, "routed_scaling_factor": 1.0})
    assert differs({**sizes, "num_experts_per_tok": 1})
    assert differs({**sizes, "first_expert": 0})
    assert differs({**sizes, "rms_norm_eps": 1.0})
    # another slice of the pattern reads the tree's blocks as other kinds
    with pytest.raises((KeyError, AssertionError)):
        REF.hidden_states(params, {**sizes, "first_layer": 0}, IDS[0])


def test_flash_kernels_in_interpret_mode_match_the_reference(share,
                                                             monkeypatch):
    """``attention_impl="flash"`` forced to the Pallas kernels (interpret
    mode; the CPU's public entry would take the einsum reference): 8 query
    heads over 2 key-value heads, no table ahead of them, tiles of 16 over a
    ragged 44, loss and every gradient against the reference's."""
    import deepspeed_tpu.ops.pallas.flash_attention as fa

    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, force_pallas=True))
    model = NemotronHForCausalLM(dataclasses.replace(
        share["cfg"], attention_impl="flash", flash_block_q=16,
        flash_block_k=16))
    fn = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, IDS, labels=IDS), has_aux=True)
    assert "name=ds_flash_fwd" in str(jax.make_jaxpr(fn)(share["params"]))
    (loss, _), grads = jax.jit(fn)(share["params"])
    np.testing.assert_allclose(loss, share["loss"], rtol=1e-5)
    for name in sorted(paths(grads)):
        np.testing.assert_allclose(
            leaf(grads, name), leaf(share["ref_grads"], name), rtol=2e-3,
            atol=2e-6, err_msg=name)


# -- the duality form against the recurrence, token by token -----------------

def _recurrence(x, dt, a, b, c):
    """The reference's own, a sequence of the batch at a time, each head
    given its group's ``B`` and ``C``."""
    r = x.shape[2] // b.shape[2]
    return jnp.stack([REF.recurrence(
        x[i], dt[i], a, jnp.repeat(b[i], r, 1), jnp.repeat(c[i], r, 1))
        for i in range(x.shape[0])])


def _ssd_case(T, scale, seed=0, H=4, P=8, G=2, N=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, T, H, P))
    dt = scale * jax.nn.softplus(jax.random.normal(ks[1], (2, T, H)))
    a = -jnp.arange(1, H + 1, dtype=jnp.float32)
    b, c = (jax.random.normal(k, (2, T, G, N)) for k in ks[2:])
    return x, dt, a, b, c


def _both(args, chunk):
    """``(value, gauge, gradients)`` of the duality form and ``(value,
    gradients)`` of the recurrence, each ONE jitted program at the highest
    precision, the gradients those of ``sum(y * probe)``."""
    probe = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def of(fn):
        def run(*z):
            (_, aux), grads = jax.value_and_grad(
                lambda *z: (lambda y, *more: (jnp.sum(y * probe), (y, *more)))(
                    *fn(*z)), argnums=(0, 1, 2, 3, 4), has_aux=True)(*z)
            return aux, grads
        with jax.default_matmul_precision("highest"):
            return jax.jit(run)(*args)

    (y, decay), got = of(lambda *z: nh.ssd(*z, chunk))
    (want,), ref = of(lambda *z: (_recurrence(*z),))
    return (y, decay, got), (want, ref)


@pytest.mark.parametrize("T,chunk", [(32, 8), (32, 16), (29, 8)])
def test_duality_form_matches_the_recurrence(T, chunk):
    """Values, the gauge and the gradient of every argument, at two chunk
    lengths; a T that is no multiple of the chunk is padded with ``dt = 0``:
    no decay, no update."""
    args = _ssd_case(T, 1.0, seed=T + chunk)
    (y, decay, got), (want, ref) = _both(args, chunk)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    dt, a = args[1], args[2]
    sums = jnp.pad(dt * -a, ((0, 0), (0, (-T) % chunk), (0, 0))).reshape(
        2, -1, chunk, 4).sum(2)
    np.testing.assert_allclose(decay, jnp.max(sums), rtol=1e-5)
    for g, r, name in zip(got, ref, "x dt a b c".split()):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-4, err_msg=name)


def test_duality_form_at_hundreds_of_nats_a_chunk():
    """Step sizes of the order of 10 under ``A`` down to -4: a chunk of 8
    holds hundreds of nats, a ratio of cumulative products would overflow
    (float32 holds 88); every exponent here is a difference ``<= 0``, so
    values and gradients stay finite and equal the recurrence's."""
    args = _ssd_case(32, 12.0, seed=7)
    (y, decay, got), (want, ref) = _both(args, 8)
    assert 300 < float(decay) < 3000
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    for g, r, name in zip(got, ref, "x dt a b c".split()):
        assert bool(jnp.isfinite(g).all()), name
        np.testing.assert_allclose(g, r, rtol=2e-3, atol=2e-4 * float(
            jnp.abs(r).max() + 1), err_msg=name)


def test_duality_form_keeps_no_state_a_position():
    """No array of the traced form holds ``[T, H, P, N]``: the largest
    intermediate is a chunk's tables or the boundary states."""
    x, dt, a, b, c = _ssd_case(64, 1.0)
    jaxpr = jax.make_jaxpr(lambda *z: nh.ssd(*z, 8))(x, dt, a, b, c)
    per_position = 2 * 64 * 4 * 8 * 8
    assert max(v.aval.size for eqn in jaxpr.eqns for v in eqn.outvars) \
        < per_position


# -- the ungated grouped experts ---------------------------------------------

@pytest.mark.parametrize("experts", [None, 32])
def test_ungated_experts_backward_matches_a_loop_over_experts(experts):
    """``_routed_experts`` under ``RELU2`` with no ``w3`` (the sorted buffer
    and, at 4 of a router's 32, the compact one): value and the written-out
    backward -- ``dx``, both weights', the routing weights' as a by-product
    of ``dh`` -- against autodiff of every token through every held
    expert."""
    N, H, I, G, K, first = 2048 if experts else 256, 16, 24, 4, 2, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (N, H))
    w1 = jax.random.normal(ks[1], (G, H, I)) / 4
    w2 = jax.random.normal(ks[2], (G, I, H)) / 4
    topk_w = jax.random.uniform(ks[3], (N, K), minval=0.2)
    # a router of 32 sends about 4 / 32 of the 4,096 pairs here: they fit
    # the compact buffer's 1,024 rows
    idx = jax.random.randint(ks[4], (N, K), 0, experts or first + G + 2)
    assert mx._compact_rows(N * K, G, experts) == (1024 if experts else None)

    def routed(x, w1, w2, topk_w):
        return mx._routed_experts(x, w1, w2, None, topk_w, idx, first,
                                  experts, mx.RELU2)[0]

    def loop(x, w1, w2, topk_w):
        out = jnp.zeros_like(x)
        for e in range(G):
            w = jnp.sum(jnp.where(idx == first + e, topk_w, 0), -1)
            out = out + w[:, None] * (jnp.maximum(x @ w1[e], 0) ** 2 @ w2[e])
        return out

    probe = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    with jax.default_matmul_precision("highest"):
        (out, got), (ref, want) = (jax.jit(jax.value_and_grad(
            lambda *z: (lambda y: (jnp.sum(y * probe), y))(fn(*z)),
            argnums=(0, 1, 2, 3), has_aux=True))(x, w1, w2, topk_w)
            for fn in (routed, loop))
    np.testing.assert_allclose(out[1], ref[1], rtol=1e-4, atol=1e-5)
    for g, w, name in zip(got, want, ("x", "w1", "w2", "topk_w")):
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4, err_msg=name)


def test_ungated_experts_take_the_compact_buffer_at_the_published_share():
    """8 of 128 held at 8,192 tokens and top-6: 6,144 compact rows for
    49,152; one first product a row in what the layer offers its remat
    policy; the SwiGLU's offers as they were."""
    assert mx._compact_rows(8192 * 6, 8, 128) == 6144
    x = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.bfloat16)
    up, rows = mx.expert_offers(x, 6, 1856, 8, 128, 4, firsts=1)
    assert up == (REMAT_MOE_UP, 4 * 6144 * 1856 * 2)
    assert mx.expert_offers(x, 6, 1856, 8, 128, 4)[0][1] == 2 * up[1]
    assert rows == mx.expert_offers(x, 6, 1856, 8, 128, 4)[1]
    assert mx._activation(tiny()) is mx.RELU2 and not mx.RELU2.gated
    assert mx._activation(mx.MixtralConfig.tiny()) is mx.SWIGLU


# -- the pattern -------------------------------------------------------------

def test_the_pattern_is_cut_into_runs_of_repeated_segments():
    assert nh.runs(nh.PUBLISHED_PATTERN) == (
        ("MEMEM*E", 5), ("ME", 3), ("M*", 1), ("EM", 4), ("E", 1))
    assert nh.runs("MEMEM*EME") == (("ME", 2), ("M*EME", 1))
    assert nh.runs("EM*EMEMEM*") == (("EM*", 1), ("EM", 3), ("*", 1))
    assert nh.runs("MEMEM*E") == (("ME", 2), ("M*E", 1))
    assert nh.runs("M") == (("M", 1),) and nh.runs("MMMM") == (("M", 4),)
    for pattern in (nh.PUBLISHED_PATTERN, "MEMEM*EME", "**EEMM*EMEM"):
        assert "".join(s * r for s, r in nh.runs(pattern)) == pattern
    assert nh.PUBLISHED_PATTERN == REF.PATTERN
    assert tiny().pattern == "EM*EMEMEM*"


def test_published_52_layers_are_built_from_shapes():
    """The published pattern at tiny widths, ``jax.eval_shape`` alone: 23
    Mamba-2, 23 expert and 6 attention layers, the attention at 5, 12, 19,
    26, 33 and 42, five runs."""
    cfg = NemotronHConfig.tiny(first_layer=0, num_hidden_layers=52)
    assert [i for i, k in enumerate(cfg.pattern) if k == FULL] == \
        [5, 12, 19, 26, 33, 42]
    shapes = jax.eval_shape(NemotronHForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), IDS)["params"]["model"]
    count = {MAMBA: 0, FULL: 0, MOE: 0}
    mark = {"mixer": MAMBA, "self_attn": FULL, "block_sparse_moe": MOE}
    for run in (k for k in shapes if k.startswith("run_")):
        for block in shapes[run]["periods"].values():
            kind = next(mark[k] for k in block if k in mark)
            count[kind] += block["norm"]["scale"].shape[0]
    assert count == {MAMBA: 23, MOE: 23, FULL: 6}
    assert sorted(k for k in shapes if k.startswith("run_")) == \
        [f"run_{i}" for i in range(5)]
    assert shapes["run_0"]["periods"]["block_5"]["self_attn"]["q_proj"][
        "kernel"].shape == (5, 32, 64)


def test_published_parameter_count():
    """The cut's and the whole model's parameters, from the model's own
    shapes: ISSUE 66's 667.0 M, and the published 31.6 B."""
    count = lambda cfg: sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(NemotronHForCausalLM(cfg).init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]))
    cut = NemotronHConfig.nemotron_3_nano_30b_a3b(
        num_hidden_layers=9, num_local_experts=8, router_experts=128,
        vocab_size=16384)
    assert count(cut) == 666962944
    assert round(count(NemotronHConfig.nemotron_3_nano_30b_a3b()) / 1e9, 2) \
        == 31.58


# -- the share tied to the model ---------------------------------------------

def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The floor's arithmetic, made a test: sixteen chips each hold one of an
    expert layer's sixteen experts; the router and the shared expert are
    whole on every chip. The parts the sixteen shares' layers add, with what
    every chip computes alike counted once (a share whose held expert adds
    nothing), sum to the reference's layer with all sixteen held."""
    full = tiny(num_local_experts=16, router_experts=16, first_expert=0,
                num_experts_per_tok=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    p = jax.jit(NemotronHBlock(full, MOE).init)(jax.random.PRNGKey(2),
                                                x)["params"]
    whole = jnp.stack([REF._layer(
        x[b], p, REF.dense._static(sizes_of(full)), "E")[0]
        for b in range(2)])

    def layer_of(s, w2=None):
        cfg = dataclasses.replace(full, num_local_experts=1, first_expert=s)
        moe = {**p["block_sparse_moe"], **{
            w: p["block_sparse_moe"][w][s:s + 1] for w in ("w1", "w2")}}
        if w2 is not None:
            moe["w2"] = w2 * moe["w2"]
        return NemotronHBlock(cfg, MOE).apply(
            {"params": {**p, "block_sparse_moe": moe}}, x)[0]

    alike = layer_of(0, w2=0.0)
    parts = alike + sum(layer_of(s) - alike for s in range(16))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=2e-5)
    assert float(jnp.abs(whole - alike).max()) > 1e-2    # the experts show
    assert float(jnp.abs(alike - x).max()) > 1e-2        # and the shared one


# -- what the stack offers its remat policy ----------------------------------

def test_remat_offers_name_each_kinds_costliest_replay():
    cfg = NemotronHConfig.nemotron_3_nano_30b_a3b(
        num_hidden_layers=9, num_local_experts=8, router_experts=128)
    x = jax.ShapeDtypeStruct((1, 8192, 2688), jnp.bfloat16)
    offers = nh.remat_offers(cfg, x)
    assert [n for n, _ in offers] == [REMAT_SSM_IN, REMAT_QKV, REMAT_MOE_UP,
                                      REMAT_MOE_ROWS]
    assert offers[0][1] == 4 * 8192 * 10304 * 2
    assert offers[1][1] == 1 * 8192 * 128 * (32 + 2 + 2) * 2
    assert offers[2:] == mx.expert_offers(x, 6, 1856, 8, 128, 4, firsts=1)
    # kept where a budget has room: the names reach the lowered step
    tiny_cfg = tiny(remat=True)
    model, params = seeded(tiny_cfg)
    # a function anew a trace: a traced one is cached whatever the room
    fn = lambda: jax.grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS)[0])
    assert "ds_ssm_in_proj" not in str(jax.make_jaxpr(fn())(params))
    with layers.remat_room(10 ** 9) as kept:
        text = str(jax.make_jaxpr(fn())(params))
    assert set(kept) == {REMAT_SSM_IN, REMAT_QKV, REMAT_MOE_UP,
                         REMAT_MOE_ROWS}
    for name in kept:
        assert f"name={name}" in text, name


# -- through the engine ------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = tiny(remat=True, router_trainable=False)
    model = NemotronHForCausalLM(cfg)
    batch = {"input_ids": np.asarray(IDS), "labels": np.asarray(IDS)}
    engine, *_ = ds.initialize(
        mesh=build_mesh(devices=jax.devices()[:1]),
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        partition_rules=NemotronHForCausalLM.partition_rules(cfg),
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
    return cfg, engine, batch


def test_trains_through_initialize_and_names_its_scopes(engine):
    """``deepspeed_tpu.initialize`` -> ``train_batch``: the loss falls, the
    optimizer never moves the frozen router, one compile, the named scalars
    become gauges; the lowered step names the three kinds' outer scopes
    around every inner name."""
    cfg, engine, batch = engine
    gate = lambda: np.asarray(engine.state.params["model"]["run_1"][
        "periods"]["block_0"]["block_sparse_moe"]["gate"]["kernel"])
    before = gate()
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(gate(), before)
    assert engine.perf.programs.program("train_step").compiles == 1
    found = engine.registry.snapshot()
    assert {"ssm_chunk_decay_max", "moe_rows_max_over_mean",
            "moe_held_rows_over_expected"} <= set(found)
    assert found["ssm_chunk_decay_max"] > 0
    text = engine._train_step.lower(
        engine.state, engine._shape_batch(batch),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("ds.layer_stack", "ds.layer_mamba", "ds.layer_full",
                  "ds.layer_moe", "ds.ssm_mix", "ds.ssm_scan",
                  "ds.attention", "ds.attn_proj", "ds.moe_router",
                  "ds.moe_experts", "ds.moe_shared", "ds.norm",
                  "ds.residual", "ds.lm_head_loss"):
        assert re.search(re.escape(scope) + r"\b", text), scope
    # an inner name under its kind's outer scope, in the forward pass and
    # in what the backward pass replays
    assert re.search(r"ds\.layer_mamba[^\"]*ds\.ssm_scan", text)
    assert re.search(r"ds\.layer_moe[^\"]*ds\.moe_shared", text)
    assert re.search(r"ds\.layer_full[^\"]*ds\.attention", text)
    assert re.search(
        r"ds\.layer_mamba[^\"]*rematted_computation[^\"]*ds\.ssm_mix", text)
    assert not re.search(r"ds\.layer_mamba[^\"]*ds\.moe_", text)
    assert not re.search(r"ds\.layer_moe[^\"]*ds\.attn_proj", text)
    assert "ds.rope_tables" not in text


def test_partition_rules_and_frozen_parameters_cover_the_new_names():
    cfg = tiny(router_trainable=False)
    rules = NemotronHForCausalLM.partition_rules(cfg)
    shapes = jax.eval_shape(NemotronHForCausalLM(cfg).init,
                            jax.random.PRNGKey(0), IDS)["params"]
    resolved = {}
    for name in paths(shapes):
        spec = next((s for pattern, s in rules if re.search(pattern, name)),
                    None)
        if spec is not None:
            spec = spec(None) if callable(spec) else spec
            assert len(spec) == leaf(shapes, name).ndim, name
        resolved[name] = spec
    assert not any("w3" in n for n in resolved)
    block = "model/run_1/periods/block_0/"
    assert tuple(resolved[block + "block_sparse_moe/w1"]) == \
        (None, "expert", None, None)
    assert tuple(resolved[block + "block_sparse_moe/w2"]) == \
        (None, "expert", None, None)
    assert tuple(resolved["model/run_2/periods/block_0/self_attn/q_proj/"
                          "kernel"]) == (None, None, "model")
    # a Mamba mixer and the shared expert stay whole on every chip
    assert all(resolved[n] is None for n in resolved
               if "/mixer/" in n or "/shared_expert/" in n)
    frozen = NemotronHForCausalLM.frozen_parameters(cfg)
    assert len([n for n in resolved if re.search(frozen[0], n)]) == 2
    assert NemotronHForCausalLM.frozen_parameters(tiny()) == []


# -- what is not built -------------------------------------------------------

def test_what_is_not_built_raises():
    """(Each raised before any arithmetic: parameters by shape.)"""
    model = NemotronHForCausalLM(tiny())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), IDS)["params"]
    with pytest.raises(NotImplementedError, match="training"):
        model.apply({"params": params}, IDS, cache={}, cache_index=0)
    with pytest.raises(NotImplementedError, match="packed"):
        model.apply({"params": params}, IDS, attention_mask=jnp.ones_like(IDS))
    init = lambda cfg: jax.eval_shape(NemotronHForCausalLM(cfg).init,
                                      jax.random.PRNGKey(0), IDS)
    with pytest.raises(ValueError, match="pattern"):
        init(tiny(first_layer=50))
    with pytest.raises(ValueError, match="one of"):
        init(tiny(hybrid_override_pattern="MEM-EMEMEMEMEME"))
    with pytest.raises(ValueError, match="group"):
        init(tiny(n_groups=3))
    with pytest.raises(ValueError, match="key-value head"):
        init(tiny(num_attention_heads=7))
    with pytest.raises(NotImplementedError, match="window"):
        init(tiny(sliding_window=8))
    with pytest.raises(NotImplementedError, match="held share"):
        init(tiny(report_expert_load=True, router_experts=None,
                  num_local_experts=4))
    with pytest.raises(ValueError, match="router"):
        init(tiny(first_expert=7))
    with pytest.raises(NotImplementedError, match="chunked"):
        init(tiny(loss_chunk=16))
    with pytest.raises(NotImplementedError, match="ungated"):
        mx._expert_mlp(tiny(router_experts=None), jnp.zeros((2, 1, 32)),
                       jnp.zeros((2, 32, 16)), jnp.zeros((2, 16, 32)), None,
                       jnp.ones((2, 1, 2)), jnp.zeros((2, 1, 2), jnp.int32))
