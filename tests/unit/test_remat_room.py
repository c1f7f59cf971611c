"""What a remat'ed block keeps beyond its policy (PR 57): the rule alone
(``models/layers.py keep_for_room``), the names under each policy (a tiny
Llama and a tiny Qwen3-Next with the delta rule's kernels interpreted), and
the engine's side -- the budget it states, the compile ahead of the first
call, the check on the compiled step and the fallback."""

import collections
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import layers
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.models.layers import (REMAT_FACTOR, keep_for_room,
                                         remat_room, resolve_remat_policy)
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        remat_offers)
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.models.ouro import OuroConfig, OuroForCausalLM
from deepspeed_tpu.ops.pallas import (GDN_GATE_BWD, GDN_GATE_FWD,
                                      GDN_PREMIX_BWD, GDN_PREMIX_FWD,
                                      GDN_RULE_BWD, GDN_RULE_FWD,
                                      REMAT_GDN_MIX, REMAT_GDN_QKVZ,
                                      REMAT_GDN_RULE, REMAT_MLP, REMAT_QKV,
                                      gdn_mix, gdn_rule)
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime import engine as engine_module
from deepspeed_tpu.utils.logging import logger

POLICIES = ("nothing", "dots", "dots_no_batch", "offload_dots_no_batch")
OFFERED = (("a", 100), ("b", 40), ("c", 10))


# -- the rule alone ----------------------------------------------------------

@pytest.mark.parametrize("budget,kept", [
    (0, ()),                               # today's policy
    (REMAT_FACTOR * 100 - 1, ("b", "c")),  # a passed over, the walk goes on
    (REMAT_FACTOR * 100, ("a",)),          # between a and a + b
    (REMAT_FACTOR * 110, ("a", "c")),      # b does not fit beside a, c does
    (REMAT_FACTOR * 140, ("a", "b")),
    (REMAT_FACTOR * 150, ("a", "b", "c")),
], ids=["none", "first_too_large", "first_only", "skip_second", "two", "all"])
def test_rule_walks_the_offer_in_order_under_the_factor(budget, kept):
    with remat_room(budget) as chosen:
        assert keep_for_room(OFFERED) == kept
        assert chosen == {n: b for n, b in OFFERED if n in kept}
    # outside a stated room the budget is 0
    assert keep_for_room(OFFERED) == ()


def test_rooms_nest_and_close():
    with remat_room(10 ** 6) as outer:
        with remat_room(0) as inner:
            assert keep_for_room(OFFERED) == ()
        assert keep_for_room(OFFERED) == ("a", "b", "c")
        assert inner == {} and set(outer) == {"a", "b", "c"}
    assert layers._room.budget == 0 and layers._room.kept is None


@pytest.mark.parametrize("policy", POLICIES)
def test_with_nothing_kept_the_policy_answers_as_before(policy):
    """An offer under budget 0 changes no answer: the offered names are not
    saved, the flash kernel's are, everything else is the named policy's."""
    from jax.ad_checkpoint import checkpoint_name

    from deepspeed_tpu.ops.pallas import FLASH_OUT

    name_p = jax.make_jaxpr(lambda x: checkpoint_name(x, "n"))(
        1.0).eqns[0].primitive
    plain = resolve_remat_policy(policy)
    offered = resolve_remat_policy(policy, ((REMAT_MLP, 8), (REMAT_QKV, 8)))
    with remat_room(10 ** 6):
        kept = resolve_remat_policy(policy, ((REMAT_MLP, 8), (REMAT_QKV, 8)))
    dot = dict(precision=None, preferred_element_type=None)
    for prim, params in [
            (name_p, dict(name=REMAT_MLP)), (name_p, dict(name=REMAT_QKV)),
            (name_p, dict(name=FLASH_OUT)),
            (jax.lax.dot_general_p, dict(
                dimension_numbers=(((1,), (0,)), ((), ())), **dot)),
            (jax.lax.exp_p, dict(accuracy=None))]:
        assert repr(offered(prim, **params)) == repr(plain(prim, **params))
    assert kept(name_p, name=REMAT_MLP) is True
    assert kept(name_p, name=REMAT_QKV) is True
    assert repr(kept(name_p, name="other")) == repr(plain(name_p,
                                                          name="other"))


def test_llama_offers_its_two_names_with_their_bytes():
    cfg = LlamaConfig.tiny()                     # 4 heads, 2 kv heads of 16
    x = jax.ShapeDtypeStruct((2, 32, cfg.hidden_size), jnp.bfloat16)
    tokens = 2 * 32 * 2                          # bf16 bytes a column
    assert remat_offers(cfg, x, 3) == (
        (REMAT_MLP, 3 * 2 * cfg.intermediate_size * tokens),
        (REMAT_QKV, 3 * (4 + 2 + 2) * cfg.head_dim * tokens))


# -- the names under each policy: a tiny Llama -------------------------------

INTER = (2, 32, 128)    # [B, T, LlamaConfig.tiny's intermediate size]


def _llama(policy, remat=True):
    cfg = LlamaConfig.tiny(remat=remat, remat_policy=policy)
    assert cfg.intermediate_size == INTER[-1] and cfg.scan_layers
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 32)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    return jax.value_and_grad(loss), params


def _products_into(jaxpr, shape):
    """``dot_general`` equations whose result is of ``shape``, the
    sub-jaxprs (scan bodies, remat'ed calls) among them."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and \
                eqn.outvars[0].aval.shape == shape:
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _products_into(sub, shape)
    return found


@pytest.mark.parametrize("policy", POLICIES)
def test_kept_names_take_the_products_out_of_llamas_replay(policy):
    """The gradient of the scanned, remat'ed tiny Llama holds three products
    ``[B, T, intermediate]`` a layer body with both names kept -- gate and
    up in the forward scan, the activation's cotangent in the backward --
    and five under ``"nothing"`` without them (the replay's gate and up);
    q, k, v the same. Loss and every gradient are, bit for bit, those of
    the step that keeps nothing (a kept value is the value the replay would
    have computed), and the un-remat'ed function's to the ulps by which
    XLA:CPU's other fusion of that backward already differs from both."""
    fn, params = _llama(policy)
    plain = jax.make_jaxpr(fn)(params)
    want = jax.jit(fn)(params)
    # a function of its own: jax keeps a function's trace, and a room is
    # no part of that cache's key (the engine states one for the FIRST
    # trace of a step it has just built)
    fn, _ = _llama(policy)
    with remat_room(10 ** 9) as kept:
        named = jax.make_jaxpr(fn)(params)
        got = jax.jit(fn)(params)
    assert set(kept) == {REMAT_MLP, REMAT_QKV}
    assert str(named).count(f"name={REMAT_MLP}") >= 2
    assert REMAT_MLP not in str(plain) and REMAT_QKV not in str(plain)
    assert _products_into(named.jaxpr, INTER) == 3
    assert _products_into(plain.jaxpr, INTER) == \
        (5 if policy == "nothing" else 3)
    total = lambda jaxpr: str(jaxpr).count("dot_general")
    if policy == "nothing":      # gate, up, q, k, v: five fewer
        assert total(plain) - total(named) == 5
    _same(got, want, jax.jit(_llama(policy, remat=False)[0])(params))


def _same(got, want, unremated):
    for a, b, c in zip(*map(jax.tree_util.tree_leaves,
                            (got, want, unremated))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-5,
                                   atol=1e-7)


def test_a_budget_between_the_two_keeps_the_first_alone():
    fn, params = _llama("nothing")
    cfg = LlamaConfig.tiny()
    x = jax.ShapeDtypeStruct((2, 32, cfg.hidden_size), jnp.float32)
    (_, mlp), _ = remat_offers(cfg, x, cfg.num_hidden_layers)
    with remat_room(REMAT_FACTOR * mlp) as kept:
        named = jax.make_jaxpr(fn)(params)
    assert list(kept) == [REMAT_MLP]
    assert REMAT_QKV not in str(named)
    assert _products_into(named.jaxpr, INTER) == 3


@pytest.mark.parametrize("build", [
    lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)),
    lambda: OuroForCausalLM(OuroConfig.tiny(remat=True)),
], ids=["mixtral", "ouro_refused"])
def test_a_model_that_offers_nothing_or_is_refused_holds_no_name(build):
    """``MixtralBlock`` runs ``LlamaAttention`` and offers nothing; ouro
    offers ``llama.py``'s two names and a small budget refuses both: neither
    gradient holds a ``name`` equation of them, whatever the room."""
    model = build()
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    loss_of = lambda p: loss(p)[0] if isinstance(loss(p), tuple) else loss(p)
    budget = 10 ** 9 if isinstance(model, MixtralForCausalLM) else 64
    with remat_room(budget) as kept:
        text = str(jax.make_jaxpr(jax.grad(loss_of))(params))
    assert kept == {}
    assert REMAT_MLP not in text and REMAT_QKV not in text


def test_ouro_counts_every_layer_of_every_pass():
    cfg = OuroConfig.tiny(remat=True)
    model = OuroForCausalLM(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    with remat_room(10 ** 9) as kept:
        jax.make_jaxpr(jax.grad(
            lambda p: model.apply({"params": p}, ids, labels=ids)))(params)
    x = jax.ShapeDtypeStruct((1, 16, cfg.hidden_size), jnp.float32)
    assert kept == dict(remat_offers(
        cfg, x, cfg.num_hidden_layers * cfg.total_ut_steps))


# -- the names under each policy: a tiny Qwen3-Next --------------------------

@pytest.fixture
def delta_rule_kernels(monkeypatch):
    """The delta rule's and its mixer's kernels forced on (interpret mode:
    the choosers answered with a tiling), as the chip runs the layer."""
    monkeypatch.setattr(qn, "_rule_tiling", lambda *a: gdn_rule.Tiling(2, 2))
    monkeypatch.setattr(qn, "_mix_tiling", lambda *a: gdn_mix.Tiling(16))


def _calls(jaxpr, found=None):
    """``{kernel or "triangular_solve": equations}`` of a jaxpr, the
    sub-jaxprs (scan bodies, remat'ed and jitted calls) among them."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] += 1
        elif eqn.primitive.name == "triangular_solve":
            found["triangular_solve"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _calls(sub, found)
    return dict(found)


def _qwen3_next(policy, remat=True):
    cfg = qn.Qwen3NextConfig.tiny(num_hidden_layers=4, remat=remat,
                                  remat_policy=policy)
    model = qn.Qwen3NextForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (1, 16)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    return jax.value_and_grad(loss), params


@pytest.mark.parametrize("policy", POLICIES)
def test_kept_names_take_the_rule_out_of_qwen3_nexts_replay(
        policy, delta_rule_kernels):
    """One period of three delta-rule layers and a full one: with the rule's
    name kept the gradient calls ``ds_gdn_rule_fwd`` once a layer, without
    it twice (the replay); with all three names kept the replay holds no
    triangular solve and no premix or gate kernel either. Under the default
    policy (the kernels interpreted are slow: one policy runs) loss and
    every gradient are those of the step that keeps nothing, bit for bit
    (``test_qwen3_next.py`` holds that step to the reference)."""
    fn, params = _qwen3_next(policy)
    plain = jax.make_jaxpr(fn)(params)
    named_fn, _ = _qwen3_next(policy)
    with remat_room(10 ** 9) as kept:
        named = jax.make_jaxpr(named_fn)(params)
        if policy == "nothing":
            got = jax.jit(named_fn)(params)
    assert list(kept) == [REMAT_GDN_RULE, REMAT_GDN_QKVZ, REMAT_GDN_MIX]
    forward = {GDN_RULE_FWD, GDN_PREMIX_FWD, GDN_GATE_FWD}
    backward = {GDN_RULE_BWD, GDN_PREMIX_BWD, GDN_GATE_BWD}
    # a layer: the forward and the replay (the backward applies the
    # inverse it holds by products: qwen3_next._chunk_inverse_jvp)
    assert _calls(plain.jaxpr) == {**dict.fromkeys(forward, 6),
                                   **dict.fromkeys(backward, 3),
                                   "triangular_solve": 6}
    assert _calls(named.jaxpr) == {**dict.fromkeys(forward | backward, 3),
                                   "triangular_solve": 3}
    if policy == "nothing":
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(jax.jit(fn)(params))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qwen3_next_keeps_the_rule_first_and_the_mixer_last(
        delta_rule_kernels):
    fn, params = _qwen3_next("nothing")
    cfg = qn.Qwen3NextConfig.tiny(num_hidden_layers=4)
    x = jax.ShapeDtypeStruct((1, 16, cfg.hidden_size), jnp.float32)
    offered = qn.remat_offers(cfg, x, qn.period_kinds(cfg))
    assert [n for n, _ in offered] == [REMAT_GDN_RULE, REMAT_GDN_QKVZ,
                                       REMAT_GDN_MIX]
    # three layers' o [T, 4 heads of 8], boundary states [4, 2 chunks, 8, 8]
    # and inverse [4, 2, 8, 8] in float32
    assert offered[0][1] == 3 * (16 * 32 * 4 + 2 * 4 * 2 * 8 * 8 * 4)
    two = sum(b for _, b in offered[:2])
    with remat_room(REMAT_FACTOR * two) as kept:
        named = jax.make_jaxpr(fn)(params)
    assert list(kept) == [REMAT_GDN_RULE, REMAT_GDN_QKVZ]
    assert REMAT_GDN_MIX not in str(named)


def test_off_the_kernels_a_delta_rule_layer_offers_nothing():
    cfg = qn.Qwen3NextConfig.tiny()
    x = jax.ShapeDtypeStruct((1, 32, cfg.hidden_size), jnp.float32)
    assert qn.remat_offers(cfg, x, qn.period_kinds(cfg)) == ()


# -- the engine --------------------------------------------------------------

CONFIG = {"train_batch_size": 2,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}


def _engine(model):
    engine, *_ = ds.initialize(
        model=model, config=CONFIG,
        mesh=build_mesh(devices=jax.devices()[:1]),
        example_batch={"input_ids": np.zeros((2, 32), np.int32),
                       "labels": np.zeros((2, 32), np.int32)})
    return engine


def _train(engine, steps=3):
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        ids = rng.integers(0, 128, (2, 32)).astype(np.int32)
        losses.append(float(engine.train_batch(
            batch={"input_ids": ids, "labels": ids})))
    return losses


def _lowered_text(engine):
    ids = np.zeros((2, 32), np.int32)
    batch = engine._shape_batch({"input_ids": ids, "labels": ids})
    return engine._train_step.lower(
        engine.state, batch, jax.random.PRNGKey(0)).as_text()


TINY = {
    "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny(remat=True)),
    "mixtral": lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)),
    "ouro": lambda: OuroForCausalLM(OuroConfig.tiny(remat=True)),
    "qwen3_next": lambda: qn.Qwen3NextForCausalLM(
        qn.Qwen3NextConfig.tiny(num_hidden_layers=4, remat=True)),
}


@pytest.mark.parametrize("family", sorted(TINY))
def test_on_a_cpu_the_lowered_step_names_nothing(family, monkeypatch):
    """A CPU keeps no allocator numbers: the budget is 0, nothing is kept,
    and the engine's lowered step holds no ``name`` equation -- it is, to
    the character, the text it lowers to with the naming helper made the
    identity (what the step was before a name was offered)."""
    engine = _engine(TINY[family]())
    assert engine_module._device_memory(jax.devices()[0]) is None
    text = _lowered_text(engine)
    from deepspeed_tpu.models import llama

    for module in (llama, qn):
        monkeypatch.setattr(module, "name_if_kept", lambda x, name: x)
    assert _lowered_text(_engine(TINY[family]())) == text
    if family != "llama":       # one family's step runs: the record is the
        return                  # engine's, not the model's
    _train(engine, 1)
    record = engine.setup.record(0)
    assert record["remat_kept_bytes"] == record["remat_room_bytes"] == 0
    assert record["remat_kept_names"] == record["remat_fallbacks"] == 0


@pytest.fixture
def device_memory(monkeypatch):
    """``device_memory(limit, in_use)``: the engine reads these numbers of
    its device, as a chip's allocator would give them."""
    def state(limit, in_use=0):
        monkeypatch.setattr(engine_module, "_device_memory",
                            lambda device: (limit, in_use))
    return state


def test_engine_states_the_budget_and_compiles_the_step_once(device_memory,
                                                             capsys):
    """With room the tiny Llama keeps both names; the step is lowered and
    compiled ahead of the first call and the call finds it (one trace, one
    lowering, one backend compile of ``train_step``, then the cost capture's
    cached lowering); the losses are those of an engine that kept nothing."""
    want = _train(_engine(TINY["llama"]()))
    device_memory(10 ** 8, 10 ** 7)
    engine = _engine(TINY["llama"]())
    before = dict(engine.setup.ledger.snapshot()["by_fun"].get(
        "train_step", {}))
    assert _train(engine) == want
    after = engine.setup.ledger.snapshot()["by_fun"]["train_step"]
    assert after["programs"] - before.get("programs", 0) == 1
    program = engine.perf.programs.program("train_step")
    assert program.compiles == 1 and not program.cost_pending
    record = engine.setup.record(0)
    cfg = LlamaConfig.tiny()
    x = jax.ShapeDtypeStruct((2, 32, cfg.hidden_size), jnp.float32)
    offered = dict(remat_offers(cfg, x, cfg.num_hidden_layers))
    assert record["remat_kept_names"] == 2
    assert record["remat_kept_bytes"] == sum(offered.values())
    assert record["remat_room_bytes"] == \
        int(layers.REMAT_SHARE * 10 ** 8) - 10 ** 7
    assert record["remat_fallbacks"] == 0
    assert record["step_argument_bytes"] > 0 and record["step_temp_bytes"] > 0
    for name in ("remat_kept_bytes", "remat_room_bytes", "step_temp_bytes"):
        assert engine.registry.gauge(f"setup_{name}").value == record[name]
    # and the one line under the program's row (/statusz, ds_report)
    from deepspeed_tpu.env_report import perf_report
    from deepspeed_tpu.monitor.export import memory_line

    (row,) = engine.perf.programs.table()
    assert row["memory"] == {k: record[k] for k in engine.setup.COUNTS}
    line = memory_line(row)
    assert line.startswith("  train/train_step: keeps 2 offered values, ")
    assert "(0 taken back); compiled: arguments " in line
    assert memory_line({"name": "mixed_step"}) is None
    perf_report()
    assert line in capsys.readouterr().out.splitlines()


def test_a_step_over_the_margin_is_built_again_with_nothing_kept(
        device_memory):
    """A limit the rule finds room under and the compiled step does not fit:
    the engine builds the step once more with budget 0, says so in one
    line, counts the fallback, and trains to the same losses."""
    want = _train(_engine(TINY["llama"]()))
    device_memory(10 ** 6)      # budget 750 kB; the step's arguments 1.3 MB
    engine = _engine(TINY["llama"]())
    heard = []
    listener = logging.Handler()
    listener.emit = lambda record: heard.append(record.getMessage())
    logger.addHandler(listener)     # the package's logger does not propagate
    try:
        assert _train(engine) == want
    finally:
        logger.removeHandler(listener)
    said = [line for line in heard if "built again with nothing kept" in line]
    assert len(said) == 1 and REMAT_MLP in said[0]
    record = engine.setup.record(0)
    assert record["remat_fallbacks"] == 1
    assert record["remat_kept_bytes"] == record["remat_kept_names"] == 0
    assert record["remat_room_bytes"] == int(layers.REMAT_SHARE * 10 ** 6)
    assert engine.perf.programs.program("train_step").compiles == 2
    assert engine._remat_budget()[0] == 0      # and stays there


def test_no_budget_under_a_mesh_of_several_devices(device_memory):
    device_memory(10 ** 9)
    engine, *_ = ds.initialize(
        model=TINY["llama"](), config={**CONFIG, "train_batch_size": 4},
        mesh=build_mesh(devices=jax.devices()[:2]),
        example_batch={"input_ids": np.zeros((4, 32), np.int32),
                       "labels": np.zeros((4, 32), np.int32)})
    assert engine._remat_budget() == (0, (10 ** 9, 0))
