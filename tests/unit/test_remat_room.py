"""What a remat'ed block keeps beyond its policy (PR 57): the rule alone
(``models/layers.py keep_for_room``), the names under each policy (a tiny
Llama, a tiny Qwen3-Next with the delta rule's kernels interpreted, and --
PR 59 -- the expert blocks: Mixtral with and without a compact row buffer,
Mellum's period, a DeepSeek-V3 share), and the engine's side -- the budget
it states, the compile ahead of the first call, the check on the compiled
step and the fallback. PR 62: budget and bytes are ONE device's whatever the
mesh -- ``layers.device_part``, the offers under ``expert`` and ``data``
axes, the expert layer's names inside its ``shard_map``. PR 65: ZAYA's block
-- the expert sublayer's output under its scaled residual, the projections
ahead of the convolutions, what the mixer hands on, the MLP router's float32
values."""

import collections
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import deepseek_v3 as dsv3
from deepspeed_tpu.models import layers, mellum
from deepspeed_tpu.models import mixtral as mx
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.models import zaya as zy
from deepspeed_tpu.models.layers import (REMAT_FACTOR, keep_for_room,
                                         remat_room, resolve_remat_policy)
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                        remat_offers)
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.models.ouro import OuroConfig, OuroForCausalLM
from deepspeed_tpu.models.sdar import SdarConfig, SdarForCausalLM
from deepspeed_tpu.ops.pallas import (GDN_GATE_BWD, GDN_GATE_FWD,
                                      GDN_PREMIX_BWD, GDN_PREMIX_FWD,
                                      GDN_RULE_BWD, GDN_RULE_FWD,
                                      REMAT_ATTN_OUT, REMAT_CCA_MIX,
                                      REMAT_GDN_MIX, REMAT_GDN_QKVZ,
                                      REMAT_GDN_RULE, REMAT_MLP,
                                      REMAT_MOE_OUT, REMAT_MOE_ROWS,
                                      REMAT_MOE_UP, REMAT_QKV, REMAT_ROUTER,
                                      gdn_mix, gdn_rule)
from deepspeed_tpu.parallel import topology
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


def _llama_offer():
    cfg = LlamaConfig.tiny()                     # 4 heads, 2 kv heads of 16
    x = jax.ShapeDtypeStruct((2, 32, cfg.hidden_size), jnp.bfloat16)
    tokens = 2 * 32 * 2                          # bf16 bytes a column
    return remat_offers(cfg, x, 3), (
        (REMAT_MLP, 3 * 2 * cfg.intermediate_size * tokens),
        (REMAT_QKV, 3 * (4 + 2 + 2) * cfg.head_dim * tokens))


def _mixtral_offer():
    """No compact buffer: every one of the 128 pairs has a sorted row (the
    index vectors: three words a pair and one an expert)."""
    cfg = MixtralConfig.tiny()      # hidden 32, 4 / 2 heads of 8, 4 experts
    x = jax.ShapeDtypeStruct((2, 32, 32), jnp.bfloat16)     # of 64, top-2
    return mx.remat_offers(cfg, x, 3), (
        (REMAT_ATTN_OUT, 3 * 32 * 2 * 32 * 2),
        (REMAT_QKV, 3 * (4 + 2 + 2) * 8 * 2 * 32 * 2),
        (REMAT_MOE_UP, 3 * 2 * 128 * 64 * 2),
        (REMAT_MOE_ROWS, 3 * (128 * 32 * 2 + 4 * (3 * 128 + 4))))


def _compact_offer():
    """2 of 16 experts held: the 1,024 pairs sort onto ``_compact_rows``'
    512 rows, and the offer counts those (the index vectors stay a pair's)."""
    cfg = MixtralConfig.tiny(num_local_experts=2, router_experts=16)
    x = jax.ShapeDtypeStruct((1, 512, 32), jnp.float32)
    assert mx._compact_rows(1024, 2, 16) == 512
    return mx.remat_offers(cfg, x, 2), (
        (REMAT_ATTN_OUT, 2 * 32 * 512 * 4),
        (REMAT_QKV, 2 * (4 + 2 + 2) * 8 * 512 * 4),
        (REMAT_MOE_UP, 2 * 2 * 512 * 64 * 4),
        (REMAT_MOE_ROWS, 2 * (512 * 32 * 4 + 4 * (3 * 1024 + 2))))


def _kept_under_a_room(model, shape):
    """``{name: bytes}`` the rule keeps of what ``model`` offers for ids of
    ``shape`` under a room with space for everything: its forward pass
    traced from shapes alone (a block makes its offer where it builds its
    policy, which is the forward pass: the gradient's trace, three times as
    long, asks the rule nothing more)."""
    ids = jnp.zeros(shape, jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    with remat_room(10 ** 9) as kept:
        jax.eval_shape(
            lambda p: model.apply({"params": p}, ids, labels=ids), params)
    return dict(kept)


def _trace_and_run(fn, *args, run=True):
    """``(jaxpr, outputs)`` of ONE trace of ``fn``: the program that is read
    is the program that runs (``make_jaxpr`` and then ``jit`` traced every
    model twice). A trace reads the room it stands in."""
    traced = jax.jit(fn).trace(*args)
    return traced.jaxpr, traced.lower().compile()(*args) if run else None


@functools.lru_cache(maxsize=None)
def _seeded(family):
    """``(ids, params)`` of a tiny model of ``family``: ONE jitted ``init``
    a family a PROCESS (run operation by operation an ``init`` is some
    hundreds of one-operation programs, each compiled cold by whichever
    worker draws the case; a process-wide memo serves a worker whichever
    cases ``--dist load`` hands it, which a fixture's scope does not
    promise). Numpy COPIES: a cached device array would stay in
    ``jax.live_arrays()`` for the worker's later files, and
    ``test_engine.py`` counts them."""
    build = {"llama": _llama_model,
             "qwen3_next": _qwen3_next_model}.get(family)
    model, ids = build("nothing") if build else _expert_model(family)
    return np.asarray(ids), jax.tree_util.tree_map(np.array, jax.jit(
        model.init)(jax.random.PRNGKey(0), jnp.asarray(ids))["params"])


def _mellum_offer():
    """``MellumModel`` offers ``MixtralBlock``'s names over every block of
    every period (the tiny model: two periods of four)."""
    cfg = mellum.MellumConfig.tiny(remat=True)
    kept = _kept_under_a_room(mellum.MellumForCausalLM(cfg), (1, 16))
    x = jax.ShapeDtypeStruct((1, 16, cfg.hidden_size), jnp.float32)
    assert cfg.num_hidden_layers == 8
    return tuple(kept.items()), mx.remat_offers(cfg, x, 8)


def _deepseek_offer():
    """One unrolled dense layer and two scanned expert layers (8 experts of
    16, top-3, two shared experts of 16; 4 heads of 8 + 4 and 8): the model
    offers what the scanned layers name, over those two alone -- XLA merges
    an unrolled layer's replay with its forward pass."""
    kept = _kept_under_a_room(dsv3.DeepseekV3ForCausalLM(
        dsv3.DeepseekV3Config.tiny(remat=True)), (2, 16))
    tokens, pairs = 2 * 16 * 4, 2 * 16 * 3      # float32 bytes a column
    return tuple(kept.items()), (
        (REMAT_ATTN_OUT, 2 * 32 * tokens),
        (REMAT_MLP, 2 * 2 * 2 * 16 * tokens),
        (REMAT_QKV, 2 * 4 * (12 + 12 + 8) * tokens),
        (REMAT_MOE_UP, 2 * 2 * pairs * 16 * 4),
        (REMAT_MOE_ROWS, 2 * (pairs * 32 * 4 + 4 * (3 * pairs + 8))))


def _zaya_offer():
    """Three scanned layers of 4 / 2 heads of 8 over hidden 32, a router of
    16 columns' width scoring 8 experts and the skip expert, top-1, experts
    of 16 (8 of 9 held: no compact buffer), the name worth most a byte
    first: the expert sublayer's output; q, k and the value's two halves as
    the projections write them; the experts' gate and up products; the
    router's four ``[.., 16]`` float32 values, its 9 logits and a word each
    for the choice and its weight; the first convolution's output with q and
    k ahead of their unit length (48 columns each) -- and neither the
    attention's output nor the experts' sorted rows, which cost more to keep
    than to replay (``zaya.remat_offers``). ``ZayaModel`` offers exactly
    that, and each offer's bytes are the bytes of the values its forward
    pass names so."""
    cfg = zy.ZayaConfig.tiny(remat=True)
    model = zy.ZayaForCausalLM(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    with remat_room(10 ** 9) as kept:
        forward = jax.make_jaxpr(
            lambda p: model.apply({"params": p}, ids, labels=ids))(params)
    assert _named_bytes(forward.jaxpr, None, inside=True) == dict(kept)
    tokens, pairs = 2 * 16 * 4, 2 * 16 * 1       # float32 bytes a column
    x = jax.ShapeDtypeStruct((2, 16, cfg.hidden_size), jnp.float32)
    assert tuple(kept.items()) == zy.remat_offers(cfg, x, 3)
    return tuple(kept.items()), (
        (REMAT_MOE_OUT, 3 * 32 * tokens),
        (REMAT_QKV, 3 * (4 + 2 + 1 + 1) * 8 * tokens),
        (REMAT_MOE_UP, 3 * 2 * pairs * 16 * 4),
        (REMAT_ROUTER, 3 * (4 * 16 + 9 + 1 + 1) * tokens),
        (REMAT_CCA_MIX, 3 * 2 * (4 + 2) * 8 * tokens))


@pytest.mark.parametrize("offer", [
    _llama_offer, _mixtral_offer, _compact_offer, _mellum_offer,
    _deepseek_offer, _zaya_offer], ids=["llama", "mixtral", "mixtral_compact",
                                        "mellum_period", "deepseek_v3",
                                        "zaya"])
def test_a_block_offers_its_names_with_their_bytes(offer):
    got, want = offer()
    assert got == want


# -- the names under each policy: a tiny Llama -------------------------------

INTER = (2, 32, 128)    # [B, T, LlamaConfig.tiny's intermediate size]


def _llama_model(policy, remat=True):
    cfg = LlamaConfig.tiny(remat=remat, remat_policy=policy)
    assert cfg.intermediate_size == INTER[-1] and cfg.scan_layers
    return LlamaForCausalLM(cfg), \
        np.random.RandomState(0).randint(0, 256, (2, 32))


def _llama(policy, remat=True):
    """A function of its own a call (jax keeps a function's trace, and a
    room is no part of that cache's key: the engine states one for the FIRST
    trace of a step it has just built) over the family's one set of
    parameters: the policy and the remat change no shape."""
    model, _ = _llama_model(policy, remat)
    ids, params = _seeded("llama")
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    return jax.value_and_grad(loss), params


@functools.lru_cache(maxsize=None)
def _llama_unremated():
    """Loss and gradients of the un-remat'ed tiny Llama, which no policy
    changes: computed once a process."""
    fn, params = _llama("nothing", remat=False)
    return jax.tree_util.tree_map(np.array, jax.jit(fn)(params))


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
    plain, want = _trace_and_run(fn, params)
    fn, _ = _llama(policy)
    with remat_room(10 ** 9) as kept:
        named, got = _trace_and_run(fn, params)
    assert set(kept) == {REMAT_MLP, REMAT_QKV}
    assert str(named).count(f"name={REMAT_MLP}") >= 2
    assert REMAT_MLP not in str(plain) and REMAT_QKV not in str(plain)
    assert _products_into(named.jaxpr, INTER) == 3
    assert _products_into(plain.jaxpr, INTER) == \
        (5 if policy == "nothing" else 3)
    total = lambda jaxpr: str(jaxpr).count("dot_general")
    if policy == "nothing":      # gate, up, q, k, v: five fewer
        assert total(plain) - total(named) == 5
    _same(got, want, _llama_unremated())


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


# -- the names under each policy: the expert blocks (PR 59) ------------------

def _expert_model(family):
    """``(model, ids)`` of a tiny remat'ed expert model: Mixtral whole (no
    compact buffer), Mixtral holding 2 of 16 experts (a compact buffer of
    512 rows for 1,024 pairs), Mellum's two periods, a DeepSeek-V3 share
    with one dense layer ahead of its scanned expert layers, ZAYA's three
    layers scanned or unrolled."""
    rng = np.random.RandomState(0)
    if family == "mixtral":
        return MixtralForCausalLM(MixtralConfig.tiny(remat=True)), \
            rng.randint(0, 128, (2, 32))
    if family == "mixtral_compact":
        return MixtralForCausalLM(MixtralConfig.tiny(
            remat=True, num_local_experts=2, router_experts=16)), \
            rng.randint(0, 128, (1, 512))
    if family.startswith("zaya"):
        return zy.ZayaForCausalLM(zy.ZayaConfig.tiny(
            remat=True, scan_layers=family == "zaya")), \
            rng.randint(0, 128, (2, 32))
    if family == "mellum":      # as published: an RMSNorm a head on q and k
        return mellum.MellumForCausalLM(mellum.MellumConfig.tiny(
            remat=True, qk_norm_per_head=True)), rng.randint(0, 128, (2, 16))
    return dsv3.DeepseekV3ForCausalLM(dsv3.DeepseekV3Config.tiny(
        remat=True)), rng.randint(0, 128, (2, 16))


def _expert_grad(family):
    """A function of its own a call over the family's one set of
    parameters (``_seeded``)."""
    model, _ = _expert_model(family)
    ids, params = _seeded(family)
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    return jax.value_and_grad(loss), params


@functools.lru_cache(maxsize=None)
def _plain(family):
    """The family's gradient traced with no room stated, once a process:
    ``(its primitives, the trace)`` -- a case that compares values runs the
    trace it has, a case that counts equations reads it."""
    traced = jax.jit(_expert_grad(family)[0]).trace(_seeded(family)[1])
    return _primitives(traced.jaxpr.jaxpr), traced


def _primitives(jaxpr, found=None):
    """``{primitive: equations}`` of a jaxpr, the sub-jaxprs among them."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


#: ``(remat'ed layer bodies that offer, projections a body's replay loses,
#: whether the layer has a compact buffer)``: q, k, v and the attention's
#: output -- of a DeepSeek-V3 expert layer q, the keys' and values' expansion,
#: the output and the shared experts' gate and up (the latent's own projection
#: stays: the expansion's weight gradient reads its norm). Mellum's q and k
#: have a norm a head, whose backward reads its input: they are named ahead
#: of it, or the replay would keep both projections
EXPERT_FAMILIES = {"mixtral": (1, 4, False), "mixtral_compact": (1, 4, True),
                   "mellum": (4, 4, False), "deepseek_v3": (1, 5, False)}


@pytest.mark.parametrize("family", sorted(EXPERT_FAMILIES))
def test_kept_names_take_the_expert_layer_out_of_the_replay(family):
    """With the expert layer's two names kept a layer body's replay holds no
    grouped product (on this CPU ``ragged_dot``; ``ds_moe_gmm`` on the chip:
    ``test_tpu_compile.py``), no ``argsort`` and no scatter of its inverse,
    and where the layer has a compact buffer no ``cond`` -- the forward's
    three products and the backward's six stand, one sort and one scatter
    for the forward's; q, k, v and the dense products beside them the
    same. Loss and every gradient are, bit for bit, those of
    the step that keeps nothing."""
    expert_bodies, projections, compact = EXPERT_FAMILIES[family]
    plain, traced = _plain(family)
    fn, params = _expert_grad(family)   # jax keeps a function's trace
    want = traced.lower().compile()(params)
    with remat_room(10 ** 9) as kept:
        named, got = _trace_and_run(fn, params)
    named = _primitives(named.jaxpr)
    assert {REMAT_ATTN_OUT, REMAT_QKV, REMAT_MOE_UP,
            REMAT_MOE_ROWS} <= set(kept)
    assert (REMAT_MLP in kept) == (family == "deepseek_v3")
    # the replay's gate and up products (in each branch of its ``cond``)
    assert plain["ragged_dot_general"] - named["ragged_dot_general"] == \
        (4 if compact else 2) * expert_bodies
    if not compact:     # the forward's three and the backward's six stand
        assert named["ragged_dot_general"] == 9 * expert_bodies
    for moved in ("sort", "scatter"):   # the argsort and its inverse
        assert plain[moved] == 2 * expert_bodies
        assert named[moved] == expert_bodies
    assert (plain["cond"], named["cond"]) == ((3, 2) if compact else (0, 0))
    assert plain["dot_general"] - named["dot_general"] == \
        expert_bodies * projections
    assert plain["name"] == 0
    # (Mellum's replay runs the heads' norm and RoPE from the kept q and k,
    # which XLA:CPU fuses otherwise than beside the projections: ulps)
    tight = dict(rtol=0, atol=0) if family != "mellum" else \
        dict(rtol=2e-5, atol=1e-7)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tight)


@pytest.mark.parametrize("family", ["mixtral", "deepseek_v3"])
def test_a_budget_between_two_expert_names_keeps_the_first_ones(family):
    """A budget that ends between the expert layer's two names keeps the
    gate and up products and every name ahead of them, and not the sorted
    rows: the replay sorts and scatters as before and runs no grouped
    product."""
    model, ids = _expert_model(family)
    x = jax.ShapeDtypeStruct((*ids.shape, model.config.hidden_size),
                             jnp.float32)
    offered = mx.remat_offers(model.config, x, 2) if family == "mixtral" \
        else dsv3.remat_offers(model.config, x, 2)
    assert [n for n, _ in offered[-2:]] == [REMAT_MOE_UP, REMAT_MOE_ROWS]
    fn, params = _expert_grad(family)
    with remat_room(REMAT_FACTOR * sum(b for _, b in offered[:-1])) as kept:
        named, _ = _trace_and_run(fn, params, run=False)
    assert list(kept) == [n for n, _ in offered[:-1]]
    assert f"name={REMAT_MOE_ROWS}" not in str(named)
    found = _primitives(named.jaxpr)
    assert found["ragged_dot_general"] == 9 and found["sort"] == 2


# -- the names under each policy: ZAYA's block (PR 65) ------------------------

@pytest.mark.parametrize("family", [
    "zaya",     # unrolled: 27 s cold (PR 69); the scanned stack asks the same
    pytest.param("zaya_unrolled", marks=pytest.mark.slow)])
def test_kept_names_take_the_projections_the_router_and_the_experts_out_of_zayas_replay(
        family):
    """With everything offered kept a layer body's replay holds ONE
    product, ``o_proj``: not q, k, v1, v2, not the grouped convolution's two
    taps, not the router's down-projection nor its three at the highest
    precision (ten ``dot_general`` a body fewer), no grouped product (with
    nothing kept the replay runs all THREE -- the down product too, for the
    scaled residual's output scale -- beside the forward's three and the
    backward's six) and no ``top_k``. What it still runs besides: the
    experts' ``argsort``, its scatter and the rows' gather (neither
    ``o_proj``'s output nor the sorted rows are offered:
    ``zaya.remat_offers``), and element-wise the norms, the residuals, the
    unit length, RoPE, the router's norm, GELUs and softmax from the kept
    state, products and logits. Loss and gradients are those of the step
    that keeps nothing."""
    bodies = 1 if family == "zaya" else 3
    plain, traced = _plain(family)
    fn, params = _expert_grad(family)   # jax keeps a function's trace
    want = traced.lower().compile()(params)
    with remat_room(10 ** 9) as kept:
        named, got = _trace_and_run(fn, params)
    named = _primitives(named.jaxpr)
    assert list(kept) == [REMAT_MOE_OUT, REMAT_QKV, REMAT_MOE_UP,
                          REMAT_ROUTER, REMAT_CCA_MIX]
    assert plain["dot_general"] - named["dot_general"] == 10 * bodies
    assert (plain["ragged_dot_general"], named["ragged_dot_general"]) == \
        (12 * bodies, 9 * bodies)
    assert (plain["top_k"], named["top_k"]) == (2 * bodies, bodies)
    for stays in ("sort", "scatter"):
        assert plain[stays] == named[stays] == 2 * bodies
    assert plain["name"] == 0
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-7)


#: ``{names kept, in the offer's order: (products, grouped products,
#: top_ks) a scanned body's replay still runs}``: 11 / 3 / 1 with nothing
#: kept, 1 / 0 / 0 (``o_proj``) with all five
ZAYA_ROOMS = {
    1: (11, 2, 1),      # the sublayer's output: no down product
    2: (7, 2, 1),       # + q, k, v1, v2
    3: (7, 0, 1),       # + gate and up: no grouped product
    4: (3, 0, 0),       # + the router: its four products and the top_k
}                       # (the mixer's values, the last: the grouped taps)


@pytest.mark.parametrize("names", sorted(ZAYA_ROOMS))
def test_a_room_between_two_of_zayas_names_keeps_the_first_ones(names):
    plain, _ = _plain("zaya")
    cfg = zy.ZayaConfig.tiny()
    x = jax.ShapeDtypeStruct((2, 32, cfg.hidden_size), jnp.float32)
    offered = zy.remat_offers(cfg, x, cfg.num_hidden_layers)
    fn, params = _expert_grad("zaya")
    with remat_room(REMAT_FACTOR * sum(b for _, b in offered[:names])) as kept:
        text, _ = _trace_and_run(fn, params, run=False)
    assert list(kept) == [n for n, _ in offered[:names]]
    assert f"name={offered[names][0]}" not in str(text)
    named = _primitives(text.jaxpr)
    products, grouped, top_ks = ZAYA_ROOMS[names]
    assert plain["dot_general"] - named["dot_general"] == 11 - products
    assert named["ragged_dot_general"] == 9 + grouped
    assert named["sort"] == 2 and named["top_k"] == 1 + top_ks


N, K, E, G, H, I = 1024, 3, 32, 4, 16, 24   # test_moe_compact.py's layer


@pytest.mark.parametrize("held_pairs", [None, 1023, 1024, N * K],
                         ids=["level", "at_capacity", "overflow_by_one",
                              "all_held"])
def test_an_overflowing_step_trains_alike_with_the_names_kept(held_pairs):
    """Two scanned, remat'ed expert layers over a compact buffer of 1,024
    rows: whether the held pairs fit it or not (then the backward computes
    its own forward again over every row: the compact-shaped values the rule
    kept are not read), the gradients with both names kept are those of the
    layer that keeps nothing, bit for bit."""
    assert mx._compact_rows(N * K, G, E) == 1024
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (N, H))
    w1, w3 = (jax.random.normal(k, (2, G, H, I)) / H ** 0.5 for k in ks[1:3])
    w2 = jax.random.normal(ks[3], (2, G, I, H)) / I ** 0.5
    topk_w = jax.random.uniform(ks[4], (N, K), minval=0.1, maxval=1.0)

    def routing(layer):
        if held_pairs is None:
            return jax.lax.top_k(jax.random.uniform(
                jax.random.PRNGKey(7 + layer), (N, E)), K)[1].astype(jnp.int32)
        slot = jnp.arange(N * K).reshape(N, K)
        n, k = slot // K, slot % K
        return jnp.where(slot < held_pairs, (n + k + layer) % G,
                         G + (n * K + k + layer) % (E - G)).astype(jnp.int32)

    idx = jnp.stack([routing(0), routing(1)])
    stream = jax.ShapeDtypeStruct((1, N, H), x.dtype)

    def grads(budget):
        def loss(x, w1, w2, w3, topk_w):
            def body(x, layer):
                w1, w2, w3, idx = layer
                out, _ = mx._routed_experts(x, w1, w2, w3, topk_w, idx, 0, E)
                return x + out, None

            with remat_room(budget) as kept:
                policy = resolve_remat_policy(
                    "nothing", mx.expert_offers(stream, K, I, G, E, 2))
                y, _ = jax.lax.scan(jax.checkpoint(
                    body, prevent_cse=False, policy=policy), x,
                    (w1, w2, w3, idx))
                assert len(kept) == (2 if budget else 0)
            return jnp.mean(y ** 2)

        fn = jax.value_and_grad(loss, argnums=tuple(range(5)))
        with remat_room(budget):        # the trace of the gradient
            jaxpr, out = _trace_and_run(fn, x, w1, w2, w3, topk_w)
            return _primitives(jaxpr.jaxpr), out

    (plain, want), (named, got) = grads(0), grads(10 ** 9)
    assert (plain["cond"], named["cond"]) == (3, 2)
    assert plain["sort"] == 2 and named["sort"] == 1
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("build,budget,names", [
    (lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)), 10 ** 9,
     {REMAT_ATTN_OUT, REMAT_QKV, REMAT_MOE_UP, REMAT_MOE_ROWS}),
    (lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)), 0, set()),
    (lambda: OuroForCausalLM(OuroConfig.tiny(remat=True)), 64, set()),
], ids=["mixtral", "mixtral_no_room", "ouro_refused"])
def test_a_gradient_holds_the_names_its_room_kept_and_no_other(build, budget,
                                                               names):
    """``MixtralBlock`` offers its attention's output, the q, k, v that
    ``LlamaAttention`` names and its expert layer's two names: under a room
    its gradient holds all four, under none not one; ouro offers ``llama.py``'s two names and a
    small budget refuses both."""
    model = build()
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    loss = lambda p: model.apply({"params": p}, ids, labels=ids)
    loss_of = lambda p: loss(p)[0] if isinstance(loss(p), tuple) else loss(p)
    with remat_room(budget) as kept:
        text = str(jax.make_jaxpr(jax.grad(loss_of))(params))
    assert set(kept) == names
    for name in (REMAT_MLP, REMAT_QKV, REMAT_ATTN_OUT, REMAT_MOE_UP,
                 REMAT_MOE_ROWS):
        assert (f"name={name}" in text) == (name in names)


def test_ouro_counts_every_layer_of_every_pass():
    cfg = OuroConfig.tiny(remat=True)
    kept = _kept_under_a_room(OuroForCausalLM(cfg), (1, 16))
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


def _qwen3_next_model(policy):
    return qn.Qwen3NextForCausalLM(qn.Qwen3NextConfig.tiny(
        num_hidden_layers=4, remat=True, remat_policy=policy)), \
        np.random.RandomState(0).randint(0, 128, (1, 16))


def _qwen3_next(policy):
    """A function of its own a call over the family's one set of
    parameters (``_seeded``: the policy changes no shape)."""
    model, _ = _qwen3_next_model(policy)
    ids, params = _seeded("qwen3_next")
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
    plain, want = _trace_and_run(fn, params, run=policy == "nothing")
    named_fn, _ = _qwen3_next(policy)
    with remat_room(10 ** 9) as kept:
        named, got = _trace_and_run(named_fn, params,
                                    run=policy == "nothing")
    assert list(kept) == [REMAT_GDN_RULE, REMAT_GDN_QKVZ, REMAT_GDN_MIX,
                          REMAT_MOE_UP, REMAT_MOE_ROWS]
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
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_qwen3_next_keeps_the_rule_first_and_the_mixer_last(
        delta_rule_kernels):
    fn, params = _qwen3_next("nothing")
    cfg = qn.Qwen3NextConfig.tiny(num_hidden_layers=4)
    x = jax.ShapeDtypeStruct((1, 16, cfg.hidden_size), jnp.float32)
    offered = qn.remat_offers(cfg, x, qn.period_kinds(cfg))
    assert [n for n, _ in offered] == [REMAT_GDN_RULE, REMAT_GDN_QKVZ,
                                       REMAT_GDN_MIX, REMAT_MOE_UP,
                                       REMAT_MOE_ROWS]
    # after its own three, what the expert layer names over all four layers
    assert offered[3:] == mx.expert_offers(
        x, cfg.num_experts_per_tok, cfg.expert_width, cfg.num_local_experts,
        cfg.router_experts, 4)
    # three layers' o [T, 4 heads of 8], boundary states [4, 2 chunks, 8, 8]
    # and inverse [4, 2, 8, 8] in float32
    assert offered[0][1] == 3 * (16 * 32 * 4 + 2 * 4 * 2 * 8 * 8 * 4)
    two = sum(b for _, b in offered[:2])
    with remat_room(REMAT_FACTOR * two) as kept:
        named, _ = _trace_and_run(fn, params, run=False)
    assert list(kept) == [REMAT_GDN_RULE, REMAT_GDN_QKVZ]
    assert REMAT_GDN_MIX not in str(named)


def test_off_the_kernels_a_delta_rule_layer_offers_nothing():
    """Nothing of the rule's: the expert layer's two names stand."""
    cfg = qn.Qwen3NextConfig.tiny()
    x = jax.ShapeDtypeStruct((1, 32, cfg.hidden_size), jnp.float32)
    assert [n for n, _ in qn.remat_offers(cfg, x, qn.period_kinds(cfg))] == \
        [REMAT_MOE_UP, REMAT_MOE_ROWS]


# -- the engine --------------------------------------------------------------

CONFIG = {"train_batch_size": 2,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}


def _engine(model, params=None):
    engine, *_ = ds.initialize(
        model=model, config=CONFIG, model_parameters=params,
        mesh=build_mesh(devices=jax.devices()[:1]),
        example_batch={"input_ids": np.zeros((2, 32), np.int32),
                       "labels": np.zeros((2, 32), np.int32)})
    return engine


def _train(engine, steps=3, batch=2):
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(steps):
        ids = rng.integers(0, 128, (batch, 32)).astype(np.int32)
        losses.append(float(engine.train_batch(
            batch={"input_ids": ids, "labels": ids})))
    return losses


def _lowered_text(engine, budget=0):
    """The engine's step as it lowers under a stated ``budget``: a step of
    its own a call (jax keeps a function's trace), built as the engine
    itself builds its step again (``_fit_train_step``) -- the engine's state
    and set-up are no part of the question, so one engine a family serves
    every lowering (``_family_engine``)."""
    ids = np.zeros((2, 32), np.int32)
    topology.set_mesh(engine.mesh)      # the autouse fixture took it away
    batch = engine._shape_batch({"input_ids": ids, "labels": ids})
    with remat_room(budget):
        return engine._compile_train_step().lower(
            engine.state, batch, jax.random.PRNGKey(0)).as_text()


TINY = {
    "llama": lambda: LlamaForCausalLM(LlamaConfig.tiny(remat=True)),
    "mixtral": lambda: MixtralForCausalLM(MixtralConfig.tiny(remat=True)),
    "mellum": lambda: mellum.MellumForCausalLM(
        mellum.MellumConfig.tiny(remat=True)),
    "deepseek_v3": lambda: dsv3.DeepseekV3ForCausalLM(
        dsv3.DeepseekV3Config.tiny(remat=True)),
    "sdar": lambda: SdarForCausalLM(SdarConfig.tiny(remat=True)),
    "ouro": lambda: OuroForCausalLM(OuroConfig.tiny(remat=True)),
    "qwen3_next": lambda: qn.Qwen3NextForCausalLM(
        qn.Qwen3NextConfig.tiny(num_hidden_layers=4, remat=True)),
    "zaya": lambda: zy.ZayaForCausalLM(zy.ZayaConfig.tiny(remat=True)),
}


@functools.lru_cache(maxsize=None)
def _family_engine(family):
    """The family's one-device engine for the cases that lower its step and
    never run it: once a process, its parameters zeros of the shapes
    ``init`` gives (no ``init`` program is compiled: half an engine's
    cost) -- and, once built, its state the shapes alone: a device array
    this memo kept would be alive for the worker's later files."""
    model = TINY[family]()
    ids = jnp.zeros((2, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids,
                            labels=ids)["params"]
    engine = _engine(model, jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), shapes))
    engine.state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        engine.state)
    return engine


@functools.lru_cache(maxsize=None)
def _llama_losses():
    """Three steps' losses of the tiny Llama's engine that keeps nothing."""
    return _train(_engine(TINY["llama"]()))


@pytest.mark.parametrize("family", sorted(TINY))
def test_on_a_cpu_the_lowered_step_names_nothing(family, monkeypatch):
    """A CPU keeps no allocator numbers: the budget is 0, nothing is kept,
    and the engine's lowered step holds no ``name`` equation -- it is, to
    the character, the text it lowers to with the naming helper made the
    identity (what the step was before a name was offered)."""
    engine = _family_engine(family)
    assert engine_module._device_memory(jax.devices()[0]) is None
    text = _lowered_text(engine)
    from deepspeed_tpu.models import llama

    for module in (llama, qn, mx, dsv3, zy):
        monkeypatch.setattr(module, "name_if_kept", lambda x, name: x)
    assert _lowered_text(engine) == text
    if family != "llama":       # one family's step runs: the record is the
        return                  # engine's, not the model's
    monkeypatch.undo()
    engine = _engine(TINY[family]())    # an engine of its own: it steps
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
    want = _llama_losses()
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
    want = _llama_losses()
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


def test_a_mesh_of_several_devices_states_one_devices_budget(device_memory):
    device_memory(10 ** 9, 10 ** 8)
    engine, *_ = ds.initialize(
        model=TINY["llama"](), config={**CONFIG, "train_batch_size": 4},
        mesh=build_mesh(devices=jax.devices()[:2]),
        example_batch={"input_ids": np.zeros((4, 32), np.int32),
                       "labels": np.zeros((4, 32), np.int32)})
    assert engine._remat_budget() == (
        int(layers.REMAT_SHARE * 10 ** 9) - 10 ** 8, (10 ** 9, 10 ** 8))


# -- a device's part under a mesh (PR 62) ------------------------------------

#: ``{case: (devices, mesh axes, moe.replicate_tokens)}``
MESHES = {"one_device": (1, {}, False),
          "data2": (2, {}, False),
          "expert4_gathered": (4, {"expert": 4}, False),
          "expert4_replicated": (4, {"expert": 4}, True),
          "data2_expert2": (4, {"expert": 2}, False),
          "data2_expert2_replicated": (4, {"expert": 2}, True)}


def _mesh(case):
    """The mesh of ``MESHES[case]`` set as the engine sets it (``data``
    takes the devices the other axes leave)."""
    devices, axes, replicate = MESHES[case]
    mesh = build_mesh(devices=jax.devices()[:devices], **axes)
    topology.set_mesh(mesh)
    topology.set_token_replication(replicate)
    return mesh


@pytest.mark.parametrize("case,batch,axes,part,gathered_part", [
    ("one_device", 4, (), 4, 4),
    ("data2", 4, ("data",), 2, 2),
    ("data2", 3, (), 3, 3),
    ("expert4_gathered", 8, ("expert",), 2, 8),
    ("expert4_gathered", 6, (), 6, 6),
    ("expert4_replicated", 8, (), 8, 8),
    ("data2_expert2", 4, ("data", "expert"), 1, 2),
    ("data2_expert2", 2, ("data",), 1, 1),
    ("data2_expert2", 3, (), 3, 3),
    ("data2_expert2_replicated", 4, ("data",), 2, 2),
])
def test_a_devices_part_of_a_batch(case, batch, axes, part, gathered_part):
    """The batch's axes as far as they divide it, a device's samples, and
    its samples where the value was gathered over ``expert``."""
    _mesh(case)
    assert layers.batch_axes(batch) == axes
    assert layers.device_part(batch) == part
    assert layers.device_part(batch, but=("expert",)) == gathered_part


def test_with_no_mesh_a_device_holds_the_whole_batch():
    assert topology.get_mesh() is None
    assert layers.batch_axes(8) == () and layers.device_part(8) == 8


def _named_bytes(jaxpr, stream, times=1, inside=False, found=None):
    """``{name: bytes ONE device holds of the values named so}`` of a forward
    pass's jaxpr: inside a ``shard_map`` the named equation's own aval, outside
    it ``stream.shard_shape`` of a value that leads with the batch (``[B, T,
    ...]``) and the whole of any other (the expert layer's rows without an
    ``expert`` axis: the partitioner sorts the whole batch's pairs on every
    device); a scan's body counts ``length`` times."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            aval = eqn.outvars[0].aval
            shape = aval.shape if inside or aval.ndim < 3 else \
                stream.shard_shape(aval.shape)
            found[eqn.params["name"]] += \
                times * int(np.prod(shape)) * aval.dtype.itemsize
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _named_bytes(
                sub, stream, times * eqn.params.get("length", 1)
                if eqn.primitive.name == "scan" else times,
                inside or eqn.primitive.name == "shard_map", found)
    return dict(found)


@pytest.mark.parametrize("columns", [False, True], ids=["experts", "columns"])
@pytest.mark.parametrize("case", sorted(MESHES))
def test_every_offer_counts_what_one_device_holds_of_the_named_value(
        case, columns):
    """The tiny Mixtral's forward pass traced with everything kept: each
    offer's bytes are the bytes ONE device holds of the values that carry
    its name -- the stream's over the batch's axes, the expert layer's as its
    ``shard_map`` sees them (rows whole on ``expert``, whole experts a chip
    or, with 1,024 columns a chip, every expert's columns)."""
    mesh = _mesh(case)
    cfg = MixtralConfig.tiny(remat=True, **(
        {"intermediate_size": 1024 * mesh.shape["expert"]} if columns else {}))
    assert (mx.expert_layout(4, cfg.expert_width, mesh.shape["expert"])
            == "columns") == (columns and mesh.shape["expert"] > 1)
    model = MixtralForCausalLM(cfg)
    ids = jnp.zeros((4, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    with remat_room(10 ** 12) as kept:
        jaxpr = jax.make_jaxpr(
            lambda p: model.apply({"params": p}, ids, labels=ids))(params)
    assert list(kept) == [REMAT_ATTN_OUT, REMAT_QKV, REMAT_MOE_UP,
                          REMAT_MOE_ROWS]
    stream = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(layers.batch_axes(4)))
    assert _named_bytes(jaxpr.jaxpr, stream) == dict(kept)


#: what each family's tiny model offered on one device before PR 62 (the
#: global count, which one device's part is there): ``{family: (ids' shape,
#: {name: bytes})}``
OFFERED_ON_ONE_DEVICE = {
    "llama": ((2, 32), {REMAT_MLP: 131072, REMAT_QKV: 65536}),
    "mixtral": ((2, 32), {REMAT_ATTN_OUT: 16384, REMAT_QKV: 32768,
                          REMAT_MOE_UP: 131072, REMAT_MOE_ROWS: 35872}),
    "mellum": ((2, 16), {REMAT_ATTN_OUT: 32768, REMAT_QKV: 65536,
                         REMAT_MOE_UP: 262144, REMAT_MOE_ROWS: 71808}),
    "deepseek_v3": ((2, 16), {REMAT_ATTN_OUT: 8192, REMAT_MLP: 16384,
                              REMAT_QKV: 32768, REMAT_MOE_UP: 24576,
                              REMAT_MOE_ROWS: 26944}),
    "sdar": ((2, 32), {REMAT_ATTN_OUT: 32768, REMAT_QKV: 65536,
                       REMAT_MOE_UP: 262144, REMAT_MOE_ROWS: 71712}),
    "ouro": ((2, 32), {REMAT_MLP: 262144, REMAT_QKV: 196608}),
    "qwen3_next": ((1, 16), {REMAT_GDN_RULE: 18432, REMAT_GDN_QKVZ: 18432,
                             REMAT_GDN_MIX: 24576, REMAT_MOE_UP: 16384,
                             REMAT_MOE_ROWS: 17984}),
    # (PR 65: what it offers since it offers)
    "zaya": ((2, 32), {REMAT_MOE_OUT: 24576, REMAT_QKV: 49152,
                       REMAT_MOE_UP: 24576, REMAT_ROUTER: 57600,
                       REMAT_CCA_MIX: 73728}),
}


@pytest.mark.parametrize("family", sorted(OFFERED_ON_ONE_DEVICE))
def test_on_one_device_every_family_offers_what_it_offered(
        family, delta_rule_kernels):
    shape, offered = OFFERED_ON_ONE_DEVICE[family]
    _mesh("one_device")
    assert _kept_under_a_room(TINY[family](), shape) == offered


@pytest.mark.parametrize("family", ["llama", "mixtral", "deepseek_v3",
                                    "qwen3_next", "zaya"])
def test_on_one_device_a_stated_budget_lowers_the_step_it_lowered(
        family, monkeypatch):
    """With a budget stated the one-device engine's step lowers, to the
    character, to the text it lowers to with ``device_part`` made the
    identity -- the offers' global count, what the rule planned with before
    a device's part was counted: the same names kept, no ``name`` equation
    moved."""
    from deepspeed_tpu.models import llama

    engine = _family_engine(family)
    text = _lowered_text(engine, 10 ** 9)
    # (a name lowers to nothing: what it keeps shows in the scans' carries)
    assert text != _lowered_text(engine)
    for module in (llama, qn, mx, dsv3, zy):
        monkeypatch.setattr(module, "device_part", lambda batch, but=(): batch)
    assert _lowered_text(engine, 10 ** 9) == text


def _expert4_engine(replicate):
    """The tiny remat'ed Mixtral under ``expert=4`` (whole experts a chip),
    its tokens all-gathered over the axis or replicated on it."""
    config = {**CONFIG, "train_batch_size": 4,
              **({"moe": {"replicate_tokens": True}} if replicate else {})}
    model = TINY["mixtral"]()
    engine, *_ = ds.initialize(
        model=model, config=config,
        mesh=build_mesh(devices=jax.devices()[:4], expert=4),
        partition_rules=MixtralForCausalLM.partition_rules(model.config),
        example_batch={"input_ids": np.zeros((4, 32), np.int32),
                       "labels": np.zeros((4, 32), np.int32)})
    return engine


@pytest.mark.parametrize("replicate", [False, True],
                         ids=["gathered", "replicated"])
def test_under_an_expert_axis_the_kept_products_leave_the_replay(
        replicate, device_memory):
    """``expert=4``: the step's gradient holds 11 grouped products a layer
    body with nothing kept -- three forward, the replay's gate and up, six
    backward, counted through the ``shard_map`` -- 9 with the names up to
    ``ds_moe_gate_up`` kept (the replay still sorts), and with the sorted
    rows kept too one sort, one scatter and, the tokens all-gathered, three
    all-gathers fewer. An engine that reads room on its device keeps all
    four under the mesh and trains to the very losses of one that keeps
    nothing."""
    traced = _expert4_engine(replicate)     # one engine, a step a trace

    def step(budget):
        engine = traced
        ids = np.zeros((4, 32), np.int32)
        batch = engine._shape_batch({"input_ids": ids, "labels": ids})
        with remat_room(budget) as kept:
            found = _primitives(jax.make_jaxpr(engine._compile_train_step())(
                engine.state, batch, jax.random.PRNGKey(0)).jaxpr)
        grouped = found["ragged_dot"] + found["ragged_dot_general"]
        return dict(kept), grouped, found

    kept, grouped, plain = step(0)
    assert (kept, grouped) == ({}, 11) and plain["name"] == 0
    x = jax.ShapeDtypeStruct((4, 32, 32), jnp.float32)
    offered = mx.remat_offers(MixtralConfig.tiny(), x, 2)   # on that mesh
    assert [n for n, _ in offered[2:]] == [REMAT_MOE_UP, REMAT_MOE_ROWS]
    kept, grouped, up = step(REMAT_FACTOR * sum(b for _, b in offered[:3]))
    assert list(kept) == [n for n, _ in offered[:3]] and grouped == 9
    assert up["sort"] == plain["sort"] == 2
    kept, grouped, rows = step(10 ** 9)
    assert kept == dict(offered) and grouped == 9
    assert (rows["sort"], rows["scatter"]) == (1, 1)
    assert plain["all_gather"] - rows["all_gather"] == (0 if replicate else 3)

    engine = _expert4_engine(replicate)
    want, record = _train(engine, batch=4), engine.setup.record(0)
    assert record["remat_kept_names"] == record["remat_room_bytes"] == 0
    device_memory(10 ** 8, 10 ** 6)
    engine = _expert4_engine(replicate)
    assert _train(engine, batch=4) == want
    record = engine.setup.record(0)
    assert record["remat_kept_names"] == 4 and record["remat_fallbacks"] == 0
    assert record["remat_kept_bytes"] == sum(b for _, b in offered)
    assert record["remat_room_bytes"] == \
        int(layers.REMAT_SHARE * 10 ** 8) - 10 ** 6
