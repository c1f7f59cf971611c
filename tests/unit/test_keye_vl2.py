"""Keye-VL-2.0's language model (``models/mixtral.py`` under ``sa_config``,
``models/indexed_attention.py``) at tiny sizes in float32 on the CPU: the
system against the benchmark's plain reference (``benchmark/reference/
keye_vl2.py``, written from the catalog row and the published description,
not from the system) at ONE CHIP'S SHARE — logits, both loss terms and the
gradient of every parameter; which loss term reaches which parameter; the
first ``topk`` positions against plain causal attention; the exact selection
against ``lax.top_k`` on rows full of ties; the flash kernels under a mask
that is data, and the indexer's loss from its two kernels, against the XLA
path; the eight shares adding up to the uncut layer; and what is not built raising."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import common
from deepspeed_tpu.models import indexed_attention as ia
from deepspeed_tpu.models.layers import repeat_kv
from deepspeed_tpu.models.mixtral import (MixtralConfig, MixtralForCausalLM,
                                          MixtralSparseMoeBlock)
from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                     mask_tiles)
from deepspeed_tpu.ops.pallas.sa_probs import index_kl
from deepspeed_tpu.parallel import build_mesh, topology

REF = common.load_file_module("reference", "keye_vl2")
SA = dict(indexer_head_dim=8, indexer_num_heads=4, indexer_num_kv_heads=1,
          kv_chunk_size=16, q_chunk_size=16, topk=12)
#: experts 4..6 of the router's 16
SHARE = dict(num_local_experts=2, router_experts=16, first_expert=4)
T = 48
IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, T)))


def tiny(**over):
    return MixtralConfig.tiny(**{**dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=16, intermediate_size=64, moe_intermediate_size=16,
        num_experts_per_tok=4, norm_topk_prob=True, router_aux_loss_coef=0.0,
        qk_norm_per_head=True, per_expert_init=True,
        max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1e7,
        sa_config=SA, **SHARE), **over})


def sizes_of(cfg):
    """The reference's ``sizes`` of a model config: its numbers, and the
    selection's three under the configuration file's flat names."""
    sizes = {k: v for k, v in dataclasses.asdict(cfg).items()
             if isinstance(v, (int, float, bool)) or v is None}
    sa = cfg.sa_config
    return {**sizes, "head_dim": cfg.head_dim, "sa_topk": sa.topk,
            "sa_indexer_num_heads": sa.indexer_num_heads,
            "sa_indexer_head_dim": sa.indexer_head_dim}


def seeded(cfg, seed=3, ids=IDS):
    """(model, params): the model's own init with the norms' scales and
    biases moved off their defaults, so that leaving one out shows."""
    model = MixtralForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed), ids)["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    return model, jax.tree_util.tree_map_with_path(
        lambda kp, p: p + 0.3 * jax.random.normal(next(keys), p.shape)
        if str(getattr(kp[-1], "key", "")) in ("scale", "bias") else p,
        params)


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
    # (operation by operation: ``test_each_loss_term_reaches_its_own_
    # parameters`` holds these gradients BIT FOR BIT to those of a step
    # without the KL term, which two fused programs are not)
    (loss, named), grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS),
        has_aux=True)(params)
    # the reference's two gradients: one program each
    term = lambda i: jax.jit(jax.grad(
        lambda p: REF.loss_terms(p, sizes, np.asarray(IDS))[i]))(params)
    return dict(cfg=cfg, model=model, params=params, sizes=sizes, loss=loss,
                named=named, grads=grads, ref_lm_grads=term(0),
                ref_kl_grads=term(1))


def test_logits_match_the_reference(share):
    got = share["model"].apply({"params": share["params"]}, IDS)
    for b in range(IDS.shape[0]):
        hidden, _, _ = REF.hidden_states(share["params"], share["sizes"],
                                         IDS[b])
        np.testing.assert_allclose(got[b], REF.logits(share["params"],
                                                      hidden),
                                   rtol=2e-5, atol=2e-5)


def test_both_loss_terms_match_the_reference(share):
    lm, kl = REF.loss_terms(share["params"], share["sizes"], np.asarray(IDS))
    assert float(kl) > 1e-3          # the indexer is not yet the attention
    np.testing.assert_allclose(share["named"]["sa_index_loss"], kl,
                               rtol=2e-5)
    np.testing.assert_allclose(share["loss"], lm + kl, rtol=1e-5)
    np.testing.assert_allclose(
        share["loss"], REF.loss(share["params"], share["sizes"],
                                np.asarray(IDS)), rtol=1e-5)


def test_gradient_of_every_parameter_matches_the_reference(share):
    """The model's weights take the language-model loss's gradient only,
    the indexer's the KL term's only: the reference detaches nothing, so its
    two terms are differentiated apart."""
    names = sorted(paths(share["grads"]))
    assert sum("indexer" in n for n in names) == 5
    for name in names:
        want = leaf(share["ref_kl_grads" if "indexer" in name
                          else "ref_lm_grads"], name)
        got = leaf(share["grads"], name)
        assert float(jnp.abs(want).max()) > 0, name
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-6,
                                   err_msg=name)


def test_each_loss_term_reaches_its_own_parameters(share, monkeypatch):
    """Without the KL term the indexer's gradients are zero and every other
    one is what it was: the selection passes no gradient and the indexer's
    input is detached."""
    monkeypatch.setattr(ia, "index_loss",
                        lambda p_hat, scores, mask: jnp.float32(0))
    grads = jax.grad(lambda p: share["model"].apply(
        {"params": p}, IDS, labels=IDS)[0])(share["params"])
    for name in paths(grads):
        got = leaf(grads, name)
        if "indexer" in name:
            assert not np.asarray(got).any(), name
        else:
            np.testing.assert_array_equal(got, leaf(share["grads"], name),
                                          err_msg=name)


def test_first_topk_positions_are_plain_causal_attention():
    """A query with at most ``topk`` keys before it attends to all of them:
    over 12 positions the model is the one without an indexer."""
    ids = IDS[:, :SA["topk"]]
    model, params = seeded(tiny(), ids=ids)
    plain = jax.tree_util.tree_map(lambda a: a, params)
    del plain["model"]["layers"]["block"]["self_attn"]["indexer"]
    want = MixtralForCausalLM(tiny(sa_config=None)).apply(
        {"params": plain}, ids)
    np.testing.assert_allclose(model.apply({"params": params}, ids), want,
                               rtol=1e-5, atol=1e-6)
    # one more position and the last query drops a key
    ids = IDS[:, :SA["topk"] + 4]
    got = model.apply({"params": params}, ids)
    want = MixtralForCausalLM(tiny(sa_config=None)).apply(
        {"params": plain}, ids)
    assert float(jnp.abs(got - want)[:, -1].max()) > 1e-4


@pytest.mark.parametrize("zero_share", [0.0, 0.5, 0.95])
@pytest.mark.parametrize("topk", [1, 7, 24, 500])
def test_selection_is_lax_top_k_on_rows_full_of_ties(zero_share, topk):
    """ReLU makes exact zeros common: the threshold with its ties in index
    order is ``lax.top_k`` over the causal part of the row, entry for
    entry — zeros of either sign being one value."""
    n, k = 96, min(topk, 96)
    ks = jax.random.split(jax.random.PRNGKey(topk), 3)
    scores = jnp.round(jax.random.normal(ks[0], (2, n, n)), 1)  # few values
    zero = jax.random.uniform(ks[1], scores.shape) < zero_share
    # a row's zeros have one sign, as the sum over the indexer's heads
    # gives them (-0.0 where every head weight of the query is negative)
    sign = jnp.where(jax.random.uniform(ks[2], (2, n, 1)) < 0.5, -0.0, 0.0)
    scores = jnp.where(zero | (scores == 0), sign, scores)
    mask = ia.select_mask(scores, topk, 32)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), k)
    real = jnp.arange(k)[None, None, :] <= jnp.arange(n)[None, :, None]
    want = jnp.zeros(scores.shape, bool).at[
        jnp.arange(2)[:, None, None], jnp.arange(n)[None, :, None],
        idx].set(real)
    np.testing.assert_array_equal(np.asarray(mask != 0), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(mask.sum(-1)), np.minimum(topk, np.arange(n) + 1)[None]
        * np.ones((2, 1), int))


def test_zeros_of_either_sign_are_one_value():
    """-0.0 and 0.0 tie, and the tie goes to the lower index."""
    scores = jnp.asarray([[[0.0] * 6, [-0.0, 0.0, -1.0, 0.0, -0.0, 2.0]] * 3])
    mask = ia.select_mask(scores, 2, 6)
    assert mask[0, 5].tolist() == [1, 0, 0, 0, 0, 1]
    assert mask[0, 4].tolist() == [1, 1, 0, 0, 0, 0]


def test_kept_tile_share_counts_causal_tiles():
    mask = np.zeros((1, 64, 64), np.int8)
    mask[0, np.arange(64), np.arange(64)] = 1       # the diagonal's 4 tiles
    mask[0, 63, 0] = 1                              # and one corner tile
    tiles = mask_tiles(jnp.asarray(mask), 16, 16)
    assert float(ia.kept_tile_share(tiles, 16, 16)) == 5 / 10


@pytest.fixture(scope="module")
def masked_case():
    B, n, H, Hkv, D = 2, 160, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, n, H, D))
    k = jax.random.normal(ks[1], (B, n, Hkv, D))
    v = jax.random.normal(ks[2], (B, n, Hkv, D))
    scores = jax.nn.relu(jax.random.normal(ks[3], (B, n, n)))
    mask = ia.select_mask(scores, 24, 32)
    weight = jax.random.normal(ks[4], (B, n, H, D))

    def xla(q, k, v):
        return ia.masked_attention_xla(q, k, v, mask, 32)

    def kernels(q, k, v):           # n is no multiple of the 64-row tiles
        kk, vv = repeat_kv(k, H // Hkv), repeat_kv(v, H // Hkv)
        out, lse = flash_attention(q, kk, vv, causal=True, block_q=64,
                                   block_k=64, mask=mask, interpret=True)
        kl = lambda sc: index_kl(q, kk, lse, sc, mask, block_q=64,
                                 block_k=64, interpret=True)
        return out, kl

    return q, k, v, mask, weight, xla, kernels, scores


def test_flash_kernels_under_a_mask_match_the_xla_path(masked_case):
    q, k, v, mask, _, xla, kernels, scores = masked_case
    (out_x, p_x), (out_k, kl) = xla(q, k, v), kernels(q, k, v)
    np.testing.assert_allclose(out_k, out_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(p_x.sum(-1), 1.0, rtol=1e-5)
    # the indexer's loss: the kernels rebuild the head-mean probabilities
    # and reduce them against the scores without writing them
    np.testing.assert_allclose(kl(scores), ia.index_loss(p_x, scores, mask),
                               rtol=1e-5)


def test_index_kl_gradient_matches_the_xla_path(masked_case):
    q, k, v, mask, _, xla, kernels, scores = masked_case
    p_x, kl = xla(q, k, v)[1], kernels(q, k, v)[1]
    want = jax.grad(ia.index_loss, argnums=1)(p_x, scores, mask)
    np.testing.assert_allclose(jax.grad(kl)(scores), want, rtol=1e-4,
                               atol=1e-7)


@pytest.mark.parametrize("arg", [0, 1, 2])
def test_flash_gradients_under_a_mask_match_the_xla_path(masked_case, arg):
    q, k, v, _, weight, xla, kernels, _ = masked_case
    loss = lambda fn: lambda *a: (fn(*a)[0] * weight).sum()
    want = jax.grad(loss(xla), argnums=arg)(q, k, v)
    got = jax.grad(loss(kernels), argnums=arg)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def emptied_case():
    """A selection that leaves tiles of the triangle empty, as a trained
    indexer's does: every query takes its 24 keys among the 20 before it and
    the sequence's first 8, so of the fifteen 32 x 32 tiles (3, 1), (4, 1)
    and (4, 2) hold no pair in either sequence."""
    B, n, H, D, blk = 2, 160, 4, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v = (jax.random.normal(key, (B, n, H, D)) for key in ks[:3])
    t, s = jnp.arange(n)[:, None], jnp.arange(n)[None]
    scores = jax.random.uniform(ks[3], (B, n, n)) \
        + 10.0 * ((s >= t - 20) | (s < 8))
    mask = ia.select_mask(scores, 24, blk)
    tiles = mask_tiles(mask, blk, blk)
    out, lse = flash_attention(q, k, v, causal=True, block_q=blk,
                               block_k=blk, mask=mask, tiles=tiles,
                               interpret=True)
    kl = lambda sc: index_kl(q, k, lse, sc, mask, block_q=blk, block_k=blk,
                             tiles=tiles, interpret=True)
    return dict(q=q, k=k, v=v, scores=scores, mask=mask, tiles=tiles,
                out=out, kl=kl, block=blk)


def test_the_emptied_case_empties_tiles(emptied_case):
    tiles = np.asarray(emptied_case["tiles"])
    want = np.tril(np.ones((5, 5), bool))
    want[3, 1] = want[4, 1] = want[4, 2] = False
    np.testing.assert_array_equal(tiles, want)
    c = emptied_case
    out_x, p_x = ia.masked_attention_xla(c["q"], c["k"], c["v"], c["mask"],
                                         c["block"])
    np.testing.assert_allclose(c["out"], out_x, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        c["kl"](c["scores"]), ia.index_loss(p_x, c["scores"], c["mask"]),
        rtol=1e-5)


def _written(c):
    """bool ``[n, n]``: the pairs in tiles the kernels' table keeps."""
    n, blk = c["mask"].shape[1], c["block"]
    written = jnp.repeat(jnp.repeat(c["tiles"], blk, 0), blk, 1)[:n, :n]
    assert not bool(written.all())
    return written


@pytest.mark.parametrize("what", ["loss", "gradient"])
def test_index_loss_never_reads_a_tile_the_probabilities_skipped(
        emptied_case, what):
    """The XLA path's loss reads ``p^`` under the mask alone: filled with NaN
    in the tiles a tile table drops, it changes neither the indexer's loss
    nor its gradient."""
    c = emptied_case
    _, p_hat = ia.masked_attention_xla(c["q"], c["k"], c["v"], c["mask"],
                                       c["block"])
    poisoned = jnp.where(_written(c)[None], p_hat, jnp.nan)
    fn = {"loss": ia.index_loss,
          "gradient": jax.grad(ia.index_loss, argnums=1)}[what]
    want = fn(p_hat, c["scores"], c["mask"])
    got = fn(poisoned, c["scores"], c["mask"])
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_index_kl_gradient_is_zero_where_the_table_drops_a_tile(emptied_case):
    """``ds_sa_probs_bwd`` walks the kept tiles and its output lies on a
    zeroed buffer: ``dI`` is EXACTLY zero in the emptied tiles, above the
    diagonal and at every unselected pair, and the XLA path's elsewhere."""
    c = emptied_case
    p_x = ia.masked_attention_xla(c["q"], c["k"], c["v"], c["mask"],
                                  c["block"])[1]
    got = jax.grad(c["kl"])(c["scores"])
    assert bool((jnp.where(_written(c)[None], 0.0, got) == 0).all())
    assert bool((jnp.where(c["mask"] != 0, 0.0, got) == 0).all())
    want = jax.grad(ia.index_loss, argnums=1)(p_x, c["scores"], c["mask"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("what", ["loss", "gradient"])
def test_index_kl_never_reads_a_score_above_the_diagonal(emptied_case, what):
    """``ds_sa_index_fwd`` leaves the tiles above the diagonal unwritten:
    NaN there (and at every other unselected pair) changes nothing."""
    c = emptied_case
    poisoned = jnp.where(c["mask"] != 0, c["scores"], jnp.nan)
    fn = {"loss": c["kl"], "gradient": jax.grad(c["kl"])}[what]
    want, got = fn(c["scores"]), fn(poisoned)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_index_kl_cotangent_scales_the_gradient(emptied_case):
    c = emptied_case
    want = jax.grad(c["kl"])(c["scores"])
    got = jax.grad(lambda sc: -2.5 * c["kl"](sc))(c["scores"])
    np.testing.assert_allclose(got, -2.5 * want, rtol=1e-5, atol=1e-9)


def test_index_kl_adds_zero_where_a_probability_is_exactly_zero():
    """A selected key every head's probability underflows at (its query is
    far from it): ``p^`` is exactly 0 there, the term adds zero — not
    ``0 log 0`` — and the key still stands in the scores' softmax."""
    B, n, H, D, blk = 1, 64, 2, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    k = jax.random.normal(ks[0], (B, n, H, D))
    q = 40.0 * k                    # every query sees its own key alone
    scores = jax.random.normal(ks[2], (B, n, n))
    mask = ia.select_mask(scores, 6, blk)
    out, lse = flash_attention(q, k, k, causal=True, block_q=blk, block_k=blk,
                               mask=mask, interpret=True)
    _, p_x = ia.masked_attention_xla(q, k, k, mask, blk)
    assert bool(((p_x == 0) & (mask != 0)).any())
    kl = lambda sc: index_kl(q, k, lse, sc, mask, block_q=blk, block_k=blk,
                             interpret=True)
    loss, grad = jax.value_and_grad(kl)(scores)
    assert bool(jnp.isfinite(loss)) and bool(jnp.isfinite(grad).all())
    np.testing.assert_allclose(loss, ia.index_loss(p_x, scores, mask),
                               rtol=1e-5)
    np.testing.assert_allclose(
        grad, jax.grad(ia.index_loss, argnums=1)(p_x, scores, mask),
        rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("by_kv", [False, True], ids=["by_q", "by_kv"])
def test_the_counter_and_the_tile_table_agree(emptied_case, by_kv):
    """``sa_kept_tile_share`` x the triangle's tiles is the number of table
    entries that run a body; the rest of the live entries are body-less
    placeholders (here none: every row of output tiles keeps its diagonal)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _CUT, _table

    c = emptied_case
    n, blk = c["mask"].shape[1], c["block"]
    table, steps = _table(c["tiles"], n, n, blk, blk, True, None, by_kv)
    flags = np.asarray(table)[2]
    share = float(ia.kept_tile_share(c["tiles"], blk, blk))
    assert share == pytest.approx(12 / 15)
    assert int((flags[:int(steps)] & _CUT != 0).sum()) == \
        round(share * 15) == 12
    assert int(steps) == 12                         # no placeholder row
    assert flags.shape == (15,)


def test_flash_path_of_the_model_is_the_xla_path(share):
    """``attention_impl="flash"`` off the chip runs the kernels' reference
    math under the mask: same loss, same named scalars."""
    model = MixtralForCausalLM(dataclasses.replace(
        share["cfg"], attention_impl="flash", report_expert_load=True))
    loss, named = model.apply({"params": share["params"]}, IDS, labels=IDS)
    np.testing.assert_allclose(loss, share["loss"], rtol=1e-5)
    assert sorted(named) == [
        "moe_held_rows_over_expected", "moe_rows_max_over_mean",
        "sa_index_loss", "sa_kept_tile_share"]
    assert 0 < float(named["sa_kept_tile_share"]) <= 1


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of a share: the parts of the expert layer's result
    that the eight shares give add up to what the reference gives with all
    16 experts held (attention and router are whole on every chip, so the
    shares differ in their experts alone)."""
    full = tiny(num_local_experts=16, router_experts=None, first_expert=0)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    block = MixtralSparseMoeBlock(full)
    p = block.init(jax.random.PRNGKey(2), h)["params"]
    whole = jnp.stack([REF.held_experts(h[b], p, sizes_of(full))[0]
                       for b in range(2)])
    parts, rows = 0, []
    for s in range(8):
        cfg = tiny(num_local_experts=2, router_experts=16, first_expert=2 * s)
        ps = {**p, **{w: p[w][2 * s:2 * s + 2] for w in ("w1", "w2", "w3")}}
        out, _, _, r = MixtralSparseMoeBlock(cfg).apply({"params": ps}, h)
        ref = jnp.stack([REF.held_experts(h[b], ps, sizes_of(cfg))[0]
                         for b in range(2)])
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)
        parts, rows = parts + out, rows + [r]
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-6)
    assert int(jnp.sum(jnp.concatenate(rows))) == 2 * T * 4
    assert float(jnp.abs(whole).max()) > 1e-3


def test_what_is_not_built_raises(share):
    model, params = share["model"], share["params"]
    cache = model.init_cache(2, T)
    with pytest.raises(NotImplementedError, match="training"):
        model.apply({"params": params}, IDS, cache=cache, cache_index=0)
    with pytest.raises(NotImplementedError, match="training"):
        model.apply({"params": params}, IDS, labels=IDS,
                    attention_mask=jnp.ones_like(IDS))
    with pytest.raises(ValueError, match="router"):
        MixtralForCausalLM(tiny(first_expert=15)).init(
            jax.random.PRNGKey(0), IDS)
    mesh = build_mesh(devices=jax.devices()[:2], expert=2)
    try:
        topology.set_mesh(mesh, None)
        with pytest.raises(NotImplementedError, match="expert"):
            model.apply({"params": params}, IDS, labels=IDS)
    finally:
        topology.set_mesh(None, None)


def test_preset_is_the_published_configuration():
    cfg = MixtralConfig.keye_vl2_30b_a3b()
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.expert_width,
            cfg.num_local_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.num_hidden_layers, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_topk_prob, cfg.qk_norm_per_head, cfg.qk_norm) == \
        (2048, 32, 4, 128, 768, 128, 128, 8, 48, 151936, 1e7, True, True,
         False)
    assert cfg.sa_config == ia.SparseAttentionConfig(
        indexer_head_dim=64, indexer_num_heads=16, indexer_num_kv_heads=1,
        kv_chunk_size=512, q_chunk_size=512, topk=2048)
    assert MixtralConfig.tiny(sa_config=SA).sa_config == \
        ia.SparseAttentionConfig(**SA)
    assert MixtralForCausalLM.frozen_parameters(cfg) == []
    assert MixtralForCausalLM.frozen_parameters(
        dataclasses.replace(cfg, router_trainable=False)) == \
        [r"block_sparse_moe/gate/kernel$"]


def test_engine_names_the_scalars_and_moves_only_what_it_may():
    """Through ``initialize`` -> ``train_batch``: the indexer's loss and the
    kept tile share become registry gauges beside the held share's; with
    ``router_trainable`` off the gate stays where it was while the indexer
    trains, and the router's choice is the published one (no selection
    bias among the parameters)."""
    cfg = tiny(report_expert_load=True, router_trainable=False, remat=True)
    model = MixtralForCausalLM(cfg)
    ids = np.random.RandomState(6).randint(0, 128, (8, T)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}    # a row a CPU device
    engine, *_ = ds.initialize(
        model=model, example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
    block = lambda: jax.tree_util.tree_map(
        np.asarray, engine.state.params["model"]["layers"]["block"])
    before = block()
    first = float(engine.train_batch(batch=batch))
    found = engine.registry.snapshot()
    assert {"sa_index_loss", "sa_kept_tile_share",
            "moe_held_rows_over_expected", "moe_rows_max_over_mean"} \
        <= set(found)
    assert 0 < found["sa_index_loss"] < first
    assert 0 < found["sa_kept_tile_share"] <= 1
    for _ in range(3):
        last = float(engine.train_batch(batch=batch))
    after = block()
    assert last < first
    moe0, moe1 = before["block_sparse_moe"], after["block_sparse_moe"]
    np.testing.assert_array_equal(moe0["gate"]["kernel"],
                                  moe1["gate"]["kernel"])
    assert (moe0["w1"] != moe1["w1"]).any()
    assert (before["self_attn"]["indexer"]["wq"]["kernel"]
            != after["self_attn"]["indexer"]["wq"]["kernel"]).any()
    assert set(moe1) == {"gate", "w1", "w2", "w3"}
