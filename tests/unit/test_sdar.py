"""SDAR's block-diffusion training step (``models/sdar.py`` over
``mixtral.MixtralModel``, the rule ``flash_attention.BlockDiffusion``) at tiny
sizes in float32 on the CPU, against the benchmark's plain reference
(``benchmark/reference/sdar.py``, written from the equations and not from the
system) at ONE CHIP'S SHARE: the loss, every parameter's gradient and the
label-free logits (the same pass's, at the noised rows), under ``attention_impl="xla"`` and under the flash kernels
in interpret mode; the noise alone; the eight shares adding up to the uncut
layer; the named scalars and scopes; and what is not built raising."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from benchmark import common
from deepspeed_tpu.models import sdar
from deepspeed_tpu.models.mixtral import MixtralBlock
from deepspeed_tpu.models.sdar import SdarConfig, SdarForCausalLM
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas.flash_attention import BlockDiffusion

REF = common.load_file_module("reference", "sdar")
#: experts 4..6 of the router's 16
SHARE = dict(num_local_experts=2, router_experts=16, first_expert=4)
L = 48
IDS = jnp.asarray(np.random.RandomState(5).randint(0, 128, (2, L)))


def tiny(**over):
    return SdarConfig.tiny(**{**dict(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
        head_dim_override=16, intermediate_size=64, moe_intermediate_size=16,
        num_experts_per_tok=4, per_expert_init=True, rms_norm_eps=1e-6,
        max_position_embeddings=512, rope_theta=1e6, block_length=4,
        flash_block_q=32, flash_block_k=32, **SHARE), **over})


def sizes_of(cfg):
    sizes = {k: v for k, v in dataclasses.asdict(cfg).items()
             if isinstance(v, (int, float, bool)) or v is None}
    return {**sizes, "head_dim": cfg.head_dim}


def seeded(cfg, seed=3):
    """(model, params): the model's own init with the norms' scales moved
    off one, so that leaving a norm out shows."""
    model = SdarForCausalLM(cfg)
    params = jax.jit(lambda key: model.init(key, IDS, labels=IDS))(
        jax.random.PRNGKey(seed))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    return model, jax.tree_util.tree_map_with_path(
        lambda kp, p: p + 0.3 * jax.random.normal(next(keys), p.shape)
        if str(getattr(kp[-1], "key", "")) == "scale" else p, params)


def interpreted(monkeypatch):
    """The flash branch runs the Pallas kernels, interpreted."""
    kernels = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention", lambda *a, **kw: kernels(
        *a, **{**kw, "interpret": True}))


@pytest.fixture(scope="module")
def case():
    cfg = tiny()
    model, params = seeded(cfg)
    sizes = sizes_of(cfg)
    return dict(cfg=cfg, model=model, params=params, sizes=sizes,
                ref_loss=REF.loss(params, sizes, np.asarray(IDS)),
                ref_grads=REF.grads(params, sizes, np.asarray(IDS)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("impl", ["xla", "flash_interpreted"])
def test_loss_gradients_and_logits_are_the_references(case, impl,
                                                      monkeypatch):
    cfg = case["cfg"]
    if impl != "xla":
        cfg = dataclasses.replace(cfg, attention_impl="flash")
        interpreted(monkeypatch)
    model, params = SdarForCausalLM(cfg), case["params"]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply({"params": p}, IDS, labels=IDS)))(params)
    np.testing.assert_allclose(loss, case["ref_loss"], rtol=1e-5)
    flat = lambda tree: dict(jax.tree_util.tree_leaves_with_path(tree))
    want = flat(case["ref_grads"])
    for path, got in flat(grads).items():
        assert float(jnp.abs(want[path]).max()) > 0, path
        assert rel(got, want[path]) < 2e-4, path
    got = model.apply({"params": params}, IDS)
    for b in range(IDS.shape[0]):
        hidden = REF.hidden_states(params, case["sizes"], IDS[b])
        np.testing.assert_allclose(got[b], REF.logits(params, hidden),
                                   rtol=2e-5, atol=2e-5)


def test_the_loss_is_not_an_autoregressive_one(case):
    """It differs from the reference's under any one clause of the rule
    changed, and its size at noise logits is about ``ln vocab``."""
    assert abs(float(case["ref_loss"]) - np.log(128)) < 1.5
    sound = REF.sees
    leak = lambda q, j, half, block: sound(q, j, half, block) | (
        (q < half) & (j >= half) & ((q % half) // block == (j % half) // block))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(REF, "sees", leak)
        mp.setattr(REF, "_layer", REF._layer.__wrapped__)   # no stale trace
        leaked = REF.loss(case["params"], case["sizes"], np.asarray(IDS))
    assert abs(float(leaked) - float(case["ref_loss"])) > 1e-4


def test_noise_is_a_function_of_the_sequence():
    cfg = tiny(block_length=8)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (3, 4096)))
    ids = ids.at[2].set(ids[0])
    masked, t = sdar.block_noise(ids, 8)
    np.testing.assert_array_equal(masked[0], masked[2])     # a repeated row
    assert (masked[0] != masked[1]).any()                   # a fresh one
    np.testing.assert_array_equal(t[:, ::8], t[:, 7::8])    # one t a block
    assert float(t.min()) >= 66 / 65536 and float(t.max()) < 1
    # a block's masked share tracks its t: over the blocks of a band of t
    share = masked.reshape(3, -1, 8).mean(-1)
    blocks_t = t[:, ::8]
    for lo in (0.0, 0.25, 0.5, 0.75):
        band = (blocks_t >= lo) & (blocks_t < lo + 0.25)
        assert abs(float(share[band].mean()) - (lo + 0.125)) < 0.04
    assert abs(float(masked.mean()) - 0.5) < 0.03
    # the reference draws the same noise from its own checksum
    for b in range(3):
        m, tt = REF.noise(sizes_of(cfg), np.asarray(ids[b]))
        np.testing.assert_array_equal(m, masked[b])
        np.testing.assert_array_equal(tt, t[b])


def test_a_call_that_is_not_deterministic_noises_a_sequence_anew(case):
    """The engine's step and the check call ``deterministic`` (the default):
    the sequence keys its own noise. ``deterministic=False`` folds the
    call's ``dropout`` rng in: an epoch resamples every ``t``."""
    apply = lambda **kw: case["model"].apply(
        {"params": case["params"]}, IDS, labels=IDS, **kw)
    assert float(apply()) == float(apply())
    drawn = [float(apply(deterministic=False,
                         rngs={"dropout": jax.random.PRNGKey(k)}))
             for k in (0, 0, 1)]
    assert drawn[0] == drawn[1] and len({float(apply()), *drawn}) == 3
    base = jax.random.PRNGKey(1)
    masked, t = sdar.block_noise(IDS, 4, base)
    again, _ = sdar.block_noise(IDS, 4)
    assert (masked != again).any() and (masked[0] != masked[1]).any()
    assert 0.3 < float(masked.mean()) < 0.7 and float(t.min()) >= 66 / 65536


def test_checksums_of_model_and_reference_agree():
    rs = np.random.RandomState(1)
    seen = set()
    checksum = jax.jit(sdar.checksum)   # a length is a program, not ten
    for n in range(100):
        ids = rs.randint(0, 151936, rs.randint(1, 9000))
        got = int(checksum(jnp.asarray(ids, jnp.int32)))
        assert got == REF.checksum(ids) and got >= 0
        seen.add(got)
    assert len(seen) == 100
    ids = np.arange(16)
    assert REF.checksum(ids) != REF.checksum(ids[::-1])     # order counts


def test_an_ignored_label_is_never_masked_and_a_mask_id_in_the_data_is_data():
    cfg = tiny(report_expert_load=True)
    model, params = seeded(cfg)
    labels = IDS.at[:, :24].set(-100)
    _, named = model.apply({"params": params}, IDS, labels=labels)
    masked, _ = sdar.block_noise(IDS, 4)
    np.testing.assert_allclose(named["bd_masked_share"],
                               masked[:, 24:].sum() / masked.size, rtol=1e-6)
    # ids that ARE the mask id: the loss reads m_i, never the id
    ids = IDS.at[:, ::3].set(cfg.vocab_size - 1)
    loss = model.apply({"params": params}, ids, labels=ids)[0]
    np.testing.assert_allclose(
        loss, REF.loss(params, sizes_of(cfg), np.asarray(ids)), rtol=1e-5)


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of a share, on the whole layer: attention and router
    are whole on every chip and counted once; the eight shares' expert parts
    added to them are what the reference's layer gives with all 16 held."""
    full = tiny(num_local_experts=16, router_experts=None, first_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2 * L, 32))
    pos = jnp.tile(jnp.arange(L), 2)
    cos, sin = _rotary(full, pos[None])
    p = MixtralBlock(full).init(jax.random.PRNGKey(2), x, cos, sin,
                                None)["params"]
    whole = REF._layer(x[0], p, pos, REF.dense._static(sizes_of(full)), L)[0]
    experts = lambda p, s: {**p, "block_sparse_moe": {
        **p["block_sparse_moe"], **{w: p["block_sparse_moe"][w][2 * s:2 * s + 2]
                                    for w in ("w1", "w2", "w3")}}}
    run = lambda cfg, p: MixtralBlock(cfg).apply({"params": p}, x, cos, sin,
                                                 None)[0][0]
    share = lambda s: dataclasses.replace(
        full, num_local_experts=2, router_experts=16, first_expert=2 * s)
    silent = experts(p, 0)
    silent["block_sparse_moe"]["w2"] = 0 * silent["block_sparse_moe"]["w2"]
    alike = run(share(0), silent)       # the residual stream and attention
    parts = sum(run(share(s), experts(p, s)) - alike for s in range(8))
    np.testing.assert_allclose(alike + parts, whole, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts).max()) > 1e-3


def _rotary(cfg, positions):
    from deepspeed_tpu.models.layers import rotary_embedding

    return rotary_embedding(positions, cfg.head_dim, cfg.rope_theta)


def test_named_scalars_count_the_doubled_rows(case):
    cfg = dataclasses.replace(case["cfg"], report_expert_load=True)
    loss, named = SdarForCausalLM(cfg).apply({"params": case["params"]}, IDS,
                                             labels=IDS)
    np.testing.assert_allclose(loss, case["ref_loss"], rtol=1e-5)
    assert sorted(named) == [
        "bd_kept_tile_share", "bd_loss_weight_mean", "bd_masked_share",
        "moe_held_rows_over_expected", "moe_rows_max_over_mean"]
    masked, t = sdar.block_noise(IDS, 4)
    assert float(named["bd_masked_share"]) == float(masked.mean())
    np.testing.assert_allclose(named["bd_loss_weight_mean"],
                               (masked / t).mean(), rtol=1e-6)
    # 2 x 48 positions in tiles of 32: 3 x 3 tiles, counted from the rule
    # pair by pair
    q = np.arange(2 * L)
    seen = np.asarray(BlockDiffusion(L, 4).sees(q[:, None], q[None, :]))
    kept = sum(seen[i:i + 32, j:j + 32].any()
               for i in range(0, 2 * L, 32) for j in range(0, 2 * L, 32))
    np.testing.assert_allclose(named["bd_kept_tile_share"], kept / 9)
    # the held load is of the 2L rows the stack ran: the reference's count
    pairs = 0
    for b in range(2):
        m, _ = REF.noise(case["sizes"], np.asarray(IDS[b]))
        both = jnp.concatenate([jnp.where(m, 127, IDS[b]), IDS[b]])
        pairs += float(REF._stack(case["params"], case["sizes"], both,
                                  jnp.tile(jnp.arange(L), 2), L)[1].sum())
    expected = 2 * (2 * 2 * L) * 4 * 2 / 16       # layers x rows x k x G / E
    np.testing.assert_allclose(named["moe_held_rows_over_expected"],
                               pairs / expected, rtol=1e-6)


def test_the_kernels_keep_the_references_pairs_at_the_cells_own_size():
    """What no check at seeded weights can hold on the chip (four more keys
    among thousands move a late row's logits by less than bfloat16 does:
    PERF.md section 6, PR 58) is held here, without weights: at 2 x 8,192
    positions, blocks of 4 and tiles of 512 -- the timed geometry -- the
    pairs the flash kernels keep are the pairs ``benchmark/reference/sdar.py``
    keeps and no other. An inside tile is kept whole, a cut tile through the
    mask ``_tile_valid`` builds inside the kernels (both ways a tile is held),
    a tile the table leaves out not at all; forward and ``by_kv`` tables."""
    half, blk, tile = 8192, 4, 512
    n = 2 * half // tile
    cut = jax.jit(lambda iq, ik, keys_first: fa._tile_valid(
        iq, ik, tile, tile, 2 * half, 2 * half, True,
        BlockDiffusion(half, blk), keys_first)[0], static_argnums=2)
    at = lambda i: np.arange(i * tile, (i + 1) * tile)
    for by_kv in (False, True):
        iq, ik, flags = fa._tile_table(2 * half, 2 * half, tile, tile, True,
                                       BlockDiffusion(half, blk), by_kv)
        word = {(int(q), int(k)): int(f) for q, k, f in zip(iq, ik, flags)}
        pairs = 0
        for q in range(n):
            for k in range(n):
                want = np.asarray(REF.sees(at(q)[:, None], at(k)[None, :],
                                           half, blk))
                f = word.get((q, k), 0)
                if f & fa._INSIDE:
                    assert want.all(), (q, k)
                elif f & fa._CUT:
                    assert (np.asarray(cut(q, k, False)) == want).all(), (q, k)
                    assert (np.asarray(cut(q, k, True)) == want.T).all(), (q, k)
                else:
                    assert not want.any(), (q, k)
                pairs += int(want.sum())
        assert pairs == 67141632


def test_the_cells_table_holds_288_tiles():
    assert fa.rule_tile_share(BlockDiffusion(8192, 4), 16384) == 288 / 1024


def test_what_is_not_built_raises(case):
    model, params = case["model"], case["params"]
    with pytest.raises(NotImplementedError, match="training only"):
        model.apply({"params": params}, IDS, cache={}, cache_index=0)
    with pytest.raises(NotImplementedError, match="packed"):
        model.apply({"params": params}, IDS, labels=IDS,
                    attention_mask=jnp.ones_like(IDS))
    with pytest.raises(NotImplementedError, match="packed"):
        SdarForCausalLM(tiny(sliding_window=8)).apply(
            {"params": params}, IDS, labels=IDS)
    with pytest.raises(ValueError, match="blocks"):
        model.apply({"params": params}, IDS[:, :46], labels=IDS[:, :46])


def test_preset_is_the_published_configuration():
    cfg = SdarConfig.sdar_30b_a3b()
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.expert_width,
            cfg.num_local_experts, cfg.num_experts_per_tok,
            cfg.num_hidden_layers, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_topk_prob, cfg.qk_norm_per_head, cfg.qk_norm,
            cfg.max_position_embeddings, cfg.tie_word_embeddings) == \
        (2048, 32, 4, 128, 768, 128, 8, 48, 151936, 1e6, True, True, False,
         32768, False)
    assert SdarForCausalLM.frozen_parameters(
        dataclasses.replace(cfg, router_trainable=False)) == \
        [r"block_sparse_moe/gate/kernel$"]


def test_the_engine_trains_it_and_publishes_the_gauges():
    """Through ``initialize`` -> ``train_batch``: one repeated batch repeats
    its noise, so its loss falls; the ``bd_*`` scalars become registry gauges
    beside the held share's; a frozen router stays where it was."""
    cfg = tiny(report_expert_load=True, router_trainable=False, remat=True,
               loss_chunk=16)
    ids = np.random.RandomState(6).randint(0, 128, (8, L)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}    # a row a CPU device
    engine, *_ = ds.initialize(
        model=SdarForCausalLM(cfg),
        example_batch={k: v[:1] for k, v in batch.items()},
        config={"train_batch_size": 8, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}}})
    gate = lambda: np.asarray(engine.state.params["model"]["layers"]["block"][
        "block_sparse_moe"]["gate"]["kernel"])
    before = gate()
    initial = jax.tree_util.tree_map(np.asarray, engine.state.params)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    np.testing.assert_allclose(
        losses[0], REF.loss(initial, sizes_of(cfg), ids), rtol=1e-4)
    assert losses[-1] < losses[0]
    found = engine.registry.snapshot()
    assert {"bd_masked_share", "bd_kept_tile_share", "bd_loss_weight_mean",
            "moe_held_rows_over_expected"} <= set(found)
    assert 0.3 < found["bd_masked_share"] < 0.7
    np.testing.assert_array_equal(before, gate())


def test_step_names_the_block_diffusion_scopes(case):
    text = jax.jit(lambda p: case["model"].apply(
        {"params": p}, IDS, labels=IDS)).lower(case["params"]).as_text(
            debug_info=True)
    for scope in ("ds.bd_noise", "ds.bd_gather", "ds.attention",
                  "ds.lm_head_loss"):
        assert scope in text, scope
