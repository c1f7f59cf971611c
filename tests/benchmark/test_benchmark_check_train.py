"""The train cells' correctness check at tiny size on the CPU: the engine
against the plain reference passes on 16 seeds, and each deliberately wrong
computation fails it — on the statistic that concentrates (relative L2 of
late-position logits), whatever the signed loss gap happens to be."""

import pytest

from bench_helpers import train_check

DENSE, MOE = "mistral-7b.train.8k", "mixtral-8x7b.train.ep4"


@pytest.mark.parametrize("seed", [2 ** 31 + 11] + list(range(40, 55)))
def test_dense_engine_matches_reference(seed):
    ok, stats = train_check(DENSE, seed)
    assert ok, stats
    # measured over these seeds: loss_gap <= 3.9e-4, logit_rel_l2 <= 0.016
    assert stats["logit_rel_l2"] < 0.025 and stats["loss_gap"] < 1e-3


@pytest.mark.parametrize("seed", [40, 41, 42])
def test_window_mask_off_fails(seed):
    ok, stats = train_check(DENSE, seed, control="window_off")
    assert not ok and not stats["verdicts"]["logit_rel_l2"]
    assert stats["logit_rel_l2"] > 0.3          # ~0.7 against ~0.016


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 40, 41, 42])
def test_moe_engine_matches_reference_on_four_devices(seed):
    ok, stats = train_check(MOE, seed)
    assert ok, stats
    # float32 at tiny size: the reference's routing, renormalised weights
    # and load-balancing loss are the engine's to rounding
    assert stats["logit_rel_l2"] < 1e-4 and stats["loss_gap"] < 1e-5


@pytest.mark.parametrize("seed", [40, 41])
def test_top1_routing_fails(seed):
    ok, stats = train_check(MOE, seed, control="top1_routing")
    assert not ok and stats["logit_rel_l2"] > 0.05
