"""The serve cell's correctness check at tiny size on the CPU: seeded probes
through submit / chunked prefill / paged decode against the plain reference
pass on 16 seeds; int8 KV, int8 weights and a dropped page each fail it,
the first two also by the types the engine stores.
The tiny engine computes in float32 so that 8-bit storage stands out from
rounding; what the bf16 cell can and cannot tell apart is in PERF.md."""

import pytest

from bench_helpers import serve_check

CELL = "mistral-7b.serve.chat"


@pytest.mark.parametrize("seed", [2 ** 31 + 11] + list(range(60, 75)))
def test_engine_matches_reference(seed):
    ok, stats = serve_check(CELL, seed)
    assert ok, stats
    assert stats["probe_tokens"] == 20
    assert stats["paged_rel_l2"] < 1e-4 and stats["margin_max"] < 1e-3


@pytest.mark.parametrize("control,floor,stored", [
    ("int8_kv", 1e-3, False),       # ~1.6e-3 against a tolerance of 5e-4
    ("int8_weights", 2e-3, False),  # ~3.5e-3
    ("page_dropped", 5e-2, True),   # ~0.1
])
@pytest.mark.parametrize("seed", [60, 61])
def test_negative_control_fails(control, floor, stored, seed):
    ok, stats = serve_check(CELL, seed, control=control)
    assert not ok and not stats["verdicts"]["paged_rel_l2"], stats
    assert stats["paged_rel_l2"] > floor
    # at bf16 the logits cannot tell 8-bit storage (PERF.md): the types can
    assert stats["verdicts"]["stored_as_stated"] is stored, stats


def test_correct_reads_no_request_state():
    """The verdict is made of logits and of the types K/V and weights are
    stored in: nothing about finished requests, drained pools, cache hits
    or token equality."""
    ok, stats = serve_check(CELL, 60)
    assert set(stats["verdicts"]) == {"margin_max", "paged_rel_l2", "finite",
                                      "stored_as_stated"}


def test_wrong_window_fails_on_the_real_path():
    """A wrong mask reaches the tokens the engine chooses: the reference's
    margin for them, not only the probe's logits, refuses it."""
    ok, stats = serve_check(CELL, 60, control="wrong_window")
    assert not ok and not stats["verdicts"]["margin_max"], stats
    assert stats["paged_rel_l2"] > 0.1
