"""benchmark/flops.py against hand counts for both configurations."""

import pytest

from benchmark import common, flops


def sizes(name, depth):
    return common.sizes_of(common.load_json("configs", f"{name}.json"), depth)


def test_mean_attended_keys():
    assert flops.mean_attended_keys(4) == 2.5                 # 1,2,3,4
    assert flops.mean_attended_keys(4, window=2) == 1.75      # 1,2,2,2
    assert flops.mean_attended_keys(4, window=8) == 2.5
    assert flops.mean_attended_keys(8192, 4096) == pytest.approx(3072.25)


def test_mistral_7b_two_layers_at_8k():
    s = sizes("mistral-7b", "train")
    assert (s["num_hidden_layers"], s["head_dim"]) == (2, 128)
    qo = 2 * 2 * 4096 * 4096            # q and o: 4096 -> 32 x 128 and back
    kv = 2 * 2 * 4096 * 1024            # k and v: 4096 -> 8 x 128
    attn = 2 * 2 * 32 * 128 * 3072.25   # scores and values, windowed
    mlp = 3 * 2 * 4096 * 14336
    head = 2 * 4096 * 32000
    want = 2 * (qo + kv + attn + mlp) + head
    assert flops.forward_flops_per_token(s, 8192) == pytest.approx(want)
    assert flops.train_flops_per_token(s, 8192) == pytest.approx(3 * want)
    # ~1.23 GFLOP forward, ~3.7 GFLOP trained, per token
    assert 3.6e9 < flops.train_flops_per_token(s, 8192) < 3.8e9


def test_mixtral_8x7b_one_layer_counts_only_routed_experts():
    s = sizes("mixtral-8x7b", "train")
    assert s["num_hidden_layers"] == 1 and s["sliding_window"] is None
    qo = 2 * 2 * 4096 * 4096
    kv = 2 * 2 * 4096 * 1024
    attn = 2 * 2 * 32 * 128 * (4096 + 1) / 2
    moe = 2 * (3 * 2 * 4096 * 14336) + 2 * 4096 * 8    # top-2 + router
    head = 2 * 4096 * 32000
    want = qo + kv + attn + moe + head
    assert flops.forward_flops_per_token(s, 4096) == pytest.approx(want)
    # all 8 experts would be 4x the expert term: not counted
    assert flops.forward_flops_per_token(s, 4096) < qo + kv + attn + 4 * moe


def test_peaks_table_has_the_v5e_with_its_source():
    peaks = common.load_json("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert "Google Cloud" in peaks["TPU v5 lite"]["source"]
    assert common.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        common.peak_flops("TPU v9")
