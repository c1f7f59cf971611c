"""The traffic generator: same seed -> same sessions; every seed the same
set of sizes in another order; the stated distributions."""

from collections import Counter

import numpy as np

from benchmark.traffic import generator as g

MIX = g.load_mix("serve.chat")
RATE, HORIZON = 2.0, 200.0


def turns_of(sessions):
    return [t for s in sessions for t in s["turns"]]


def test_same_seed_same_sessions():
    a = g.sessions(MIX, RATE, HORIZON, 3000000007)
    assert a == g.sessions(MIX, RATE, HORIZON, 3000000007)
    assert a != g.sessions(MIX, RATE, HORIZON, 8)


def test_every_seed_draws_the_same_set_in_another_order():
    a, b = (g.sessions(MIX, RATE, HORIZON, s) for s in (1, 2))
    # the first session arrives half its gap into the run
    gaps = lambda ss: sorted(np.round(np.diff(
        [-ss[0]["arrival_s"]] + [s["arrival_s"] for s in ss]), 9))
    assert len(a) == len(b) == round(RATE * HORIZON)
    assert gaps(a) == gaps(b)
    assert [s["arrival_s"] for s in a] != [s["arrival_s"] for s in b]
    assert Counter(s["system"] for s in a) == Counter(s["system"] for s in b)
    # a session that would pass max_context ends early, so the realised sets
    # may differ by the few turns that pairing cut
    ua, ub = (Counter(t["user_tokens"] for t in turns_of(x)) for x in (a, b))
    assert sum((ua - ub).values()) <= 0.02 * sum(ua.values())


def test_stated_distributions():
    ss = g.sessions(MIX, RATE, HORIZON, 5)
    n = len(ss)
    first = Counter(s["system"] for s in ss)
    assert [first[i] / n for i in range(3)] == [0.5, 0.3, 0.2]
    arrivals = [s["arrival_s"] for s in ss]
    assert arrivals == sorted(arrivals) and 0 <= arrivals[0]
    assert arrivals[-1] < HORIZON
    assert abs(np.mean(np.diff(arrivals)) - 1 / RATE) < 0.05 / RATE
    # exponential gaps: standard deviation ~ mean
    assert 0.85 < np.std(np.diff(arrivals)) * RATE < 1.1
    turns = turns_of(ss)
    user = [t["user_tokens"] for t in turns]
    answer = [t["answer_tokens"] for t in turns]
    assert abs(np.median(user) - 64) <= 3 and 8 <= min(user) and max(user) <= 1024
    assert abs(np.median(answer) - 128) <= 5
    assert 16 <= min(answer) and max(answer) <= 512
    # lognormal(median 128, sigma 0.7) clipped at 512: mean ~ 128 e^(0.245)
    assert 150 < np.mean(answer) < 170
    think = [t["think_s"] for s in ss for t in s["turns"][1:]]
    assert abs(np.mean(think) - 5.0) < 0.5
    assert all(s["turns"][0]["think_s"] == 0.0 for s in ss)


def test_turn_counts_and_context_cap():
    ss = g.sessions(MIX, RATE, HORIZON, 9)
    counts = Counter(len(s["turns"]) for s in ss)
    # drawn 0.4/0.3/0.2/0.1; the context cap can only shorten a session
    assert counts[1] / len(ss) >= 0.4 and counts[4] / len(ss) <= 0.1
    assert 1.9 < sum(k * v for k, v in counts.items()) / len(ss) <= 2.0
    for s in ss:
        ctx = MIX["system_prompts"]["tokens"][s["system"]]
        for t in s["turns"]:
            ctx += t["user_tokens"] + t["answer_tokens"]
        assert ctx <= MIX["max_context"]


def test_quantiles_and_apportion():
    v = g.quantile_values({"dist": "exponential", "mean": 2.0}, 1000)
    assert abs(v.mean() - 2.0) < 0.02 and (np.diff(v) > 0).all()
    assert list(g.apportion(["a", "b", "c"], [0.5, 0.3, 0.2], 7)) == \
        ["a"] * 4 + ["b"] * 2 + ["c"]
    assert list(g.quantile_values({"dist": "constant", "value": 3}, 2)) == [3, 3]


def test_token_ids_and_packed_batches_are_seeded():
    assert g.token_ids(7, 1, 50, 32000) == g.token_ids(7, 1, 50, 32000)
    assert g.token_ids(7, 1, 50, 32000) != g.token_ids(7, 2, 50, 32000)
    mix = g.load_mix("train.8k")
    a = g.packed_batch(mix, 2 ** 31 + 5, 3, 32000, 1)
    b = g.packed_batch(mix, 2 ** 31 + 5, 3, 32000, 1)
    c = g.packed_batch(mix, 2 ** 31 + 5, 4, 32000, 1)
    assert a["input_ids"].shape == (1, 8192)
    assert (a["input_ids"] == b["input_ids"]).all()
    assert (a["input_ids"] != c["input_ids"]).any()
    assert a["labels"] is a["input_ids"]
    assert g.packed_batch(g.load_mix("train.ep4"), 1, 0, 32000, 4)[
        "input_ids"].shape == (4, 4096)
