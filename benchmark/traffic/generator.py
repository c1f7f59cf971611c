"""The one traffic generator. A mix is a data file beside this module
(``<traffic>.json``); a later PR adds a mix by adding a file.

Every seed gets the SAME set of sizes, gaps and think times — the
``n`` equal-probability quantile midpoints of each stated distribution — in
another order and pairing. So a run's amount of work does not depend on its
seed, only how that work is interleaved.
"""

import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name):
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


# -- quantiles of the stated distributions -----------------------------------

def quantile_values(spec, n):
    """``n`` values standing for the distribution ``spec``: its quantiles at
    (i + 0.5) / n. Lengths (a ``min``/``max`` in the spec) are clipped and
    rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "exponential":
        vals = -spec["mean"] * np.log1p(-u)
    elif spec["dist"] == "constant":
        vals = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    if "min" in spec or "max" in spec:
        vals = np.clip(vals, spec.get("min", -math.inf),
                       spec.get("max", math.inf))
        vals = np.rint(vals).astype(np.int64)
    return vals


def apportion(values, weights, n):
    """``n`` draws of a categorical in its stated proportions (largest
    remainder), as an array of the values."""
    w = np.asarray(weights, float)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(values), counts)


# -- sessions: open-loop multi-turn chat ------------------------------------

def sessions(mix, sessions_per_s, horizon_s, seed):
    """Sessions arriving over ``[0, horizon_s)``: a list of dicts
    ``{"arrival_s", "system", "turns": [{"user_tokens", "answer_tokens",
    "think_s"}]}``, sorted by arrival. ``think_s`` is the pause AFTER the
    previous answer ended, so the first turn's is 0 and unused.

    A turn's prompt is the system prompt, the whole history and the new
    message; a session ends before the turn that would pass
    ``mix["max_context"]``.
    """
    n = max(1, round(sessions_per_s * horizon_s))
    rs = np.random.RandomState(seed % (2 ** 32))
    gaps = rs.permutation(quantile_values(
        {"dist": "exponential", "mean": 1.0 / sessions_per_s}, n))
    arrivals = np.cumsum(gaps) - gaps[0] * 0.5
    arrivals *= min(1.0, horizon_s / (arrivals[-1] + gaps[0] * 0.5))
    n_turns = rs.permutation(apportion(mix["turns"]["values"],
                                       mix["turns"]["weights"], n))
    system = rs.permutation(apportion(
        np.arange(len(mix["system_prompts"]["tokens"])),
        mix["system_prompts"]["weights"], n))
    m = int(n_turns.sum())
    user = rs.permutation(quantile_values(mix["user_tokens"], m))
    answer = rs.permutation(quantile_values(mix["answer_tokens"], m))
    think = rs.permutation(quantile_values(mix["think_s"], m))
    out, k = [], 0
    for i in range(n):
        ctx = mix["system_prompts"]["tokens"][system[i]]
        turns = []
        for j in range(k, k + n_turns[i]):
            u, a = int(user[j]), int(answer[j])
            if ctx + u + a > mix["max_context"]:
                break
            turns.append({"user_tokens": u, "answer_tokens": a,
                          "think_s": float(think[j]) if turns else 0.0})
            ctx += u + a
        k += n_turns[i]           # the draws are consumed either way
        if turns:
            out.append({"arrival_s": float(arrivals[i]),
                        "system": int(system[i]), "turns": turns})
    return out


def token_ids(seed, stream, n, vocab_size):
    """``n`` seeded token ids; ``stream`` separates the system prompts
    (shared by every session of a kind) from the user messages."""
    rs = np.random.RandomState((seed * 1000003 + stream) % (2 ** 32))
    return rs.randint(0, vocab_size, n).tolist()


# -- packed: a training job's batches ----------------------------------------

def packed_batch(mix, seed, step, vocab_size, chips):
    """The global batch of step ``step``: ``sequences_per_chip * chips``
    packed sequences of ``seq_len`` uniform token ids, labels = inputs."""
    rs = np.random.RandomState((seed * 1000003 + step) % (2 ** 32))
    ids = rs.randint(0, vocab_size,
                     (mix["sequences_per_chip"] * chips, mix["seq_len"]),
                     dtype=np.int32)
    return {"input_ids": ids, "labels": ids}
