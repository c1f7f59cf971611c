"""Speculative-decoding drafters for the paged serving engine.

A drafter proposes ``k`` continuation tokens for a decoding resident;
the engine packs them — together with the resident's last committed
token — as ONE verify row of the resident mixed step (``query_len =
k + 1``, exactly a prefill-like chunk starting at the row's current
``seq_len``), greedily accepts the longest matching prefix of the
model's own predictions, and rolls the rejected KV back by rewinding
``context_len`` (partial pages are overwritten by the next append,
whole rejected pages drop through the pool's reference sets). One
dispatch thus commits up to ``k + 1`` tokens instead of one, without a
second compiled program and without the recompile sentinel firing.

The default drafter is model-free **prompt lookup** (n-gram matching —
the PLD/"prompt lookup decoding" lineage): match the last n-gram of the
resident's OWN prompt + generated history against an earlier occurrence
in that same history and propose the tokens that followed it. Zero
extra device work, no draft model to load, and it pays exactly on the
repetitive traffic the prefix cache serves: multi-turn replays,
quote-heavy completions, structured output, greedy repetition loops.

A draft MODEL can slot in later by implementing :class:`Drafter` —
the engine only calls :meth:`Drafter.draft` once per speculating
resident per step and never inspects the drafter beyond ``kind``.
"""

from typing import List, Sequence

__all__ = ["Drafter", "PromptLookupDrafter"]


class Drafter:
    """Pluggable draft-token source (``ServingConfig.drafter``).

    Contract: :meth:`draft` returns AT MOST ``k`` proposed continuation
    tokens for ``history`` (the resident's prompt + every committed
    generated token, newest last). Fewer — including zero — is always
    legal and simply shrinks (or skips) that resident's verify row this
    step; the engine never retries within a step. Drafters must be
    stateless across requests or key any state they keep on content,
    not call order: the engine gives no identity, and a resident may be
    preempted and resumed (its history replayed) between calls."""

    #: short slug for reports (``ds_report`` / ``ds_serve`` stats)
    kind = "base"

    def draft(self, history: Sequence[int], k: int) -> List[int]:
        raise NotImplementedError


class PromptLookupDrafter(Drafter):
    """Model-free prompt-lookup (n-gram) drafting.

    Finds the MOST RECENT earlier occurrence of the history's trailing
    n-gram (trying ``max_ngram`` down to ``min_ngram``) and proposes the
    tokens that followed it, up to ``k``. No match -> no draft -> that
    resident runs a plain decode row this step, so adversarial
    (pattern-free) traffic pays nothing beyond the failed host-side
    scan. Histories are bounded by ``max_model_len`` (hundreds to a few
    thousand tokens), so the scan is a cheap host loop."""

    kind = "prompt_lookup"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if max_ngram < 1 or min_ngram < 1 or min_ngram > max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram "
                f"(got min={min_ngram}, max={max_ngram})")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def draft(self, history: Sequence[int], k: int) -> List[int]:
        n_hist = len(history)
        if k <= 0 or n_hist < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, n_hist - 1),
                       self.min_ngram - 1, -1):
            pattern = tuple(history[n_hist - n:])
            # newest earlier occurrence first: recent context predicts
            # the continuation better than a stale one (and greedy
            # repetition loops — the common tiny-model attractor — are
            # matched at their latest period)
            for i in range(n_hist - n - 1, -1, -1):
                if tuple(history[i:i + n]) == pattern:
                    # i + n < n_hist by the range bound, so at least one
                    # continuation token always exists
                    cont = [int(t) for t in history[i + n:i + n + k]]
                    # the continuation runs into the tail after one
                    # period of the implied loop (d = match-to-tail
                    # distance); extend it PERIODICALLY — a stream that
                    # looped once tends to keep looping, and without
                    # this the draft length is capped by the loop
                    # period (a constant tail would cap every draft
                    # at one token)
                    d = (n_hist - n) - i
                    while len(cont) < k:
                        cont.append(cont[-d])
                    return cont
        return []
