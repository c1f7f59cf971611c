"""Share of admitted prompt tokens served from the prefix cache:
cached_prefill_tokens / prefill_tokens, deltas over the window."""


def read(run):
    o = run["observed"]
    if o["kind"] != "serve" or not o["counters"]["prefill_tokens"]:
        return None
    c = o["counters"]
    return 100.0 * c["cached_prefill_tokens"] / c["prefill_tokens"]
