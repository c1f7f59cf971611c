"""Tokens the average step carried: (tokens generated + prompt tokens
computed) / steps, deltas of the engine's counters over the window."""


def read(run):
    o = run["observed"]
    if o["kind"] != "serve" or not o["counters"]["steps"]:
        return None
    c = o["counters"]
    return (c["tokens_generated"] + c["prefill_tokens_computed"]) / c["steps"]
