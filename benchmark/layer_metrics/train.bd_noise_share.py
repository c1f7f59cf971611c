"""Share of device busy time under ``ds.bd_noise`` (the checksum, the draws,
the masking, the doubled sequence and its positions) and ``ds.bd_gather``
(the noised half taken before the head, and its gradient's padding), forward,
backward and recomputed together (benchmark/scope_reduce). None for a program
without those scopes."""

from benchmark import scope_reduce


def read(run):
    parts = [scope_reduce.share(run, "train", scope)
             for scope in ("ds.bd_noise", "ds.bd_gather")]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
