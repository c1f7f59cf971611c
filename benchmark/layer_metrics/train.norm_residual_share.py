"""Share of device busy time under ``ds.norm`` and ``ds.residual``: a block's
two pre-norms and its two residual sums (scaled ones in ``models/zaya.py``);
norms inside a projection's or the head's scope stay theirs
(benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    parts = [scope_reduce.share(run, "train", scope)
             for scope in ("ds.norm", "ds.residual")]
    if all(p is None for p in parts):
        return None
    return sum(p or 0.0 for p in parts)
