"""Share of device busy time in XLA:TPU's grouped-matmul kernels: operations
whose instruction name starts ``ragged-dot`` (``ragged-dot-none.N``, the
products of ``models/mixtral.py _sorted_experts``, and the small
``ragged-dot-metadata`` call that tiles the groups for them). They carry no
scope path, so ``moe.expert_share`` misses them: the expert layer's time is
that share plus this one."""

from benchmark import instruction_times, scope_reduce

PREFIX = "ragged-dot"


def read(run):
    if run["observed"]["kind"] != "train":
        return None
    r = scope_reduce.reduced(run)
    ops = instruction_times.by_instruction(run, PREFIX)
    if not r or not r["busy_s"] or not ops:
        return None
    return 100.0 * sum(v["s"] for v in ops.values()) / r["busy_s"]
