"""Share of device busy time in the small-group grouped-matmul kernels of
``deepspeed_tpu/ops/pallas/grouped_matmul.py``: operations whose
instruction name starts ``ds_moe_gmm`` (``ds_moe_gmm.N``, rows x a group's
weight; ``ds_moe_gmm_t.N``, a stacked weight's gradient). Where they run,
the products of ``models/mixtral.py _sorted_experts`` are theirs and
``moe.grouped_matmul_share`` (XLA:TPU's ``ragged-dot*``) finds nothing; a
program without them (ep4, every step before PR 50) reads None here."""

from benchmark import instruction_times, scope_reduce

PREFIX = "ds_moe_gmm"


def read(run):
    if run["observed"]["kind"] != "train":
        return None
    r = scope_reduce.reduced(run)
    ops = instruction_times.by_instruction(run, PREFIX)
    if not r or not r["busy_s"] or not ops:
        return None
    return 100.0 * sum(v["s"] for v in ops.values()) / r["busy_s"]
