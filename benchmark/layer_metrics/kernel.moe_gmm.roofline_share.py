"""XLA:TPU's grouped matmul (``ragged-dot-none.N``) against its roofline:
the least time one call needs on this chip over the mean time of a call in
the trace. Forward, replayed and backward calls are all one product of
``[M, H]``, ``[M, I]`` and ``[G, H, I]`` (which of the three is the result
differs), M = tokens x top-k: exact where every routed row is computed on
this chip, so the metric lists only cells with no expert axis."""

from benchmark import instruction_times, kernel_costs

KERNEL = "ragged-dot-none"


def grouped_matmul(rows, hidden, inter, groups, elem=2):
    """2 x rows x hidden x inter operations (each sorted row meets one
    group's ``[hidden, inter]`` weight), and every operand and the result
    moved once, each in the compute dtype: the kernel writes a weight's
    gradient ``[G, H, I]`` in bfloat16 too, as ``test_tpu_compile.py`` pins
    (the engine widens it afterwards)."""
    return {"flops": 2.0 * rows * hidden * inter,
            "bytes": elem * (rows * hidden + rows * inter
                             + groups * hidden * inter)}


def read(run):
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    ops = instruction_times.by_instruction(run, KERNEL)
    if not files or not ops:
        return None
    sizes, _, mix = files
    rows = mix["sequences_per_chip"] * mix["seq_len"] \
        * sizes["num_experts_per_tok"]
    cost = grouped_matmul(rows, sizes["hidden_size"],
                          sizes["intermediate_size"],
                          sizes["num_local_experts"])
    total = {"s": sum(v["s"] for v in ops.values()),
             "calls": sum(v["calls"] for v in ops.values())}
    return kernel_costs.roofline_share(run, {"by_kernel": {KERNEL: total}},
                                       (KERNEL,), cost)
