"""The small-group grouped-matmul kernels (``ds_moe_gmm.N``,
``ds_moe_gmm_t.N``) against their roofline: the least time one call needs on
this chip over the mean time of a call in the trace. What a call is charged
is ``kernel.moe_gmm.roofline_share``'s own count -- forward, replayed and
backward calls are all one product of ``[M, H]``, ``[M, I]`` and
``[G, H, I]``, M = tokens x top-k, exact where every routed row is computed
on this chip -- so the two metrics compare kernel with kernel on one cell.
None where the program holds none of these kernels."""

from benchmark import common, instruction_times, kernel_costs

KERNEL = "ds_moe_gmm"

grouped_matmul = common.load_file_module(
    "layer_metrics", "kernel.moe_gmm.roofline_share").grouped_matmul


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
