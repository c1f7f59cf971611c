"""``ds_ragged_paged_attention``: the least time the window's calls need on
this chip (each step's rows, packed tokens and context tokens from its
``ds.dispatch`` span; benchmark/kernel_costs.py) over their time in the trace."""

from benchmark import kernel_costs, scope_reduce

KERNEL = "ds_ragged_paged_attention"


def read(run):
    if run["observed"]["kind"] != "serve":
        return None
    r = scope_reduce.reduced(run)
    files = kernel_costs.cell_files(run)
    steps = [a for a in (r or {}).get("dispatch_args", [])
             if "context_tokens" in a]
    if not files or not steps or KERNEL not in r["by_kernel"]:
        return None
    sizes, workload, _ = files
    elem = kernel_costs.DTYPE_BYTES[workload["dtype"]]
    kv_elem = kernel_costs.DTYPE_BYTES[workload["check"]["kv_dtype"]]
    per_layer = {"flops": 0.0, "bytes": 0.0}
    for a in steps:
        tokens = a["decode_tokens"] + a["verify_tokens"] + a["prefill_tokens"]
        cost = kernel_costs.ragged_paged_attention(
            tokens, a["rows"], a["context_tokens"],
            sizes["num_attention_heads"], sizes["num_key_value_heads"],
            sizes["head_dim"], kv_elem, elem)
        per_layer = {k: per_layer[k] + cost[k] / len(steps) for k in cost}
    return kernel_costs.roofline_share(run, r, (KERNEL,), per_layer)
