"""What a GQA / sparse-expert decoder under a PATTERN of layer kinds NEEDS,
from its shapes: every ``full_attention_period``-th layer attends the whole
causal prefix, the others a ``sliding_window`` (``benchmark/flops.py`` and
``kernel_costs.flash_share`` count one window a model and every expert), at
this chip's share of the experts.

As in ``flops.py``, ``mla_costs.py`` and ``sa_costs.py``: recomputed work
does not count, nor padding, nor element-wise passes (norms, the rotary
tables and rotations, the softmax of the router); each kind's core is charged
the pairs its own mask leaves, ``flops.mean_attended_keys`` of its window;
the held experts are charged the pairs a LEVEL router sends them, tokens x
top-k x held / routed. ``common.sizes_of`` overwrites ``sizes["head_dim"]``
with ``hidden_size // num_attention_heads``; this model's heads are
``head_dim_override`` wide, so every count here reads that key.

Also the two readers that tell the kinds apart in a trace: the share of
device time of operations whose PATH holds a kind's outer scope
(``scope_reduce.by_scope`` keeps an operation's innermost scope alone).
"""

import json

from benchmark import flops, kernel_costs, scope_reduce
from benchmark.trace_reduce import CONTAINERS, WINDOW


def is_swa_moe(sizes):
    return bool(sizes.get("full_attention_period"))


def layer_counts(sizes):
    """Layers of each kind, by the window each attends (None: full)."""
    L = sizes["num_hidden_layers"]
    full = L // sizes["full_attention_period"]
    return {sizes["sliding_window"]: L - full, None: full}


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes["head_dim_override"]
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    window = sizes["sliding_window"]
    n = layer_counts(sizes)
    pair = 2 * 2 * Hq * D               # scores and values, a key a query
    return {
        # q_proj, k_proj, v_proj, o_proj
        "attn_proj": L * 2 * H * D * (Hq + Hkv + Hkv + Hq),
        "attention_window": n[window] * pair
        * flops.mean_attended_keys(seq_len, window),
        "attention_full": n[None] * pair * flops.mean_attended_keys(seq_len),
        "router": L * 2 * H * routed,
        "held_experts": L * (sizes["num_experts_per_tok"] * held / routed)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_swa_fwd(sizes, batch, seq_len, window):
    """One forward call of a layer that attends ``window`` (None: full)."""
    return kernel_costs.flash_fwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"], window)


def flash_swa_bwd(sizes, batch, seq_len, window):
    return kernel_costs.flash_bwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"], window)


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of such a decoder,
    else None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_swa_moe(files[0]):
        return None
    return files[0], files[2]


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a step's calls (one a layer: most at the window,
    the rest full) against their rooflines: the calls' least times on this
    chip summed, over their summed time -- each kernel's time per call in
    the trace times the step's calls. None off the chip, for another
    program, or where the trace has none of the kernels."""
    found = cell_sizes(run)
    if not found or run["device"]["platform"] != "tpu":
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced:
        return None
    rows = [reduced["by_kernel"].get(k) for k in kernels]
    if not all(rows) or not all(r["calls"] for r in rows):
        return None
    sizes, mix = found
    calls = layer_counts(sizes)
    least = {window: kernel_costs.least_seconds(
        cost_fn(sizes, mix["sequences_per_chip"], mix["seq_len"], window),
        run["device"]["kind"]) for window in calls}
    least_s = sum(calls[window] * s for window, (s, _) in least.items())
    measured_s = sum(calls.values()) * sum(r["s"] / r["calls"] for r in rows)
    print(json.dumps({
        "observation": "kernel_roofline", "kernels": list(kernels),
        "calls_a_step": {str(k): v for k, v in calls.items()},
        "bound": {str(k): b for k, (_, b) in least.items()},
        "least_ms": 1e3 * least_s, "measured_ms": 1e3 * measured_s}),
        flush=True)
    return 100.0 * least_s / measured_s


_PATH_NS = {}


def _path_ns(trace):
    """{scope: exclusive device nanoseconds of the operations whose path
    holds it}, summed over the devices, inside the traced window as
    ``scope_reduce.reduce`` clips and attributes it (each instant to the
    operation that started last). One pass a trace however many readers."""
    if id(trace) not in _PATH_NS:
        span = [(s, s + d) for n, s, d, *_ in trace["host"] if n == WINDOW]
        lo, hi = span[0] if span else (-float("inf"), float("inf"))
        total = {}
        for events in trace["devices"].values():
            work = [(n, max(s, lo), min(s + d, hi), op)
                    for n, s, d, op in events if not CONTAINERS.match(n)]
            work = [w for w in work if w[2] > w[1]]
            for k, ns in scope_reduce.exclusive(work):
                for scope in set(scope_reduce.SCOPE.findall(work[k][3])):
                    total[scope] = total.get(scope, 0) + ns
        _PATH_NS[id(trace)] = (trace, total)    # the trace kept: ids stay apart
    return _PATH_NS[id(trace)][1]


def path_share(run, scope):
    """100 x device time of the operations whose ``op_name`` path holds
    ``scope`` anywhere / busy time. None for another kind of run, no trace,
    or a program that never names ``scope``."""
    if run["observed"]["kind"] != "train":
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced or not reduced["busy_s"]:
        return None
    trace = run["scope_trace"] if "scope_trace" in run \
        else scope_reduce.load_run()
    under = _path_ns(trace).get(scope)
    if not under:
        return None
    return 100.0 * under / reduced["devices"] / 1e9 / reduced["busy_s"]
