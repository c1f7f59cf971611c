"""What a GQA / sparse-expert decoder NEEDS, from its shapes, where the kinds
of its layer pattern differ in their HEAD COUNT as well as their window and
the stack opens with dense layers (``deepspeed_tpu/models/laguna.py``): layer
``l`` attends the whole causal prefix with ``num_attention_heads`` query heads
where ``l mod full_attention_period = 0`` and a ``sliding_window`` with
``sliding_num_attention_heads`` otherwise; the first ``first_k_dense`` layers
carry a dense SwiGLU, the others a router over ``router_experts``, this chip's
``num_local_experts`` of them and a shared expert.

As in ``flops.py`` and ``swa_costs.py``: recomputed work does not count, nor
padding, nor element-wise passes (norms, the rotations, the gate's product,
the router's sigmoid); each kind's core is charged the KEPT pairs, the keys
its own mask leaves a query (``flops.mean_attended_keys``), at its own head
count -- what a tile half masked by the window computes beyond them shows as
lost roofline share; the held experts are charged the pairs a LEVEL router
sends them, tokens x top-k x held / routed. Heads are ``head_dim_override``
wide (``common.sizes_of`` overwrites ``head_dim``).
"""

import json

from benchmark import flops, kernel_costs, scope_reduce

FULL, WINDOW = "full", "window"


def is_laguna(sizes):
    return bool(sizes.get("sliding_num_attention_heads"))


def kinds(sizes):
    """{kind: (layers of it, its query heads, the window it attends (None:
    the whole prefix))} of the stack as run."""
    full = sum(l % sizes["full_attention_period"] == 0
               for l in range(sizes["num_hidden_layers"]))
    return {FULL: (full, sizes["num_attention_heads"], None),
            WINDOW: (sizes["num_hidden_layers"] - full,
                     sizes["sliding_num_attention_heads"],
                     sizes["sliding_window"])}


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hkv, D = sizes["num_key_value_heads"], sizes["head_dim_override"]
    dense = sizes["first_k_dense"]
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    parts = {
        "dense_mlp": dense * 3 * 2 * H * sizes["intermediate_size"],
        "router": (L - dense) * 2 * H * routed,
        "shared_expert": (L - dense) * 3 * 2 * H
        * sizes["shared_expert_intermediate_size"],
        "held_experts": (L - dense)
        * (sizes["num_experts_per_tok"] * held / routed)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }
    for kind, (n, Hq, window) in kinds(sizes).items():
        # q_proj, k_proj, v_proj, o_proj and the gate's [hidden, heads]
        parts[f"attn_proj_{kind}"] = n * 2 * H * (
            D * (Hq + Hkv + Hkv + Hq) + Hq)
        # scores and values, a kept key a query a head
        parts[f"attention_{kind}"] = n * 2 * 2 * Hq * D \
            * flops.mean_attended_keys(seq_len, window)
    return parts


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_lg_fwd(sizes, batch, seq_len, kind):
    """One forward call of a layer of ``kind``: the kept pairs at its own
    head count."""
    _, Hq, window = kinds(sizes)[kind]
    return kernel_costs.flash_fwd(
        batch, seq_len, Hq, sizes["num_key_value_heads"],
        sizes["head_dim_override"], window)


def flash_lg_bwd(sizes, batch, seq_len, kind):
    _, Hq, window = kinds(sizes)[kind]
    return kernel_costs.flash_bwd(
        batch, seq_len, Hq, sizes["num_key_value_heads"],
        sizes["head_dim_override"], window)


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of such a decoder,
    else None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_laguna(files[0]):
        return None
    return files[0], files[2]


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a step's calls (one a layer, each kind at its
    own head count and window) against their rooflines: the calls' least
    times on this chip summed, over their summed time -- each kernel's time
    per call in the trace times the step's calls. None off the chip, for
    another program, or where the trace has none of the kernels."""
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
    calls = {kind: n for kind, (n, _, _) in kinds(sizes).items()}
    least = {kind: kernel_costs.least_seconds(
        cost_fn(sizes, mix["sequences_per_chip"], mix["seq_len"], kind),
        run["device"]["kind"]) for kind in calls}
    least_s = sum(calls[kind] * s for kind, (s, _) in least.items())
    measured_s = sum(calls.values()) * sum(r["s"] / r["calls"] for r in rows)
    print(json.dumps({
        "observation": "kernel_roofline", "kernels": list(kernels),
        "calls_a_step": calls,
        "bound": {kind: b for kind, (_, b) in least.items()},
        "least_ms": 1e3 * least_s, "measured_ms": 1e3 * measured_s}),
        flush=True)
    return 100.0 * least_s / measured_s
