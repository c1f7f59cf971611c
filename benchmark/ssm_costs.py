"""What a decoder-hybrid-decoder with state-space layers NEEDS, from its
shapes: the operations of a token's forward pass (``benchmark/flops.py``
counts a GQA layer and nothing else), the selective scan's costs, and the
flash kernels' costs under differential attention.

As in ``flops.py``: recomputed work does not count, nor padding, nor the
element-wise passes (the depthwise convolution's and the scan's multiply-adds
are counted; norms, gates, softplus and the differential mix are not).

The layers of each kind come from ``num_hidden_layers``,
``self_decoder_layers`` and ``mb_per_layer`` = 2: ``S / 2`` (Mamba, window)
periods, the memory layer (Mamba), the key/value layer (full attention),
``(L - S - 2) / 2`` (gated memory unit, cross-attention) periods; every layer
carries the SwiGLU.

A differential-attention call runs both streams as query heads: every query
head meets 64-wide keys and 128-wide values (the key pair's two value heads
side by side), so a (query, key) pair of a head is ``2 d + 2 (2 d)``
operations; q and the ``2 d``-wide output move once a query head, k and v
once a key/value head.
"""

import json
import math

from benchmark import flops, kernel_costs, scope_reduce

#: multiply-adds x 2 of one (position, channel, state) of the scan: the
#: decay's product, its exponential, the state's multiply-add, the input's
#: two products, the output's multiply-add; the backward needs about three
#: times the forward's
SCAN_FLOPS = 9
SCAN_BWD_FLOPS = 3 * SCAN_FLOPS


def is_ssm_hybrid(sizes):
    return bool(sizes.get("mamba_d_state"))


def layer_counts(sizes):
    L = sizes["num_hidden_layers"]
    S = sizes.get("self_decoder_layers")
    S = L // 2 if S is None else S
    return {"mamba": S // 2 + 1, "window": S // 2, "full": 1,
            "gmu": (L - S - 2) // 2, "cross": (L - S - 2) // 2}


def widths(sizes):
    H = sizes["hidden_size"]
    return {"H": H, "C": sizes["mamba_expand"] * H,
            "N": sizes["mamba_d_state"],
            "R": sizes.get("mamba_dt_rank") or math.ceil(H / 16),
            "Hq": sizes["num_attention_heads"],
            "Hkv": sizes["num_key_value_heads"],
            "d": H // sizes["num_attention_heads"]}


def pair_flops(sizes):
    """Operations of one (query, key) pair over all the query heads."""
    w = widths(sizes)
    return w["Hq"] * (2 * w["d"] + 2 * 2 * w["d"])


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    n, w = layer_counts(sizes), widths(sizes)
    H, C, N, R, d = w["H"], w["C"], w["N"], w["R"], w["d"]
    q, kv = w["Hq"] * d, w["Hkv"] * d
    keys = flops.mean_attended_keys
    return {
        "mlp": sizes["num_hidden_layers"] * 3 * 2 * H
        * sizes["intermediate_size"],
        # in, x_proj, dt, out, the depthwise taps
        "ssm_proj": n["mamba"] * 2 * (H * 2 * C + C * (R + 2 * N) + R * C
                                      + C * H + sizes["mamba_d_conv"] * C),
        "ssm_scan": n["mamba"] * SCAN_FLOPS * C * N,
        # W_qkv and W_o; the cross layers W_q and W_o
        "attn_proj": (n["window"] + n["full"]) * 2 * H * (2 * q + 2 * kv)
        + n["cross"] * 2 * H * 2 * q,
        "attention": pair_flops(sizes) * (
            n["window"] * keys(seq_len, sizes.get("sliding_window"))
            + (n["full"] + n["cross"]) * keys(seq_len)),
        "gmu": n["gmu"] * 2 * 2 * H * C,
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def selective_scan_fwd(batch, seq_len, channels, states, elem=2):
    """What ANY implementation moves: ``u``, ``delta`` and ``y`` ``[T, C]``
    and ``B``, ``C`` ``[T, N]`` at the compute type's width, ``A`` and ``D``
    once in float32. No chunk states, no recompute."""
    cells = batch * seq_len * channels
    return {"flops": SCAN_FLOPS * cells * states,
            "bytes": elem * (3 * cells + 2 * batch * seq_len * states)
            + 4 * channels * (states + 1)}


def selective_scan_bwd(batch, seq_len, channels, states, elem=2):
    """Reads the forward's five inputs and ``dy``, writes the gradients of
    ``u``, ``delta``, ``B``, ``C`` and of ``A`` and ``D``."""
    cells = batch * seq_len * channels
    return {"flops": SCAN_BWD_FLOPS * cells * states,
            "bytes": elem * (5 * cells + 4 * batch * seq_len * states)
            + 2 * 4 * channels * (states + 1)}


def flash_da_fwd(sizes, batch, seq_len, window=None, elem=2):
    w = widths(sizes)
    moved = elem * batch * seq_len * w["d"] * (3 * w["Hq"] + 2 * w["Hkv"])
    return {"flops": batch * seq_len * pair_flops(sizes)
            * flops.mean_attended_keys(seq_len, window),
            "bytes": moved + 4 * batch * w["Hq"] * seq_len}


def flash_da_bwd(sizes, batch, seq_len, window=None, elem=2):
    """Five matrix products to the forward's two; reads q, k, v, o and its
    cotangent and the two float32 rows, writes dq, dk, dv."""
    w = widths(sizes)
    fwd = flash_da_fwd(sizes, batch, seq_len, window, elem)
    moved = elem * batch * seq_len * w["d"] * (6 * w["Hq"] + 4 * w["Hkv"])
    return {"flops": 2.5 * fwd["flops"],
            "bytes": moved + 2 * 4 * batch * w["Hq"] * seq_len}


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of a state-space
    hybrid on the chip, else None."""
    if run["observed"]["kind"] != "train" or \
            run["device"]["platform"] != "tpu":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_ssm_hybrid(files[0]):
        return None
    return files[0], files[2]


def scan_share(run, kernel, cost_fn):
    """One scan kernel against its roofline: least time of a call over its
    time per call in the trace (``kernel_costs.roofline_share``)."""
    found = cell_sizes(run)
    reduced = found and scope_reduce.reduced(run)
    if not reduced:
        return None
    sizes, mix = found
    w = widths(sizes)
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"], w["C"], w["N"])
    return kernel_costs.roofline_share(run, reduced, (kernel,), cost)


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a step's differential-attention calls (one a
    window layer, one a full or cross layer) against their rooflines: the
    calls' least times summed over their summed time -- each kernel's time
    per call in the trace times the step's calls."""
    found = cell_sizes(run)
    reduced = found and scope_reduce.reduced(run)
    if not reduced:
        return None
    rows = [reduced["by_kernel"].get(k) for k in kernels]
    if not all(rows) or not all(r["calls"] for r in rows):
        return None
    sizes, mix = found
    n = layer_counts(sizes)
    calls = {sizes.get("sliding_window"): n["window"],
             None: n["full"] + n["cross"]}
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
