"""What a configuration with a learned sparse attention NEEDS, from its
shapes: the operations of a token's forward pass at this chip's share of the
experts, and the operations and bytes of the kernels that run under the
selection (``benchmark/flops.py`` counts every causal pair, a dense
feed-forward and a router that holds every expert).

The attention core is charged the SELECTED pairs: query ``t`` attends
``min(topk, t + 1)`` keys, a mean of ``flops.mean_attended_keys(T, topk)``
(the same sum a window of ``topk`` gives: 1,920 at 16,384 positions), so a
kernel that computes every causal pair under a mask reads as the low share of
its roofline that it is, and none can pass 100%. The indexer is charged every
causal pair (it scores them all) at ``2 x heads x head_dim`` a pair.

``common.sizes_of`` overwrites ``sizes["head_dim"]`` with ``hidden_size //
num_attention_heads``; this model's heads are ``head_dim_override`` wide, so
every count here reads that key. As in ``flops.py`` and ``mla_costs.py``:
recomputed work does not count, nor padding, nor element-wise passes (the
selection's counting passes, the softmax and KL of the indexer's loss); the
held experts are charged the pairs a LEVEL router sends them, tokens x top-k
x held / routed.
"""

from benchmark import flops, kernel_costs, scope_reduce


def is_sa(sizes):
    return bool(sizes.get("sa_topk"))


def selected_keys(sizes, seq_len):
    """Mean over the queries of the keys the selection leaves them."""
    return flops.mean_attended_keys(seq_len, sizes["sa_topk"])


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes["head_dim_override"]
    J, d = sizes["sa_indexer_num_heads"], sizes["sa_indexer_head_dim"]
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    return {
        # q_proj, k_proj, v_proj, o_proj
        "attn_proj": L * 2 * H * D * (Hq + Hkv + Hkv + Hq),
        # the indexer's query, its one key, its head weights
        "sa_index_proj": L * 2 * H * (J * d + d + J),
        # every causal pair, J heads of d
        "sa_index_scores": L * 2 * J * d * flops.mean_attended_keys(seq_len),
        # scores and values over D, per SELECTED key
        "attention": L * 2 * 2 * Hq * D * selected_keys(sizes, seq_len),
        "router": L * 2 * H * routed,
        "held_experts": L * (sizes["num_experts_per_tok"] * held / routed)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's (the
    indexer's scores too: its loss sends a gradient through every selected
    pair's score, and the count keeps the forward's factor)."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def _mask_bytes(batch, seq_len):
    """The selection as the least a kernel could read: one bit a pair."""
    return batch * seq_len * seq_len // 8


def flash_sa_fwd(batch, seq_len, q_heads, kv_heads, head_dim, topk):
    """The flash forward under the selection: ``kernel_costs.flash_fwd`` at
    the selected pairs, plus the mask."""
    cost = kernel_costs.flash_fwd(batch, seq_len, q_heads, kv_heads,
                                  head_dim, window=topk)
    return {**cost, "bytes": cost["bytes"] + _mask_bytes(batch, seq_len)}


def flash_sa_bwd(batch, seq_len, q_heads, kv_heads, head_dim, topk):
    cost = kernel_costs.flash_bwd(batch, seq_len, q_heads, kv_heads,
                                  head_dim, window=topk)
    return {**cost, "bytes": cost["bytes"] + _mask_bytes(batch, seq_len)}


def sa_probs(batch, seq_len, q_heads, kv_heads, head_dim, topk, elem=2):
    """``ds_sa_probs``: the scores again (2 x D a selected pair a head), q
    once a query head and k once a key/value head, the log-sum-exp rows, the
    mask, and the head-mean in float32 for the selected pairs. Since PR 44
    the kernel no longer WRITES that head-mean (it reduces it against the
    index scores in VMEM to five row statistics); the 4 bytes a selected
    pair stay in the count, which at these shapes is bound by operations
    (the least time is ``flops`` / peak with or without them), so no number
    moves."""
    selected = batch * seq_len * flops.mean_attended_keys(seq_len, topk)
    return {"flops": 2 * head_dim * q_heads * selected,
            "bytes": elem * batch * seq_len * head_dim * (q_heads + kv_heads)
            + 4 * batch * q_heads * seq_len + _mask_bytes(batch, seq_len)
            + 4 * selected}


def kernel_share(run, kernels, cost_fn):
    """``kernels`` of a sparse-attention training cell against their
    roofline; None for any other run, or a program without them."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_sa(files[0]):
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced:
        return None
    sizes, _, mix = files
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"],
                   sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim_override"],
                   sizes["sa_topk"])
    return kernel_costs.roofline_share(run, reduced, kernels, cost)
