"""What a block-diffusion training step over a GQA / sparse-expert stack
NEEDS, from its shapes (``configs/sdar-30b-a3b.json``): one pass over ``[x_t
; x_0]`` -- ``2L`` positions for ``L`` trained tokens -- under the rule that a
noised block sees itself and the clean blocks before it and the clean blocks
are block-causal.

Counted per TRAINED token (the ``L`` of a sequence, what
``train_tokens_per_s_per_chip`` counts): the projections, the router and the
held experts run on BOTH halves' rows; the attention core is charged the
pairs the rule KEEPS, ``L (L + B)`` of the ``4 L^2`` (a noised row of block
``b`` sees ``B + b B`` keys, a clean row ``(b + 1) B``), so the 16 diagonal
tiles computed whole for 4 live columns in 512 show as lost share; the head
runs once, over the noised rows. As in ``flops.py`` and ``sa_costs.py``:
recomputed work does not count, nor padding, nor element-wise passes; the
held experts are charged the pairs a LEVEL router sends them, rows x top-k x
held / routed. ``common.sizes_of`` overwrites ``sizes["head_dim"]``; this
model's heads are ``head_dim_override`` wide, so every count here reads that
key.
"""

from benchmark import kernel_costs, scope_reduce


def is_bd(sizes):
    return bool(sizes.get("block_length"))


def kept_pairs(seq_len, block):
    """Pairs of the ``2L x 2L`` square the rule keeps, a sequence."""
    return seq_len * (seq_len + block)


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one TRAINED token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes["head_dim_override"]
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    rows = 2                            # positions a trained token
    return {
        # q_proj, k_proj, v_proj, o_proj, both halves
        "attn_proj": rows * L * 2 * H * D * (Hq + Hkv + Hkv + Hq),
        # scores and values over D, per KEPT pair
        "attention": L * 2 * 2 * Hq * D
        * kept_pairs(seq_len, sizes["block_length"]) / seq_len,
        "router": rows * L * 2 * H * routed,
        "held_experts": rows * L
        * (sizes["num_experts_per_tok"] * held / routed)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_bd_fwd(batch, seq_len, q_heads, kv_heads, head_dim, block, elem=2):
    """``ds_flash_fwd`` over the ``2L`` rows: 2 x 2 x D operations a kept
    pair a query head; q and o move once a query head, k and v once a
    key/value head, the log-sum-exp row in float32."""
    rows = 2 * seq_len
    return {"flops": 4 * head_dim * batch * q_heads
            * kept_pairs(seq_len, block),
            "bytes": elem * batch * rows * head_dim
            * (2 * q_heads + 2 * kv_heads) + 4 * batch * q_heads * rows}


def flash_bd_bwd(batch, seq_len, q_heads, kv_heads, head_dim, block, elem=2):
    """The fused ``ds_flash_bwd``: five matrix products a pair to the
    forward's two; reads q, k, v, o's cotangent and the two float32 rows,
    writes dq, dk, dv (``kernel_costs.flash_bwd``'s count at ``2L`` rows)."""
    rows = 2 * seq_len
    fwd = flash_bd_fwd(batch, seq_len, q_heads, kv_heads, head_dim, block,
                       elem)
    return {"flops": 2.5 * fwd["flops"],
            "bytes": elem * batch * rows * head_dim
            * (3 * q_heads + 4 * kv_heads) + 2 * 4 * batch * q_heads * rows}


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run under the rule, else
    None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_bd(files[0]):
        return None
    return files[0], files[2]


def kernel_share(run, kernels, cost_fn):
    """``kernels`` of a block-diffusion training cell against their
    roofline; None for any other run, or a program without them."""
    found = cell_sizes(run)
    reduced = scope_reduce.reduced(run) if found else None
    if not reduced:
        return None
    sizes, mix = found
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"],
                   sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim_override"],
                   sizes["block_length"])
    return kernel_costs.roofline_share(run, reduced, kernels, cost)
