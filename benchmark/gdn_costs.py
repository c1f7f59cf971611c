"""What a decoder of gated delta-rule layers and gated full-attention layers
over a sparse-expert layer with a shared expert NEEDS, from its shapes, at
this chip's share of the experts (``benchmark/flops.py`` counts a GQA layer
and every expert).

As in ``flops.py``, ``mla_costs.py`` and ``swa_costs.py``: recomputed work
does not count, nor padding, nor element-wise passes (norms, the convolution's
four taps, the rotation, gates, decays, the softmax of the router); the full
layers' core is charged the causal pairs, ``flops.mean_attended_keys``; the
held experts the pairs a LEVEL router sends them, tokens x top-k x held /
routed. ``common.sizes_of`` overwrites ``sizes["head_dim"]`` with
``hidden_size // num_attention_heads``; the full layers' heads are
``head_dim_override`` wide, so every count here reads that key.

The delta rule is charged the RECURRENCE's products, 6 dk dv a value head
and a token (``S^T k``, ``k d^T`` and ``S^T q``: ``rule_per_token``), which no
chunk size moves: a program that raises ``gdn_chunk`` spends more products
inside its chunks and is required no more. What the chunked form the program
runs spends is ``chunked_rule_per_token``, an observation no reader charges
(at chunk 64 the chunk's own pairs add 42%).
"""

from benchmark import flops, kernel_costs, scope_reduce


def is_gdn_moe(sizes):
    return bool(sizes.get("linear_num_value_heads"))


def layer_counts(sizes):
    """(delta-rule layers, full layers)."""
    L = sizes["num_hidden_layers"]
    full = L // sizes["full_attention_interval"]
    return L - full, full


def rule_per_token(sizes):
    """Multiply-adds x 2 of the delta rule for one token of one layer, all
    value heads, as the recurrence needs them: the state read for the key
    (``S^T k``), updated (``k d^T``) and read for the query (``S^T q``),
    dk dv each."""
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return sizes["linear_num_value_heads"] * 2 * 3 * dk * dv


def chunked_rule_per_token(sizes, chunk):
    """What the CHUNKED form spends on the same token (an observation: the
    program's choice of ``chunk`` moves it, so nothing is charged it): in a
    chunk of ``C`` a token meets ``(C - 1) / 2`` earlier keys for ``K K^T``
    (dk) and for the forward substitution of ``dv + dk`` right-hand
    columns, ``(C + 1) / 2`` keys for ``Q K^T`` (dk) and for its product
    with the corrections (dv), and the boundary state three times (``W S``,
    ``Q S``, ``K^T D``: dk dv each)."""
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    before, upto = (chunk - 1) / 2.0, (chunk + 1) / 2.0
    head = 2 * (before * dk + before * (dv + dk) + upto * dk + upto * dv
                + 3 * dk * dv)
    return sizes["linear_num_value_heads"] * head


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D = sizes["head_dim_override"]
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    gdn, full = layer_counts(sizes)
    return {
        # in_proj_qkvz, in_proj_ba, out_proj
        "gdn_proj": gdn * 2 * H * (2 * Hk * dk + 2 * Hv * dv + 2 * Hv
                                   + Hv * dv),
        "gdn_rule": gdn * rule_per_token(sizes),
        # q_proj (a query and a gate a head), k_proj, v_proj, o_proj
        "attn_proj": full * 2 * H * D * (2 * Hq + Hkv + Hkv + Hq),
        "attention": full * 2 * 2 * Hq * D
        * flops.mean_attended_keys(seq_len),
        "router": L * 2 * H * routed,
        # the shared SwiGLU and its one-column gate
        "shared_expert": L * (3 * 2 * H
                              * sizes["shared_expert_intermediate_size"]
                              + 2 * H),
        "held_experts": L * (sizes["num_experts_per_tok"] * held / routed)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_ga_fwd(sizes, batch, seq_len):
    """One forward call of a full layer: 16 / 2 heads of 256."""
    return kernel_costs.flash_fwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"])


def flash_ga_bwd(sizes, batch, seq_len):
    """One backward call: five products to the forward's two."""
    return kernel_costs.flash_bwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"])


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of such a decoder,
    else None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_gdn_moe(files[0]):
        return None
    return files[0], files[2]


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a full layer's call against their roofline (a
    step calls them once a full layer, every call alike). None off the chip,
    for another program, or where the trace has none of the kernels."""
    found = cell_sizes(run)
    reduced = scope_reduce.reduced(run) if found else None
    if not reduced:
        return None
    sizes, mix = found
    return kernel_costs.roofline_share(
        run, reduced, kernels,
        cost_fn(sizes, mix["sequences_per_chip"], mix["seq_len"]))
