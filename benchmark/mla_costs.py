"""What a DeepSeek-V3-shaped configuration NEEDS, from its shapes: the
operations of a token's forward pass at this chip's share of the experts
(``benchmark/flops.py`` counts a GQA layer and a router that holds every
expert), and the operations and bytes of the flash kernels where queries
and keys are ``qk_nope_head_dim + qk_rope_head_dim`` wide and values
``v_head_dim`` (``benchmark/kernel_costs.py`` takes one ``head_dim``).

As there: recomputed work does not count, nor padding; the held experts are
charged the pairs a LEVEL router sends them, tokens x top-k x held / routed.
"""

from benchmark import flops, kernel_costs, scope_reduce


def is_mla(sizes):
    return bool(sizes.get("kv_lora_rank"))


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    r, L = sizes["kv_lora_rank"], sizes["num_hidden_layers"]
    dense = min(sizes.get("first_k_dense_replace", 1), L)
    moe = L - dense
    Im, K = sizes["moe_intermediate_size"], sizes["num_experts_per_tok"]
    held = sizes["n_routed_experts"]
    routed = sizes.get("router_experts") or held
    return {
        # q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj
        "attn_proj": L * 2 * (H * heads * (dn + dr) + H * (r + dr)
                              + r * heads * (dn + dv) + heads * dv * H),
        # scores over dn + dr, values over dv, per attended key
        "attention": L * 2 * heads * (dn + dr + dv)
        * flops.mean_attended_keys(seq_len),
        "dense_mlp": dense * 3 * 2 * H * sizes["intermediate_size"],
        "router": moe * 2 * H * routed,
        "shared_experts": moe * 3 * 2 * H * Im
        * sizes.get("n_shared_experts", 0),
        "held_experts": moe * (K * held / routed) * 3 * 2 * H * Im,
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_mla_fwd(batch, seq_len, heads, qk_dim, v_dim, elem=2):
    """Causal flash forward with two widths: per (query, attended key) pair
    of every head 2 x qk_dim operations for the score and 2 x v_dim for the
    value; q and k move once at qk_dim, v and o at v_dim, the log-sum-exp
    row in float32."""
    pairs = batch * heads * seq_len * flops.mean_attended_keys(seq_len)
    return {"flops": 2 * (qk_dim + v_dim) * pairs,
            "bytes": elem * batch * seq_len * heads * 2 * (qk_dim + v_dim)
            + 4 * batch * heads * seq_len}


def flash_mla_bwd(batch, seq_len, heads, qk_dim, v_dim, elem=2):
    """Flash backward (dq and dkv kernels together), five products: the
    scores again, dQ and dK over qk_dim, dP and dV over v_dim. Reads q, k,
    v, o's cotangent and the two float32 rows; writes dq, dk, dv."""
    pairs = batch * heads * seq_len * flops.mean_attended_keys(seq_len)
    return {"flops": 2 * (3 * qk_dim + 2 * v_dim) * pairs,
            "bytes": elem * batch * seq_len * heads
            * (4 * qk_dim + 3 * v_dim)
            + 2 * 4 * batch * heads * seq_len}


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a latent-attention training cell against their
    rooflines; None for any other run."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_mla(files[0]):
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced:
        return None
    sizes, _, mix = files
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"],
                   sizes["num_attention_heads"],
                   sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
                   sizes["v_head_dim"])
    return kernel_costs.roofline_share(run, reduced, kernels, cost)
