"""What a ZAYA1-shaped configuration NEEDS, from its shapes: the operations
of a token's forward pass at this chip's share of the experts
(``benchmark/flops.py`` counts a GQA layer at ``hidden / heads`` columns a
head, a dense feed-forward and a router that holds every expert), and the
flash kernels' costs at this model's own head width.

``common.sizes_of`` overwrites ``sizes["head_dim"]`` with ``hidden_size //
num_attention_heads``; compressed attention runs in a latent of
``head_dim_override`` columns a head, so every count here reads that key.

As in ``flops.py`` and ``mla_costs.py``: recomputed work does not count, nor
padding, nor the element-wise passes (the depthwise convolution's
multiply-adds are counted, the mean, the unit-length norm and the shift are
not); the held experts are charged the tokens a LEVEL router sends them,
tokens x top-k x held / (routed + the skip expert), and the skip expert
computes nothing.
"""

from benchmark import flops, kernel_costs, scope_reduce


def is_cca(sizes):
    return bool(sizes.get("cca_time0"))


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    H, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    D, R = sizes["head_dim_override"], sizes["router_hidden_size"]
    held = sizes["n_routed_experts"]
    # the router's experts and the skip expert
    columns = (sizes.get("router_experts") or held) + 1
    return {
        # q_proj, k_proj, v1_proj + v2_proj, o_proj
        "attn_proj": L * 2 * H * D * (Hq + Hkv + Hkv + Hq),
        # depthwise taps over every channel, one D x D matrix a head a tap
        "cca_conv": L * 2 * (Hq + Hkv) * D
        * (sizes["cca_time0"] + sizes["cca_time1"] * D),
        # scores and values over D, per attended key
        "attention": L * 2 * 2 * Hq * D * flops.mean_attended_keys(seq_len),
        # down-projection, two square layers, the scores
        "router": L * 2 * (H * R + 2 * R * R + R * columns),
        "held_experts": L * (sizes["num_experts_per_tok"] * held / columns)
        * 3 * 2 * H * sizes["moe_intermediate_size"],
        "head": 2 * H * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a compressed-attention training cell against
    their rooflines (``kernel_costs.flash_fwd`` / ``flash_bwd`` at this
    model's heads and head width); None for any other run."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_cca(files[0]):
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced:
        return None
    sizes, _, mix = files
    cost = cost_fn(mix["sequences_per_chip"], mix["seq_len"],
                   sizes["num_attention_heads"],
                   sizes["num_key_value_heads"], sizes["head_dim_override"])
    return kernel_costs.roofline_share(run, reduced, kernels, cost)
