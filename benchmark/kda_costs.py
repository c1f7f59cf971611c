"""What a stack of vector-decay delta-rule (KDA) layers and latent-attention
layers without rotation NEEDS, from its shapes, over a leading dense SwiGLU
and sigmoid-routed expert layers at this chip's share
(``deepspeed_tpu/models/kimi_linear.py``; which published layer is of which
kind is ``benchmark/reference/kimi_linear.py``'s two lists).

As in ``flops.py``, ``mla_costs.py`` and ``gdn_costs.py``: recomputed work
does not count, nor padding, nor element-wise passes (norms, the convolutions'
four taps, unit length, gates, softplus, the router's sigmoid). The RECURRENCE
is charged its own work whatever form or chunk implements it -- a head and
position: the decay on ``S [dk, dv]``, the state read for the key (``S^T
k``), updated (``k d^T``) and read for the query (``S^T q``), ``7 dk dv``
operations -- so what a chunked form spends beyond that (the pair tables a
channel, the solve, the boundary states' products) shows as lost share. The
latent attention's core is charged the causal triangle's kept pairs at its
two widths; the held experts the pairs a LEVEL router sends them, tokens x
top-k x held / routed.
"""

from benchmark import flops, kernel_costs, mla_costs, scope_reduce
from benchmark.reference import kimi_linear as reference


def is_kimi_linear(sizes):
    return bool(sizes.get("kda_num_heads"))


def layer_counts(sizes):
    """(KDA layers, latent-attention layers, dense layers) of the stack as
    run."""
    layers = range(sizes["num_hidden_layers"])
    kda = sum(reference.is_kda(sizes, l) for l in layers)
    dense = sum(reference.is_dense(sizes, l) for l in layers)
    return kda, len(layers) - kda, dense


def _kda_products(sizes):
    """Weights of a KDA mixer's matrices: q, k, v, o; the decay's and the
    gate's low-rank pairs; beta's."""
    h, H, D = sizes["hidden_size"], sizes["kda_num_heads"], \
        sizes["kda_head_dim"]
    # both low-rank paths have rank D
    return 4 * h * H * D + 2 * (h * D + D * H * D) + h * H


def _mla_products(sizes):
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj."""
    h, H = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    r = sizes["kv_lora_rank"]
    return h * H * (dn + dr) + h * (r + dr) + r * H * (dn + dv) + H * dv * h


def layer_parameters(sizes, held):
    """{part: parameters of ONE layer's part}: the two mixers (a block's two
    norms with each), the dense SwiGLU, the sparse layer with ``held`` of the
    router's experts."""
    h, H, D = sizes["hidden_size"], sizes["kda_num_heads"], \
        sizes["kda_head_dim"]
    routed = sizes.get("router_experts") or sizes["n_routed_experts"]
    expert = 3 * h * sizes["moe_intermediate_size"]
    return {
        # three sets of taps, dt_bias, A_log, the gated norm's scale
        "kda": _kda_products(sizes) + 3 * sizes["kda_conv_kernel"] * H * D
        + H * D + H + D + 2 * h,
        # the latent's norm
        "mla": _mla_products(sizes) + sizes["kv_lora_rank"] + 2 * h,
        "dense": 3 * h * sizes["intermediate_size"],
        # the router and its selection bias
        "sparse": h * routed + routed
        + (held + sizes["n_shared_experts"]) * expert,
    }


def parameters(sizes, active=False):
    """Parameters of the stack as ``sizes`` states it (both tables and the
    final norm among them); ``active``: what one token uses, its top-k of
    the experts and no input table."""
    held = sizes["num_experts_per_tok"] if active \
        else sizes["n_routed_experts"]
    per = layer_parameters(sizes, held)
    kda, mla, dense = layer_counts(sizes)
    h = sizes["hidden_size"]
    return kda * per["kda"] + mla * per["mla"] + dense * per["dense"] \
        + (kda + mla - dense) * per["sparse"] \
        + (1 if active else 2) * sizes["vocab_size"] * h + h


def rule_per_token(sizes):
    """Operations of the recurrence for one token of one layer, all heads:
    ``7 dk dv`` a head."""
    return sizes["kda_num_heads"] * 7 * sizes["kda_head_dim"] ** 2


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    h, H = sizes["hidden_size"], sizes["num_attention_heads"]
    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    kda, mla, dense = layer_counts(sizes)
    sparse = kda + mla - dense
    held = sizes["n_routed_experts"]
    routed = sizes.get("router_experts") or held
    expert = 3 * 2 * h * sizes["moe_intermediate_size"]
    return {
        "kda_proj": kda * 2 * _kda_products(sizes),
        "kda_rule": kda * rule_per_token(sizes),
        "attn_proj": mla * 2 * _mla_products(sizes),
        "attention": mla * 2 * H * (dn + dr + dv)
        * flops.mean_attended_keys(seq_len),
        "dense_mlp": dense * 3 * 2 * h * sizes["intermediate_size"],
        "router": sparse * 2 * h * routed,
        "shared_experts": sparse * sizes["n_shared_experts"] * expert,
        "held_experts": sparse * (sizes["num_experts_per_tok"] * held
                                  / routed) * expert,
        "head": 2 * h * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def _flash(cost_fn, sizes, batch, seq_len):
    return cost_fn(batch, seq_len, sizes["num_attention_heads"],
                   sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
                   sizes["v_head_dim"])


def flash_kl_fwd(sizes, batch, seq_len):
    """One forward call of a latent-attention layer: 32 heads of 192 / 128,
    the causal triangle's kept pairs."""
    return _flash(mla_costs.flash_mla_fwd, sizes, batch, seq_len)


def flash_kl_bwd(sizes, batch, seq_len):
    """One backward call: five products to the forward's two."""
    return _flash(mla_costs.flash_mla_bwd, sizes, batch, seq_len)


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of such a stack, else
    None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_kimi_linear(files[0]):
        return None
    return files[0], files[2]


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a step's calls (one a latent-attention layer,
    all alike) against their roofline: a call's least time on this chip over
    its time in the trace. None off the chip, for another program, or where
    the trace has none of the kernels."""
    found = cell_sizes(run)
    reduced = scope_reduce.reduced(run) if found else None
    if not reduced:
        return None
    sizes, mix = found
    return kernel_costs.roofline_share(
        run, reduced, kernels,
        cost_fn(sizes, mix["sequences_per_chip"], mix["seq_len"]))
