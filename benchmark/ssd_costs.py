"""What a stack of single-branch layers NEEDS, from its shapes, where each
layer is a scalar-decay state-space mixer (``M``), a GQA attention layer
without rotation (``*``) or an ungated sparse-expert layer beside a shared
expert (``E``) under a pattern string (``deepspeed_tpu/models/nemotron_h.py``;
the string is the published one, written out here too: a configuration's
sizes are numbers).

As in ``flops.py`` and ``laguna_costs.py``: recomputed work does not count,
nor padding, nor element-wise passes (norms, the gates, the router's sigmoid,
softplus). The RECURRENCE is charged its own work whatever form or chunk
implements it -- a position and head: the decay on ``S [P, N]``, the update
``+ (dt x) B^T`` and the read ``S C``, ``5 P N`` operations -- so what a
chunked matrix form spends beyond that (the tables inside a chunk, the
boundary states' product) shows as lost share. The attention's core is
charged the causal triangle's kept pairs; the held experts the pairs a LEVEL
router sends them, tokens x top-k x held / routed, at TWO products an expert.
Heads are ``head_dim_override`` wide (``common.sizes_of`` overwrites
``head_dim``).
"""

from benchmark import flops, kernel_costs, scope_reduce

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
MAMBA, FULL, MOE = "M", "*", "E"


def is_nemotron_h(sizes):
    return bool(sizes.get("mamba_num_heads"))


def kinds(sizes):
    """{kind: layers of it} of the stack as run."""
    first = sizes.get("first_layer") or 0
    stack = PATTERN[first:first + sizes["num_hidden_layers"]]
    return {kind: stack.count(kind) for kind in (MAMBA, FULL, MOE)}


def _widths(sizes):
    H, P = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    gn = sizes["n_groups"] * sizes["ssm_state_size"]
    return H, P, H * P, H * P + 2 * gn


def layer_parameters(sizes, held):
    """{kind: parameters of ONE layer of it, its norm's scale among them}
    with ``held`` of the router's experts."""
    hidden = sizes["hidden_size"]
    H, _, d, conv = _widths(sizes)
    Hq, Hkv, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim_override"])
    routed = sizes.get("router_experts") or sizes["num_local_experts"]
    return {
        # in_proj, out_proj; taps and their bias; A_log, D, dt_bias; the
        # gated norm's scale
        MAMBA: hidden * (d + conv + H) + d * hidden
        + (sizes["conv_kernel"] + 1) * conv + 3 * H + d + hidden,
        FULL: hidden * D * (Hq + 2 * Hkv) + Hq * D * hidden + hidden,
        MOE: hidden * routed + held * 2 * hidden
        * sizes["moe_intermediate_size"] + 2 * hidden
        * sizes["moe_shared_expert_intermediate_size"] + hidden,
    }


def parameters(sizes, active=False):
    """Parameters of the stack as ``sizes`` states it (both tables and the
    final norm among them); ``active``: what one token uses, its top-k of
    the experts and no input table."""
    held = sizes["num_experts_per_tok"] if active \
        else sizes["num_local_experts"]
    per_layer = layer_parameters(sizes, held)
    tables = (1 if active else 2) * sizes["vocab_size"] * sizes["hidden_size"]
    return sum(n * per_layer[kind] for kind, n in kinds(sizes).items()) \
        + tables + sizes["hidden_size"]


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part."""
    hidden = sizes["hidden_size"]
    H, P, d, conv = _widths(sizes)
    Hq, Hkv, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                  sizes["head_dim_override"])
    held = sizes["num_local_experts"]
    routed = sizes.get("router_experts") or held
    n = kinds(sizes)
    return {
        "ssm_in_proj": n[MAMBA] * 2 * hidden * (d + conv + H),
        "ssm_out_proj": n[MAMBA] * 2 * d * hidden,
        "ssm_conv": n[MAMBA] * 2 * sizes["conv_kernel"] * conv,
        "ssm_recurrence": n[MAMBA] * 5 * H * P * sizes["ssm_state_size"],
        "attn_proj": n[FULL] * 2 * hidden * D * (2 * Hq + 2 * Hkv),
        "attention": n[FULL] * 2 * 2 * Hq * D
        * flops.mean_attended_keys(seq_len),
        "router": n[MOE] * 2 * hidden * routed,
        "shared_expert": n[MOE] * 2 * 2 * hidden
        * sizes["moe_shared_expert_intermediate_size"],
        "held_experts": n[MOE] * (sizes["num_experts_per_tok"] * held / routed)
        * 2 * 2 * hidden * sizes["moe_intermediate_size"],
        "head": 2 * hidden * sizes["vocab_size"],
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def flash_nh_fwd(sizes, batch, seq_len):
    """One forward call of an attention layer: the causal triangle's kept
    pairs at its grouping."""
    return kernel_costs.flash_fwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"])


def flash_nh_bwd(sizes, batch, seq_len):
    return kernel_costs.flash_bwd(
        batch, seq_len, sizes["num_attention_heads"],
        sizes["num_key_value_heads"], sizes["head_dim_override"])


def cell_sizes(run):
    """(sizes, traffic mix) of a traced training run of such a stack, else
    None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_nemotron_h(files[0]):
        return None
    return files[0], files[2]


def flash_share(run, kernels, cost_fn):
    """The flash kernels of a step's calls (one an attention layer, all
    alike) against their roofline: a call's least time on this chip over
    its time in the trace. None off the chip, for another program, or where
    the trace has none of the kernels."""
    found = cell_sizes(run)
    if not found:
        return None
    reduced = scope_reduce.reduced(run)
    if not reduced:
        return None
    sizes, mix = found
    return kernel_costs.roofline_share(
        run, reduced, kernels,
        cost_fn(sizes, mix["sequences_per_chip"], mix["seq_len"]))
