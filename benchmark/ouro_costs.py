"""What a LOOPED decoder needs, from its shapes: one stack of layers run
``total_ut_steps`` times over shared weights, the head and a one-column exit
gate read after every pass (``benchmark/flops.py`` counts one walk down the
stack and one head).

As in ``flops.py``: recomputed work does not count, nor element-wise passes
(the four norms a layer, the rotation, the exit distribution and the mixing
of the passes' losses); the attention core is charged the causal pairs,
``flops.mean_attended_keys``. A pass is charged whole ``R`` times -- the
weights are shared, the work is not: every pass's layers, every pass's head
(training reads each pass's logits for that pass's loss) and every pass's
gate.
"""

from benchmark import flops, kernel_costs


def is_looped(sizes):
    return bool(sizes.get("total_ut_steps"))


def forward_parts(sizes, seq_len):
    """Multiply-adds x 2 of one token's forward pass, by part, all passes."""
    R = sizes["total_ut_steps"]
    H, V = sizes["hidden_size"], sizes["vocab_size"]
    one_walk = flops.forward_flops_per_token(sizes, seq_len) - 2 * H * V
    D = sizes["head_dim"]
    core = sizes["num_hidden_layers"] * 2 * 2 * D \
        * sizes["num_attention_heads"] * flops.mean_attended_keys(seq_len)
    return {
        "layer_products": R * (one_walk - core),
        "attention": R * core,
        "head": R * 2 * H * V,
        "gate": R * 2 * H,
    }


def train_flops_per_token(sizes, seq_len):
    """Forward + backward: the backward pass needs twice the forward's."""
    return 3 * sum(forward_parts(sizes, seq_len).values())


def cell_sizes(run):
    """(sizes, traffic mix) of a training run of a looped decoder, else
    None."""
    if run["observed"]["kind"] != "train":
        return None
    files = kernel_costs.cell_files(run)
    if not files or not is_looped(files[0]):
        return None
    return files[0], files[2]
