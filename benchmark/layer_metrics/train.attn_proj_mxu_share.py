"""Share of the MXU's bf16 peak that ``ds.attn_proj`` reaches (the
projections around the attention core, with the norms, RoPE and layout work
that stand beside them): the matrix operations the program counts under the
scope a step (``matmul_flops_attn_proj - replayed_flops_attn_proj`` of
``ds.step_cost``:
forward and backward, the replays left out) x the traced window's steps, over
the device seconds under that scope less its replays' x the peak x the
devices (benchmark/step_cost.py)."""

from benchmark import step_cost


def read(run):
    return step_cost.mxu_share(run, "ds.attn_proj")
