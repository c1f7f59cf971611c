"""Share of the MXU's bf16 peak that ``ds.lm_head_loss`` reaches (the head's
three products, with the softmax, the loss and the head weight's AdamW
update in the same scope): the matrix operations the program counts under
the scope a step (``matmul_flops_lm_head_loss - replayed_flops_lm_head_loss``
of ``ds.step_cost``:
forward and backward, the replays left out) x the traced window's steps, over
the device seconds under that scope less its replays' x the peak x the
devices (benchmark/step_cost.py)."""

from benchmark import step_cost


def read(run):
    return step_cost.mxu_share(run, "ds.lm_head_loss")
