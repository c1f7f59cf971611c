"""Share of the MXU's bf16 peak that ``ds.moe_experts`` reaches (the experts'
grouped products -- every row of the buffer they run on, ``2 M A B`` -- with
the sort, scatter and gather beside them): the matrix operations the program
counts under the scope a step (``matmul_flops_moe_experts -
replayed_flops_moe_experts`` of ``ds.step_cost``:
forward and backward, the replays left out) x the traced window's steps, over
the device seconds under that scope less its replays' x the peak x the
devices (benchmark/step_cost.py)."""

from benchmark import step_cost


def read(run):
    return step_cost.mxu_share(run, "ds.moe_experts")
