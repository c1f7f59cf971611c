"""``ds_flash_fwd`` under a learned selection: the least time one call needs
on this chip for the SELECTED pairs (benchmark/sa_costs.py ``flash_sa_fwd``:
512 operations a selected pair a head, the mask at a bit a pair) over its
time per call in the trace. The kernel computes every causal tile under the
mask, so this reads what of its time the selection needed."""

from benchmark import sa_costs


def read(run):
    return sa_costs.kernel_share(run, ("ds_flash_fwd",),
                                 sa_costs.flash_sa_fwd)
