"""``ds_flash_bwd`` under gated full attention (16 / 2 heads of 256): the one
backward kernel's least time on this chip (benchmark/gdn_costs.py
``flash_ga_bwd``: five products to the forward's two) over its time in the
trace. None where the backward ran as two kernels (a dQ too long for VMEM)."""

from benchmark import gdn_costs


def read(run):
    return gdn_costs.flash_share(run, ("ds_flash_bwd",),
                                 gdn_costs.flash_ga_bwd)
