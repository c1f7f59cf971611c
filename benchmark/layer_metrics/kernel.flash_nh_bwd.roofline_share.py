"""The fused ``ds_flash_bwd`` at 32 query heads over 2 key-value heads of 128
(one call an attention layer of the pattern): a call's least time on this
chip for the kept pairs (benchmark/ssd_costs.py ``flash_nh_bwd``: five
products to the forward's two) over its time in the trace. None where the
backward ran as two kernels."""

from benchmark import ssd_costs


def read(run):
    return ssd_costs.flash_share(run, ("ds_flash_bwd",),
                                 ssd_costs.flash_nh_bwd)
