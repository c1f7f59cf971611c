"""The fused ``ds_flash_bwd`` (dQ, dK and dV in one kernel) under the
block-diffusion rule: the least time the chip could take for the pairs the
rule KEEPS (benchmark/sdar_costs.py ``flash_bd_bwd``) over the kernel's time
per call in the trace."""

from benchmark import sdar_costs


def read(run):
    return sdar_costs.kernel_share(run, ("ds_flash_bwd",),
                                   sdar_costs.flash_bd_bwd)
