"""``ds_flash_fwd`` under the block-diffusion rule (32 / 4 heads of 128 over
``[x_t ; x_0]``, 2 x 8,192 rows, 288 of 1,024 tiles): the least time the
chip could take for the pairs the rule KEEPS (benchmark/sdar_costs.py
``flash_bd_fwd``) over the kernel's time per call in the trace. The 16
diagonal tiles of the noised quadrant run whole for 4 live columns in 512:
that is lost share, by the count's design."""

from benchmark import sdar_costs


def read(run):
    return sdar_costs.kernel_share(run, ("ds_flash_fwd",),
                                   sdar_costs.flash_bd_fwd)
