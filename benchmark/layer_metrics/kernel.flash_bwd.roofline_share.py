"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call: the least
time it needs on this chip (benchmark/kernel_costs.py) over the two kernels'
time per call in the trace."""

from benchmark import kernel_costs


def read(run):
    return kernel_costs.flash_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), kernel_costs.flash_bwd)
