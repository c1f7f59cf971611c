"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call under a
learned selection: the least time it needs on this chip for the SELECTED
pairs (benchmark/sa_costs.py ``flash_sa_bwd``) over the two kernels' time per
call in the trace."""

from benchmark import sa_costs


def read(run):
    return sa_costs.kernel_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), sa_costs.flash_sa_bwd)
