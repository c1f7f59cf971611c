"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call under
differential attention: the calls' least times on this chip
(benchmark/ssm_costs.py ``flash_da_bwd``) summed over a step's window and full
calls, over the two kernels' summed time in the trace."""

from benchmark import ssm_costs


def read(run):
    return ssm_costs.flash_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), ssm_costs.flash_da_bwd)
