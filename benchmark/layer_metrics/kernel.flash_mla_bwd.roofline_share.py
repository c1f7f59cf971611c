"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call where
queries and keys are wider than values (latent attention): the least time it
needs on this chip (benchmark/mla_costs.py) over the two kernels' time per
call in the trace."""

from benchmark import mla_costs


def read(run):
    return mla_costs.flash_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"),
        mla_costs.flash_mla_bwd)
