"""``ds_flash_fwd`` under differential attention (40 query heads of 64-wide
keys and 128-wide values; a step calls it once a window layer and once a full
or cross layer): the calls' least times on this chip (benchmark/ssm_costs.py
``flash_da_fwd``) summed, over their summed time in the trace."""

from benchmark import ssm_costs


def read(run):
    return ssm_costs.flash_share(run, ("ds_flash_fwd",), ssm_costs.flash_da_fwd)
