"""``ds_flash_fwd`` at 32 query heads over 2 key-value heads of 128, no
rotation ahead of it (a step calls it once an attention layer of the
pattern): a call's least time on this chip for the causal triangle's kept
pairs (benchmark/ssd_costs.py ``flash_nh_fwd``) over its time in the trace."""

from benchmark import ssd_costs


def read(run):
    return ssd_costs.flash_share(run, ("ds_flash_fwd",),
                                 ssd_costs.flash_nh_fwd)
