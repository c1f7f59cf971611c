"""``ds_flash_fwd`` under gated full attention (16 / 2 heads of 256; a step
calls it once a full layer): the call's least time on this chip
(benchmark/gdn_costs.py ``flash_ga_fwd``) over its time in the trace."""

from benchmark import gdn_costs


def read(run):
    return gdn_costs.flash_share(run, ("ds_flash_fwd",),
                                 gdn_costs.flash_ga_fwd)
