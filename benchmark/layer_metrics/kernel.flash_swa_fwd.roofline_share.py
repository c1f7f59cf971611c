"""``ds_flash_fwd`` under a pattern of layer kinds (32 / 4 heads of 128; a
step calls it once a layer, most under the sliding window's tile table and
every ``full_attention_period``-th under the full triangle's): the calls'
least times on this chip (benchmark/swa_costs.py ``flash_swa_fwd``) summed,
over their summed time in the trace."""

from benchmark import swa_costs


def read(run):
    return swa_costs.flash_share(run, ("ds_flash_fwd",),
                                 swa_costs.flash_swa_fwd)
