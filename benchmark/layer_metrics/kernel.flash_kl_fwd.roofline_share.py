"""``ds_flash_fwd`` at 32 heads of 192-wide keys and 128-wide values, no
rotation ahead of it (a step calls it once a latent-attention layer): a
call's least time on this chip for the causal triangle's kept pairs
(benchmark/kda_costs.py ``flash_kl_fwd``) over its time in the trace."""

from benchmark import kda_costs


def read(run):
    return kda_costs.flash_share(run, ("ds_flash_fwd",),
                                 kda_costs.flash_kl_fwd)
