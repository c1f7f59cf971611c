"""The fused ``ds_flash_bwd`` at 32 heads of 192-wide keys and 128-wide
values (one call a latent-attention layer): a call's least time on this chip
for the kept pairs (benchmark/kda_costs.py ``flash_kl_bwd``: five products to
the forward's two) over its time in the trace. None where the backward ran
as two kernels."""

from benchmark import kda_costs


def read(run):
    return kda_costs.flash_share(run, ("ds_flash_bwd",),
                                 kda_costs.flash_kl_bwd)
