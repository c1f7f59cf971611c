"""The fused ``ds_flash_bwd`` where the layer kinds differ in head count (one
call a layer, 64 heads under the window and 48 under none): the calls' least
times on this chip for the KEPT pairs (benchmark/laguna_costs.py
``flash_lg_bwd``: five products to the forward's two) summed, over their
summed time in the trace. None where the backward ran as two kernels."""

from benchmark import laguna_costs


def read(run):
    return laguna_costs.flash_share(run, ("ds_flash_bwd",),
                                    laguna_costs.flash_lg_bwd)
