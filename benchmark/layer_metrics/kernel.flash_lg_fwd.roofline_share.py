"""``ds_flash_fwd`` where the layer kinds differ in head count (a step calls
it once a layer: 64 query heads under the 512 window's tile table, 48 under
the full triangle's, 8 key-value heads of 128 in both): the calls' least
times on this chip for the KEPT pairs (benchmark/laguna_costs.py
``flash_lg_fwd``) summed, over their summed time in the trace -- so what a
tile half masked by the window computes beyond its kept pairs shows as lost
share."""

from benchmark import laguna_costs


def read(run):
    return laguna_costs.flash_share(run, ("ds_flash_fwd",),
                                    laguna_costs.flash_lg_fwd)
