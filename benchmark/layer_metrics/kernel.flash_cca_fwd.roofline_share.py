"""``ds_flash_fwd`` in a compressed-attention cell: the least time one call
needs on this chip (benchmark/kernel_costs.py ``flash_fwd`` at the model's 8
query and 2 key/value heads of ``head_dim_override`` columns) over its time
per call in the trace."""

from benchmark import cca_costs, kernel_costs


def read(run):
    return cca_costs.flash_share(run, ("ds_flash_fwd",),
                                 kernel_costs.flash_fwd)
