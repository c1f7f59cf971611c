"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call in a
compressed-attention cell: the least time it needs on this chip
(benchmark/kernel_costs.py ``flash_bwd`` at the model's 8 query and 2
key/value heads of ``head_dim_override`` columns) over the two kernels' time
per call in the trace."""

from benchmark import cca_costs, kernel_costs


def read(run):
    return cca_costs.flash_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), kernel_costs.flash_bwd)
