"""``ds_flash_fwd``: the least time one call needs on this chip (operations
and bytes from its shapes, benchmark/kernel_costs.py) over the time a call
takes in the trace; recomputed calls have the same shapes and count as calls."""

from benchmark import kernel_costs


def read(run):
    return kernel_costs.flash_share(run, ("ds_flash_fwd",),
                                    kernel_costs.flash_fwd)
