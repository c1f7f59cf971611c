"""``ds_flash_fwd`` where queries and keys are wider than values (latent
attention): the least time one call needs on this chip (operations and bytes
from its shapes, benchmark/mla_costs.py) over the time a call takes in the
trace; recomputed calls have the same shapes and count as calls."""

from benchmark import mla_costs


def read(run):
    return mla_costs.flash_share(run, ("ds_flash_fwd",),
                                 mla_costs.flash_mla_fwd)
