"""``ds_flash_bwd_dq`` + ``ds_flash_bwd_dkv`` as one backward call under a
pattern of layer kinds: the calls' least times on this chip
(benchmark/swa_costs.py ``flash_swa_bwd``) summed over a step's window and
full calls, over the two kernels' summed time in the trace."""

from benchmark import swa_costs


def read(run):
    return swa_costs.flash_share(
        run, ("ds_flash_bwd_dq", "ds_flash_bwd_dkv"), swa_costs.flash_swa_bwd)
