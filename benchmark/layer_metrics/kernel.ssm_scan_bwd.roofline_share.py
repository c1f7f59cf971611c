"""``ds_ssm_scan_bwd`` (the selective scan's backward: a chunk's states
recomputed in VMEM, then its steps in reverse): the least time one call needs
on this chip (benchmark/ssm_costs.py ``selective_scan_bwd``: three times the
forward's operations; the inputs, ``dy`` and the gradients moved once) over
its time per call in the trace."""

from benchmark import ssm_costs


def read(run):
    return ssm_costs.scan_share(run, "ds_ssm_scan_bwd",
                                ssm_costs.selective_scan_bwd)
