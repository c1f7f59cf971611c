"""``ds_ssm_scan_fwd`` (the selective scan of one Mamba layer, a chunk at a time
with the state in VMEM): the least time one call needs on this chip
(benchmark/ssm_costs.py ``selective_scan_fwd``: 9 operations a position,
channel and state; ``u``, ``delta``, ``y``, ``B`` and ``C`` moved once) over
its time per call in the trace."""

from benchmark import ssm_costs


def read(run):
    return ssm_costs.scan_share(run, "ds_ssm_scan_fwd",
                                ssm_costs.selective_scan_fwd)
