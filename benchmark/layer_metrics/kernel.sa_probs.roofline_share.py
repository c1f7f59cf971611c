"""``ds_sa_probs`` (the head-mean attention probabilities the indexer learns
from, recomputed from the saved log-sum-exp): the least time one call needs
on this chip for the SELECTED pairs (benchmark/sa_costs.py ``sa_probs``) over
its time per call in the trace."""

from benchmark import sa_costs


def read(run):
    return sa_costs.kernel_share(run, ("ds_sa_probs",), sa_costs.sa_probs)
