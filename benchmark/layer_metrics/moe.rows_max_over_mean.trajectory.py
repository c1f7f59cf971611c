"""``moe.rows_max_over_mean`` in the cells whose rate is
``train_tokens_per_s_per_chip.trajectory`` (a step's work follows the run's
own training trajectory, so the rate has a bound of its own): the same
reader under the name that moves that metric."""

from benchmark import common

read = common.load_file_module("layer_metrics",
                               "moe.rows_max_over_mean").read
