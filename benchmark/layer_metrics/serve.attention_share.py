"""Share of device busy time under ``ds.attention`` inside the resident
``ds.mixed_step`` program (the ragged paged kernel or its XLA counterpart)
(benchmark/scope_reduce)."""

from benchmark import scope_reduce


def read(run):
    return scope_reduce.share(run, "serve", "ds.attention")
