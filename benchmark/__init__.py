"""The yardstick: cells, traffic, references and reductions the driver runs
through ``python benchmark/run.py``. Later PRs add files here and edit none."""
