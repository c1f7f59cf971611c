"""Test harness: run everything on a virtual 8-device CPU mesh.

TPU translation of the reference's multi-process ``DistributedTest`` harness
(``tests/unit/common.py:67`` forks N NCCL processes): we instead give one
process 8 virtual XLA CPU devices and exercise real SPMD sharding/collectives
on them. The suite never touches an accelerator: the chip is reached only
through ``chip_smoke.py``.
"""

import os
import sys

# the environment, not only jax.config: the subprocesses tests spawn inherit it
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.utils.jax_compat import (configure_compile_cache,  # noqa: E402
                                            force_cpu_devices)

force_cpu_devices(8)

import jax  # noqa: E402

# Persistent compilation cache: most of the suite's wall-clock is XLA compiles
# of the same tiny-model programs, so even the fastest compile is cached.
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Isolate tests from each other's global mesh state."""
    yield
    from deepspeed_tpu.parallel import topology

    topology.set_mesh(None, None)
    topology._CURRENT_TOPOLOGY = None


@pytest.fixture
def mesh8():
    from deepspeed_tpu.parallel import build_mesh

    return build_mesh(data=8)


def pytest_report_header(config):
    return f"jax {jax.__version__} | devices: {jax.device_count()} ({jax.devices()[0].platform})"


#: Standing tests that assert a state of ``BENCHMARK.json`` which a later
#: cell ended: mellum2's entries LAST, ``train.full_layer_share`` listing
#: mellum2 alone. PR 52 appended a cell; their file lies under the
#: benchmark's ``paths``, which only a ``benchmark`` PR may edit (ROADMAP M1:
#: look-ups by name). Expected failures until then, STRICT: the repair makes
#: them pass, which fails the run until the entry here goes with it.
OVERTAKEN_BY_A_LATER_CELL = {
    "tests/benchmark/test_benchmark_mellum2.py::"
    "test_the_benchmark_gained_one_configuration_one_cell_and_five_metrics",
    "tests/benchmark/test_benchmark_mellum2.py::"
    "test_cost_readers_know_their_own_cells",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid in OVERTAKEN_BY_A_LATER_CELL:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="asserts mellum2's entries are the "
                "benchmark's last: ROADMAP M1"))
