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
