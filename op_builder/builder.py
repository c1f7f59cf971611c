"""Native-op build system.

Counterpart of the reference's ``op_builder/builder.py`` (``OpBuilder`` ABC
:105 with ``sources/is_compatible/load/jit_load``, registry ``ALL_OPS``
``op_builder/__init__.py:32``). Deliberately much smaller: TPU compute
kernels are Pallas (JIT by construction), so native builds exist only for
host-side ops — the SIMD CPU optimizers and the async-IO module. No
nvcc/hipify machinery; one g++ invocation per op, cached by source mtime
per building machine. Loading returns a ``ctypes.CDLL`` (no pybind11 in this
environment).
"""

import ctypes
import hashlib
import os
import platform
import subprocess
import sys
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# csrc ships INSIDE the deepspeed_tpu package (setuptools package-data is
# package-relative; the old repo-root location could never reach a wheel,
# breaking the rebuild-on-foreign-glibc path for pip installs). The repo-root
# fallback keeps old checkouts working.
_csrc_candidates = [os.path.join(REPO_ROOT, "deepspeed_tpu", "csrc"),
                    os.path.join(REPO_ROOT, "csrc")]
CSRC = next((p for p in _csrc_candidates if os.path.isdir(p)),
            _csrc_candidates[0])


def _machine_tag() -> str:
    """Identity of the CPU the libs are built for: ``-march=native`` code
    runs only where the building machine's instruction set does, and a
    checkout's disk can travel to another machine (the chip tool copies
    it), so every machine builds into — and loads from — its own
    sub-directory."""
    features = ""
    try:
        with open("/proc/cpuinfo") as f:
            features = next((line for line in f
                             if line.startswith(("flags", "Features"))), "")
    except OSError:
        pass
    digest = hashlib.sha1(features.encode()).hexdigest()[:12]
    return f"{platform.machine()}-{digest}"


BUILD_DIR = os.path.join(CSRC, "build", _machine_tag())


class OpBuilder:
    NAME = "op"

    def sources(self) -> List[str]:
        raise NotImplementedError

    def headers(self) -> List[str]:
        """Headers the sources include — part of the staleness check (a
        stale shared header otherwise dlopens an ABI-mismatched lib)."""
        return []

    def lib_name(self) -> str:
        return f"libds_{self.NAME}.so"

    def cxx_args(self) -> List[str]:
        return ["-O3", "-march=native", "-std=c++17", "-fPIC", "-shared",
                "-pthread", "-Wall"]

    def compiler(self) -> str:
        return os.environ.get("CXX", "g++")

    def is_compatible(self, verbose: bool = False) -> bool:
        from shutil import which

        if which(self.compiler()) is None:
            if verbose:
                print(f"[{self.NAME}] no C++ compiler found")
            return False
        return True

    def absolute_sources(self) -> List[str]:
        return [os.path.join(CSRC, s) for s in self.sources()]

    def lib_path(self) -> str:
        return os.path.join(BUILD_DIR, self.lib_name())

    def _stale(self) -> bool:
        lib = self.lib_path()
        if not os.path.exists(lib):
            return True
        lib_mtime = os.path.getmtime(lib)
        deps = self.absolute_sources() + [os.path.join(CSRC, h)
                                          for h in self.headers()]
        return any(os.path.getmtime(d) > lib_mtime for d in deps)

    def jit_load(self, verbose: bool = True) -> ctypes.CDLL:
        """Compile (if stale) and dlopen. Reference: ``jit_load`` :472."""
        if not self.is_compatible(verbose=verbose):
            raise RuntimeError(f"op {self.NAME} is not compatible on this system")
        if self._stale():
            os.makedirs(BUILD_DIR, exist_ok=True)
            cmd = [self.compiler(), *self.cxx_args(), "-o", self.lib_path(),
                   *self.absolute_sources()]
            if verbose:
                print(f"[{self.NAME}] building: {' '.join(cmd)}", file=sys.stderr)
            subprocess.run(cmd, check=True, capture_output=not verbose)
        return ctypes.CDLL(self.lib_path())

    #: cache of loaded libs per builder class
    _loaded: Dict[str, ctypes.CDLL] = {}

    def load(self, verbose: bool = False) -> ctypes.CDLL:
        lib = OpBuilder._loaded.get(self.NAME)
        if lib is None:
            lib = self.jit_load(verbose=verbose)
            OpBuilder._loaded[self.NAME] = lib
        return lib


class CPUAdamBuilder(OpBuilder):
    """SIMD Adam for host-offloaded optimizer partitions (reference
    ``CPUAdamBuilder``; kernel ``csrc/adam/cpu_adam.cpp``)."""

    NAME = "cpu_adam"

    def sources(self):
        return ["cpu_optimizer/cpu_adam.cpp"]


class CPUAdagradBuilder(OpBuilder):
    NAME = "cpu_adagrad"

    def sources(self):
        return ["cpu_optimizer/cpu_adagrad.cpp"]


class AsyncIOBuilder(OpBuilder):
    """Async file IO (reference ``AsyncIOBuilder``; ``csrc/aio/``): io_uring
    ring backend when the kernel allows it, thread-pool pread/pwrite
    otherwise."""

    NAME = "aio"

    def sources(self):
        return ["aio/ds_aio.cpp", "aio/ds_aio_uring.cpp"]

    def headers(self):
        return ["aio/ds_aio_backend.h"]


ALL_OPS: Dict[str, OpBuilder] = {
    b.NAME: b for b in (CPUAdamBuilder(), CPUAdagradBuilder(), AsyncIOBuilder())
}


def get_default_compute_capabilities() -> str:
    """Reference API parity; meaningless for TPU — Pallas targets the chip
    the runtime sees."""
    return "tpu"
