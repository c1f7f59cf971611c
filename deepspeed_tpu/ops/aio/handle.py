"""Async file IO handle over the native thread-pool module.

Counterpart of ``deepspeed/ops/aio/__init__.py`` (``aio_handle`` with
``block_size, queue_depth, single_submit, overlap_events, num_threads`` —
``csrc/aio/py_lib/deepspeed_py_aio_handle.h:12``) backing NVMe/SSD swap of
params and optimizer state (ZeRO-Infinity role). Buffers are numpy arrays;
async ops return immediately and ``wait()`` fences them.
"""

import ctypes
from typing import Optional

import numpy as np


_BACKENDS = {"auto": 0, "pool": 1, "uring": 2}


class AsyncIOHandle:
    def __init__(self, block_size: int = 1 << 20, queue_depth: int = 32,
                 single_submit: bool = False, overlap_events: bool = False,
                 num_threads: int = 1, use_o_direct: bool = False,
                 backend: str = "auto"):
        from op_builder import AsyncIOBuilder

        self._lib = AsyncIOBuilder().load()
        self._lib.ds_aio_handle_create3.restype = ctypes.c_void_p
        self._lib.ds_aio_pread.restype = ctypes.c_int64
        self._lib.ds_aio_pwrite.restype = ctypes.c_int64
        self._lib.ds_aio_wait.restype = ctypes.c_int64
        self._lib.ds_aio_backend_name.restype = ctypes.c_char_p
        # backend "uring" is the libaio-io_context equivalent (queue_depth
        # kernel-async ops in flight off one driver thread); "pool" is the
        # pread/pwrite worker pool; "auto" currently resolves to pool (no
        # ``tools/aio_bench.py`` sweep on real NVMe has shown uring ahead
        # — flip it when one does). O_DIRECT (reference: libaio
        # O_DIRECT is the default path): aligned chunks bypass the page
        # cache through aligned bounce buffers; filesystems that refuse
        # O_DIRECT degrade to buffered IO.
        if backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {sorted(_BACKENDS)}, "
                             f"got {backend!r}")
        self._h = self._lib.ds_aio_handle_create3(
            ctypes.c_int64(block_size), ctypes.c_int(queue_depth),
            ctypes.c_int(int(single_submit)), ctypes.c_int(int(overlap_events)),
            ctypes.c_int(num_threads), ctypes.c_int(int(use_o_direct)),
            ctypes.c_int(_BACKENDS[backend]))
        if not self._h:
            raise OSError(f"aio backend {backend!r} unavailable on this kernel")
        self.backend = self._lib.ds_aio_backend_name(
            ctypes.c_void_p(self._h)).decode()
        self.block_size = block_size
        self.queue_depth = queue_depth
        self.num_threads = num_threads
        self.use_o_direct = use_o_direct

    def _buf(self, array: np.ndarray):
        assert array.flags["C_CONTIGUOUS"], "aio buffers must be contiguous"
        return array.ctypes.data_as(ctypes.c_void_p)

    def pwrite(self, array: np.ndarray, path: str, offset: int = 0,
               async_op: bool = False) -> int:
        rc = self._lib.ds_aio_pwrite(
            ctypes.c_void_p(self._h), path.encode(), self._buf(array),
            ctypes.c_int64(array.nbytes), ctypes.c_int64(offset),
            ctypes.c_int(int(async_op)))
        if rc < 0:
            raise OSError(f"aio write failed: {path}")
        return int(rc)

    def pread(self, array: np.ndarray, path: str, offset: int = 0,
              async_op: bool = False) -> int:
        rc = self._lib.ds_aio_pread(
            ctypes.c_void_p(self._h), path.encode(), self._buf(array),
            ctypes.c_int64(array.nbytes), ctypes.c_int64(offset),
            ctypes.c_int(int(async_op)))
        if rc < 0:
            raise OSError(f"aio read failed: {path}")
        return int(rc)

    # reference verb aliases
    sync_pwrite = pwrite
    sync_pread = pread

    def async_pwrite(self, array, path, offset: int = 0):
        return self.pwrite(array, path, offset, async_op=True)

    def async_pread(self, array, path, offset: int = 0):
        return self.pread(array, path, offset, async_op=True)

    def wait(self) -> int:
        rc = int(self._lib.ds_aio_wait(ctypes.c_void_p(self._h)))
        if rc < 0:
            raise OSError("aio op failed during wait")
        return rc

    def close(self):
        if self._h:
            self._lib.ds_aio_handle_destroy(ctypes.c_void_p(self._h))
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def aio_handle(block_size: int = 1 << 20, queue_depth: int = 32,
               single_submit: bool = False, overlap_events: bool = False,
               num_threads: int = 1, use_o_direct: bool = False,
               backend: str = "auto") -> AsyncIOHandle:
    """Reference factory name (``deepspeed.ops.aio.aio_handle``)."""
    return AsyncIOHandle(block_size, queue_depth, single_submit, overlap_events,
                         num_threads, use_o_direct, backend)


def uring_available() -> bool:
    from op_builder import AsyncIOBuilder

    lib = AsyncIOBuilder().load()
    return bool(lib.ds_aio_uring_available())
