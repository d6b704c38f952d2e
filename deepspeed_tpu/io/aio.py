"""ctypes bindings + JIT builder for the native async I/O engine.

Python face of ``csrc/aio.cpp`` — the DeepNVMe equivalent (reference
``ops/op_builder/async_io.py AsyncIOBuilder`` + ``csrc/aio/py_lib``
``deepspeed_py_io_handle``).  The reference JIT-compiles CUDA/C++ ops at
first use through its op_builder; the same pattern here: ``g++`` builds
the shared library on first import (cached next to the source, rebuilt
when the source is newer), and ``ctypes`` provides the bindings — no
pybind11 in this image.

API mirrors the reference handle surface::

    h = aio_handle(block_size=1<<20, queue_depth=..., thread_count=8)
    h.sync_pwrite(array, path)          # parallel chunked pwrite
    h.sync_pread(array, path)
    op = h.async_pwrite(array, path)    # returns op id immediately
    h.wait(op)                          # 0 on success

Buffers are numpy arrays (or anything exposing the buffer protocol);
``pinned`` host memory is not a TPU-visible concept — host RAM is the
staging tier, jax handles H2D.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "csrc", "aio.cpp")
_LIB = os.path.join(os.path.dirname(__file__), "csrc", "libdstpu_aio.so")
_build_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


class AsyncIOBuilder:
    """Reference ``AsyncIOBuilder`` shape: ``.load()`` returns the bound
    module (building it first if needed), ``.is_compatible()`` reports
    whether a toolchain exists."""

    NAME = "async_io"

    def is_compatible(self) -> bool:
        from shutil import which

        return which("g++") is not None

    def load(self):
        _ensure_built()
        import deepspeed_tpu.io.aio as mod

        return mod


def _ensure_built() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        stale = (not os.path.exists(_LIB) or
                 os.path.getmtime(_LIB) < os.path.getmtime(_SRC))
        if stale:
            # built from aio.cpp alone, into a name of this process's own:
            # several processes (xdist workers) may find it stale at once,
            # and each must publish a whole file
            tmp = f"{_LIB}.{os.getpid()}.tmp"
            cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread",
                   "-std=c++17", _SRC, "-o", tmp]
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, _LIB)
        lib = ctypes.CDLL(_LIB)
        lib.aio_create.restype = ctypes.c_void_p
        lib.aio_create.argtypes = [ctypes.c_int, ctypes.c_int64,
                                   ctypes.c_int]
        lib.aio_create2.restype = ctypes.c_void_p
        lib.aio_create2.argtypes = [ctypes.c_int, ctypes.c_int64,
                                    ctypes.c_int, ctypes.c_int,
                                    ctypes.c_int]
        lib.aio_backend.restype = ctypes.c_int
        lib.aio_backend.argtypes = [ctypes.c_void_p]
        lib.aio_destroy.argtypes = [ctypes.c_void_p]
        for fn in (lib.aio_submit_read, lib.aio_submit_write):
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        for fn in (lib.aio_pread, lib.aio_pwrite):
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
        lib.aio_wait.restype = ctypes.c_int
        lib.aio_wait.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.aio_poll.restype = ctypes.c_int
        lib.aio_poll.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        for fn in (lib.aio_bytes_read, lib.aio_bytes_written):
            fn.restype = ctypes.c_int64
            fn.argtypes = [ctypes.c_void_p]
        lib.aio_file_size.restype = ctypes.c_int64
        lib.aio_file_size.argtypes = [ctypes.c_char_p]
        lib.aio_prealloc.restype = ctypes.c_int
        lib.aio_prealloc.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        _lib = lib
        return lib


_DIRECT_ALIGN = 4096


def aligned_empty(n: int, dtype=np.uint8) -> np.ndarray:
    """Uninitialized 1-D array of ``n`` elements whose data pointer is
    kDirectAlign(4096)-aligned — the O_DIRECT eligibility requirement
    the native engine checks per chunk.  numpy's allocator only
    guarantees 16-byte alignment, so buffers meant for O_DIRECT
    streaming (swap bucket buffers, bench buffers) come from here."""
    dt = np.dtype(dtype)
    raw = np.empty(n * dt.itemsize + _DIRECT_ALIGN, np.uint8)
    off = (-raw.ctypes.data) % _DIRECT_ALIGN
    return raw[off:off + n * dt.itemsize].view(dt)


def _buf_ptr(arr: np.ndarray):
    assert arr.flags["C_CONTIGUOUS"], "aio buffers must be contiguous"
    return arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes


class aio_handle:
    """Reference ``aio_handle`` surface (``deepspeed_py_io_handle.cpp``):
    thread-pooled, chunk-parallel file I/O with sync and async calls."""

    def __init__(self, block_size: int = 1 << 20, queue_depth: int = 128,
                 single_submit: bool = False, overlap_events: bool = True,
                 thread_count: int = 8, use_odirect: bool = False,
                 backend: str = "auto"):
        """``queue_depth``: io_uring ops in flight per worker (the
        reference's libaio queue_depth — device parallelism comes from
        ring depth, not threads).  ``backend``: "auto" | "uring" |
        "threadpool"."""
        del single_submit, overlap_events   # libaio-era knobs
        self._lib = _ensure_built()
        bk = {"auto": -1, "uring": 1, "threadpool": 0}[backend]
        self._h = self._lib.aio_create2(int(thread_count), int(block_size),
                                        int(bool(use_odirect)), bk,
                                        int(queue_depth))
        self.block_size = block_size
        self.thread_count = thread_count
        self.queue_depth = queue_depth
        self.backend = ("uring" if self._lib.aio_backend(self._h) == 1
                        else "threadpool")
        # keep submitted buffers alive until wait() (the C side reads them)
        self._live: dict = {}

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.aio_destroy(self._h)
                self._h = None
        except Exception:
            pass

    # -- sync ----------------------------------------------------------

    def sync_pread(self, buffer: np.ndarray, path: str,
                   offset: int = 0) -> int:
        ptr, n = _buf_ptr(buffer)
        st = self._lib.aio_pread(self._h, path.encode(), ptr, n, offset)
        if st != 0:
            raise OSError(-st, os.strerror(-st), path)
        return n

    def sync_pwrite(self, buffer: np.ndarray, path: str,
                    offset: int = 0) -> int:
        ptr, n = _buf_ptr(buffer)
        _pretruncate(path, offset + n, exact=offset == 0)
        st = self._lib.aio_pwrite(self._h, path.encode(), ptr, n, offset)
        if st != 0:
            raise OSError(-st, os.strerror(-st), path)
        return n

    # -- async ---------------------------------------------------------

    def async_pread(self, buffer: np.ndarray, path: str,
                    offset: int = 0) -> int:
        ptr, n = _buf_ptr(buffer)
        op = self._lib.aio_submit_read(self._h, path.encode(), ptr, n,
                                       offset)
        self._live[op] = buffer
        return op

    def async_pwrite(self, buffer: np.ndarray, path: str,
                     offset: int = 0, _truncate: bool = True) -> int:
        ptr, n = _buf_ptr(buffer)
        if _truncate:
            # extend-only: concurrent multi-part writes to one file must
            # size it up-front (see checkpoint writer) — a shrink here
            # could cut an in-flight higher-offset chunk
            _pretruncate(path, offset + n, exact=False)
        op = self._lib.aio_submit_write(self._h, path.encode(), ptr, n,
                                        offset)
        self._live[op] = buffer
        return op

    def poll(self, op: int) -> Optional[int]:
        """None while pending, else final status (0 = ok)."""
        st = self._lib.aio_poll(self._h, op)
        return None if st == -1 else st

    def wait(self, op: int) -> int:
        st = self._lib.aio_wait(self._h, op)
        self._live.pop(op, None)
        if st != 0:
            raise OSError(-st, os.strerror(-st))
        return st

    # -- stats ----------------------------------------------------------

    def bytes_read(self) -> int:
        return self._lib.aio_bytes_read(self._h)

    def bytes_written(self) -> int:
        return self._lib.aio_bytes_written(self._h)


def _pretruncate(path: str, size: int, exact: bool = True) -> None:
    """Size the file before parallel chunk writes (chunk opens use
    O_CREAT without O_TRUNC — truncating per-chunk would race).
    ``exact=False`` only ever EXTENDS, safe around in-flight writes.
    Extensions go through the native ``aio_prealloc`` (fallocate), so
    the extents exist before the parallel writers hit them — extent
    allocation mid-stream is one of the two things that held the write
    path below the read path (the other is the page cache; O_DIRECT)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "ab"):
        pass
    cur = os.path.getsize(path)
    if cur < size:
        st = _ensure_built().aio_prealloc(path.encode(), size)
        if st != 0:
            raise OSError(-st, os.strerror(-st), path)
    elif cur > size and exact:
        os.truncate(path, size)


def file_size(path: str) -> int:
    lib = _ensure_built()
    n = lib.aio_file_size(path.encode())
    if n < 0:
        raise OSError(-n, os.strerror(-n), path)
    return n
