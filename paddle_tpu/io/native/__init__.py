"""ctypes bindings for the native data-loader core (queue.cc).

Compiled on first use with g++ through `utils.cpp_extension` — the
library is named by the hash of its source, so a stale build is never
loaded; every entry point degrades to pure-Python when no toolchain is
present, so the data loader never hard-depends on the native path."""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "queue.cc")
_lib = None
_lock = threading.Lock()


NATIVE_COLLATE_MIN_BYTES = 1 << 16  # below this np.stack wins


def _build() -> Optional[str]:
    from ...utils.cpp_extension import _compile
    try:
        return _compile("ptio", [_SRC], ["-pthread"], None, False)
    except (RuntimeError, OSError, subprocess.CalledProcessError):
        return None     # no toolchain: the pure-Python paths serve


def load():
    """The shared library, or None when unavailable."""
    global _lib
    if _lib is not None:
        return _lib if _lib is not False else None
    with _lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        path = _build()
        if path is None:
            _lib = False
            return None
        lib = ctypes.CDLL(path)
        lib.ptq_create.restype = ctypes.c_void_p
        lib.ptq_create.argtypes = [ctypes.c_uint64]
        lib.ptq_destroy.argtypes = [ctypes.c_void_p]
        lib.ptq_push.restype = ctypes.c_int
        lib.ptq_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_int64]
        lib.ptq_pop.restype = ctypes.c_int
        lib.ptq_pop.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_void_p),
                                ctypes.c_int64]
        lib.ptq_size.restype = ctypes.c_uint64
        lib.ptq_size.argtypes = [ctypes.c_void_p]
        lib.ptq_close.argtypes = [ctypes.c_void_p]
        lib.ptq_collate.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
            ctypes.c_int]
        _lib = lib
        return lib


def available() -> bool:
    return load() is not None


class NativeQueue:
    """Blocking bounded queue over the C++ core. Items are arbitrary
    Python objects (a registry keeps them alive; the queue transports
    opaque handles). Push/pop release the GIL while blocked — Python
    producer threads and the consumer genuinely overlap."""

    def __init__(self, capacity: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native io library unavailable")
        self._lib = lib
        self._q = lib.ptq_create(capacity)
        self._items = {}
        self._next = 1
        self._reg_lock = threading.Lock()

    def push(self, obj, timeout_ms: int = -1) -> bool:
        with self._reg_lock:
            handle = self._next
            self._next += 1
            self._items[handle] = obj
        rc = self._lib.ptq_push(self._q, ctypes.c_void_p(handle),
                                timeout_ms)
        if rc != 1:
            with self._reg_lock:
                self._items.pop(handle, None)
        if rc == -1:
            raise RuntimeError("queue closed")
        return rc == 1

    def pop(self, timeout_ms: int = -1):
        out = ctypes.c_void_p()
        rc = self._lib.ptq_pop(self._q, ctypes.byref(out), timeout_ms)
        if rc == 0:
            raise TimeoutError("queue pop timed out")
        if rc == -1:
            raise StopIteration
        with self._reg_lock:
            return self._items.pop(out.value)

    def qsize(self) -> int:
        return int(self._lib.ptq_size(self._q))

    def close(self):
        self._lib.ptq_close(self._q)

    def __del__(self):
        try:
            self._lib.ptq_close(self._q)
            self._lib.ptq_destroy(self._q)
        except Exception:
            pass


def collate_stack(arrays, threads: int = 4) -> np.ndarray:
    """np.stack via the parallel native memcpy (falls back to
    np.stack). Sample arrays must share shape and dtype."""
    lib = load()
    first = np.ascontiguousarray(arrays[0])
    if (lib is None or first.nbytes < NATIVE_COLLATE_MIN_BYTES
            or first.dtype.hasobject):
        # object dtypes hold PyObject* — a raw memcpy would duplicate
        # pointers without incref and segfault after GC
        return np.stack(arrays)
    n = len(arrays)
    srcs = [np.ascontiguousarray(a) for a in arrays]
    for a in srcs[1:]:
        if a.shape != first.shape or a.dtype != first.dtype:
            return np.stack(arrays)
    out = np.empty((n,) + first.shape, first.dtype)
    src_ptrs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in srcs])
    sizes = (ctypes.c_uint64 * n)(*[a.nbytes for a in srcs])
    lib.ptq_collate(ctypes.c_void_p(out.ctypes.data), src_ptrs,
                    sizes, n, threads)
    return out
