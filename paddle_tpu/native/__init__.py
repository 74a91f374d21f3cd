"""Native (C++) runtime support: blocking queue, arena allocator,
profiler events, stat registry.

Reference parity map (see src/native.cc header): blocking_queue.h,
auto_growth_best_fit_allocator.h:30, platform/profiler.h:216,
platform/monitor.h:77.

The library is compiled in-repo on first use (g++ -O2 -shared, keyed by
source hash — ``_build.py``) and bound via ctypes — the image has no
pybind11, and a C ABI keeps the binding layer trivial.  Every consumer
has a pure-Python path, so the framework still works if no toolchain is
present; a failed build says so once on stderr.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading

from ._build import build_shared, failure_text

__all__ = ["available", "lib", "BlockingQueue", "Arena", "Profiler",
           "stat_add", "stat_get", "stat_reset"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "native.cc")
_SO = os.path.join(_HERE, "_paddle_native.so")

_lib = None
_lock = threading.Lock()


def lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib if _lib is not False else None
    with _lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        try:
            build_shared(_SO, [_SRC], ["-pthread"])
            L = ctypes.CDLL(_SO)
        except (OSError, subprocess.SubprocessError) as e:
            print(f"paddle_tpu.native: {_SO} could not be built or "
                  f"loaded ({failure_text(e)}); using the pure-Python "
                  "queue/arena/profiler paths", file=sys.stderr)
            _lib = False
            return None
        # signatures
        L.arena_create.restype = ctypes.c_void_p
        L.arena_create.argtypes = [ctypes.c_uint64]
        L.arena_alloc.restype = ctypes.c_void_p
        L.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        L.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        L.arena_reserved.restype = ctypes.c_uint64
        L.arena_reserved.argtypes = [ctypes.c_void_p]
        L.arena_in_use.restype = ctypes.c_uint64
        L.arena_in_use.argtypes = [ctypes.c_void_p]
        L.arena_destroy.argtypes = [ctypes.c_void_p]
        L.bq_create.restype = ctypes.c_void_p
        L.bq_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
        L.bq_push.restype = ctypes.c_int
        L.bq_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_uint64, ctypes.c_int64]
        L.bq_peek_size.restype = ctypes.c_int64
        L.bq_peek_size.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        L.bq_fetch.restype = ctypes.c_int64
        L.bq_fetch.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_uint64]
        L.bq_size.restype = ctypes.c_uint64
        L.bq_size.argtypes = [ctypes.c_void_p]
        L.bq_close.argtypes = [ctypes.c_void_p]
        L.bq_destroy.argtypes = [ctypes.c_void_p]
        L.prof_enable.argtypes = [ctypes.c_uint64]
        L.prof_is_enabled.restype = ctypes.c_int
        L.prof_now_ns.restype = ctypes.c_int64
        L.prof_record.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.c_int64, ctypes.c_int64]
        L.prof_event_count.restype = ctypes.c_uint64
        L.prof_dump_json.restype = ctypes.c_int64
        L.prof_dump_json.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        L.stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        L.stat_get.restype = ctypes.c_int64
        L.stat_get.argtypes = [ctypes.c_char_p]
        L.stat_reset.argtypes = [ctypes.c_char_p]
        _lib = L
    return _lib


def available() -> bool:
    return lib() is not None


class Arena:
    """Host staging-buffer allocator (auto-growth best-fit)."""

    def __init__(self, chunk_size: int = 8 << 20):
        L = lib()
        if L is None:
            raise RuntimeError("native library unavailable")
        self._L = L
        self._h = L.arena_create(chunk_size)

    def alloc(self, size: int) -> int:
        return self._L.arena_alloc(self._h, size)

    def free(self, ptr: int):
        self._L.arena_free(self._h, ctypes.c_void_p(ptr))

    @property
    def reserved(self) -> int:
        return self._L.arena_reserved(self._h)

    @property
    def in_use(self) -> int:
        return self._L.arena_in_use(self._h)

    def __del__(self):
        try:
            self._L.arena_destroy(self._h)
        except Exception:
            pass


class BlockingQueue:
    """Bounded byte-buffer queue; blocking waits run outside the GIL
    (ctypes releases it), so producer/consumer threads overlap with
    device compute — reference blocking_queue.h semantics."""

    def __init__(self, capacity: int = 8, arena_chunk: int = 8 << 20):
        L = lib()
        if L is None:
            raise RuntimeError("native library unavailable")
        self._L = L
        self._h = L.bq_create(capacity, arena_chunk)

    def push(self, data: bytes, timeout_ms: int = -1) -> bool:
        rc = self._L.bq_push(self._h, data, len(data), timeout_ms)
        if rc == -1:
            raise RuntimeError("queue closed")
        if rc == -3:
            raise MemoryError("arena alloc failed")
        return rc == 0

    def pop(self, timeout_ms: int = -1):
        size = self._L.bq_peek_size(self._h, timeout_ms)
        if size == -1:
            return None  # closed + drained
        if size == -2:
            raise TimeoutError("queue pop timed out")
        buf = ctypes.create_string_buffer(int(size))
        got = self._L.bq_fetch(self._h, buf, int(size))
        if got < 0:
            return None
        return buf.raw[:got]

    def __len__(self):
        return int(self._L.bq_size(self._h))

    def close(self):
        self._L.bq_close(self._h)

    def __del__(self):
        try:
            self._L.bq_destroy(self._h)
        except Exception:
            pass


class Profiler:
    """Host-span collector; chrome-trace export (profiler.h:216)."""

    @staticmethod
    def enable(capacity: int = 1 << 20):
        L = lib()
        if L is not None:
            L.prof_enable(capacity)

    @staticmethod
    def disable():
        L = lib()
        if L is not None:
            L.prof_disable()

    @staticmethod
    def enabled() -> bool:
        L = lib()
        return bool(L and L.prof_is_enabled())

    @staticmethod
    def now_ns() -> int:
        L = lib()
        return L.prof_now_ns() if L else 0

    @staticmethod
    def record(name: str, start_ns: int, end_ns: int, tid: int = 0):
        L = lib()
        if L is not None:
            L.prof_record(name.encode(), start_ns, end_ns, tid)

    @staticmethod
    def event_count() -> int:
        L = lib()
        return int(L.prof_event_count()) if L else 0

    @staticmethod
    def dump_chrome_trace(path: str):
        L = lib()
        if L is None:
            return
        cap = 1 << 20
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = L.prof_dump_json(buf, cap)
            if n >= 0:
                with open(path, "wb") as f:
                    f.write(buf.raw[:n])
                return
            cap = -int(n) + 16


def stat_add(name: str, delta: int = 1):
    L = lib()
    if L is not None:
        L.stat_add(name.encode(), delta)


def stat_get(name: str) -> int:
    L = lib()
    return int(L.stat_get(name.encode())) if L else 0


def stat_reset(name: str = ""):
    L = lib()
    if L is not None:
        L.stat_reset(name.encode())
