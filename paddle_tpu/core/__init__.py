"""paddle_tpu.core — the native (C++) runtime core, via ctypes.

Reference parity: the C++ platform layer that survives on TPU (SURVEY.md
§2.11 items 1/12/13): flags registry (platform/flags.cc), monitor
(platform/monitor.cc), profiler events + chrome-trace export
(platform/profiler.cc + tools/timeline.py), double-buffer ring handoff
(operators/reader/buffered_reader.cc), parallel batch assembly
(framework/data_feed.cc).  Device compute is XLA/Pallas; this is host-side
runtime.  The library is a generated file: it is compiled from csrc/core.cc
on first use (g++) and again whenever the source is newer, and it is never
loaded without that source beside it.  Every entry point has a pure-Python
path so the package works without a toolchain; `available()` says which of
the two is in use.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libpaddle_tpu_core.so")
_SRC = os.path.join(os.path.dirname(os.path.dirname(_DIR)), "csrc", "core.cc")

_lib = None
_load_failed = False  # cache failure: never retry g++ per call
_build_lock = threading.Lock()


def _build():
    # build beside the target and rename: another process importing the
    # package meanwhile loads the old library or the whole new one
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-std=c++17", "-fPIC", "-Wall", "-pthread",
           "-shared", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, _SO)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _build_lock:
        if _lib is not None:
            return _lib
        if _load_failed:
            return None
        try:
            if not os.path.exists(_SRC):
                raise OSError(f"{_SRC} is missing")
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SRC) > os.path.getmtime(_SO)):
                _build()
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            logging.getLogger("paddle_tpu.core").warning(
                "native core unavailable, using the pure-Python path: %s%s",
                e, getattr(e, "stderr", None) or "")
            _load_failed = True
            return None
        # signatures
        lib.pt_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.pt_flag_get.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                    ctypes.c_int]
        lib.pt_flag_get.restype = ctypes.c_int
        lib.pt_stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.pt_stat_set.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.pt_stat_get.argtypes = [ctypes.c_char_p]
        lib.pt_stat_get.restype = ctypes.c_int64
        lib.pt_stat_reset.argtypes = [ctypes.c_char_p]
        lib.pt_stat_list.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.pt_stat_list.restype = ctypes.c_int
        lib.pt_event_push.argtypes = [ctypes.c_char_p]
        lib.pt_event_complete.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                          ctypes.c_int64]
        lib.pt_event_count.restype = ctypes.c_int64
        lib.pt_trace_export.argtypes = [ctypes.c_char_p]
        lib.pt_trace_export.restype = ctypes.c_int
        lib.pt_profiler_enable.argtypes = [ctypes.c_int]
        lib.pt_profiler_enabled.restype = ctypes.c_int
        lib.pt_ring_create.argtypes = [ctypes.c_int, ctypes.c_int64]
        lib.pt_ring_create.restype = ctypes.c_int64
        lib.pt_ring_acquire_write.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.pt_ring_acquire_write.restype = ctypes.c_int
        lib.pt_ring_slot_ptr.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.pt_ring_slot_ptr.restype = ctypes.c_void_p
        lib.pt_ring_slot_bytes.argtypes = [ctypes.c_int64]
        lib.pt_ring_slot_bytes.restype = ctypes.c_int64
        lib.pt_ring_commit_write.argtypes = [ctypes.c_int64, ctypes.c_int,
                                             ctypes.c_int64]
        lib.pt_ring_acquire_read.argtypes = [
            ctypes.c_int64, ctypes.c_int, ctypes.POINTER(ctypes.c_int64)]
        lib.pt_ring_acquire_read.restype = ctypes.c_int
        lib.pt_ring_release_read.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.pt_ring_write.argtypes = [ctypes.c_int64, ctypes.c_char_p,
                                      ctypes.c_int64, ctypes.c_int]
        lib.pt_ring_write.restype = ctypes.c_int
        lib.pt_ring_read.argtypes = [ctypes.c_int64, ctypes.c_void_p,
                                     ctypes.c_int64, ctypes.c_int]
        lib.pt_ring_read.restype = ctypes.c_int64
        lib.pt_ring_close.argtypes = [ctypes.c_int64]
        lib.pt_ring_destroy.argtypes = [ctypes.c_int64]
        lib.pt_batch_assemble.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ctypes.c_int64, ctypes.c_int]
        lib.pt_version.restype = ctypes.c_char_p
        _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def version():
    lib = _load()
    return lib.pt_version().decode() if lib else None


# ---------------------------------------------------------------------------
# Flags mirror (framework/flags.py remains the typed source of truth)
# ---------------------------------------------------------------------------
def flag_set(name: str, value) -> None:
    lib = _load()
    if lib:
        lib.pt_flag_set(name.encode(), str(value).encode())


def flag_get(name: str):
    lib = _load()
    if not lib:
        return None
    buf = ctypes.create_string_buffer(4096)
    n = lib.pt_flag_get(name.encode(), buf, 4096)
    return buf.value.decode() if n >= 0 else None


# ---------------------------------------------------------------------------
# Monitor (platform/monitor.cc StatRegistry)
# ---------------------------------------------------------------------------
_py_stats: dict[str, int] = {}
_py_stats_lock = threading.Lock()


def stat_add(name: str, value: int = 1) -> None:
    lib = _load()
    if lib:
        lib.pt_stat_add(name.encode(), int(value))
    else:
        with _py_stats_lock:
            _py_stats[name] = _py_stats.get(name, 0) + int(value)


def stat_get(name: str) -> int:
    lib = _load()
    if lib:
        return int(lib.pt_stat_get(name.encode()))
    with _py_stats_lock:
        return _py_stats.get(name, 0)


def stat_reset(name: str) -> None:
    lib = _load()
    if lib:
        lib.pt_stat_reset(name.encode())
    else:
        with _py_stats_lock:
            _py_stats.pop(name, None)


def stat_list() -> dict:
    lib = _load()
    if not lib:
        with _py_stats_lock:
            return dict(_py_stats)
    import json
    size = 1 << 16
    while True:
        buf = ctypes.create_string_buffer(size)
        n = lib.pt_stat_list(buf, size)
        if n >= 0:
            return json.loads(buf.value.decode())
        size = -n + 1


# ---------------------------------------------------------------------------
# Profiler events (host scopes; complements jax.profiler device traces)
# ---------------------------------------------------------------------------
_profiler_on = False    # mirrors the native flag, which only we set


def profiler_enable(on: bool = True) -> None:
    global _profiler_on
    lib = _load()
    if lib:
        lib.pt_profiler_enable(1 if on else 0)
        _profiler_on = bool(on)


def profiler_enabled() -> bool:
    """Read from Python: every RecordEvent asks, and a ctypes call would
    release the GIL each time, handing the hot loop's interpreter to
    whichever thread waits for it."""
    return _profiler_on


def event_push(name: str) -> None:
    lib = _load()
    if lib:
        lib.pt_event_push(name.encode())


def event_pop() -> None:
    lib = _load()
    if lib:
        lib.pt_event_pop()


def event_complete(name: str, begin_us: int, end_us: int) -> None:
    lib = _load()
    if lib:
        lib.pt_event_complete(name.encode(), int(begin_us), int(end_us))


def event_count() -> int:
    lib = _load()
    return int(lib.pt_event_count()) if lib else 0


def trace_export(path: str) -> int:
    """Write chrome://tracing JSON (tools/timeline.py analog).
    Returns number of events exported, -1 if unavailable."""
    lib = _load()
    if not lib:
        return -1
    return int(lib.pt_trace_export(path.encode()))


def trace_clear() -> None:
    lib = _load()
    if lib:
        lib.pt_trace_clear()


# ---------------------------------------------------------------------------
# Ring buffer (buffered_reader.cc double-buffer handoff)
# ---------------------------------------------------------------------------
class RingBuffer:
    """Blocking fixed-slot byte ring for producer/consumer handoff.

    put(bytes-like) blocks while full; get() blocks while empty and
    returns a memoryview of the committed payload which MUST be consumed
    (copied/used) before the paired `release` — `get` hands out
    (view, release_fn).  Falls back to a pure-Python deque when the native
    library is unavailable.
    """

    def __init__(self, capacity: int, slot_bytes: int):
        self._lib = _load()
        self._cap = capacity
        self._slot_bytes = slot_bytes
        if self._lib:
            self._h = self._lib.pt_ring_create(capacity, slot_bytes)
            if self._h < 0:
                raise ValueError("bad ring parameters")
        else:
            import collections
            self._q = collections.deque()
            self._mu = threading.Condition()
            self._closed = False

    # -- native-backed ----------------------------------------------------
    def put(self, data, timeout_ms: int = -1) -> bool:
        data = memoryview(data).cast("B")
        if len(data) > self._slot_bytes:
            raise ValueError(f"payload {len(data)} > slot {self._slot_bytes}")
        if self._lib:
            # One-shot native call: the copy happens under the ring's
            # in-flight pin, so a concurrent destroy cannot free the slot
            # mid-copy (the split acquire/slot_ptr/commit API leaves an
            # unpinned window).
            rc = self._lib.pt_ring_write(self._h, bytes(data), len(data),
                                         timeout_ms)
            if rc == -2:
                raise RuntimeError("ring closed")
            if rc == -4:
                raise ValueError(
                    f"payload {len(data)} > slot {self._slot_bytes}")
            return rc == 0
        with self._mu:
            while len(self._q) >= self._cap and not self._closed:
                if not self._mu.wait(
                        None if timeout_ms < 0 else timeout_ms / 1000):
                    return False
            if self._closed:
                raise RuntimeError("ring closed")
            self._q.append(bytes(data))
            self._mu.notify_all()
            return True

    def get(self, timeout_ms: int = -1):
        """Returns (payload: bytes, release: callable) or None on timeout;
        raises EOFError when closed and drained."""
        if self._lib:
            buf = ctypes.create_string_buffer(self._slot_bytes)
            n = self._lib.pt_ring_read(self._h, buf, self._slot_bytes,
                                       timeout_ms)
            if n == -2:
                raise EOFError("ring closed")
            if n < 0:
                return None
            # copy+release happened atomically in native code; release is
            # kept in the signature for API compatibility
            return buf.raw[:n], (lambda: None)
        with self._mu:
            while not self._q and not self._closed:
                if not self._mu.wait(
                        None if timeout_ms < 0 else timeout_ms / 1000):
                    return None
            if not self._q:
                raise EOFError("ring closed")
            payload = self._q.popleft()
            self._mu.notify_all()
            return payload, (lambda: None)

    def close(self):
        if self._lib:
            self._lib.pt_ring_close(self._h)
        else:
            with self._mu:
                self._closed = True
                self._mu.notify_all()

    def __del__(self):
        if getattr(self, "_lib", None) and getattr(self, "_h", 0) > 0:
            try:
                self._lib.pt_ring_destroy(self._h)
            except Exception:
                pass


# ---------------------------------------------------------------------------
# Batch assemble (parallel memcpy collate)
# ---------------------------------------------------------------------------
def assemble_batch(samples, out=None, nthreads: int = 0):
    """Stack N equal-shape contiguous numpy arrays into one [N, ...] batch
    using parallel memcpy (data_feed.cc batch packing). Falls back to
    np.stack."""
    import numpy as np

    lib = _load()
    n = len(samples)
    if n == 0:
        raise ValueError("empty batch")
    first = np.ascontiguousarray(samples[0])
    if lib is None:
        return np.stack([np.asarray(s) for s in samples], out=out)
    arrs = [first] + [np.ascontiguousarray(s) for s in samples[1:]]
    for a in arrs[1:]:
        if a.shape != first.shape or a.dtype != first.dtype:
            return np.stack(arrs, out=out)
    if out is None:
        out = np.empty((n,) + first.shape, first.dtype)
    sample_bytes = first.nbytes
    srcs = (ctypes.c_void_p * n)(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs])
    if nthreads <= 0:
        nthreads = min(8, os.cpu_count() or 1)
    lib.pt_batch_assemble(out.ctypes.data_as(ctypes.c_void_p), srcs, n,
                          sample_bytes, nthreads)
    return out
