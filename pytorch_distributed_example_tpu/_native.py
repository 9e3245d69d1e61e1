"""ctypes loader for the native C++ core (csrc/libtdx.so).

Plays the role of torch's pybind11 surface (`_C/_distributed_c10d.pyi`,
SURVEY.md §2.2 N18) with ctypes instead of pybind11 (not available in this
environment — task rules). `libtdx.so` is git-ignored, so what is on disk
proves nothing: every first `load()` runs `make`, which rebuilds when the
library is absent or older than the tracked `csrc/*.cpp`. If the build
fails (no toolchain), callers use the pure-Python implementations
(store.py, reducer.py) — and `status()` says so, with the reason.

Env: TDX_NATIVE=0 disables native entirely (forces the Python paths).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_status = "not loaded yet"

_CSRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc")
_SO = os.path.join(_CSRC, "libtdx.so")


def _make() -> Optional[str]:
    """Run the csrc Makefile; None on success, else the reason."""
    try:
        subprocess.run(
            ["make", "-C", _CSRC], capture_output=True, timeout=120,
            check=True,
        )
    except FileNotFoundError as e:
        return f"make not found ({e})"
    except subprocess.TimeoutExpired:
        return "make timed out after 120 s"
    except subprocess.CalledProcessError as e:
        tail = (e.stderr or b"").decode(errors="replace").strip()[-300:]
        return f"make failed (rc={e.returncode}): {tail}"
    return None


def status() -> str:
    """Which path `load()` took: "native: <path> (built from source)",
    "native: <path> (up to date)", "python: <why>"."""
    load()
    return _status


def load() -> Optional[ctypes.CDLL]:
    """Load the native library, (re)building it from the tracked sources
    first if they are newer; None means the Python paths are in use."""
    global _lib, _tried, _status
    if os.environ.get("TDX_NATIVE", "1") == "0":
        _status = "python: TDX_NATIVE=0"
        return None
    with _lock:
        if _tried:
            return _lib
        _tried = True
        before = os.path.getmtime(_SO) if os.path.exists(_SO) else None
        err = _make()
        if err is None:
            try:
                _lib = _bind(ctypes.CDLL(_SO))
            except (OSError, AttributeError) as e:
                err = f"{type(e).__name__}: {e}"
            else:
                built = before is None or os.path.getmtime(_SO) != before
                _status = f"native: {_SO} (" + (
                    "built from source" if built else "up to date"
                ) + ")"
                return _lib
        _status = f"python: native build unavailable — {err}"
        warnings.warn(
            "libtdx.so could not be built; using the Python store and "
            f"reducer ({err})",
            RuntimeWarning,
            stacklevel=2,
        )
        return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare ctypes signatures; raises AttributeError on a stale library."""
    lib.tdx_store_server_start.restype = ctypes.c_void_p
    lib.tdx_store_server_start.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.tdx_store_server_port.restype = ctypes.c_int
    lib.tdx_store_server_port.argtypes = [ctypes.c_void_p]
    lib.tdx_store_server_stop.argtypes = [ctypes.c_void_p]
    lib.tdx_store_client_connect.restype = ctypes.c_void_p
    lib.tdx_store_client_connect.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_double,
    ]
    lib.tdx_store_client_close.argtypes = [ctypes.c_void_p]
    lib.tdx_store_client_call.restype = ctypes.c_long
    lib.tdx_store_client_call.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_char_p,
        ctypes.c_long,
        ctypes.c_char_p,
        ctypes.c_long,
    ]
    lib.tdx_store_client_response.restype = ctypes.POINTER(ctypes.c_char)
    lib.tdx_store_client_response.argtypes = [ctypes.c_void_p]
    lib.tdx_compute_buckets.restype = ctypes.c_long
    lib.tdx_compute_buckets.argtypes = [
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.POINTER(ctypes.c_long),
    ]
    # reducer core (csrc/reducer.cpp)
    PF = ctypes.POINTER(ctypes.c_float)
    lib.tdx_pack_f32.argtypes = [
        ctypes.POINTER(PF),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        PF,
    ]
    lib.tdx_unpack_f32.argtypes = [
        PF,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(PF),
    ]
    lib.tdx_count_nonfinite_f32.restype = ctypes.c_int64
    lib.tdx_count_nonfinite_f32.argtypes = [PF, ctypes.c_int64]
    # flight recorder (csrc/flight_recorder.cpp)
    lib.tdx_fr_create.restype = ctypes.c_void_p
    lib.tdx_fr_create.argtypes = [ctypes.c_int64]
    lib.tdx_fr_destroy.argtypes = [ctypes.c_void_p]
    lib.tdx_fr_record.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_double,
    ]
    lib.tdx_fr_complete.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_double,
    ]
    lib.tdx_fr_size.restype = ctypes.c_int64
    lib.tdx_fr_size.argtypes = [ctypes.c_void_p]
    # POINTER(c_char), not c_char_p: we must keep the raw pointer to
    # free it after copying (heap-allocated per dump; see .cpp)
    lib.tdx_fr_dump_json.restype = ctypes.POINTER(ctypes.c_char)
    lib.tdx_fr_dump_json.argtypes = [ctypes.c_void_p]
    lib.tdx_fr_dump_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
    return lib


def available() -> bool:
    return load() is not None


def compute_buckets(sizes, cap_bytes: float, first_cap_bytes: float):
    """Native bucket planner; returns list of buckets (lists of indices),
    or None if the native lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    n = len(sizes)
    arr = (ctypes.c_long * n)(*[int(s) for s in sizes])
    out = (ctypes.c_long * n)()
    nb = lib.tdx_compute_buckets(arr, n, cap_bytes, first_cap_bytes, out)
    buckets = [[] for _ in range(nb)]
    for i in range(n):
        buckets[out[i]].append(i)
    return buckets


def _f32_ptr(a):
    import numpy as np

    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def pack_f32(leaves):
    """Concatenate 1-D float32 numpy arrays into one flat buffer (native
    multithreaded memcpy); returns the flat array or None w/o native."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    n = len(leaves)
    leaves = [np.ascontiguousarray(l, dtype=np.float32).reshape(-1) for l in leaves]
    lengths = (ctypes.c_int64 * n)(*[l.size for l in leaves])
    srcs = (ctypes.POINTER(ctypes.c_float) * n)(*[_f32_ptr(l) for l in leaves])
    total = sum(l.size for l in leaves)
    out = np.empty((total,), np.float32)
    lib.tdx_pack_f32(srcs, lengths, n, _f32_ptr(out))
    return out


def unpack_f32(flat, shapes):
    """Split a flat float32 buffer back into arrays of the given shapes."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    flat = np.ascontiguousarray(flat, dtype=np.float32).reshape(-1)
    n = len(shapes)
    sizes = [int(np.prod(s)) for s in shapes]  # () -> 1, (0,) -> 0
    outs = [np.empty((sz,), np.float32) for sz in sizes]
    lengths = (ctypes.c_int64 * n)(*sizes)
    dsts = (ctypes.POINTER(ctypes.c_float) * n)(*[_f32_ptr(o) for o in outs])
    lib.tdx_unpack_f32(_f32_ptr(flat), lengths, n, dsts)
    return [o.reshape(s) for o, s in zip(outs, shapes)]


def count_nonfinite_f32(arr) -> Optional[int]:
    """Native NaN/Inf count over a float32 array; None w/o native."""
    import numpy as np

    lib = load()
    if lib is None:
        return None
    a = np.ascontiguousarray(arr, dtype=np.float32).reshape(-1)
    return int(lib.tdx_count_nonfinite_f32(_f32_ptr(a), a.size))


class NativeFlightRecorder:
    """ctypes handle over the C++ ring buffer (csrc/flight_recorder.cpp)."""

    def __init__(self, capacity: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.tdx_fr_create(int(capacity))

    def record(self, seq, op, group, shape, dtype, numel, ts):
        self._lib.tdx_fr_record(
            self._h,
            int(seq),
            str(op).encode(),
            str(group).encode(),
            str(tuple(shape)).encode(),
            str(dtype).encode(),
            int(numel),
            float(ts),
        )

    def complete(self, seq, group, failed, ts):
        self._lib.tdx_fr_complete(
            self._h, int(seq), str(group).encode(), 1 if failed else 0, float(ts)
        )

    def size(self) -> int:
        return int(self._lib.tdx_fr_size(self._h))

    def dump_entries(self):
        import json

        ptr = self._lib.tdx_fr_dump_json(self._h)
        try:
            raw = ctypes.string_at(ptr)
        finally:
            self._lib.tdx_fr_dump_free(ptr)
        return json.loads(raw.decode())

    def close(self):
        if self._h:
            self._lib.tdx_fr_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
