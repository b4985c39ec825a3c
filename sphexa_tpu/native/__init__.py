"""ctypes bindings for the native host-runtime library.

The compute path is JAX/XLA/Pallas; the *host runtime* around it — key
generation, sort-order and occupancy/window accounting at reconfiguration
time — has a native C++ implementation (sfc_runtime.cpp), mirroring the
reference's C++ host drivers. The library is built from sfc_runtime.cpp
on first use, and rebuilt when the source is newer than the library
(``make -C sphexa_tpu/native`` does the same by hand); every entry point
takes the numpy/jax implementation when the library is unavailable, so
the package stays import-safe everywhere — ``describe()`` names the route
in use.
"""

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC_PATH = os.path.join(_DIR, "sfc_runtime.cpp")
_LIB_PATH = os.path.join(_DIR, "libsfc_runtime.so")
_lib = None
_tried_build = False
#: why the numpy route is in use ("" while the native library serves)
_fallback_reason = ""


def _stale() -> bool:
    """The library is missing or older than its source (a working tree
    copied with a leftover build must not run yesterday's code)."""
    try:
        return os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH)
    except OSError:
        return True


def _build() -> None:
    """Compile the library to a process-unique temp name and atomically
    rename, so concurrent builders never dlopen a partial file."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-fPIC", "-fopenmp", "-Wall",
             "-shared", "-o", tmp, _SRC_PATH],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB_PATH)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load() -> Optional[ctypes.CDLL]:
    """dlopen the runtime library, (re)building it once per process when
    it is missing or older than sfc_runtime.cpp. Returns None — every
    entry point then takes its numpy route — when it cannot be built or
    loaded; ``describe()`` says which route runs and why."""
    global _lib, _tried_build, _fallback_reason
    if _lib is not None:
        return _lib
    if _stale() and not _tried_build:
        _tried_build = True
        try:
            _build()
        except (OSError, subprocess.SubprocessError) as e:
            _fallback_reason = f"build failed: {e}"
    if not os.path.exists(_LIB_PATH):
        _fallback_reason = _fallback_reason or "library not built"
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        _fallback_reason = f"dlopen failed: {e}"
        return None

    u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")

    lib.sfc_compute_keys.argtypes = [
        f32p, f32p, f32p, ctypes.c_int64, f32p, f32p, ctypes.c_int, u32p
    ]
    lib.sfc_argsort.argtypes = [u32p, ctypes.c_int64, i64p]
    lib.sfc_max_cell_occupancy.argtypes = [u32p, ctypes.c_int64, ctypes.c_int]
    lib.sfc_max_cell_occupancy.restype = ctypes.c_int64
    lib.sfc_group_extents.argtypes = [
        f32p, f32p, f32p, i64p, ctypes.c_int64, ctypes.c_int, f32p
    ]
    lib.sfc_runtime_abi_version.restype = ctypes.c_int
    if lib.sfc_runtime_abi_version() != 1:
        _fallback_reason = "ABI version mismatch"
        return None
    _lib = lib
    _fallback_reason = ""
    return _lib


def available() -> bool:
    return _load() is not None


def describe() -> str:
    """Which host runtime serves this process: the native library or
    the numpy route, with the reason for the latter."""
    if available():
        return "native (libsfc_runtime.so)"
    return f"numpy ({_fallback_reason})"


def compute_keys(x, y, z, box_lo, box_len, curve: str = "hilbert") -> np.ndarray:
    """Host-side SFC keys (native when available, else the jax codec)."""
    if curve not in ("hilbert", "morton"):
        raise ValueError(f"unknown curve {curve!r}; have hilbert, morton")
    lib = _load()
    x = np.ascontiguousarray(x, np.float32)
    y = np.ascontiguousarray(y, np.float32)
    z = np.ascontiguousarray(z, np.float32)
    if lib is None:
        from sphexa_tpu.sfc.box import Box, BoundaryType
        from sphexa_tpu.sfc.keys import compute_sfc_keys
        import jax.numpy as jnp

        lo = np.asarray(box_lo, np.float32)
        ln = np.asarray(box_len, np.float32)
        box = Box(
            lo=jnp.asarray(lo), hi=jnp.asarray(lo + ln),
            boundaries=(BoundaryType.open,) * 3,
        )
        return np.asarray(
            compute_sfc_keys(jnp.asarray(x), jnp.asarray(y), jnp.asarray(z),
                             box, curve=curve)
        )
    keys = np.empty(len(x), np.uint32)
    lib.sfc_compute_keys(
        x, y, z, len(x),
        np.ascontiguousarray(box_lo, np.float32),
        np.ascontiguousarray(box_len, np.float32),
        0 if curve == "hilbert" else 1, keys,
    )
    return keys


def argsort_keys(keys: np.ndarray) -> np.ndarray:
    lib = _load()
    keys = np.ascontiguousarray(keys, np.uint32)
    if lib is None:
        return np.argsort(keys, kind="stable").astype(np.int64)
    order = np.empty(len(keys), np.int64)
    lib.sfc_argsort(keys, len(keys), order)
    return order


def max_cell_occupancy(sorted_keys: np.ndarray, level: int) -> int:
    lib = _load()
    sorted_keys = np.ascontiguousarray(sorted_keys, np.uint32)
    if lib is None:
        from sphexa_tpu.dtypes import KEY_BITS

        shift = 3 * (KEY_BITS - level)
        cells = (sorted_keys.astype(np.uint64) >> np.uint64(shift)).astype(np.int64)
        return int(np.bincount(cells).max()) if len(cells) else 0
    return int(lib.sfc_max_cell_occupancy(sorted_keys, len(sorted_keys), level))


def group_extents(x, y, z, order: np.ndarray, group: int) -> Tuple[float, float, float]:
    """Max per-dimension extent over SFC-consecutive particle groups."""
    lib = _load()
    x = np.ascontiguousarray(x, np.float32)
    y = np.ascontiguousarray(y, np.float32)
    z = np.ascontiguousarray(z, np.float32)
    order = np.ascontiguousarray(order, np.int64)
    if lib is None:
        out = []
        n = len(x)
        ng = -(-n // group)
        pad = ng * group - n
        for a in (x, y, z):
            s = a[order]
            if pad:
                s = np.concatenate([s, np.repeat(s[-1], pad)])
            g = s.reshape(ng, group)
            out.append(float((g.max(axis=1) - g.min(axis=1)).max()))
        return tuple(out)
    ext = np.empty(3, np.float32)
    lib.sfc_group_extents(x, y, z, order, len(x), group, ext)
    return float(ext[0]), float(ext[1]), float(ext[2])
