"""Native (C++) host kernels with lazy in-tree builds (ctypes, no pybind11).

The TPU compute path is XLA; these kernels cover the *host-side*
runtime hot loops the reference delegates to C-backed libraries
(SURVEY.md §2 native table). Without a compiler on PATH the numpy
implementation is used instead; with one, a failed build is an error
(a silently slower featurizer is not a fallback anyone asked for).
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "_cgnn_native.so")
_SRC = os.path.join(_DIR, "neighbors.cpp")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def _build() -> str | None:
    """Compile the shared library if missing/stale; None when there is
    no compiler. Raises RuntimeError when the compiler fails."""
    if os.path.exists(_LIB_PATH) and os.path.getmtime(
        _LIB_PATH
    ) >= os.path.getmtime(_SRC):
        return _LIB_PATH
    if shutil.which("g++") is None:
        return None
    # per-process temp name: concurrent first uses (pool workers) each
    # build their own copy and the last rename wins
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
        raise RuntimeError(
            f"native neighbor search failed to build ({' '.join(cmd)}):\n"
            f"{getattr(e, 'stderr', '') or e}"
        ) from e
    os.replace(tmp, _LIB_PATH)
    return _LIB_PATH


def get_native_lib() -> ctypes.CDLL | None:
    """The loaded native library, building it on first use; None if absent."""
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        path = _build()
        if path is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(path)
        fn = lib.cgnn_neighbor_search
        fn.restype = ctypes.c_longlong
        fn.argtypes = [
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),  # lattice
            np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),  # frac
            ctypes.c_longlong,
            ctypes.c_double,
            ctypes.c_longlong,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return get_native_lib() is not None


def neighbor_search_native(
    lattice: np.ndarray, frac: np.ndarray, radius: float
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray] | None:
    """(centers, neighbors, distances, offsets) or None if no native lib."""
    lib = get_native_lib()
    if lib is None:
        return None
    lattice = np.ascontiguousarray(lattice, np.float64)
    frac = np.ascontiguousarray(frac, np.float64)
    n = len(frac)
    cap = max(1024, n * 64)
    for _ in range(4):
        centers = np.empty(cap, np.int32)
        neighbors = np.empty(cap, np.int32)
        dists = np.empty(cap, np.float32)
        offsets = np.empty(cap * 3, np.int32)
        got = lib.cgnn_neighbor_search(
            lattice, frac, n, float(radius), cap, centers, neighbors, dists,
            offsets,
        )
        if got >= 0:
            return (
                centers[:got],
                neighbors[:got],
                dists[:got],
                offsets[: got * 3].reshape(-1, 3),
            )
        if got == -1:
            raise ValueError("native neighbor search: bad input (singular cell?)")
        cap = int(-got) + 16
    raise RuntimeError("native neighbor search: capacity negotiation failed")
