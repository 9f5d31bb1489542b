"""ctypes binding for the native scheduler core (libschedcore.so).

Reference analogue: the Cython/C++ boundary of the reference's scheduling
substrate (``src/ray/common/scheduling/`` reached from Python through
``_raylet.pyx``). Built from ``src/`` on first use
(:mod:`raytpu.core.native`). Falls back cleanly — callers check
:func:`available` and keep the pure-Python path otherwise.
"""

from __future__ import annotations

import ctypes
import threading
from typing import List, Optional, Sequence, Tuple

from raytpu.core.native import lib_path

_lib = None
_load_lock = threading.Lock()


def _load():
    global _lib
    with _load_lock:
        if _lib is not None:
            return _lib
        try:
            lib = ctypes.CDLL(lib_path("libschedcore.so"))
        except (RuntimeError, OSError):
            return None
        lib.topo_create.argtypes = [ctypes.POINTER(ctypes.c_int),
                                    ctypes.c_int]
        lib.topo_create.restype = ctypes.c_int64
        lib.topo_destroy.argtypes = [ctypes.c_int64]
        lib.topo_num_free.argtypes = [ctypes.c_int64]
        lib.topo_num_free.restype = ctypes.c_int64
        for fn in (lib.topo_alloc_subcube, lib.topo_alloc_any):
            fn.argtypes = [ctypes.c_int64, ctypes.c_int64,
                           ctypes.POINTER(ctypes.c_int)]
            fn.restype = ctypes.c_int64
        lib.topo_release.argtypes = [ctypes.c_int64,
                                     ctypes.POINTER(ctypes.c_int),
                                     ctypes.c_int64]
        lib.score_nodes.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.c_double,
        ]
        lib.score_nodes.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class NativeTopology:
    """Native-backed occupancy grid with the same contract as
    :class:`raytpu.core.topology.TpuTopology`'s allocation methods."""

    def __init__(self, shape: Sequence[int]):
        lib = _load()
        if lib is None:
            raise RuntimeError("libschedcore.so unavailable")
        self._lib = lib
        self.shape = tuple(int(d) for d in shape)
        arr = (ctypes.c_int * len(self.shape))(*self.shape)
        self._h = lib.topo_create(arr, len(self.shape))
        if self._h < 0:
            raise ValueError(f"bad topology shape {self.shape}")

    @property
    def num_free(self) -> int:
        return int(self._lib.topo_num_free(self._h))

    def _alloc(self, fn, chips: int) -> Optional[List[Tuple[int, ...]]]:
        ndim = len(self.shape)
        out = (ctypes.c_int * (chips * ndim))()
        n = fn(self._h, chips, out)
        if n <= 0:
            return None
        return [tuple(out[i * ndim + j] for j in range(ndim))
                for i in range(n)]

    def allocate_subcube(self, chips: int) -> Optional[List[Tuple[int, ...]]]:
        if chips <= 0:
            return None
        return self._alloc(self._lib.topo_alloc_subcube, chips)

    def allocate_any(self, chips: int) -> Optional[List[Tuple[int, ...]]]:
        if chips <= 0:
            return None
        return self._alloc(self._lib.topo_alloc_any, chips)

    def release(self, coords: Sequence[Tuple[int, ...]]) -> None:
        coords = list(coords)
        if not coords:
            return
        ndim = len(self.shape)
        flat = (ctypes.c_int * (len(coords) * ndim))(
            *[c[i] for c in coords for i in range(ndim)])
        self._lib.topo_release(self._h, flat, len(coords))

    def __del__(self):
        try:
            self._lib.topo_destroy(self._h)
        except Exception:
            pass


def score_nodes(avail: Sequence[Sequence[float]],
                total: Sequence[Sequence[float]],
                request: Sequence[float],
                spread_threshold: float = 0.5) -> int:
    """Hybrid pack/spread choice over node resource rows; -1 if none
    feasible. Native single pass (reference: hybrid policy scoring)."""
    lib = _load()
    n_nodes = len(avail)
    n_res = len(request)
    if lib is None:
        raise RuntimeError("libschedcore.so unavailable")
    fa = (ctypes.c_double * (n_nodes * n_res))(
        *[v for row in avail for v in row])
    ft = (ctypes.c_double * (n_nodes * n_res))(
        *[v for row in total for v in row])
    fr = (ctypes.c_double * n_res)(*request)
    return int(lib.score_nodes(fa, ft, n_nodes, n_res, fr,
                               spread_threshold))
