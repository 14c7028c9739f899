"""Native extension loader: host segment trees.

The C++ tree (segment_tree.cpp) is compiled with g++ at first
construction into ``_build/`` (ignored by git: the library is built on
the machine that runs it, never committed) and bound via ctypes. A build
that fails raises — host PER does not quietly run on something slower.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

__all__ = ["SumSegmentTree", "MinSegmentTree"]

_LIB = None


def _build_and_load():
    global _LIB
    if _LIB is not None:
        return _LIB
    src = os.path.join(os.path.dirname(__file__), "segment_tree.cpp")
    cache_dir = os.path.join(os.path.dirname(__file__), "_build")
    lib_path = os.path.join(cache_dir, "libsegment_tree.so")
    if not os.path.exists(lib_path) or os.path.getmtime(lib_path) < os.path.getmtime(src):
        os.makedirs(cache_dir, exist_ok=True)
        # build beside the target and rename: a concurrent first use in
        # another process never loads a half-written library
        tmp = f"{lib_path}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
                check=True,
                capture_output=True,
                text=True,
            )
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"building {src} failed (g++ exit {e.returncode}): {e.stderr}"
            ) from e
        os.replace(tmp, lib_path)
    lib = ctypes.CDLL(lib_path)

    lib.st_new.restype = ctypes.c_void_p
    lib.st_new.argtypes = [ctypes.c_int64, ctypes.c_int32]
    lib.st_free.argtypes = [ctypes.c_void_p]
    lib.st_capacity.restype = ctypes.c_int64
    lib.st_capacity.argtypes = [ctypes.c_void_p]
    lib.st_set.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_double]
    lib.st_get.restype = ctypes.c_double
    lib.st_get.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.st_set_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64,
    ]
    lib.st_get_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int64,
    ]
    lib.st_reduce.restype = ctypes.c_double
    lib.st_reduce.argtypes = [ctypes.c_void_p]
    lib.st_reduce_range.restype = ctypes.c_double
    lib.st_reduce_range.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64]
    lib.st_prefix_search.restype = ctypes.c_int64
    lib.st_prefix_search.argtypes = [ctypes.c_void_p, ctypes.c_double]
    lib.st_prefix_search_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    _LIB = lib
    return lib


def _i64(a):
    return np.ascontiguousarray(a, np.int64)


def _f64(a):
    return np.ascontiguousarray(a, np.float64)


class _NativeTree:
    def __init__(self, capacity: int, is_min: bool):
        self._lib = lib = _build_and_load()
        self.capacity = capacity
        self._h = ctypes.c_void_p(lib.st_new(capacity, 1 if is_min else 0))
        if not self._h:
            raise MemoryError("segment tree allocation failed")

    def __del__(self):
        if getattr(self, "_h", None) and self._lib:
            self._lib.st_free(self._h)

    def __setitem__(self, idx, value):
        if np.isscalar(idx) or np.ndim(idx) == 0:
            self._lib.st_set(self._h, int(idx), float(value))
        else:
            idx = _i64(idx)
            vals = _f64(np.broadcast_to(value, idx.shape))
            self._lib.st_set_batch(
                self._h,
                idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                idx.size,
            )

    def __getitem__(self, idx):
        if np.isscalar(idx) or np.ndim(idx) == 0:
            return self._lib.st_get(self._h, int(idx))
        idx = _i64(idx)
        out = np.empty(idx.shape, np.float64)
        self._lib.st_get_batch(
            self._h,
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            idx.size,
        )
        return out

    def reduce(self, start: int = 0, end: int | None = None) -> float:
        if start == 0 and end is None:
            return self._lib.st_reduce(self._h)
        end = self.capacity if end is None else end
        return self._lib.st_reduce_range(self._h, start, end)


class SumSegmentTree(_NativeTree):
    """O(log N) sum tree with prefix-sum search (reference SumSegmentTree,
    csrc/segment_tree.h:243). The build happens lazily at FIRST
    construction — importing rl_tpu stays side-effect free."""

    def __init__(self, capacity: int):
        super().__init__(capacity, is_min=False)

    def scan(self, us) -> np.ndarray:
        """For each u: smallest idx with prefix-sum(0..idx) > u."""
        us = _f64(np.atleast_1d(us))
        out = np.empty(us.shape, np.int64)
        self._lib.st_prefix_search_batch(
            self._h,
            us.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            us.size,
        )
        return out


class MinSegmentTree(_NativeTree):
    """O(log N) min tree (reference MinSegmentTree, csrc/segment_tree.h:303)."""

    def __init__(self, capacity: int):
        super().__init__(capacity, is_min=True)


class _NumpySumTree:
    """Plain numpy reference with identical semantics (O(N) scan): what
    ``tests/test_csrc.py`` holds the native tree to."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._v = np.zeros(capacity, np.float64)

    def __setitem__(self, idx, value):
        self._v[idx] = value

    def __getitem__(self, idx):
        return self._v[idx]

    def reduce(self, start: int = 0, end: int | None = None) -> float:
        return float(self._v[start:end].sum())

    def scan(self, us):
        cs = np.cumsum(self._v)
        return np.clip(np.searchsorted(cs, np.atleast_1d(us), side="right"), 0, self.capacity - 1)
