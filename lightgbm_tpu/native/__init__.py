"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its data-loading runtime in C++ (src/io/parser.cpp,
src/io/dataset_loader.cpp); this package holds the TPU build's native
equivalents. Sources compile on first use with the system g++ into a
cached shared object next to the source (no pybind11 dependency — plain
C ABI + ctypes), and every entry point has a NumPy fallback so a missing
toolchain degrades gracefully.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_FAILED = False


def compile_and_load(src_name: str, so_name: str) -> ctypes.CDLL:
    """Compile a C++ source in this directory into a cached shared
    object and dlopen it. The object is rebuilt whenever it is absent
    or the source hash recorded beside it (``<so>.sha256``) differs
    from the committed source's — mtimes say nothing in a copied tree.
    Shared by every native component; raises on a missing/broken
    toolchain (each caller decides how to degrade). The .tmp renames
    keep a concurrent builder in another process from dlopening a
    half-written file."""
    src = os.path.join(_HERE, src_name)
    so = os.path.join(_HERE, so_name)
    stamp = so + ".sha256"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    built = None
    if os.path.exists(so) and os.path.exists(stamp):
        with open(stamp) as f:
            built = f.read().strip()
    if built != digest:
        tmp = so + ".%d.tmp" % os.getpid()
        subprocess.check_call(
            ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
             "-o", tmp, src],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        os.replace(tmp, so)
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, stamp)
    return ctypes.CDLL(so)


def _build_and_load() -> Optional[ctypes.CDLL]:
    """Load the parser library via compile_and_load, binding signatures.
    Returns None when no working toolchain is available."""
    global _LIB, _LIB_FAILED
    with _LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        try:
            lib = compile_and_load("parser.cpp", "_parser.so")
            lib.ParseDense.restype = ctypes.c_int
            lib.ParseDense.argtypes = [
                ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long)]
            lib.ParseLibSVM.restype = ctypes.c_int
            lib.ParseLibSVM.argtypes = [
                ctypes.c_char_p,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
                ctypes.POINTER(ctypes.c_long),
                ctypes.POINTER(ctypes.c_long)]
            lib.FreeBuffer.restype = None
            lib.FreeBuffer.argtypes = [ctypes.c_void_p]
            lib.GreedyFindBin.restype = ctypes.c_int
            lib.GreedyFindBin.argtypes = [
                ctypes.POINTER(ctypes.c_double),
                ctypes.POINTER(ctypes.c_double),
                ctypes.c_long, ctypes.c_int, ctypes.c_double,
                ctypes.c_int, ctypes.POINTER(ctypes.c_double)]
            _LIB = lib
        except Exception:
            _LIB_FAILED = True
            from ..utils import log
            log.warning("native parser unavailable (g++ build failed); "
                        "falling back to numpy text parsing")
        return _LIB


def available() -> bool:
    """True when the native library built and loaded (binning and text
    parsing then take the C++ paths, else their Python fallbacks)."""
    return _build_and_load() is not None


def parse_dense(path: str, delim: str, skip_rows: int
                ) -> Optional[np.ndarray]:
    """Parse a CSV/TSV file into a row-major float64 array, or None if
    the native library is unavailable (caller falls back to numpy)."""
    lib = _build_and_load()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_double)()
    rows = ctypes.c_long()
    cols = ctypes.c_long()
    rc = lib.ParseDense(path.encode(), delim.encode(), skip_rows,
                        ctypes.byref(out), ctypes.byref(rows),
                        ctypes.byref(cols))
    if rc != 0:
        if rc == 1:
            raise OSError("cannot read %s" % path)
        return None
    try:
        n = rows.value * cols.value
        arr = np.ctypeslib.as_array(out, shape=(n,)).copy()
        return arr.reshape(rows.value, cols.value)
    finally:
        lib.FreeBuffer(out)


def greedy_find_bin(distinct_values: np.ndarray, counts: np.ndarray,
                    max_bin: int, total_cnt: int,
                    min_data_in_bin: int) -> Optional[np.ndarray]:
    """Native GreedyFindBin (reference: src/io/bin.cpp:78) — returns the
    bin upper bounds, or None when the native library is unavailable
    (caller falls back to the Python implementation)."""
    lib = _build_and_load()
    if lib is None:
        return None
    dv = np.ascontiguousarray(distinct_values, dtype=np.float64)
    cn = np.ascontiguousarray(counts, dtype=np.float64)
    out = np.empty(max(max_bin, 1) + 1, dtype=np.float64)
    n = lib.GreedyFindBin(
        dv.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cn.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_long(len(dv)), ctypes.c_int(int(max_bin)),
        ctypes.c_double(float(total_cnt)),
        ctypes.c_int(int(min_data_in_bin)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return out[:n].copy()


def parse_libsvm(path: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Parse a LibSVM file → (dense X, labels), or None if unavailable."""
    lib = _build_and_load()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_double)()
    labels = ctypes.POINTER(ctypes.c_double)()
    rows = ctypes.c_long()
    cols = ctypes.c_long()
    rc = lib.ParseLibSVM(path.encode(), ctypes.byref(out),
                         ctypes.byref(labels), ctypes.byref(rows),
                         ctypes.byref(cols))
    if rc != 0:
        if rc == 1:
            raise OSError("cannot read %s" % path)
        return None
    try:
        n = rows.value * cols.value
        X = np.ctypeslib.as_array(out, shape=(n,)).copy() \
            .reshape(rows.value, cols.value)
        y = np.ctypeslib.as_array(labels, shape=(rows.value,)).copy()
        return X, y
    finally:
        lib.FreeBuffer(out)
        lib.FreeBuffer(labels)
