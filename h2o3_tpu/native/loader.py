"""Loader for the native C++ fast CSV parser (built lazily with g++).

The reference's ingest hot loop is Java (water/parser/CsvParser.java:16
parseChunk); its only native code arrives via the XGBoost JNI channel
(SURVEY.md §2.10). Here the data-loader IS native: csv_parser.cpp exposes a
C ABI consumed via ctypes, parsing file chunks in parallel threads into
typed column buffers that are handed straight to device_put. Falls back to
the pandas path in ingest/parser.py when the shared lib isn't built."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

_HERE = os.path.dirname(__file__)
_LIB_PATH = os.path.join(_HERE, "libh2o3tpu.so")
# hash of the sources the .so next to it was built from: a library whose
# stamp does not match today's .cpp files (or that has none — copied in from
# another machine) is rebuilt here rather than trusted
_STAMP_PATH = _LIB_PATH + ".sha256"
_SOURCES = ("csv_parser.cpp", "treeshap.cpp")
_LOCK = threading.Lock()
_LIB = None
_TRIED = False


def _source_hash() -> Optional[str]:
    """sha256 over the native sources present in this checkout (None when
    there are none)."""
    h = hashlib.sha256()
    found = False
    for name in _SOURCES:
        try:
            with open(os.path.join(_HERE, name), "rb") as f:
                h.update(name.encode() + b"\0" + f.read())
            found = True
        except OSError:
            continue
    return h.hexdigest() if found else None


def _built_hash() -> Optional[str]:
    try:
        with open(_STAMP_PATH, encoding="ascii") as f:
            return f.read().strip() or None
    except OSError:
        return None


def _build(src_hash: str) -> bool:
    srcs = [os.path.join(_HERE, f) for f in _SOURCES
            if os.path.exists(os.path.join(_HERE, f))]
    # build to a temp name then rename: an in-place relink would reuse
    # the inode, and glibc dlopen dedupes by dev/inode — a stale mapped
    # handle would be returned by the next CDLL (and truncating a mapped
    # .so can SIGBUS calls into the old mapping). No -march=native: the
    # library must run on whatever host the tree is copied to.
    tmp = f"{_LIB_PATH}.{os.getpid()}.build"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
             "-pthread", "-o", tmp] + srcs,
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, _LIB_PATH)
        with open(_STAMP_PATH + ".part", "w", encoding="ascii") as f:
            f.write(src_hash)
        os.replace(_STAMP_PATH + ".part", _STAMP_PATH)
        return True
    except (OSError, subprocess.SubprocessError):
        # no compiler / compile error: callers take their Python paths
        return False


def _wire_treeshap(lib) -> None:
    lib.h2o_treeshap.restype = None
    lib.h2o_treeshap.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_longlong, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_int, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int,
    ]


def get_lib():
    """The native library, (re)built when its stamp differs from the hash
    of today's sources; None when it cannot be built (no g++) — callers
    then take their pure-Python paths."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        src_hash = _source_hash()
        if src_hash is None:
            return None
        if not (os.path.exists(_LIB_PATH) and _built_hash() == src_hash) \
                and not _build(src_hash):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
            if hasattr(lib, "h2o_treeshap"):
                _wire_treeshap(lib)
            lib.h2o_parse_csv.restype = ctypes.c_longlong
            lib.h2o_parse_csv.argtypes = [
                ctypes.c_char_p,          # path
                ctypes.c_char,            # sep
                ctypes.c_int,             # has_header
                ctypes.c_int,             # ncols
                ctypes.POINTER(ctypes.c_int),  # col kinds (0=num,1=str)
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),  # out numeric bufs
                ctypes.c_longlong,        # capacity rows
                ctypes.c_int,             # nthreads
            ]
            lib.h2o_count_rows.restype = ctypes.c_longlong
            lib.h2o_count_rows.argtypes = [ctypes.c_char_p]
            _LIB = lib
        except (OSError, AttributeError):
            # AttributeError: a checkout missing one of the .cpp sources
            # builds a lib without that symbol — honor the None contract
            # (callers fall back to their pure-Python paths)
            _LIB = None
        return _LIB


def native_treeshap(binned: np.ndarray, forest, nthreads: int = 0
                    ) -> Optional[np.ndarray]:
    """Run the C++ TreeSHAP over a (n, F) int32 binned matrix and a
    CompressedForest; returns (n, F+1) float64 phi (bias column untouched)
    or None when the native lib is unavailable."""
    lib = get_lib()
    if lib is None or not hasattr(lib, "h2o_treeshap"):
        return None
    # treeshap.cpp's unique-path buffer is PE m[72]; extend() writes one
    # entry per root-to-leaf level, so forests deeper than ~70 would
    # overflow it — route those to the pure-Python fallback instead
    if getattr(forest, "max_depth", 0) + 2 > 70:
        return None
    n, F = binned.shape
    T, M = forest.feat.shape
    b = np.ascontiguousarray(binned, np.int32)
    feat = np.ascontiguousarray(forest.feat, np.int32)
    thresh = np.ascontiguousarray(forest.thresh_bin, np.int32)
    na_left = np.ascontiguousarray(forest.na_left, np.uint8)
    left = np.ascontiguousarray(forest.left, np.int32)
    right = np.ascontiguousarray(forest.right, np.int32)
    leaf_val = np.ascontiguousarray(forest.leaf_val, np.float32)
    cat_split = np.ascontiguousarray(forest.cat_split, np.int32)
    cat_table = np.ascontiguousarray(forest.cat_table, np.uint8)
    na_bins = np.ascontiguousarray(forest.na_bins, np.int32)
    cover = np.ascontiguousarray(forest.cover, np.float32)
    phi = np.zeros((n, F + 1), np.float64)

    def P(a, ct):
        return a.ctypes.data_as(ctypes.POINTER(ct))

    lib.h2o_treeshap(
        P(b, ctypes.c_int32), n, F,
        P(feat, ctypes.c_int32), P(thresh, ctypes.c_int32),
        P(na_left, ctypes.c_uint8), P(left, ctypes.c_int32),
        P(right, ctypes.c_int32), P(leaf_val, ctypes.c_float),
        P(cat_split, ctypes.c_int32), P(cat_table, ctypes.c_uint8),
        int(cat_table.shape[1]), P(na_bins, ctypes.c_int32),
        P(cover, ctypes.c_float), T, M,
        P(phi, ctypes.c_double),
        nthreads or min(os.cpu_count() or 4, 16))
    return phi


def native_parse_csv(path: str, setup) -> Optional[Dict[str, np.ndarray]]:
    """Parse numerics with the native lib; returns None to fall back when the
    lib is unavailable, the file is compressed, or any column is non-numeric
    (string/enum/time columns need host interning anyway)."""
    from h2o3_tpu.core.frame import T_NUM

    if path.endswith((".gz", ".zip")):
        return None
    if any(t != T_NUM for t in setup.column_types):
        return None
    lib = get_lib()
    if lib is None:
        return None
    nrows_cap = lib.h2o_count_rows(path.encode())
    if nrows_cap < 0:
        return None
    ncols = len(setup.column_names)
    bufs = [np.empty(nrows_cap, np.float64) for _ in range(ncols)]
    ptrs = (ctypes.POINTER(ctypes.c_double) * ncols)(
        *[b.ctypes.data_as(ctypes.POINTER(ctypes.c_double)) for b in bufs])
    kinds = (ctypes.c_int * ncols)(*([0] * ncols))
    n = lib.h2o_parse_csv(
        path.encode(), setup.separator.encode(), 1 if setup.check_header == 1 else 0,
        ncols, kinds, ptrs, nrows_cap, min(os.cpu_count() or 4, 16))
    if n < 0:
        return None
    return {name: bufs[i][:n] for i, name in enumerate(setup.column_names)}
