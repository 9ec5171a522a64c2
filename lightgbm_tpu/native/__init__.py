"""Native (C++) runtime components, loaded through ctypes.

The reference's data loader is C++ (src/io/parser.cpp, text_reader.h);
this package holds the TPU build's native equivalents. Libraries are
compiled ON DEMAND with the system toolchain (g++ -O3 -shared) and
cached next to the source; everything degrades gracefully to the pure
NumPy fallbacks when no compiler is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fastparse.cpp")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
# how this process got the library: "built" (compiled here), "loaded"
# (a library built from this exact source was already on disk) or
# "absent" (no compiler / build failed -> NumPy fallbacks). None until
# the first get_lib(). chip_smoke.py prints it: the NumPy binning
# fallback is several times slower and must not pass unnoticed.
_status: Optional[str] = None


def _lib_path() -> str:
    """The library's path embeds a hash of the source it was built
    from, so freshness is a CONTENT check: a copied tree, a restored
    backup or an `rsync -t` cannot make a stale build look current the
    way an mtime comparison could."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"_fastparse.{digest}.so")


def _build(lib_path: str) -> bool:
    """Compile fastparse to a tmp file and atomically rename into
    place. The rename makes concurrent builders safe WITHOUT a lock:
    each builder — thread or process — writes its own tmp .so (pid +
    thread id in the name) and os.replace is atomic, so a reader only
    ever sees a complete library — get_lib deliberately does not hold
    the module lock across this (the concurrency linter's
    blocking-under-lock rule: a 180 s g++ run under `_lock` would
    stall every thread touching the parser)."""
    import time

    from .. import log
    from ..obs.metrics import record_native_build

    tmp = f"{lib_path}.build.{os.getpid()}.{threading.get_ident()}"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        _SRC, "-o", tmp,
    ]
    t0 = time.perf_counter()
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=180)
        if r.returncode != 0:
            record_native_build(time.perf_counter() - t0, ok=False)
            log.warning(
                f"native fastparse build failed (falling back to numpy "
                f"parsers): {r.stderr.strip()[-300:]}"
            )
            return False
        os.replace(tmp, lib_path)
        record_native_build(time.perf_counter() - t0, ok=True)
        return True
    except (OSError, subprocess.TimeoutExpired) as e:
        record_native_build(time.perf_counter() - t0, ok=False)
        log.warning(
            f"native fastparse not built ({type(e).__name__}: {e}); "
            "falling back to the slower numpy parsers/binning"
        )
        return False
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def _load_or_build() -> Tuple[Optional[ctypes.CDLL], str]:
    """Build-if-missing + dlopen + bind, called OUTSIDE the module lock
    (only the _lib/_tried/_status state below is lock-guarded).
    Returns (library or None, status)."""
    lib_path = _lib_path()
    status = "loaded"
    if not os.path.exists(lib_path):
        if not _build(lib_path):
            return None, "absent"
        status = "built"
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None, "absent"
    _bind(lib)
    return lib, status


def get_lib() -> Optional[ctypes.CDLL]:
    """The fastparse library, building it on first use; None if
    unavailable (no g++ / build failure). Concurrent first callers may
    each run a build (atomic-rename safe); the winner's handle is the
    one cached."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
    lib, status = _load_or_build()
    with _lock:
        # prefer a non-None result: a transiently-failing concurrent
        # loader must not cache None over another thread's good handle
        if not _tried or (_lib is None and lib is not None):
            _tried = True
            _lib = lib
            _status = status
        return _lib


def status() -> str:
    """"built" | "loaded" | "absent" — see `_status`."""
    get_lib()
    with _lock:
        return _status or "absent"


def _bind(lib: ctypes.CDLL) -> None:
    lib.fp_parse_delim.restype = ctypes.c_int
    lib.fp_parse_delim.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.fp_parse_libsvm.restype = ctypes.c_int
    lib.fp_parse_libsvm.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.fp_free.restype = None
    lib.fp_free.argtypes = [ctypes.POINTER(ctypes.c_double)]
    lib.fp_greedy_find_bin.restype = ctypes.c_int64
    lib.fp_greedy_find_bin.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double),
    ]
    lib.fp_values_to_bins.restype = None
    lib.fp_values_to_bins.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
    ]
    P = ctypes.POINTER
    lib.fp_predict.restype = ctypes.c_int64
    lib.fp_predict.argtypes = [
        P(ctypes.c_double), ctypes.c_int64, ctypes.c_int64,
        P(ctypes.c_int32), ctypes.c_int64,
        P(ctypes.c_int64), P(ctypes.c_int32), P(ctypes.c_double),
        P(ctypes.c_int32), P(ctypes.c_int32), P(ctypes.c_int32),
        P(ctypes.c_int64), P(ctypes.c_double),
        P(ctypes.c_uint32), P(ctypes.c_int64), P(ctypes.c_int64),
        P(ctypes.c_double),
    ]


def _take(lib, ptr, shape) -> np.ndarray:
    arr = np.ctypeslib.as_array(ptr, shape=shape).copy()
    lib.fp_free(ptr)
    return arr


def parse_delim(path: str, delim: str, skip_rows: int) -> Optional[np.ndarray]:
    """(rows, cols) float64 matrix, or None when native is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_double)()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.fp_parse_delim(
        path.encode(), delim.encode(), skip_rows,
        ctypes.byref(out), ctypes.byref(rows), ctypes.byref(cols),
    )
    if rc != 0:
        return None
    return _take(lib, out, (rows.value, cols.value))


def greedy_find_bin(distinct: np.ndarray, counts: np.ndarray, max_bin: int,
                    total_cnt: int, min_data_in_bin: int
                    ) -> Optional[np.ndarray]:
    """Native GreedyFindBin (bit-exact C++ mirror of binning.py:46 /
    reference bin.cpp:80); None when the library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    distinct = np.ascontiguousarray(distinct, dtype=np.float64)
    counts = np.ascontiguousarray(counts, dtype=np.int64)
    out = np.empty(max(int(max_bin), 1) + 2, dtype=np.float64)
    n = lib.fp_greedy_find_bin(
        distinct.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(distinct), int(max_bin), int(total_cnt), int(min_data_in_bin),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
    )
    return out[:n]


def values_to_bins(values: np.ndarray, bounds: np.ndarray, nan_target: int
                   ) -> Optional[np.ndarray]:
    """Native multithreaded numerical ValueToBin; None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.float64)
    out = np.empty(len(values), dtype=np.int32)
    lib.fp_values_to_bins(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(values),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(bounds), int(nan_target),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out


class PackedModel:
    """Flat tree arrays for fp_predict, built once per Booster model
    state (reference SingleRowPredictor caching, c_api.cpp:66).

    This offset-flat layout (per-tree node/leaf offsets into shared 1-D
    arrays) is the host/C++ walker's shape; the TPU serving predictor
    packs the same per-tree fields into DENSE (T, max_nodes) tables
    instead (serving/forest.py pack_forest_tables), because lockstep
    device traversal wants every lane indexing one rectangular table.
    Decision semantics must stay identical across all three predictors
    (tree.py go_left is the single source of truth; the serving parity
    tests assert it)."""

    def __init__(self, trees) -> None:
        n_nodes = [max(t.num_leaves - 1, 0) for t in trees]
        off = np.zeros(len(trees) + 1, np.int64)
        np.cumsum(n_nodes, out=off[1:])
        loff = np.zeros(len(trees) + 1, np.int64)
        np.cumsum([max(t.num_leaves, 1) for t in trees], out=loff[1:])
        tot = int(off[-1])
        self.node_off = off
        self.leaf_off = loff
        self.feature = np.zeros(tot, np.int32)
        self.threshold = np.zeros(tot, np.float64)
        self.dtype = np.zeros(tot, np.int32)
        self.left = np.zeros(tot, np.int32)
        self.right = np.zeros(tot, np.int32)
        self.leaf_value = np.zeros(int(loff[-1]), np.float64)
        catw_parts = []
        self.cat_lo = np.zeros(tot, np.int64)
        self.cat_hi = np.zeros(tot, np.int64)
        wbase = 0
        for ti, t in enumerate(trees):
            a, b = int(off[ti]), int(off[ti + 1])
            if b > a:
                self.feature[a:b] = t.split_feature[: b - a]
                self.threshold[a:b] = t.threshold[: b - a]
                self.dtype[a:b] = np.asarray(
                    t.decision_type[: b - a], np.int32
                )
                self.left[a:b] = t.left_child[: b - a]
                self.right[a:b] = t.right_child[: b - a]
                cb = np.asarray(t.cat_boundaries, np.int64)
                words = np.asarray(t.cat_threshold, np.uint32)
                if len(words):
                    catw_parts.append(words)
                cat_k = a + np.flatnonzero(self.dtype[a:b] & 1)
                if len(cat_k):
                    ci = self.threshold[cat_k].astype(np.int64)
                    self.cat_lo[cat_k] = wbase + cb[ci]
                    self.cat_hi[cat_k] = wbase + cb[ci + 1]
                wbase += len(words)
            la = int(loff[ti])
            lv = np.asarray(t.leaf_value, np.float64)
            self.leaf_value[la : la + len(lv)] = lv
        self.catw = (
            np.concatenate(catw_parts).astype(np.uint32)
            if catw_parts else np.zeros(1, np.uint32)
        )
        # widest feature referenced: callers must verify X has more
        # columns (the numpy walk raises IndexError; the C side would
        # read out of bounds)
        self.max_feature = int(self.feature.max()) if tot else -1


def predict_packed(pm: "PackedModel", X: np.ndarray,
                   tree_idx: np.ndarray) -> Optional[np.ndarray]:
    """Sum of leaf outputs of `tree_idx` trees per row; None when the
    native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    if X.shape[1] <= pm.max_feature:
        return None  # host walk raises the proper IndexError
    X = np.ascontiguousarray(X, dtype=np.float64)
    tree_idx = np.ascontiguousarray(tree_idx, dtype=np.int32)
    out = np.empty(X.shape[0], np.float64)

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    lib.fp_predict(
        p(X, ctypes.c_double), X.shape[0], X.shape[1],
        p(tree_idx, ctypes.c_int32), len(tree_idx),
        p(pm.node_off, ctypes.c_int64), p(pm.feature, ctypes.c_int32),
        p(pm.threshold, ctypes.c_double), p(pm.dtype, ctypes.c_int32),
        p(pm.left, ctypes.c_int32), p(pm.right, ctypes.c_int32),
        p(pm.leaf_off, ctypes.c_int64), p(pm.leaf_value, ctypes.c_double),
        p(pm.catw, ctypes.c_uint32), p(pm.cat_lo, ctypes.c_int64),
        p(pm.cat_hi, ctypes.c_int64), p(out, ctypes.c_double),
    )
    return out


def parse_libsvm(path: str) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(labels (N,), dense features (N, F)) or None when unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_double)()
    lab = ctypes.POINTER(ctypes.c_double)()
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.fp_parse_libsvm(
        path.encode(), ctypes.byref(out), ctypes.byref(lab),
        ctypes.byref(rows), ctypes.byref(cols),
    )
    if rc != 0:
        return None
    feats = _take(lib, out, (rows.value, cols.value))
    labels = _take(lib, lab, (rows.value,))
    return labels, feats
