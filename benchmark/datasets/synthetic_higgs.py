"""Higgs-shaped synthetic data from a seed: dense float32 features and a
binary label.

A copy of ``bench.synthetic_higgs`` (the generator every chip record of
this repo before the benchmark was about) with the seed made a
parameter and the draw made fast enough not to dominate set-up: float32
normals from ``numpy.random.Generator`` in fixed-size blocks, each block
seeded from (seed, block index), so the data does not depend on how many
threads draw it. The label rule is the original's and is FIXED (its
coefficients do not depend on the seed, so that quality is comparable
across seeds; the rows and the noise are the seed's): half of the
features act linearly, one through a sine, plus unit noise. Every feature is
continuous, so all ``max_bin`` bins of every feature are filled — real
Higgs has a few low-cardinality columns; for the histogram kernel this
is the worst case."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

BLOCK_ROWS = 1 << 20
DRAW_THREADS = 8
RULE_SEED = 17  # bench.synthetic_higgs's seed, kept for the label rule


def _weights(features: int) -> np.ndarray:
    return np.random.default_rng(RULE_SEED).standard_normal(features)


def _fill(seed: int, stream: int, X: np.ndarray, y: np.ndarray,
          w: np.ndarray) -> None:
    half = X.shape[1] // 2

    def block(b: int) -> None:
        lo, hi = b * BLOCK_ROWS, min((b + 1) * BLOCK_ROWS, X.shape[0])
        rng = np.random.default_rng([seed, stream, b])
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        logits = (X[lo:hi, :half] @ w[:half].astype(np.float32)
                  + 2.0 * np.sin(X[lo:hi, half]))
        noise = rng.standard_normal(hi - lo, dtype=np.float32)
        y[lo:hi] = (logits + noise > 0)

    n_blocks = -(-X.shape[0] // BLOCK_ROWS)
    with ThreadPoolExecutor(DRAW_THREADS) as pool:
        list(pool.map(block, range(n_blocks)))


def make(seed: int, rows: int, valid_rows: int, features: int
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(X, y, X_valid, y_valid); the valid rows are a separate draw,
    never part of the training matrix."""
    w = _weights(features)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    Xv = np.empty((valid_rows, features), np.float32)
    yv = np.empty(valid_rows, np.float32)
    _fill(seed, 1, X, y, w)
    _fill(seed, 2, Xv, yv, w)
    return X, y, Xv, yv
