"""Expo-shaped synthetic data from a seed: the 8 raw columns of the Data
Expo 2009 airline on-time set in their NATIVE form (the reference's Expo
experiment publishes their one-hot, 700 columns; its documentation tells
users to pass integer codes as ``categorical_feature`` instead), and a
binary label "the flight was delayed".

Columns, in order, float32 (a code is a non-negative integer):

0. ``Month``          12 codes, uniform
1. ``DayofMonth``     31 codes, uniform
2. ``DayOfWeek``       7 codes, uniform
3. ``DepTime``        numerical hhmm 0..2359 from a two-peak day curve
                      (morning and evening banks); NaN in 2% of the rows
4. ``UniqueCarrier``  22 codes, Zipf exponent 1.0
5. ``Origin``         305 codes, Zipf exponent 1.0
6. ``Dest``           305 codes, Zipf exponent 1.0 (drawn apart from 5)
7. ``Distance``       numerical, log-normal clipped to [11, 4962]

At ``max_bin=255`` the 254 most frequent airports keep a bin each and
the rest, about 2.9% of the rows of each airport column, share the other
bin; the valid rows draw from the same 305 codes. The label is a
Bernoulli draw of a logistic score: a fixed effect per code of carrier,
origin, destination, month, weekday and day of the month, a smooth curve
over the hour of the day, an effect of its own for a missing ``DepTime``,
and a weak distance term; about 19% of the rows are positive and no
column is pure noise. The rule is FIXED (``RULE_SEED``: the effects do not
depend on the seed, so that quality compares across seeds); the rows and
the Bernoulli draws are the seed's. Rows are drawn in blocks of 2^20,
each seeded from (seed, stream, block), on several threads: the data
does not depend on how many draw it."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

BLOCK_ROWS = 1 << 20
DRAW_THREADS = 8
RULE_SEED = 2009
FEATURES = 8
CATEGORICAL = (0, 1, 2, 4, 5, 6)
# (column, codes, Zipf exponent or None for uniform, effect scale)
CODED = ((0, 12, None, 0.25), (1, 31, None, 0.08), (2, 7, None, 0.15),
         (4, 22, 1.0, 0.35), (5, 305, 1.0, 0.45), (6, 305, 1.0, 0.35))
NAN_SHARE = 0.02
NAN_EFFECT = 1.2
INTERCEPT = -1.60


class _Rule:
    """The fixed part: per-code effects and the code distributions."""

    def __init__(self) -> None:
        rng = np.random.default_rng(RULE_SEED)
        self.effects = {}
        self.cdf = {}
        for col, codes, zipf, scale in CODED:
            self.effects[col] = (scale * rng.standard_normal(codes)
                                 ).astype(np.float32)
            if zipf is not None:
                p = 1.0 / np.arange(1, codes + 1) ** zipf
                self.cdf[col] = np.cumsum(p / p.sum())


def _block(rule: _Rule, rng: np.random.Generator, X: np.ndarray,
           y: np.ndarray) -> None:
    n = X.shape[0]
    score = np.full(n, INTERCEPT, np.float32)
    for col, codes, zipf, _scale in CODED:
        if zipf is None:
            code = rng.integers(0, codes, n)
        else:
            code = np.minimum(
                np.searchsorted(rule.cdf[col], rng.random(n)), codes - 1)
        X[:, col] = code
        score += rule.effects[col][code]
    # departure hour: a morning and an evening bank
    evening = rng.random(n) < 0.55
    hour = np.where(evening, 16.5, 8.5) + np.where(evening, 3.2, 2.2) \
        * rng.standard_normal(n, dtype=np.float32)
    hour = np.clip(hour, 0.0, 23.99)
    minute = rng.integers(0, 60, n)
    missing = rng.random(n) < NAN_SHARE
    X[:, 3] = np.where(missing, np.nan, np.floor(hour) * 100 + minute)
    # delays build up over the day; a cancelled departure has no time
    score += np.where(missing, NAN_EFFECT,
                      0.7 * np.tanh((hour - 13.0) / 5.0)).astype(np.float32)
    log_dist = 6.4 + 0.8 * rng.standard_normal(n, dtype=np.float32)
    dist = np.clip(np.exp(log_dist), 11.0, 4962.0)
    X[:, 7] = np.floor(dist)
    score += 0.15 * (np.log(dist) - 6.4)
    y[:] = rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-score))


def _fill(seed: int, stream: int, X: np.ndarray, y: np.ndarray,
          rule: _Rule) -> None:
    def block(b: int) -> None:
        lo, hi = b * BLOCK_ROWS, min((b + 1) * BLOCK_ROWS, X.shape[0])
        _block(rule, np.random.default_rng([seed, stream, b]), X[lo:hi],
               y[lo:hi])

    with ThreadPoolExecutor(DRAW_THREADS) as pool:
        list(pool.map(block, range(-(-X.shape[0] // BLOCK_ROWS))))


def make(seed: int, rows: int, valid_rows: int, features: int
         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(X, y, X_valid, y_valid); the valid rows are a separate draw."""
    if features != FEATURES:
        raise ValueError(f"synthetic_expo draws {FEATURES} columns, "
                         f"not {features}")
    rule = _Rule()
    X = np.empty((rows, FEATURES), np.float32)
    y = np.empty(rows, np.float32)
    Xv = np.empty((valid_rows, FEATURES), np.float32)
    yv = np.empty(valid_rows, np.float32)
    _fill(seed, 1, X, y, rule)
    _fill(seed, 2, Xv, yv, rule)
    return X, y, Xv, yv
