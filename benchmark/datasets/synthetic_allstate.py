"""Allstate-shaped synthetic data from a seed: the 4,228 columns of the
reference's Allstate experiment (``docs/Experiments.rst``, the one-hot
of the Allstate Claim Prediction Challenge table, Kaggle 2011) as a
scipy CSR matrix, and a binary label "the policy had a claim".

Columns, in the Kaggle table's order, float32:

- one-hot blocks, one column per level, exactly one 1.0 a row:
  ``Calendar_Year`` 3, ``Model_Year`` 29, ``Blind_Make`` 85,
  ``Blind_Model`` 1,302, ``Blind_Submodel`` 2,708 (the three vehicle
  codes Zipf, exponent 1.0), ``Cat1``..``Cat12``, ``OrdCat`` (near
  uniform, except ``Cat9``: 80 / 20);
- 8 numerical ``Var1``..``Var8``, standard normal (dense);
- the one-hot block ``NVCat`` 15, near uniform;
- 4 numerical ``NVVar1``..``NVVar4``, standard normal (dense).

That is 4,216 one-hot columns + 12 numerical = 4,228, 31 stored entries a
row. The label is a Bernoulli draw of a logistic score: a fixed effect
per level of the three vehicle codes and of ``Cat1`` and ``NVCat``, a
weak term in ``Var1`` and ``NVVar1``, and an intercept that leaves about
1% of the rows positive. The rule is FIXED (``RULE_SEED``: the effects do
not depend on the seed, so that quality compares across seeds).

The TRAINING table is one table (``TABLE_SEED``), whatever the seed, as
the deployment's is: a team re-trains on its own table. The valid rows
are the seed's. Why: a leaf-wise tree on this table stops splitting a
leaf whose hessian falls under ``min_sum_hessian_in_leaf`` (100, about
15,600 rows at a 1.3% positive rate), so how many rounds of the rounds
grower a tree takes to reach 255 leaves depends on the training rows
drawn: over fresh draws an 8-tree job took 28 to 30 rounds at 48 slots
and the rate spread 2.0-2.2% by seed, past half the 2.5% bound (PERF.md
section 6, PR 38). On one table every seed does the same work.

Rows are drawn in blocks of 2^20, each seeded from (seed, stream,
block), on several threads: the data does not depend on how many draw
it. The table is the draw of seed ``TABLE_SEED`` = 1, row for row: a run
with seed 1 reads what it read when the training rows were the seed's."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

BLOCK_ROWS = 1 << 20
DRAW_THREADS = 8
RULE_SEED = 2011
TABLE_SEED = 1  # the training table's draw, whatever the run's seed
# (name, levels or 0 for one numerical column, distribution, effect)
# distribution: "zipf" (exponent 1.0), "flat" (near uniform), or a
# tuple of shares
VARIABLES = (
    ("Calendar_Year", 3, "flat", 0.0),
    ("Model_Year", 29, "flat", 0.0),
    ("Blind_Make", 85, "zipf", 0.35),
    ("Blind_Model", 1302, "zipf", 0.45),
    ("Blind_Submodel", 2708, "zipf", 0.55),
    ("Cat1", 11, "flat", 0.30),
    ("Cat2", 4, "flat", 0.0),
    ("Cat3", 7, "flat", 0.0),
    ("Cat4", 4, "flat", 0.0),
    ("Cat5", 4, "flat", 0.0),
    ("Cat6", 7, "flat", 0.0),
    ("Cat7", 5, "flat", 0.0),
    ("Cat8", 4, "flat", 0.0),
    ("Cat9", 2, (0.8, 0.2), 0.0),
    ("Cat10", 4, "flat", 0.0),
    ("Cat11", 7, "flat", 0.0),
    ("Cat12", 7, "flat", 0.0),
    ("OrdCat", 8, "flat", 0.0),
) + tuple((f"Var{i}", 0, "normal", 0.25 if i == 1 else 0.0)
          for i in range(1, 9)) + (
    ("NVCat", 15, "flat", 0.30),
) + tuple((f"NVVar{i}", 0, "normal", 0.2 if i == 1 else 0.0)
          for i in range(1, 5))
FEATURES = sum(max(n, 1) for _, n, _, _ in VARIABLES)  # 4,228
NNZ_PER_ROW = len(VARIABLES)  # 31
INTERCEPT = -4.75


class _Rule:
    """The fixed part: level distributions and per-level effects, and
    where each variable's columns start."""

    def __init__(self) -> None:
        rng = np.random.default_rng(RULE_SEED)
        self.start: List[int] = []
        self.cdf: List[np.ndarray] = []
        self.effects: List[np.ndarray] = []
        col = 0
        for _name, levels, dist, scale in VARIABLES:
            self.start.append(col)
            col += max(levels, 1)
            if levels == 0:
                self.cdf.append(np.empty(0))
                self.effects.append(np.float32(scale))
                continue
            if dist == "zipf":
                p = 1.0 / np.arange(1, levels + 1)
            elif dist == "flat":
                p = 1.0 + 0.5 * rng.random(levels)
            else:
                p = np.asarray(dist, np.float64)
            self.cdf.append(np.cumsum(p / p.sum()))
            self.effects.append(
                (scale * rng.standard_normal(levels)).astype(np.float32))
        assert col == FEATURES


def _block(rule: _Rule, rng: np.random.Generator, indices: np.ndarray,
           data: np.ndarray, y: np.ndarray) -> None:
    """One block of rows: their stored entries, ``NNZ_PER_ROW`` a row in
    column order, as (rows, NNZ_PER_ROW) views of the CSR arrays."""
    n = y.shape[0]
    score = np.full(n, INTERCEPT, np.float32)
    for k, (_name, levels, _dist, _scale) in enumerate(VARIABLES):
        if levels == 0:
            v = rng.standard_normal(n, dtype=np.float32)
            indices[:, k] = rule.start[k]
            data[:, k] = v
            score += rule.effects[k] * v
            continue
        code = np.minimum(np.searchsorted(rule.cdf[k], rng.random(n)),
                          levels - 1)
        indices[:, k] = rule.start[k] + code
        data[:, k] = 1.0
        score += rule.effects[k][code]
    y[:] = rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-score))


def _draw(seed: int, stream: int, rows: int, rule: _Rule):
    import scipy.sparse as sp

    indices = np.empty((rows, NNZ_PER_ROW), np.int32)
    data = np.empty((rows, NNZ_PER_ROW), np.float32)
    y = np.empty(rows, np.float32)

    def block(b: int) -> None:
        lo, hi = b * BLOCK_ROWS, min((b + 1) * BLOCK_ROWS, rows)
        _block(rule, np.random.default_rng([seed, stream, b]),
               indices[lo:hi], data[lo:hi], y[lo:hi])

    with ThreadPoolExecutor(DRAW_THREADS) as pool:
        list(pool.map(block, range(-(-rows // BLOCK_ROWS))))
    indptr = np.arange(0, rows * NNZ_PER_ROW + 1, NNZ_PER_ROW,
                       dtype=np.int64)
    X = sp.csr_matrix((data.ravel(), indices.ravel(), indptr),
                      shape=(rows, FEATURES))
    X.has_sorted_indices = True  # column order within every row
    return X, y


def make(seed: int, rows: int, valid_rows: int, features: int
         ) -> Tuple[object, np.ndarray, object, np.ndarray]:
    """(X, y, X_valid, y_valid), X as scipy CSR: the training table is
    ``TABLE_SEED``'s, the valid rows are a separate draw of ``seed``."""
    if features != FEATURES:
        raise ValueError(f"synthetic_allstate draws {FEATURES} columns, "
                         f"not {features}")
    rule = _Rule()
    X, y = _draw(TABLE_SEED, 1, rows, rule)
    Xv, yv = _draw(seed, 2, valid_rows, rule)
    return X, y, Xv, yv
