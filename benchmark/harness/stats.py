"""The two sample statistics the benchmark is judged by."""

from __future__ import annotations

from typing import Sequence

import numpy as np


def median(samples: Sequence[float]) -> float:
    return float(np.median(np.asarray(samples, np.float64)))


def quartile_spread(samples: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the spread the
    bounds in BENCHMARK.json are set from."""
    a = np.asarray(samples, np.float64)
    q1, q2, q3 = np.percentile(a, [25, 50, 75])
    return float((q3 - q1) / q2)
