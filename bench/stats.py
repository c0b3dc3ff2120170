"""The benchmark's arithmetic: rates over a window and tails over all
samples.  No chunk medians, no JAX."""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def rate(stamps: np.ndarray, t0: float, seconds: float) -> float:
    """Items whose stamp falls inside [t0, t0 + seconds], per second of
    the whole window."""
    s = np.asarray(stamps, float)
    n = int(((s >= t0) & (s <= t0 + seconds)).sum())
    return n / seconds


def percentile(values: Sequence[float], q: float) -> float:
    """The nearest-rank ``q`` quantile (0 < q <= 1) of every value: the
    smallest value with at least ``q`` of all values at or below it."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        raise ValueError("percentile of no values")
    k = max(1, int(math.ceil(q * v.size - 1e-9)))
    return float(v[k - 1])

