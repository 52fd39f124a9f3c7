"""Clocks and percentiles."""
from __future__ import annotations

import math
import time

now = time.perf_counter


def percentile(values, p):
    """The p-th percentile (0..100) by linear interpolation between order
    statistics; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    k = (len(vals) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return vals[lo] + (vals[hi] - vals[lo]) * (k - lo)


def fold_seed(seed, salt=0):
    """A seed numpy's RandomState takes (< 2**32), from any whole number."""
    return (int(seed) * 1000003 + salt) % (2 ** 32 - 1)
