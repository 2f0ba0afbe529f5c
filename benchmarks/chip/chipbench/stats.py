"""Order statistics over every sample of a window."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile of all ``values`` (linear interpolation
    between closest ranks, numpy's default); None for no samples. An
    infinite sample (a request that failed) at or next to the rank makes
    the percentile infinite."""
    values = np.sort(np.asarray(values, np.float64))
    if values.size == 0:
        return None
    pos = q / 100 * (values.size - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if np.isinf(values[hi]) and (hi > lo or np.isinf(values[lo])):
        return float("inf")
    return float(values[lo] + (pos - lo) * (values[hi] - values[lo]))
