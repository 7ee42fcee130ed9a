"""Percentiles and shares as the benchmark defines them."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile: the smallest sample with at least ``q``% of
    the samples at or below it.  None for no samples."""
    if not values:
        return None
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])


def share(part: float, whole: float) -> Optional[float]:
    """``part / whole`` in percent, None when ``whole`` is 0."""
    return 100.0 * part / whole if whole else None
