"""Percentiles over every sample, never over chunks or medians of them."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Nearest-rank ``p``-th percentile of all ``values``.

    ``inf`` (a request that never got its answer) ranks above every finite
    value and is returned as such when the rank lands on it.  ``None`` for
    no samples.
    """
    v: List[float] = sorted(float(x) for x in values)
    if not v:
        return None
    rank = max(1, math.ceil(p / 100.0 * len(v)))
    return v[rank - 1]

