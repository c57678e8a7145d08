"""Order statistics the metric readers and tools share."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank ``p``-th percentile: the smallest value with at least
    p% of ``values`` at or below it.  None for no values."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]

