"""Statistics over every sample of a window (no subsampling, no trimming)."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of all ``values`` (linear interpolation
    between order statistics); ``None`` for no samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(count: float, seconds: float) -> float:
    """Work over the whole window: ``count`` over all ``seconds``."""
    if seconds <= 0:
        raise ValueError("a window must last longer than 0 s")
    return count / seconds


def window_gaps(stamps: Sequence[float], lo: float, hi: float):
    """Gaps between consecutive stamps of one request, both in [lo, hi]."""
    inside = [t for t in stamps if lo <= t <= hi]
    return [b - a for a, b in zip(inside, inside[1:])]
