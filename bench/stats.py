"""Statistics over a measured window: every request the window completed
counts, and a rate divides all the work by all the time."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation) of all values."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(amount: float, seconds: float) -> float | None:
    return None if seconds <= 0 else amount / seconds
