"""The one reduction two modules share."""

from __future__ import annotations

import math


def percentile(xs, p: float) -> float:
    """Nearest rank: the smallest value with at least ``p`` per cent of
    the sample at or below it."""
    xs = sorted(xs)
    return xs[max(0, math.ceil(p / 100.0 * len(xs)) - 1)]
