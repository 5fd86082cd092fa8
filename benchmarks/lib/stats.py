"""The arithmetic every end-to-end number goes through."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics — numpy's default rule, written out so the
    yardstick depends on no library's choice."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate_between_fences(fences: Sequence[tuple[float, float]]) -> float:
    """Units per second between the first and the last fence.

    ``fences`` are ``(seconds, units completed so far)`` pairs taken where
    the host waited for the device. The rate is over all the work and all
    the time between the two outer fences — not over the nominal window,
    which whole steps would quantise.
    """
    if len(fences) < 2:
        raise ValueError("a rate needs two fences")
    (t0, n0), (t1, n1) = fences[0], fences[-1]
    if t1 <= t0:
        raise ValueError("fences out of order")
    return (n1 - n0) / (t1 - t0)
