"""Rate and percentile arithmetic of the benchmark (no JAX, no program code).

Every number a cell reports as a time or a rate goes through these
functions, so a later PR cannot change how a median or a tail is taken.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

# a tail is reported only where at least this many samples lie beyond it
MIN_SAMPLES_BEYOND_TAIL = 10


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100], of a non-empty
    sequence (the same definition as ``numpy.percentile``'s default)."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError("q must lie in [0, 100], got %r" % (q,))
    xs = sorted(float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def tail(values: Sequence[float], q: float) -> Optional[float]:
    """``percentile(values, q)`` where at least MIN_SAMPLES_BEYOND_TAIL
    samples lie beyond it, else None: a 99th percentile of 200 samples
    is two samples' opinion."""
    beyond = len(values) * (100.0 - q) / 100.0
    if beyond < MIN_SAMPLES_BEYOND_TAIL:
        return None
    return percentile(values, q)


def rate(samples: float, t_first: float, t_last: float) -> float:
    """Samples completed per second between two clock readings."""
    if t_last <= t_first:
        raise ValueError("window of no length: %r .. %r" % (t_first, t_last))
    return samples / (t_last - t_first)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the run-to-run
    spread the bounds are set from."""
    m = median(values)
    if m == 0:
        raise ValueError("spread around a zero median")
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(m)


def metric(value: float, unit: str, **extra) -> Dict:
    """One entry of the result line's ``metrics``: the value as
    measured, with all its digits."""
    if value is None or not math.isfinite(float(value)):
        raise ValueError("metric value must be a finite number, got %r"
                         % (value,))
    out = {"value": float(value), "unit": unit}
    out.update(extra)
    return out
