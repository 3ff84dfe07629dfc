"""Percentiles over every sample, as ``repro.core.tracing.LatencyStats``
computes them (numpy's linear interpolation), copied here so that the
yardstick cannot change with the program."""
from __future__ import annotations

from typing import Sequence

import numpy as np


def percentile(samples: Sequence[float], p: float) -> float:
    """``p``-th percentile of all ``samples``; a sample of ``inf`` (a
    request that never returned) counts as slower than every other."""
    if not len(samples):
        raise ValueError("no samples")
    return float(np.percentile(np.asarray(samples, np.float64), p))


def prorated(spans: Sequence, t0: float, t1: float) -> float:
    """Sum of ``amount * overlap`` over ``(start, end, amount)`` spans, where
    ``overlap`` is the share of [start, end] that lies inside [t0, t1]."""
    total = 0.0
    for start, end, amount in spans:
        if end <= start:
            total += amount if t0 <= end <= t1 else 0.0
            continue
        inside = max(0.0, min(end, t1) - max(start, t0))
        total += amount * inside / (end - start)
    return total
