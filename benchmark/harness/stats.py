"""Percentile and spread arithmetic over the samples of one run."""

from __future__ import annotations

import math
import statistics

import numpy as np


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 1]) of ``samples``: the smallest
    sample with at least ``q`` of the samples at or below it. No
    interpolation, so the value is one that was measured."""
    ordered = sorted(samples)
    if not ordered:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must lie in (0, 1], got {q}")
    return ordered[max(1, math.ceil(q * len(ordered))) - 1]


def samples_beyond(samples, q: float) -> int:
    """How many samples lie strictly above the ``q`` percentile. A tail with
    fewer than ten beyond it is closer to a maximum than to a percentile."""
    p = percentile(samples, q)
    return sum(1 for s in samples if s > p)


def median(samples) -> float:
    return statistics.median(samples)


def quartiles(samples) -> tuple[float, float, float]:
    """(q1, median, q3), interpolated between order statistics: for spreads
    over a handful of runs."""
    q1, med, q3 = np.percentile(list(samples), [25, 50, 75])
    return float(q1), float(med), float(q3)


def spread(samples) -> float:
    """Distance between the quartiles over the median: the run-to-run spread
    the bounds are set from."""
    q1, med, q3 = quartiles(samples)
    return (q3 - q1) / abs(med) if med else float("inf")
