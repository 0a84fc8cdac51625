"""Arithmetic on raw samples: exact percentiles and open-loop latency."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile of the raw samples: the smallest sample with
    at least ``p`` percent of the samples at or below it. No buckets and
    no interpolation, so the value is always one that was measured."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[rank - 1]


def latencies_from_due(due, done) -> list[float]:
    """Per-request latency in an open loop: completion minus the time the
    request was due, so a stall also charges every request queued behind
    it (not only the one that stalled)."""
    return [d1 - d0 for d0, d1 in zip(due, done)]

