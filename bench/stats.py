"""Percentiles, owned by the benchmark so that a change to the program's
own metric helpers cannot move the yardstick."""

from __future__ import annotations

from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over all samples: ``sorted(x)[min(n-1,
    int(q*n))]``.  An empty sample has no percentile."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    if not samples:
        raise ValueError("percentile of an empty sample")
    srt = sorted(samples)
    return float(srt[min(len(srt) - 1, int(q * len(srt)))])

