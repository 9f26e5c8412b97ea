"""The arithmetic behind the end-to-end metrics."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile of all ``samples`` by nearest rank: the
    smallest sample that at least ``q`` % of the samples do not exceed.
    A failed request enters as ``math.inf``, so it misses every limit."""
    if not samples:
        raise ValueError("no samples")
    ordered = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate(count: int, window_s: float) -> float:
    """Work over the whole window: ``count`` items in ``window_s`` seconds."""
    if window_s <= 0:
        raise ValueError("empty window")
    return count / window_s


def share(part: float, whole: float) -> Optional[float]:
    """``part`` as a percentage of ``whole``; None where ``whole`` is 0."""
    return None if whole <= 0 else 100.0 * part / whole
