"""Tail arithmetic over requests, with failed requests counted as misses."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated ``q``-th percentile (0..100); ``inf`` entries
    (failed requests) sort last, so a tail that reaches them is ``inf``."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if vals[hi] == math.inf:
        return math.inf
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def with_misses(values: Iterable[Optional[float]]) -> List[float]:
    """Latencies with ``None`` (no answer: failed, shed, never finished)
    replaced by ``inf``, so it misses every limit."""
    return [math.inf if v is None else float(v) for v in values]


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles``, exclusive method)."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else math.inf
