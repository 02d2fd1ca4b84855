"""Percentiles, spreads and the comparisons of norms: host arithmetic only."""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest ranks. Raises on an empty sample: a metric without samples is
    left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail_support(n: int, q: float) -> int:
    """How many samples lie beyond the ``q``-th percentile of ``n``."""
    return int(math.floor(n * (100.0 - q) / 100.0))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them."""
    q1, _, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / statistics.median(values)


def histogram_quantile(before: List, after: List, q: float) -> Optional[float]:
    """Quantile of what a cumulative histogram gained between two readings
    (lists of ``(upper_bound, cumulative_count)``), interpolated inside the
    bucket that holds it, as Prometheus does. None if nothing was observed."""
    gained = [(b, a_c - b_c) for (b, a_c), (_, b_c) in zip(after, before)]
    total = gained[-1][1]
    if total <= 0:
        return None
    rank = q * total
    lo_bound, lo_count = 0.0, 0
    for bound, count in gained:
        if count >= rank:
            if math.isinf(bound):
                return lo_bound
            share = (rank - lo_count) / max(count - lo_count, 1)
            return lo_bound + (bound - lo_bound) * share
        lo_bound, lo_count = bound, count
    return lo_bound


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float]) -> dict:
    """Per leaf: |got - ref| of two norms over the larger of the reference's
    norm of that leaf and of the median leaf. Returns the worst and its leaf."""
    if set(got) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(ref))[:4]}")
    med = statistics.median(ref.values())
    worst, name = 0.0, None
    for k, r in ref.items():
        gap = abs(got[k] - r) / max(r, med, 1e-30)
        if not math.isfinite(gap):
            return {"gap": math.inf, "leaf": k}
        if gap >= worst:
            worst, name = gap, k
    return {"gap": worst, "leaf": name}


def rel_gap(got: float, ref: float) -> float:
    if not (math.isfinite(got) and math.isfinite(ref)):
        return math.inf
    return abs(got - ref) / max(abs(ref), 1e-30)


def summarize(values: Iterable[float]) -> dict:
    xs = [float(v) for v in values]
    if not xs:
        return {"n": 0}
    return {"n": len(xs), "p50": percentile(xs, 50), "p95": percentile(xs, 95),
            "max": max(xs)}
