"""Percentile and lateness arithmetic, kept with the yardstick."""

from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it.  No interpolation, so a reported tail is
    always a latency some request really had."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def lateness(due: Sequence[float], sent: Sequence[float]) -> Dict[str, float]:
    """How late the generator ran: sent minus due, in milliseconds."""
    late = [max(0.0, (s - d) * 1e3) for d, s in zip(due, sent)]
    if not late:
        return {"n": 0, "p50_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    return {
        "n": len(late),
        "p50_ms": percentile(late, 50),
        "p99_ms": percentile(late, 99),
        "max_ms": max(late),
    }


def worst_leaf_gap(prog: List[float], ref: List[float]) -> float:
    """Worst leaf of |program's norm - reference's norm|, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' gradients are all but zero)."""
    med = median(ref)
    return max(
        abs(p - r) / max(r, med, 1e-30) for p, r in zip(prog, ref)
    )
