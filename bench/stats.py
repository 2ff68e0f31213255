"""Order statistics and rates over raw samples, computed exactly.

Every end-to-end number the benchmark prints is reduced here, from the
raw samples of one run: no histogram buckets, no interpolation between
buckets.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0 < q <= 100) by the nearest-rank rule.

    The smallest sample with at least ``q`` percent of the samples at or
    below it.  ``inf`` samples (requests that never completed) sort last,
    so a tail that reaches them is ``inf``.
    """
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"q must be in (0, 100], got {q}")
    xs = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def snapped_rate(
    completions: Sequence[Tuple[float, int]], t0: float, t1: float
) -> Optional[Tuple[float, int, float]]:
    """Work per second over a window whose ends snap outward to completions.

    ``completions`` are ``(time, units)`` pairs, one per readback.  The
    window opens at the last completion at or before ``t0`` and closes
    at the first completion at or after ``t1`` (where there is none on a
    side, at the nearest completion inside ``[t0, t1]``); the units of
    the completions after the opening one, up to and including the
    closing one, are divided by the time between the two.  No completion
    is counted in part, and a stall at either end of ``[t0, t1]`` stays
    in the time.  Returns ``(rate, units, seconds)``, or ``None`` without
    two distinct completions to span.
    """
    times = sorted(completions)
    before = [c for c in times if c[0] <= t0]
    after = [c for c in times if c[0] >= t1]
    inside = [c for c in times if t0 <= c[0] <= t1]
    first = before[-1] if before else (inside[0] if inside else None)
    last = after[0] if after else (inside[-1] if inside else None)
    if first is None or last is None or last[0] <= first[0]:
        return None
    units = sum(n for t, n in times if first[0] < t <= last[0])
    return units / (last[0] - first[0]), units, last[0] - first[0]


def interval_union(
    intervals: Sequence[Tuple[float, float]], lo: float, hi: float
) -> List[Tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out
