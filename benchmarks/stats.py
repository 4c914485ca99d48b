"""Metric arithmetic on samples taken by the host's clock."""
from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between closest
    ranks, as numpy's default does; an empty list is an error."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float, per: float = 1.0) -> float:
    """``count`` per ``per`` seconds over a stretch of ``seconds``."""
    if seconds <= 0:
        raise ValueError(f"rate over {seconds} s")
    return count / seconds * per


def covered(spans: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that the union of (start, end) spans covers."""
    total, edge = 0.0, lo
    for s, e in sorted(spans):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            total += e - s
            edge = e
    return total


def degenerate(text: str, ids: list[int]) -> bool:
    """An output row that renders as nothing, or that is one token id
    repeated for its whole length of eight or more (how a NaN row looks).
    ``ids`` are the row's token ids, re-encoded from its text where the
    program gives only text."""
    return not text.strip() or (len(ids) >= 8 and len(set(ids)) == 1)


def at_most(bad: int, total: int, allowed: int) -> bool:
    """No more than ``allowed`` of ``total`` are ``bad``, and there is a
    total at all."""
    return total > 0 and bad <= allowed
