"""Quantile, due-time and lateness arithmetic (copied in kind from
``tools/loadgen.py``'s ``LoadReport``, which is listed in PERF.md for a
later PR to fold into this one)."""

from __future__ import annotations

import math


def quantile(values, q: float):
    """Linear-interpolated quantile of ``values`` (numpy's default
    rule), ``None`` for no values."""
    ordered = sorted(values)
    if not ordered:
        return None
    place = q * (len(ordered) - 1)
    low = math.floor(place)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (place - low)


def due_times(gaps, start: float = 0.0):
    """Cumulative due times of an open-loop schedule from its gaps."""
    out, now = [], start
    for gap in gaps:
        now += gap
        out.append(now)
    return out


def lateness_ms(due, sent):
    """How late each request left the generator (never negative: a
    request is not sent early)."""
    return [max(0.0, (s - d) * 1e3) for d, s in zip(due, sent)]
