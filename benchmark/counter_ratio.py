"""A per-layer metric that is one program counter over another.

Such a metric's file names ``numerator``, ``denominator`` and
``scale``; its reader hands them here with the counter deltas of the
interval it reads (``run.counters``: the window).
"""

from __future__ import annotations


def of(counters: dict, spec: dict):
    """``scale * numerator / denominator``, or ``None`` where the
    program has no such counter (an older program) or the denominator
    counted nothing."""
    above = counters.get(spec["numerator"])
    below = counters.get(spec["denominator"])
    if above is None or not below:
        return None
    return spec["scale"] * above / below
