"""A per-layer metric that is a sum of the compile ledger's totals.

The ledger (``obs/compiles.py``) keeps monotonic totals, and the run
marks a snapshot of them at the window's start (``ledger0``: set-up
and the ramp lie behind it) and at its end (``ledger1``).  Such a
metric's file names the ``totals`` it adds, a ``scale``, and what it
is ``over``: ``"setup"`` reads ``ledger0``, ``"window"`` the
difference of the two.
"""

from __future__ import annotations


def of(marks: dict, spec: dict):
    """``scale × Σ totals`` over set-up or over the window, or ``None``
    where a snapshot lacks one of them (an older program's ledger)."""
    start, end = marks["ledger0"], marks["ledger1"]
    if any(key not in start or key not in end for key in spec["totals"]):
        return None
    if spec["over"] == "window":
        return spec["scale"] * sum(end[key] - start[key]
                                   for key in spec["totals"])
    return spec["scale"] * sum(start[key] for key in spec["totals"])
