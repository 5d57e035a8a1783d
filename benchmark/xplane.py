"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: busy and idle time of the device, time by
program and by operation, and the longest idle gaps with what the host
was doing in them.

Read with ``jax.profiler.ProfileData`` alone.  The arithmetic works on
plain tuples ``(name, start_ns, duration_ns)`` so that it is tested on
hand-built lists (tests/benchmark/test_reduction.py) as well as on a
recorded trace (benchmark/testdata/).

What a TPU v5e trace holds (jax 0.9, PR 23's chip runs): one plane per
chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
run of a compiled program, named ``<jit name>(<fingerprint>)``, and
whose line ``XLA Ops`` has one event per HLO operation, named by the
HLO instruction (``fusion.123``, ``custom-call.7`` for a Pallas
kernel).  Host threads are lines of the plane ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_trace(directory: str):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` directory."""
    found = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}},
    "host": {thread: [...]}}``, every list of ``(name, start_ns,
    duration_ns)`` sorted by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": {}}
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = sorted(
                        ((e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
            out["devices"][int(match.group(1))] = {
                "ops": lines.get(OPS_LINE, []),
                "modules": lines.get(MODULES_LINE, [])}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = sorted(
                    ((e.name, int(e.start_ns), int(e.duration_ns))
                     for e in line.events), key=lambda e: e[1])
                if events:
                    out["host"][line.name] = events
    return out


def describe(path: str, top: int = 12, with_stats: bool = False) -> str:
    """What a trace holds, for a person: planes, lines, commonest
    names.  (Look at one trace by hand before trusting a reduction.)"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            by_name = total_by_name(
                (e.name, int(e.start_ns), int(e.duration_ns))
                for e in events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            sample = {}
            for event in events:
                sample.setdefault(event.name, event)
            for name, (count, total) in list(by_name.items())[:top]:
                rows.append(f"    {total / 1e6:10.3f} ms {count:7d} x "
                            f"{name[:100]}")
                if with_stats:
                    rows.append("        stats: " + "; ".join(
                        f"{key}={str(value)[:120]}"
                        for key, value in sample[name].stats)[:700])
    return "\n".join(rows)


# --- arithmetic on (name, start_ns, duration_ns) ------------------------- #


def clip(events, window):
    """Events cut to ``window = (start_ns, end_ns)``."""
    low, high = window
    out = []
    for name, start, duration in events:
        a, b = max(start, low), min(start + duration, high)
        if b > a:
            out.append((name, a, b - a))
    return out


def extent(events):
    """``(first start, last end)`` of a list of events."""
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def busy_intervals(events):
    """Union of the events' intervals, as sorted disjoint
    ``(start, end)`` pairs."""
    merged = []
    for _, start, duration in sorted(events, key=lambda e: e[1]):
        end = start + duration
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_ns(events) -> int:
    return sum(b - a for a, b in busy_intervals(events))


def idle_gaps(events, window):
    """Idle intervals inside ``window``, longest first."""
    low, high = window
    gaps, cursor = [], low
    for start, end in busy_intervals(clip(events, window)):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if high > cursor:
        gaps.append((cursor, high))
    return sorted(gaps, key=lambda gap: gap[0] - gap[1])


def total_by_name(events) -> dict:
    """``{name: (count, total_ns)}``, largest total first."""
    totals = {}
    for name, _, duration in events:
        count, total = totals.get(name, (0, 0))
        totals[name] = (count + 1, total + duration)
    return dict(sorted(totals.items(), key=lambda item: -item[1][1]))


def self_times(events) -> dict:
    """``{label: ns}``: every instant of device time charged to the
    most recently started operation still running then, summed by
    :func:`label`.  A loop (``%while``) that contains its body's
    operations, or an asynchronous copy that runs under the compute it
    overlaps, is so charged only for the time nothing started inside
    it."""
    points = []
    for index, (_, start, duration) in enumerate(events):
        if duration > 0:
            points.append((start, 1, index))
            points.append((start + duration, 0, index))
    points.sort()
    totals, stack, open_, before = {}, [], set(), None
    for at, opening, index in points:
        while stack and stack[-1] not in open_:
            stack.pop()
        if stack and at > before:
            name = label(events[stack[-1]][0])
            totals[name] = totals.get(name, 0) + (at - before)
        if opening:
            stack.append(index)
            open_.add(index)
        else:
            open_.discard(index)
        before = at
    return dict(sorted(totals.items(), key=lambda item: -item[1]))


_SHAPE = re.compile(r" = (\(?[a-z]+[0-9]*\[[\d,]*\])")


def label(name: str) -> str:
    """An HLO instruction's text as a short label: its name without the
    instance number, and its (first) result shape, so that the 32
    layers' copies of one kernel read as one row."""
    head = name.split(" ", 1)[0].lstrip("%")
    kind = re.sub(r"\.\d+$", "", head)
    shape = _SHAPE.search(name[:200])
    call = " custom-call" if "custom-call(" in name[:600] else ""
    return f"{kind}{call} {shape.group(1)}" if shape else kind


def inside(events, intervals):
    """The events that lie wholly inside one of ``intervals``."""
    out, spans = [], sorted(intervals)
    for event in events:
        _, start, duration = event
        for low, high in spans:
            if low <= start and start + duration <= high:
                out.append(event)
                break
    return out


def matching(events, pattern: str):
    matcher = re.compile(pattern)
    return [event for event in events if matcher.search(event[0])]


def durations_of(events, pattern: str):
    """Durations (ns) of the events whose name matches ``pattern``."""
    return [event[2] for event in matching(events, pattern)]


def overlapping(events, interval):
    """Nanoseconds of ``interval`` covered by each event name."""
    low, high = interval
    cover = {}
    for name, start, duration in events:
        a, b = max(start, low), min(start + duration, high)
        if b > a:
            cover[name] = cover.get(name, 0) + (b - a)
    return cover


def attribute_gaps(gaps, host_threads: dict, top: int = 10):
    """For the longest ``top`` gaps: the host span (any thread) that
    covers most of each.  Returns ``[(label, seconds)]`` summed by
    label, longest first; a gap nothing covers is ``unattributed``."""
    totals = {}
    for low, high in gaps[:200]:
        best, best_ns = "unattributed", 0
        for thread, events in host_threads.items():
            for name, covered in overlapping(events, (low, high)).items():
                if covered > best_ns:
                    best, best_ns = name, covered
        totals[best] = totals.get(best, 0) + (high - low)
    ranked = sorted(totals.items(), key=lambda item: -item[1])[:top]
    return [[name[:80], ns / 1e9] for name, ns in ranked]
