"""Engine step timeline: fixed-size ring-buffer span recorder.

The profiling instrument ROADMAP item 5 asks for: WHERE does the
host-path tax between raw decode throughput and served throughput go?
The serving engines already count sync stalls; this recorder captures
the per-step phase SEQUENCE — admission wave, state upload, prefill,
dispatch, ring-sync wait, commit, sampling-param edit — each as a
span with a start and an end, so a slow step is attributable, not
just countable.

Zero-cost discipline (identical to ``faults.PLAN``): the module-level
:data:`RECORDER` defaults to ``None`` and every call site in
``orchestration/continuous.py`` / ``orchestration/paged.py`` is
guarded::

    span = None
    if steplog.RECORDER is not None:
        span = steplog.RECORDER.begin("dispatch", steps=n)
    ...                                  # the phase's work
    if span is not None:
        span.end(ring=k)

Disabled cost: one module-attribute load + identity test at the
opening guard, one local identity test at the closing one.  (A span
that was opened is closed into the recorder that opened it, so
installing or uninstalling mid-phase loses that phase and nothing
else.)  AST tests pin the guard on every site, and the jaxpr guard
test pins that an installed recorder cannot change the traced step
program — recording is HOST-side orchestration only, never inside jit.

While a recorder is installed every open span is also a
``jax.profiler.TraceAnnotation("engine:<phase>", **fields)``: a
profile taken meanwhile holds the engine's phases in its host plane
on the profiler's own clock, beside the device's operations, with no
clock to align afterwards.  (``jax`` is imported when a recorder is
constructed, never when this module is.)

:meth:`StepRecorder.events` is the older view of the same ring, one
``(t, event, fields)`` row per span with ``t`` its end — what
:mod:`.attrib`, the flight bundles and ``tools/doctor.py`` read.
Spans export as Chrome trace-event durations on a dedicated "engine"
track so a step timeline can be overlaid with request spans
(:func:`aiko_services_tpu.obs.trace.chrome_events`) in one Perfetto
view.
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = ["StepRecorder", "EngineSpan", "RECORDER", "install",
           "uninstall"]

_EPOCH0 = time.time() - time.perf_counter()


def _now() -> float:
    return _EPOCH0 + time.perf_counter()


class EngineSpan:
    """One engine-loop phase in progress: opened by
    :meth:`StepRecorder.begin`, closed by :meth:`end` (recorded) or
    :meth:`drop` (the phase turned out empty: nothing is recorded).
    While it is open it is also a ``jax.profiler.TraceAnnotation``
    named ``engine:<phase>``, so a profile taken meanwhile holds the
    phase in its host plane, on the profiler's own clock."""

    __slots__ = ("_recorder", "_annotation", "name", "start", "fields")

    def __init__(self, recorder, name, fields):
        self._recorder, self.name, self.fields = recorder, name, fields
        self._annotation = None
        if recorder._annotate is not None:
            self._annotation = recorder._annotate(f"engine:{name}",
                                                  **_scalars(fields))
            self._annotation.__enter__()
        self.start = _now()

    def note(self, **fields):
        """Fields learned while the phase runs (the cause of a
        dispatch: which slice it carries, for whom)."""
        self.fields.update(fields)
        if self._annotation is not None:
            self._annotation.set_metadata(**_scalars(fields))

    def end(self, **fields):
        end = _now()
        if fields:
            self.note(**fields)
        self.drop()
        self._recorder._append(self.start, end, self.name, self.fields)

    def drop(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None


def _scalars(fields: Dict) -> Dict:
    return {key: value for key, value in fields.items()
            if isinstance(value, (int, float, str, bool))}


class StepRecorder:
    """Bounded ring of ``(start, end, event, fields)`` host-step spans.

    Two views of the one ring: :meth:`spans` as recorded, and
    :meth:`events`, the older ``(t, event, fields)`` rows with ``t``
    the span's END (what ``obs/attrib``, the flight bundles and the
    doctor read: a row closes the phase that ran before it)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._ring: deque = deque(maxlen=capacity)
        self.dropped = 0  # events that fell off the ring
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:      # recording works without a profiler
            TraceAnnotation = None
        self._annotate = TraceAnnotation

    def _append(self, start: float, end: float, event: str,
                fields: Dict):
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1
        self._ring.append((start, end, event, fields))

    def begin(self, event: str, **fields) -> EngineSpan:
        """Open a phase; the caller closes it (``span.end(...)``)."""
        return EngineSpan(self, event, fields)

    def record(self, event: str, **fields):
        """An instant: a phase too short to bracket, or one whose
        duration rides in a field (``wait_ms`` / ``ms``)."""
        now = _now()
        self._append(now, now, event, fields)

    def spans(self) -> List[Tuple[float, float, str, Dict]]:
        return list(self._ring)

    def events(self) -> List[Tuple[float, str, Dict]]:
        return [(end, event, fields)
                for _, end, event, fields in self._ring]

    def clear(self):
        self._ring.clear()
        self.dropped = 0

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for _, _, event, _fields in self._ring:
            out[event] = out.get(event, 0) + 1
        return out

    # -- export -------------------------------------------------------------- #

    def chrome_events(self, pid: int = 0, tid: int = 0) -> List[Dict]:
        """Spans render as complete events.  An instant renders as an
        instant, unless it carries a ``wait_ms`` / ``ms`` field: then
        as a complete event ENDING at the recorded timestamp (the wait
        is measured, then recorded)."""
        events: List[Dict] = [
            {"ph": "M", "name": "process_name", "pid": pid, "tid": tid,
             "args": {"name": "engine"}},
        ]
        for start, end, event, fields in self._ring:
            ts = int(round(end * 1e6))
            duration = 0
            if end > start:
                duration = max(1, int(round((end - start) * 1e6)))
            else:
                embedded = fields.get("wait_ms", fields.get("ms"))
                if embedded:
                    duration = max(1, int(round(float(embedded) * 1e3)))
            common = {"name": event, "cat": "engine", "pid": pid,
                      "tid": tid, "args": _scalars(fields)}
            if duration:
                events.append(dict(common, ph="X", ts=ts - duration,
                                   dur=duration))
            else:
                events.append(dict(common, ph="i", ts=ts, s="t"))
        return events

    def export_chrome(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as handle:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, handle, indent=1)
        return path


#: Module switchboard — ``None`` means recording is OFF everywhere.
RECORDER: Optional[StepRecorder] = None


def install(recorder: Optional[StepRecorder] = None,
            capacity: int = 4096) -> StepRecorder:
    global RECORDER
    RECORDER = recorder or StepRecorder(capacity=capacity)
    return RECORDER


def uninstall():
    global RECORDER
    RECORDER = None
