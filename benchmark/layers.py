"""What a ``--trace 1`` run hands the per-layer readers, and how their
answers become the result line.

A reader is ``benchmark/layer_metrics/<metric>.py`` with one function
``read(run)``; it returns the metric's value, or ``None`` when it finds
nothing to read (the metric is then left out of the line).  ``run`` is
a :class:`RunView`: the window's counter deltas, the client's records,
the trace reduction, the configuration's sizes, the published peaks
and the shape functions.  No reader is named here.
"""

from __future__ import annotations

import functools

from benchmark import peaks, shapes, stats, xplane


class RunView:
    def __init__(self, cell, records, arrivals, window, marks, tracer,
                 device, final_stats):
        self.cell, self.records, self.arrivals = cell, records, arrivals
        self.window, self.marks, self.tracer = window, marks, tracer
        self.device, self.final_stats = device, final_stats
        self.stats, self.shapes, self.xplane = stats, shapes, xplane
        self.sizes = cell.builder.sizes(cell.config)
        self.counters = {
            key: marks["t1"][key] - marks["t0"][key]
            for key in marks["t0"]
            if isinstance(marks["t0"][key], (int, float))}
        self.traced = {}
        if "end" in tracer.marks:
            # Counter deltas over the traced span (what the trace-based
            # readers divide device time by).
            self.traced = {
                key: tracer.marks["end"][key] - tracer.marks["begin"][key]
                for key in tracer.marks["begin"]
                if isinstance(tracer.marks["begin"][key], (int, float))}
        self.ledger = {
            key: marks["ledger1"][key] - marks["ledger0"][key]
            for key in ("compiles", "cache_hits", "cache_misses")}

    @property
    def peaks(self):
        return peaks.of(self.device["kind"])

    @functools.cached_property
    def due(self):
        t0, t1 = self.window
        return [r for r in self.records if t0 <= r.due < t1]

    @functools.cached_property
    def trace(self):
        """The reduced trace of the first chip, clipped to the time the
        device was being traced, or ``None``."""
        if self.tracer.span is None:
            return None
        path = xplane.find_trace(self.tracer.out_dir)
        if path is None:
            return None
        loaded = xplane.load(path)
        if not loaded["devices"]:
            return None
        devices = [loaded["devices"][index]
                   for index in sorted(loaded["devices"])
                   if loaded["devices"][index]["ops"]]
        if not devices:
            return None
        low = min(xplane.extent(d["ops"])[0] for d in devices)
        high = max(xplane.extent(d["ops"])[1] for d in devices)
        return {"devices": devices, "host": loaded["host"],
                "window": (low, high), "ops": devices[0]["ops"],
                "modules": devices[0]["modules"],
                "steps": self._steps_on_trace_clock(loaded["host"])}

    def _steps_on_trace_clock(self, host):
        """The step recorder's events as spans on the profiler's clock:
        an event closes the host phase that ran since the event before
        it (``obs/steplog``'s own reading).  ``None`` when the marker
        is not in the trace, so the clocks cannot be aligned."""
        marker = [start for events in host.values()
                  for name, start, _ in events
                  if name == self.tracer.MARKER]
        if not marker or not self.tracer.steps:
            return None
        offset = marker[0] - self.tracer.marker_unix_s * 1e9
        spans, before = [], None
        for at, event, _fields in self.tracer.steps:
            at_ns = int(at * 1e9 + offset)
            if before is not None and at_ns > before:
                spans.append((event, before, at_ns - before))
            before = at_ns
        return spans

    def device_times(self):
        trace = self.trace
        if trace is None:
            return {}
        low, high = trace["window"]
        # A program on the device is an operation running: the union
        # of program runs (their operations where a trace has no
        # program line).  Gaps between programs are the host's.
        busy = [xplane.busy_ns(xplane.clip(d["modules"] or d["ops"],
                                           (low, high)))
                for d in trace["devices"]]
        return {"busy_s": sum(busy) / len(busy) / 1e9,
                "window_s": (high - low) / 1e9}

    def breakdown(self):
        trace = self.trace
        if trace is None:
            return None
        device_ops = [[name[:80], total / 1e9] for name, total in list(
            xplane.self_times(trace["ops"]).items())[:10]]
        gaps = xplane.idle_gaps(trace["modules"] or trace["ops"],
                                trace["window"])
        host = ({"engine steps": trace["steps"]} if trace["steps"]
                else {})
        return {"device_ops": device_ops,
                "idle_gaps": xplane.attribute_gaps(gaps, host)}

    def per_layer_metrics(self):
        out = {}
        for metric, _described, read in self.cell.per_layer:
            value = read(self)
            if value is not None:
                out[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
        return out
