import json
import pathlib
import re

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def engine_spans(run):
    """``(name, start_ns, duration_ns)`` of the engine loop's
    annotations, from every line of the profile's host plane."""
    if run.tracer.span is None:
        return []
    path = run.xplane.find_trace(run.tracer.out_dir)
    if path is None:
        return []
    from jax.profiler import ProfileData
    wanted = re.compile(SPEC["span_pattern"])
    return [(event.name, int(event.start_ns), int(event.duration_ns))
            for plane in ProfileData.from_file(path).planes
            if plane.name == run.xplane.HOST_PLANE
            for line in plane.lines for event in line.events
            if wanted.search(event.name)]


def per_chunk_ms(spans, xplane):
    starts = sorted(event[1] for event in xplane.matching(
        spans, SPEC["dispatch_pattern"]))
    if len(starts) < 2:
        return None
    wait = re.compile(SPEC["wait_pattern"])
    working = [event for event in spans if not wait.search(event[0])]
    busy = xplane.busy_ns(xplane.clip(working, (starts[0], starts[-1])))
    return busy / 1e6 / (len(starts) - 1)


def read(run):
    return per_chunk_ms(engine_spans(run), run.xplane)
