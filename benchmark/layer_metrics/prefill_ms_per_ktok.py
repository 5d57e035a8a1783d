import json
import pathlib

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    tokens = run.traced.get("prefill_tokens", 0)
    if run.trace is None or not tokens:
        return None
    x = run.xplane
    programs = x.matching(run.trace["modules"], SPEC["prefill_programs"])
    if not programs:
        return None
    spans = [(start, start + duration) for _, start, duration in programs]
    loops = x.inside(x.matching(run.trace["ops"], SPEC["loop_pattern"]),
                     spans)
    busy = sum(e[2] for e in programs) - sum(e[2] for e in loops)
    return busy / 1e6 / (tokens / 1000.0)
