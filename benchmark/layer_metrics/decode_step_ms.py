import json
import pathlib

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    if run.trace is None:
        return None
    loops = run.xplane.durations_of(run.trace["ops"], SPEC["loop_pattern"])
    if not loops:
        return None
    steps = run.cell.config["serving"]["chunk_steps"]
    return run.stats.quantile(loops, 0.5) / 1e6 / steps
