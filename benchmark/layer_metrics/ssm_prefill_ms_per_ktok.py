import json
import pathlib
import re

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    tokens = run.traced.get("ssm_prefill_tokens", 0)
    if run.trace is None or not tokens or not z.get("mamba_layers"):
        return None
    x = run.xplane
    ops = run.trace["ops"]
    loops = [(start, start + duration) for _, start, duration
             in x.matching(ops, SPEC["loop_pattern"])]
    in_scan = {id(event) for event in x.inside(ops, loops)}
    skip = re.compile(SPEC["skip_pattern"])
    wanted = [re.compile(shape.format(**z))
              for shape in SPEC["scan_shapes"]]
    spent = sum(event[2] for event in ops
                if id(event) not in in_scan and not skip.match(event[0])
                and any(shape.search(event[0]) for shape in wanted))
    return spent / 1e6 / (tokens / 1000.0) if spent else None
