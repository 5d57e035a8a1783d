import json
import pathlib

from benchmark import counter_ratio

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    z = run.sizes
    if not z.get("experts") or not z.get("expert_layers"):
        return None
    return counter_ratio.of(run.counters, dict(
        SPEC, scale=1.0 / (z["experts"] * z["expert_layers"])))
