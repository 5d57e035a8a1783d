import json
import pathlib

from benchmark import counter_ratio

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    return counter_ratio.of(run.counters, SPEC)
