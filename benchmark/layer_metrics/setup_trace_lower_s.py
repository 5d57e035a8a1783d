import json
import pathlib

from benchmark import ledger_totals

SPEC = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def read(run):
    return ledger_totals.of(run.marks, SPEC)
