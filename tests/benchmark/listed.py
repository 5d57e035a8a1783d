"""The benchmark file the structural tests read: ``BENCHMARK.json`` as
it is, and a copy of it that has GROWN as a later PR grows it — one
configuration, one cell and one per-layer entry appended at the ends
of their lists, nothing that was there touched.

Every test that checks what ``BENCHMARK.json`` lists takes the
``listed`` fixture (``conftest.py``) and so runs on both.  A test that
passes on the root and fails on the grown copy depends on a position
or a count in those lists, which is what shut the file to additions
before PR 38: find the entry by its name.

The appended entries' files are the ``tiny`` twin's, under
``tests/benchmark/data/`` (that directory joins the copy's ``paths``).
"""

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from benchmark import cells  # noqa: E402

TWINS = "tests/benchmark/data"
#: PR 24's seven: the scheduler's own account of time to first token.
SCHEDULER_METRICS = (
    "queue_wait_mean_ms", "slice_wait_mean_ms", "first_chunk_mean_ms",
    "prefill_slices_per_chunk", "prefill_backlog_slots",
    "prefill_useful_tokens", "engine_host_ms_per_chunk")
#: What a later PR appends: each entry with exactly the contract's keys.
GROWN_CONFIG = {
    "name": "tiny-test", "source": "tests only: the program's tiny widths",
    "file": f"{TWINS}/configs/tiny-test.json", "reduced": [],
    "why": "the fifth configuration: a file under a directory of paths"}
GROWN_CELL = {
    "name": "tiny.grown", "config": "tiny-test", "traffic": "tiny_closed2",
    "chips": 1, "why": "the fifth cell: two callers, tiny lengths"}
GROWN_METRIC = {
    "name": "requests_due", "unit": "count", "better": "higher",
    "source": "host_clock", "layer": "client and wire",
    "moves": "out_tokens_per_s", "workloads": ["tiny.grown"]}


class Listed:
    """One benchmark file: ``file`` is what ``cells.Cell`` and
    ``run.py --benchmark`` take, ``bench`` what it holds."""

    def __init__(self, file):
        self.file = str(file)
        self.bench = json.loads((ROOT / self.file).read_text())

    def cell(self, name):
        return cells.Cell(ROOT, self.file, name)

    def cells(self):
        return [self.cell(w["name"]) for w in self.bench["workloads"]]

    def entry(self, group, name):
        """The one entry of ``group`` called ``name``."""
        found = [e for e in self.bench[group] if e["name"] == name]
        assert len(found) == 1, (group, name, len(found))
        return found[0]

    def per_layer(self, names):
        return [self.entry("per_layer", name) for name in names]


def grow(directory, config=None, cell=None, metric=None):
    """Writes the grown copy under ``directory``; the overrides change
    keys of what is appended (a test of what must fail)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["paths"] = bench["paths"] + [TWINS]
    bench["configs"].append(dict(GROWN_CONFIG, **(config or {})))
    bench["workloads"].append(dict(GROWN_CELL, **(cell or {})))
    bench["per_layer"].append(dict(copy.deepcopy(GROWN_METRIC),
                                   **(metric or {})))
    path = pathlib.Path(directory) / "BENCHMARK.json"
    path.write_text(json.dumps(bench, indent=1))
    return Listed(path)


def sound(listed):
    """What every benchmark file must satisfy, whatever it lists: the
    contract's keys, names and units, a file behind every name, every
    cell loading with a per-layer metric that moves something it
    reports.  Raises (``cells.CellError`` is a ``SystemExit``) or
    fails an assertion where it does not."""
    bench = listed.bench
    assert cells.check_names(bench) == []
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [entry["name"] for entry in bench[group]]
        assert len(names) == len(set(names)), (group, names)
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for cell in listed.cells():
        assert cell.chips in (1, 4) and len(cell.entry["why"]) <= 200
        assert cell.per_layer, "a cell reports a per-layer metric"
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m, _, _ in cell.per_layer)
        for metric, described, _ in cell.per_layer:
            for key in ("layer", "unit", "moves", "source"):
                assert described[key] == metric[key], (metric["name"],
                                                       key)
    for config in bench["configs"]:
        held = json.loads((ROOT / config["file"]).read_text())
        assert sorted(held["reduced"]) == sorted(config["reduced"])


def by_name(cell):
    """A loaded cell's per-layer metrics: ``{name: (entry, described,
    read)}``."""
    return {metric["name"]: (metric, described, read)
            for metric, described, read in cell.per_layer}


def held_to(listed, metric):
    """A rehearsal twin's per-layer entry is the entry of that name in
    ``listed``, but for the cells it lists."""
    wanted = listed.entry("per_layer", metric["name"])
    return {k: v for k, v in metric.items() if k != "workloads"} == \
        {k: v for k, v in wanted.items() if k != "workloads"}


def last_json_line(output):
    """The result line of a run whose two streams were read as one:
    the last line of standard output, which is one JSON object (the
    checks' lines on standard error may follow it)."""
    return json.loads(next(line for line
                           in reversed(output.strip().splitlines())
                           if line.startswith("{")))
