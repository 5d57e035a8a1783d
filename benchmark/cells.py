"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell is ``{name, config, traffic, chips, why}``.  Its configuration
is the file the ``configs`` entry names; its traffic mix is
``<path>/traffic/<traffic>.json`` and each per-layer metric
``<path>/layer_metrics/<metric>.json`` with its reader
``<metric>.py`` beside it, in whichever directory of ``paths`` holds
them.  A later PR therefore adds a cell by adding files and entries; no
file that is there needs an edit, and nothing here knows a name.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class CellError(SystemExit):
    """A name in BENCHMARK.json with no file behind it: loud, code 2."""

    def __init__(self, message: str):
        print(f"benchmark: {message}", flush=True)
        super().__init__(2)


def _load_json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise CellError(f"{what}: no file {path}")
    with open(path, encoding="utf-8") as source:
        return json.load(source)


def _find(root, paths, relative: str, what: str) -> pathlib.Path:
    for directory in paths:
        candidate = root / directory / relative
        if candidate.is_file():
            return candidate
    raise CellError(f"{what}: no {relative} under any of {list(paths)}")


def _import(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_" + re.sub(r"\W", "_", path.stem), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root: pathlib.Path, benchmark_file: str,
                 workload: str):
        self.root = root
        self.benchmark = bench = _load_json(root / benchmark_file,
                                            "benchmark")
        self.paths = paths = bench["paths"]
        cells = {cell["name"]: cell for cell in bench["workloads"]}
        if workload not in cells:
            raise CellError(f"no workload {workload!r} in "
                            f"{benchmark_file}; it has {sorted(cells)}")
        self.entry = entry = cells[workload]
        self.name, self.chips = entry["name"], int(entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        if entry["config"] not in configs:
            raise CellError(f"workload {workload!r} names configuration "
                            f"{entry['config']!r}, which configs lacks")
        self.config_name = entry["config"]
        self.config = _load_json(root / configs[entry["config"]]["file"],
                                 f"configuration {entry['config']!r}")
        self.traffic_name = entry["traffic"]
        self.traffic = _load_json(
            _find(root, paths, f"traffic/{entry['traffic']}.json",
                  f"traffic mix {entry['traffic']!r}"), "traffic mix")
        self.builder = _import(
            _find(root, paths, f"builders/{self.config['builder']}.py",
                  f"builder {self.config['builder']!r}"))
        self.reference = _import(
            _find(root, paths,
                  f"reference/{self.config['reference']}.py",
                  f"reference {self.config['reference']!r}"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = []
        reported = {m["name"] for m in self.end_to_end}
        for metric in bench["per_layer"]:
            if workload not in metric.get("workloads", [workload]) \
                    or metric["moves"] not in reported:
                continue
            described = _load_json(
                _find(root, paths,
                      f"layer_metrics/{metric['name']}.json",
                      f"per-layer metric {metric['name']!r}"), "metric")
            reader = _import(
                _find(root, paths, f"layer_metrics/{metric['name']}.py",
                      f"reader of {metric['name']!r}"))
            self.per_layer.append((metric, described, reader.read))


def check_names(bench: dict) -> list:
    """Every name and unit of the file against the allowed characters;
    returns the offenders (empty when sound)."""
    bad = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            for key in ("name", "config", "traffic", "moves"):
                if key in entry and not NAME.match(entry[key]):
                    bad.append((group, key, entry[key]))
            if "unit" in entry and not UNIT.match(entry["unit"]):
                bad.append((group, "unit", entry["unit"]))
            for key in entry.get("reduced", []):
                if not NAME.match(key):
                    bad.append((group, "reduced", key))
    return bad
