"""The per-layer metrics beneath ``setup_s`` (PR 36): they read the
compile ledger's totals from the snapshots the run already marks at
the window's edges.  Their readers on hand-made marks, the root's
entries for them (found by name, on the root as it is and on a grown
copy: the ``listed`` fixture), and one traced rehearsal under
``data/BENCHMARK_setup.json`` (``tiny.sched``'s cell as ``tiny.setup``,
whose trace directory is then its own)."""

import os
import pathlib
import subprocess
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = "tests/benchmark/data/BENCHMARK_setup.json"

sys.path.insert(0, str(ROOT))
from benchmark import cells, ledger_totals  # noqa: E402
from tests.benchmark.listed import (Listed, by_name, held_to,  # noqa: E402
                                    last_json_line)

NEW = ("setup_trace_lower_s", "setup_backend_s", "setup_programs",
       "setup_gc_s", "window_gc_ms")

#: A warm run's ledger as the window opens, and as it closes after one
#: full collection of 3.5 ms and nothing else.
LEDGER0 = {"compiles": 2, "cache_hits": 28, "cache_misses": 2,
           "compile_wall_ms_total": 6500.0, "cache_load_ms_total": 2250.0,
           "trace_ms_total": 30125.0, "lower_ms_total": 9875.0,
           "programs_traced": 30, "gc_full_pauses": 9,
           "gc_full_pause_ms": 4200.0}
LEDGER1 = dict(LEDGER0, gc_full_pauses=10, gc_full_pause_ms=4203.5)


def readers(listed, cell):
    return {name: read for name, (_, _, read)
            in by_name(listed.cell(cell)).items()}


def test_the_five_readers_on_hand_made_marks(listed):
    read = readers(listed, "mixtral8x7b.chat")
    run = types.SimpleNamespace(
        marks={"ledger0": LEDGER0, "ledger1": LEDGER1})
    assert {name: read[name](run) for name in NEW} == {
        "setup_trace_lower_s": pytest.approx(40.0),
        "setup_backend_s": pytest.approx(8.75),
        "setup_programs": 30,
        "setup_gc_s": pytest.approx(4.2),
        "window_gc_ms": pytest.approx(3.5)}


def test_the_readers_read_nothing_from_an_older_programs_ledger(listed):
    """The parent's snapshot has the counts and one total: every new
    metric is left out of its line, and none raises."""
    older = {key: LEDGER0[key] for key in (
        "compiles", "cache_hits", "cache_misses",
        "compile_wall_ms_total")}
    run = types.SimpleNamespace(marks={"ledger0": older,
                                       "ledger1": dict(older)})
    read = readers(listed, "mistral7b.chat")
    assert [read[name](run) for name in NEW] == [None] * 5
    spec = {"totals": ["compile_wall_ms_total"], "scale": 0.001,
            "over": "setup"}
    assert ledger_totals.of(run.marks, spec) == pytest.approx(6.5)


def test_the_root_lists_the_five_and_every_cell_loads_them(listed):
    """``BENCHMARK.json`` lists the five itself (since PR 38; until
    then a sibling file held them behind a copy of the root's
    entries).  Found by name: each is listed once, for every cell,
    with the contract's six keys, and every cell loads it as the
    entry describes it."""
    judged = {m["name"] for m in listed.bench["end_to_end"]}
    entries = listed.per_layer(NEW)
    for metric in entries:
        assert set(metric) == {"name", "unit", "better", "source",
                               "layer", "moves"}
        assert (metric["layer"], metric["source"], metric["better"]) == \
            ("compile", "program_counter", "lower")
        assert metric["moves"] in judged
    assert [m["moves"] for m in entries] == \
        ["setup_s"] * 4 + ["out_tokens_per_s"]
    # Every cell reports setup_s and out_tokens_per_s: each loads the
    # five, described as the entry says.
    for cell in listed.cells():
        held = by_name(cell)
        assert set(NEW) <= set(held)
        for name in NEW:
            metric, described, _ = held[name]
            for key in ("layer", "unit", "moves", "source"):
                assert described[key] == metric[key]


def test_the_twins_benchmark_file_holds_the_roots_setup_entries(listed):
    twin = Listed(DATA)
    assert cells.check_names(twin.bench) == []
    held = by_name(twin.cell("tiny.setup"))
    assert set(NEW) <= set(held)
    for metric, _, _ in held.values():
        assert held_to(listed, metric), metric


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.setup", "--seed", "13",
         "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, text=True, timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert done.returncode == 0, done.stdout[-3000:]
    lines = done.stdout.strip().splitlines()
    result = last_json_line(done.stdout)
    # A traced run's line holds the per-layer metrics only: set-up
    # ended, but for the ramp, when the log said the programs were
    # warm (``[ seconds since the process began] warm: ...``).
    warm_s = next(float(line[1:].split("]")[0]) for line in lines
                  if "] warm: compiles" in line)
    return result, warm_s


def test_traced_rehearsal_prints_the_setup_metrics(traced):
    """A cold run on the CPU: every program is traced, lowered and
    compiled in this process, and the parts fit inside ``setup_s``
    (the collector's share overlaps the tracing it interrupted and is
    left out of the sum)."""
    traced, warm_s = traced
    assert traced["correct"] is True
    metrics = {name: entry["value"]
               for name, entry in traced["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert metrics["window_compiles"] == 0
    assert metrics["setup_programs"] >= 8
    assert metrics["setup_trace_lower_s"] > 0
    assert metrics["setup_backend_s"] > 0
    assert metrics["setup_trace_lower_s"] + metrics["setup_backend_s"] \
        <= warm_s
    assert 0 <= metrics["setup_gc_s"] < warm_s
    assert 0 <= metrics["window_gc_ms"] < 2000
    assert {traced["metrics"][name]["unit"] for name in NEW} == \
        {"s", "count", "ms"}
