"""The per-layer metrics that read the scheduler's own account of time
to first token (PR 24): one traced rehearsal under a benchmark file
that lists them (``data/BENCHMARK_scheduler.json``: ``tiny.chat``'s
configuration and traffic as the cell ``tiny.sched``, whose trace
directory is then its own), and their readers on hand-built inputs."""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = "tests/benchmark/data/BENCHMARK_scheduler.json"

sys.path.insert(0, str(ROOT))
from benchmark import cells, counter_ratio, xplane  # noqa: E402
from tests.benchmark.listed import (SCHEDULER_METRICS, Listed,  # noqa: E402
                                    by_name, held_to, last_json_line)

SEVEN = SCHEDULER_METRICS
COUNTER_METRICS = tuple(name for name in SEVEN
                        if name != "engine_host_ms_per_chunk")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("cache"))
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--benchmark", DATA, "--workload", "tiny.sched", "--seed", "11",
         "--seconds", "2", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, text=True, timeout=300,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    assert done.returncode == 0, done.stdout[-3000:]
    return last_json_line(done.stdout)


def test_traced_rehearsal_prints_the_scheduler_metrics(traced):
    assert traced["correct"] is True
    metrics = {name: entry["value"]
               for name, entry in traced["metrics"].items()}
    assert set(COUNTER_METRICS) <= set(metrics)
    # Two callers on four slots, prompts of 20-90 tokens under the
    # 256-token slice: a slot is free at once, nothing queues for
    # slices, and the first token takes a chunk of 8 steps.
    assert 0 <= metrics["queue_wait_mean_ms"] < 50
    assert 0 <= metrics["slice_wait_mean_ms"] < 50
    assert metrics["first_chunk_mean_ms"] > 0
    assert metrics["prefill_slices_per_chunk"] == 0
    assert metrics["prefill_backlog_slots"] == 0
    # Prompts are padded to a power-of-two bucket of at most twice
    # their length (a window's edges can cut an admission from its
    # dispatch, hence the slack above 100).
    assert 40 <= metrics["prefill_useful_tokens"] <= 125
    assert traced["metrics"]["prefill_useful_tokens"]["unit"] == "%"
    # Read wherever the traced second held two dispatches (a loaded
    # machine can stretch a chunk past that).
    if "engine_host_ms_per_chunk" in metrics:
        assert 0 < metrics["engine_host_ms_per_chunk"] < 1000


def test_engine_phases_are_in_the_profiles_host_plane(traced):
    """The engine loop's spans are TraceAnnotations: the CPU profile
    of the traced rehearsal holds them in its host plane, on the
    profiler's clock, with their fields."""
    from jax.profiler import ProfileData
    path = xplane.find_trace(str(ROOT / "chiprun_out" / "trace"
                                 / "tiny.sched"))
    assert path is not None
    engine = [event for plane in ProfileData.from_file(path).planes
              if plane.name == xplane.HOST_PLANE
              for line in plane.lines for event in line.events
              if event.name.startswith("engine:")]
    names = {event.name for event in engine}
    assert names and names <= {
        "engine:admission", "engine:paged_prefill",
        "engine:sampling_edit", "engine:dispatch",
        "engine:state_upload", "engine:sync", "engine:commit"}
    assert all(event.duration_ns >= 0 for event in engine)
    for event in engine:
        if event.name == "engine:dispatch":
            assert {"chunk", "steps", "live_rows"} <= \
                {key for key, _ in event.stats}


def test_every_chip_cell_reports_the_seven_metrics(listed):
    """Found by name, wherever in the list they stand: each of the
    seven is listed once, for every cell, and every cell loads it."""
    for entry in listed.per_layer(SEVEN):
        assert "workloads" not in entry
    for cell in listed.cells():
        held = by_name(cell)
        assert set(SEVEN) <= set(held)
        for name in SEVEN:
            metric, described, _ = held[name]
            assert described["layer"] == metric["layer"] == \
                "replica actor and scheduler"
            assert described["unit"] == metric["unit"]
            assert described["moves"] == metric["moves"]


def test_the_rehearsals_benchmark_file_is_sound(listed):
    """The twin is held to the root by name: each of its per-layer
    entries is the root's entry of that name but for the cells it
    lists, and the seven are among them."""
    twin = Listed(DATA)
    assert cells.check_names(twin.bench) == []
    held = by_name(twin.cell("tiny.sched"))
    assert set(SEVEN) <= set(held)
    for metric, _, _ in held.values():
        assert held_to(listed, metric), metric


def test_a_counter_ratio_reads_nothing_from_an_older_program():
    spec = {"numerator": "slice_wait_ms", "denominator": "first_tokens",
            "scale": 1.0}
    assert counter_ratio.of({"dispatches": 9}, spec) is None
    assert counter_ratio.of({"slice_wait_ms": 5.0, "first_tokens": 0},
                            spec) is None
    assert counter_ratio.of({"slice_wait_ms": 5.0, "first_tokens": 2},
                            spec) == 2.5
    percent = dict(spec, numerator="prompt_tokens",
                   denominator="prefill_tokens", scale=100.0)
    assert counter_ratio.of({"prompt_tokens": 520,
                             "prefill_tokens": 768}, percent) == \
        pytest.approx(67.7, abs=0.05)


def test_engine_host_time_per_chunk_on_hand_built_spans():
    cell = cells.Cell(ROOT, "BENCHMARK.json", "mistral7b.chat")
    reader = next(read for metric, _, read in cell.per_layer
                  if metric["name"] == "engine_host_ms_per_chunk")
    per_chunk_ms = reader.__globals__["per_chunk_ms"]
    ms = 1_000_000
    spans = [
        ("engine:commit", 0, 2 * ms),             # before the first
        ("engine:dispatch", 10 * ms, 4 * ms),     # cycle 1
        ("engine:state_upload", 11 * ms, 1 * ms),     # nested: once
        ("engine:sync", 14 * ms, 80 * ms),            # waiting: not host
        ("engine:commit", 94 * ms, 3 * ms),
        ("engine:admission", 97 * ms, 1 * ms),
        ("engine:dispatch", 100 * ms, 4 * ms),    # cycle 2
        ("engine:sync", 104 * ms, 80 * ms),
        ("engine:commit", 184 * ms, 3 * ms),
        ("engine:dispatch", 200 * ms, 4 * ms),    # the last: the edge
        ("engine:commit", 290 * ms, 3 * ms)]
    # (4 + 3 + 1) + (4 + 3) ms of host work over two cycles.
    assert per_chunk_ms(spans, xplane) == pytest.approx(7.5)
    assert per_chunk_ms([], xplane) is None
    assert per_chunk_ms(spans[1:4], xplane) is None   # no whole cycle
