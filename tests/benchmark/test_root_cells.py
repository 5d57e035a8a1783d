"""The entries ``BENCHMARK.json`` lists for the two cells that brought
per-layer metrics of their own (``nemotron3super.reason``, PR 26;
``mistralsmall4.docs``, PR 31), PR 24's seven scheduler metrics, and
the rehearsal twins held to the root: everything found by NAME, on
the root as it is and on a grown copy (the ``listed`` fixture).

Until PR 38 these were ``test_the_chip_size_file_*`` in
``test_nemotron_h.py`` and ``test_mistral4.py``, held to two sibling
benchmark files by position; they stand in a file of their own so
that no file here outgrows twenty tests (``test_root_file.py`` says
why).
"""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from benchmark import cells  # noqa: E402
from tests.benchmark.listed import (SCHEDULER_METRICS, Listed,  # noqa: E402
                                    by_name, held_to)

HYBRID_TWIN = "tests/benchmark/data/BENCHMARK_nemotron.json"
HYBRID_CELL = "nemotron3super.reason"
HYBRID_CONFIG = "nemotron-3-super-120b-l11e128"
HYBRID_NEW = {"moe_expert_roofline", "ssm_update_roofline",
              "moe_pairs_per_expert", "ssm_prefill_ms_per_ktok"}
LATENT_TWIN = "tests/benchmark/data/BENCHMARK_mistral4.json"
LATENT_CELL = "mistralsmall4.docs"
LATENT_CONFIG = "mistral-small-4-119b-l4e32"
LATENT_NEW = {"latent_prefill_roofline", "swiglu_expert_roofline",
              "latent_decode_roofline", "moe_pairs_per_expert"}
LATENT_PUBLISHED = json.loads((ROOT / "benchmark/configs/"
                               "mistral-small-4-119b-l4e32.json").read_text())


def test_names_units_and_files_of_the_latent_twin_and_the_new_entries(listed):
    twin = Listed(LATENT_TWIN)
    assert cells.check_names(twin.bench) == []
    assert LATENT_NEW <= set(by_name(twin.cell("tiny.docs")))
    cell = listed.cell(LATENT_CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "out_tokens_per_s", "setup_s"}
    assert (cell.chips, cell.config["builder"], cell.config["reference"]) \
        == (1, "mistral4", "mla_moe")
    # The cell reports its own four, and none of another cell's own.
    held = set(by_name(cell))
    assert LATENT_NEW <= held
    assert not held & {"open_req_p50_ms", "prefill_ms_per_ktok",
                       "moe_expert_roofline", "ssm_update_roofline",
                       "ssm_prefill_ms_per_ktok"}
    for metric in listed.per_layer(sorted(LATENT_NEW)):
        described = json.loads(
            (ROOT / "benchmark" / "layer_metrics"
             / f"{metric['name']}.json").read_text())
        assert (described["layer"], described["unit"],
                described["moves"], described["source"]) == (
            metric["layer"], metric["unit"], metric["moves"],
            metric["source"])
        assert LATENT_CELL in metric["workloads"]


def test_the_root_lists_the_latent_cell_and_its_four_metrics(listed):
    """``BENCHMARK.json`` lists the cell's own per-layer metrics itself
    (since PR 38; until then a sibling file held them behind a copy of
    the root's entries).  Found by name: the root has the configuration
    and the cell, each of the four is listed once with the contract's
    keys and names the cell, and the cell loads them."""
    assert cells.check_names(listed.bench) == []
    config = listed.entry("configs", LATENT_CONFIG)
    workload = listed.entry("workloads", LATENT_CELL)
    assert workload["config"] == config["name"]
    assert sorted(config["reduced"]) == sorted(LATENT_PUBLISHED["reduced"])
    judged = {m["name"] for m in listed.bench["end_to_end"]}
    for metric in listed.per_layer(sorted(LATENT_NEW)):
        assert set(metric) == {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        assert metric["moves"] in judged and metric["better"] == "higher"
    own = LATENT_NEW - {"moe_pairs_per_expert"}
    for metric in listed.per_layer(sorted(own)):
        assert metric["workloads"] == [LATENT_CELL]
        assert (metric["unit"], metric["source"], metric["layer"]) == \
            ("%", "device_trace", "kernels")
    # The accepted reader listed for this cell too: ONE entry, which
    # names both routed cells.
    shared = listed.entry("per_layer", "moe_pairs_per_expert")
    assert shared["workloads"] == ["nemotron3super.reason", LATENT_CELL]
    assert (shared["unit"], shared["source"], shared["layer"],
            shared["moves"]) == ("tokens", "program_counter",
                                 "model step", "out_tokens_per_s")
    held = by_name(listed.cell(LATENT_CELL))
    for name in LATENT_NEW:
        metric, described, _ = held[name]
        for key in ("layer", "unit", "moves", "source"):
            assert described[key] == metric[key]
    # No other cell of the file loads the three that are this cell's.
    for cell in listed.cells():
        if cell.name != LATENT_CELL:
            assert not own & set(by_name(cell))


def test_the_latent_twins_benchmark_file_holds_the_roots_entries(listed):
    twin = Listed(LATENT_TWIN)
    assert cells.check_names(twin.bench) == []
    for metric, _, _ in twin.cell("tiny.docs").per_layer:
        assert held_to(listed, metric), metric


def test_names_units_and_files_of_the_hybrid_twin_and_the_new_entries(listed):
    twin = Listed(HYBRID_TWIN)
    assert cells.check_names(twin.bench) == []
    assert HYBRID_NEW <= set(by_name(twin.cell("tiny.reason")))
    cell = listed.cell(HYBRID_CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p50_ms", "tpot_p50_ms", "out_tokens_per_s", "setup_s"}
    assert HYBRID_NEW <= set(by_name(cell))
    for metric in listed.per_layer(sorted(HYBRID_NEW)):
        described = json.loads(
            (ROOT / "benchmark" / "layer_metrics"
             / f"{metric['name']}.json").read_text())
        assert (described["layer"], described["unit"],
                described["moves"], described["source"]) == (
            metric["layer"], metric["unit"], metric["moves"],
            metric["source"])
        assert HYBRID_CELL in metric["workloads"]


def test_the_root_lists_the_hybrid_cell_and_its_four_metrics(listed):
    """``BENCHMARK.json`` lists the cell's four own per-layer metrics
    itself (since PR 38; until then a sibling file held them behind a
    copy of the root's entries).  Found by name: the root has the
    configuration and the cell, each of the four is listed once with
    the contract's keys and names the cell, and the cell loads them."""
    assert cells.check_names(listed.bench) == []
    config = listed.entry("configs", HYBRID_CONFIG)
    assert listed.entry("workloads", HYBRID_CELL)["config"] == config["name"]
    judged = {m["name"] for m in listed.bench["end_to_end"]}
    for metric in listed.per_layer(sorted(HYBRID_NEW)):
        assert set(metric) == {"name", "unit", "better", "source",
                               "layer", "moves", "workloads"}
        assert metric["workloads"][0] == HYBRID_CELL
        assert metric["moves"] in judged
    own = HYBRID_NEW - {"moe_pairs_per_expert"}
    for metric in listed.per_layer(sorted(own)):
        assert metric["workloads"] == [HYBRID_CELL]
    held = by_name(listed.cell(HYBRID_CELL))
    for name in HYBRID_NEW:
        metric, described, _ = held[name]
        for key in ("layer", "unit", "moves", "source"):
            assert described[key] == metric[key]
    # No other cell of the file loads the three that are this cell's.
    for cell in listed.cells():
        if cell.name != HYBRID_CELL:
            assert not own & set(by_name(cell))


def test_every_chip_cell_reports_the_scheduler_metrics_by_name(listed):
    for cell in listed.cells():
        held = by_name(cell)
        assert set(SCHEDULER_METRICS) <= set(held)
        for name in SCHEDULER_METRICS:
            metric, described, _ = held[name]
            assert described["layer"] == metric["layer"] == \
                "replica actor and scheduler"
            assert (described["unit"], described["moves"]) == (
                metric["unit"], metric["moves"])


@pytest.mark.parametrize("data, workload", [
    ("tests/benchmark/data/BENCHMARK_scheduler.json", "tiny.sched"),
    (HYBRID_TWIN, "tiny.reason")])
def test_a_rehearsals_benchmark_file_holds_the_roots_entries(
        listed, data, workload):
    """A rehearsal file's per-layer entries are the root file's own,
    but for the cells they list."""
    twin = Listed(data)
    assert cells.check_names(twin.bench) == []
    held = by_name(twin.cell(workload))
    for metric, _, _ in held.values():
        assert held_to(listed, metric), metric
    assert set(SCHEDULER_METRICS) <= set(held)
