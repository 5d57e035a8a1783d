"""A copy of ``BENCHMARK.json`` grown by one configuration, one cell and
one per-layer entry loads EVERY cell the root lists as the root does,
and one more with its own metric: ``test_root_file.py``'s
``test_the_grown_root_loads_all_five_cells`` with the root's cells
counted from the root and not held to four (that test cannot be edited
by the PR that adds a cell, ``tests/conftest.py`` says why).
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from tests.benchmark.listed import Listed, grow  # noqa: E402


def test_the_grown_root_loads_every_cell_of_the_root_and_one_more(
        tmp_path):
    root, grown = Listed("BENCHMARK.json"), grow(tmp_path)
    for group in ("configs", "workloads", "per_layer"):
        assert grown.bench[group][:len(root.bench[group])] == \
            root.bench[group]
        assert len(grown.bench[group]) == len(root.bench[group]) + 1
    assert grown.bench["end_to_end"] == root.bench["end_to_end"]
    loaded = {cell.name: cell for cell in grown.cells()}
    assert set(loaded) == {w["name"] for w in root.bench["workloads"]} \
        | {"tiny.grown"}
    assert len(loaded) == len(root.bench["workloads"]) + 1
    for cell in root.cells():
        assert [m for m, _, _ in loaded[cell.name].per_layer] == \
            [m for m, _, _ in cell.per_layer]
    last = loaded["tiny.grown"]
    assert last.config_name == "tiny-test"
    assert "requests_due" in {m["name"] for m, _, _ in last.per_layer}
