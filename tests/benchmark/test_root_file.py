"""What ``BENCHMARK.json`` lists, whatever it lists: the contract's keys,
names and units, a file behind every name, every cell loading.  On the
root as it is and on a copy grown by one configuration, one cell and
one per-layer entry (the ``listed`` fixture), on the rehearsal twins
under ``data/``, and on grown copies that must be refused.

(A file of its own since PR 38, and kept under twenty tests like every
file here: xdist hands files out largest first, and a benchmark file
that grows past ``tests/test_compiles.py`` re-deals which worker runs
that file after ``tests/test_host_tax.py``, whose leftover compile
label one of its tests cannot take.)
"""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
from tests.benchmark.listed import Listed, grow, sound  # noqa: E402

TWINS = sorted(path.relative_to(ROOT).as_posix() for path in
               (ROOT / "tests/benchmark/data").glob("BENCHMARK*.json"))


def test_names_units_and_files(listed):
    sound(listed)


@pytest.mark.parametrize("benchmark_file", TWINS)
def test_names_units_and_files_of_a_rehearsals_twin(benchmark_file):
    sound(Listed(benchmark_file))


def test_the_grown_root_loads_all_five_cells(tmp_path):
    """The door is open: a copy of ``BENCHMARK.json`` with one
    configuration, one cell and one per-layer entry appended, no entry
    that was there touched, loads the four cells as the root does and
    the fifth with its own metric."""
    root, grown = Listed("BENCHMARK.json"), grow(tmp_path)
    for group in ("configs", "workloads", "per_layer"):
        assert grown.bench[group][:len(root.bench[group])] == \
            root.bench[group]
        assert len(grown.bench[group]) == len(root.bench[group]) + 1
    assert grown.bench["end_to_end"] == root.bench["end_to_end"]
    loaded = {cell.name: cell for cell in grown.cells()}
    assert len(loaded) == 5
    for cell in root.cells():
        assert [m for m, _, _ in loaded[cell.name].per_layer] == \
            [m for m, _, _ in cell.per_layer]
    fifth = loaded["tiny.grown"]
    assert fifth.config_name == "tiny-test"
    assert "requests_due" in {m["name"] for m, _, _ in fifth.per_layer}


@pytest.mark.parametrize("appended, needle", [
    ({"cell": {"traffic": "no_such_mix"}}, "traffic mix"),
    ({"cell": {"config": "no-such-config"}}, "configuration"),
    ({"config": {"file": "tests/benchmark/data/configs/none.json"}},
     "configuration"),
    ({"metric": {"name": "no_such_metric"}}, "no_such_metric"),
])
def test_a_grown_root_that_names_no_file_is_refused(tmp_path, capsys,
                                                    appended, needle):
    with pytest.raises(SystemExit) as raised:
        sound(grow(tmp_path, **appended))
    assert raised.value.code == 2
    assert needle in capsys.readouterr().out


def test_a_grown_root_that_reuses_a_name_is_refused(tmp_path):
    with pytest.raises(AssertionError):
        sound(grow(tmp_path, metric={"name": "batch_occupancy"}))
