"""``listed``: the benchmark file a structural test reads, the root as
it is and the root grown by one configuration, one cell and one
per-layer entry (``listed.py`` says why and how)."""

import pytest

from tests.benchmark.listed import Listed, grow


@pytest.fixture(params=["root", "grown"])
def listed(request, tmp_path):
    if request.param == "root":
        return Listed("BENCHMARK.json")
    return grow(tmp_path)
