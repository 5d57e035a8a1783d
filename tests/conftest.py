"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (no TPU needed): the env vars
must be set before jax initializes, hence the top-of-file placement.
"""

import os

# The suite is a CPU suite wherever it runs — it must never take a chip
# from the process that owns it: force the CPU backend through
# jax.config (works post-import, pre-backend-init; JAX_PLATFORMS=cpu on
# the command line does the same) and an 8-device virtual host platform
# for mesh tests.
_ORIG_XLA_FLAGS = os.environ.get("XLA_FLAGS")
xla_flags = _ORIG_XLA_FLAGS or ""
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Force backend init NOW (while the flag is set), then restore the
# caller's XLA_FLAGS so subprocesses spawned by tests (CLI smoke
# runs, the benchmark's rehearsals) don't inherit the 8-device
# virtual platform.
jax.devices()
if _ORIG_XLA_FLAGS is None:
    os.environ.pop("XLA_FLAGS", None)
else:
    os.environ["XLA_FLAGS"] = _ORIG_XLA_FLAGS

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multichip: needs the virtual 8-device CPU mesh "
        "(skipped when fewer devices are available)")

#: Tests measured ≥4 s on the reference 1-core box (regenerate with
#: ``pytest --durations=0`` and refresh this file).  They carry the
#: ``slow`` marker via pytest_collection_modifyitems so the fast
#: default selection ``pytest -m "not slow"`` stays under ~2 minutes
#: while the FULL suite remains the merge gate (see README).
_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


#: A test of ``tests/benchmark/`` that holds what ``BENCHMARK.json``
#: lists to a NUMBER (``len(loaded) == 5``: the four cells of PR 38 and
#: the one its grown copy appends), so that ANY fifth cell fails it.
#: Its file is under the benchmark's ``paths``, where a PR that adds a
#: cell may add files and edit none, and a ``model_config`` PR that adds
#: no cell is refused; so PR 39 marks it here as expected to fail,
#: STRICTLY: it is not counted as passing, and the run fails the day it
#: passes, so the ``benchmark`` PR that turns the 5 into
#: ``len(root.bench["workloads"]) + 1`` has to delete this entry with
#: it (PERF.md section 7, CHANGES.md PR 39).  Until then
#: ``tests/benchmark/test_root_grows.py`` asserts the same by name on
#: whatever the root lists.
_COUNTS_THE_CELLS = {
    "tests/benchmark/test_root_file.py::"
    "test_the_grown_root_loads_all_five_cells":
        "holds the root to four cells by number; BENCHMARK.json has "
        "five since PR 39 (tests/benchmark/test_root_grows.py asserts "
        "the same by name)",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in _COUNTS_THE_CELLS:
            item.add_marker(pytest.mark.xfail(
                reason=_COUNTS_THE_CELLS[item.nodeid], strict=True,
                raises=AssertionError))
    try:
        with open(_SLOW_LIST, encoding="utf-8") as fh:
            slow_ids = {line.strip() for line in fh if line.strip()}
    except FileNotFoundError:
        return
    for item in items:
        if item.nodeid in slow_ids:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _fresh_brokers():
    """Isolate loopback-broker state between tests."""
    from aiko_services_tpu.transport import reset_brokers
    reset_brokers()
    yield
    reset_brokers()


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    """Never let a fault-injection plan escape the test that armed it."""
    yield
    from aiko_services_tpu.runtime import faults
    faults.uninstall()


@pytest.fixture()
def engine():
    """Deterministic event engine driven by a virtual clock."""
    from aiko_services_tpu.runtime.event import EventEngine, VirtualClock
    return EventEngine(clock=VirtualClock())


@pytest.fixture()
def virtual_mesh_devices():
    """The 8 virtual CPU devices ``multichip`` tests shard over;
    skips (rather than fails) if the backend came up with fewer —
    e.g. a stray XLA_FLAGS override from the invoking shell."""
    if jax.device_count() < 8:
        pytest.skip(f"needs 8 devices, have {jax.device_count()}")
    return jax.devices()[:8]
