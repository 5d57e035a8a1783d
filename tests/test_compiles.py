"""Compile observability (ISSUE 14): the ledger, the persistent
compilation cache, and the on-demand device-profile bracket.

Pins the contracts OBSERVABILITY.md's compile sections promise:

* the ledger books real compiles under the engine's thread-local
  labels, and a persistent-cache HIT is booked as a retrieval — never
  as a compile (the paired hit+duration classification);
* the steady-state detector: after the warmup fence ANY real compile
  bumps the counter and fires a flight capture with the ledger
  attached;
* the pow2 bucket discipline is EXECUTABLE: a ragged prompt wave
  across bucket edges compiles at most log2-many distinct prefill
  shapes, and an identical second wave compiles NOTHING;
* invariant 15: installing ledger + profiler leaves the serve-chunk
  jaxpr byte-identical (compile observability never reaches a traced
  program);
* the ``(profile)`` bracket measures real per-step device ms on the
  live paged engine and its manifest lands in flight bundles /
  ``doctor --json`` (schema pinned here);
* a record holds all of a program's host phases (ISSUE 36): nested
  trace events are booked once, a cache hit's backend wall is a load,
  threads do not mix, and the collector's full pauses are timed.
"""

import json
import math
import pathlib

import numpy as np
import pytest

from aiko_services_tpu.obs import compiles, flight, profiler, steplog

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "aiko_services_tpu"


@pytest.fixture(autouse=True)
def _no_leaked_ledger():
    """Never let a ledger / profiler session escape its test."""
    yield
    compiles.uninstall()
    profiler.PROFILER = None
    profiler.LAST = None
    steplog.uninstall()
    flight.uninstall()


# ---------------------------------------------------------------- #
# Ledger unit behavior (no jax needed)
# ---------------------------------------------------------------- #

def test_ledger_books_labeled_compiles_and_fence():
    ledger = compiles.install(service="unit")
    with compiles.label("prefill", "b32x2"):
        ledger.record_compile(12.5)
    assert ledger.compiles == 1
    assert ledger.steady_compiles == 0
    entry = ledger.records[-1]
    assert (entry["program"], entry["signature"]) == ("prefill",
                                                      "b32x2")
    ledger.fence()
    ledger.record_compile(3.0, program="serve_chunk", signature="s4")
    assert ledger.steady_compiles == 1
    assert ledger.records[-1]["steady"] is True
    # lift_fence re-enters warmup (intentional reconfigure)
    ledger.lift_fence()
    ledger.record_compile(1.0, program="merge_state")
    assert ledger.steady_compiles == 1
    assert ledger.signatures("prefill") == [("prefill", "b32x2")]


def test_cache_hit_books_retrieval_not_compile():
    """A persistent-cache hit still fires the backend-compile duration
    event (it times the ~ms retrieval); the same-thread pending-hit
    flag must reclassify it."""
    ledger = compiles.install(service="unit")
    ledger.fence()
    # hit event then its paired duration event, as jax emits them
    compiles._on_event("/jax/compilation_cache/cache_hits")
    compiles._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.002)
    assert ledger.cache_hits == 1
    assert ledger.compiles == 0
    assert ledger.steady_compiles == 0       # retrieval is NOT steady
    assert ledger.records[-1]["cache_hit"] is True
    # a miss then its duration books a REAL compile
    compiles._on_event("/jax/compilation_cache/cache_misses")
    compiles._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.050)
    assert ledger.cache_misses == 1
    assert ledger.compiles == 1
    assert ledger.steady_compiles == 1
    # the hit's backend wall is the measured load, in a total of its
    # own; the compile's is the only one in compile_wall_ms_total
    snapshot = ledger.snapshot()
    assert snapshot["cache_load_ms_total"] == pytest.approx(2.0)
    assert snapshot["compile_wall_ms_total"] == pytest.approx(50.0)
    # neither program was traced here: an executable loaded (or
    # compiled) for a jaxpr the process already held
    assert snapshot["programs_traced"] == 0
    assert [r["trace_ms"] for r in snapshot["records"]] == [0.0, 0.0]
    # jax's own estimate of what a hit saved is no longer kept
    compiles._on_duration(
        "/jax/compilation_cache/compile_time_saved_sec", -0.001)
    assert ledger.snapshot() == snapshot


# ---------------------------------------------------------------- #
# A record holds all of a program's host phases (synthetic events)
# ---------------------------------------------------------------- #

class _Clock:
    """Stands in for the ledger's clock: an event is handed to the
    listener at the moment its phase ends."""

    def __init__(self, monkeypatch):
        self.now = 1000.0
        monkeypatch.setattr(compiles, "_now", lambda: self.now)

    def fire(self, event, end, duration, **kwargs):
        self.now = 1000.0 + end
        compiles._on_duration(event, duration, **kwargs)


@pytest.fixture()
def clock(monkeypatch):
    # A stack of this thread's, left by an earlier test's real jit,
    # holds intervals on the real clock.
    compiles._TLS.stack = []
    yield _Clock(monkeypatch)
    compiles._TLS.stack = []


def _program(clock, start, fun_name="serve_chunk", hit=False):
    """One program as jax fires it: a jit that calls a jit three
    times — ten trace events, nested — then lowering with a ``jnp``
    wrapper traced inside it, then the backend.  Returns the time
    the call took: 1.0 s of tracing, 0.5 of lowering (0.1 of it the
    nested trace), 2.0 of backend, and gaps between them."""
    at = start + 0.01                   # the outer trace began here
    for call in range(3):
        inner = at + 0.05 + call * 0.3  # an inner jit: 0.25 s
        for wrapper in range(2):        # two jnp wrappers inside it
            clock.fire(compiles.TRACE_EVENT,
                       inner + 0.05 + wrapper * 0.1 + 0.04, 0.04,
                       fun_name="_where")
        clock.fire(compiles.TRACE_EVENT, inner + 0.25, 0.25,
                   fun_name="paged_decode_append")
    clock.fire(compiles.TRACE_EVENT, at + 1.0, 1.0, fun_name=fun_name)
    lowering = at + 1.1                 # 0.1 s of Python in between
    clock.fire(compiles.TRACE_EVENT, lowering + 0.3, 0.1,
               fun_name="_take")
    clock.fire(compiles.LOWER_EVENT, lowering + 0.5, 0.5,
               fun_name="jit_" + fun_name)
    if hit:
        compiles._on_event("/jax/compilation_cache/cache_hits")
    clock.fire(compiles.BACKEND_EVENT, lowering + 2.6, 2.0,
               fun_name="jit_" + fun_name)
    return lowering + 2.6 - start


def test_nested_trace_events_are_booked_once(clock):
    ledger = compiles.install(service="unit")
    with compiles.label("serve_chunk", "s8"):
        wall_s = _program(clock, 0.0)
    (record,) = ledger.snapshot()["records"]
    # ten trace events sum to 1.99 s; their union is the outer 1.0 s,
    # plus the 0.1 s traced inside the lowering
    assert record["trace_ms"] == pytest.approx(1100.0)
    assert record["lower_ms"] == pytest.approx(400.0)
    assert record["wall_ms"] == pytest.approx(2000.0)
    assert record["trace_ms"] + record["lower_ms"] + record["wall_ms"] \
        <= wall_s * 1e3
    assert (record["program"], record["signature"],
            record["fun_name"]) == ("serve_chunk", "s8", "serve_chunk")
    assert record["ts"] == pytest.approx(1000.0 + wall_s)
    snapshot = ledger.snapshot()
    assert snapshot["trace_ms_total"] == pytest.approx(1100.0)
    assert snapshot["lower_ms_total"] == pytest.approx(400.0)
    assert snapshot["compile_wall_ms_total"] == pytest.approx(2000.0)
    assert snapshot["cache_load_ms_total"] == 0.0
    assert snapshot["programs_traced"] == 1


def test_a_hits_backend_wall_is_a_load_with_its_trace_and_lowering(
        clock):
    ledger = compiles.install(service="unit")
    _program(clock, 0.0, hit=True)
    snapshot = ledger.snapshot()
    assert snapshot["compiles"] == 0
    assert snapshot["compile_wall_ms_total"] == 0.0
    assert snapshot["cache_load_ms_total"] == pytest.approx(2000.0)
    # a warm start still pays Python for every program it loads
    assert snapshot["trace_ms_total"] == pytest.approx(1100.0)
    assert snapshot["programs_traced"] == 1
    assert ledger.signatures() == []


def test_a_trace_nothing_lowers_goes_to_the_totals_not_to_a_record(
        clock):
    """``eval_shape``, or a program whose executable is held: the
    trace is booked under no record when the thread next closes a
    program, and the next program's record holds only its own."""
    ledger = compiles.install(service="unit")
    clock.fire(compiles.TRACE_EVENT, 0.30, 0.05, fun_name="_where")
    clock.fire(compiles.TRACE_EVENT, 0.50, 0.40, fun_name="shapes")
    assert ledger.snapshot()["trace_ms_total"] == 0.0   # still open
    _program(clock, 1.0)
    snapshot = ledger.snapshot()
    (record,) = snapshot["records"]
    assert record["trace_ms"] == pytest.approx(1100.0)
    assert snapshot["trace_ms_total"] == pytest.approx(1500.0)
    assert snapshot["programs_traced"] == 1
    # the stack holds booked time only, an entry a program or trace
    assert len(compiles._TLS.stack) == 2 * compiles._ENTRY
    # a program run eagerly inside an open trace is not counted twice
    # by that trace: 10 s open, 3.7 of them the program's own
    began = 10.0
    _program(clock, began + 1.0)
    clock.fire(compiles.TRACE_EVENT, began + 10.0, 10.0,
               fun_name="outer")
    clock.fire(compiles.BACKEND_EVENT, began + 11.0, 0.5,
               fun_name="jit_outer")
    outer = ledger.snapshot()["records"][-1]
    assert outer["fun_name"] == "outer"
    assert outer["trace_ms"] == pytest.approx(10000.0 - 3700.0)


def test_two_threads_stacks_do_not_mix(clock):
    """Another thread's events arrive between this thread's children
    and their parent, inside its interval."""
    import threading
    ledger = compiles.install(service="unit")
    clock.fire(compiles.TRACE_EVENT, 0.5, 0.3, fun_name="inner")

    def other():
        compiles.set_label("other", "t")
        clock.fire(compiles.TRACE_EVENT, 0.7, 0.6, fun_name="theirs")
        clock.fire(compiles.BACKEND_EVENT, 0.9, 0.1,
                   fun_name="jit_theirs")

    thread = threading.Thread(target=other)
    thread.start()
    thread.join(timeout=10)
    assert not thread.is_alive()
    clock.fire(compiles.TRACE_EVENT, 1.0, 1.0, fun_name="mine")
    clock.fire(compiles.BACKEND_EVENT, 1.5, 0.4, fun_name="jit_mine")
    theirs, mine = ledger.snapshot()["records"]
    assert (theirs["program"], theirs["fun_name"]) == ("other", "theirs")
    assert theirs["trace_ms"] == pytest.approx(600.0)
    assert (mine["program"], mine["fun_name"]) == ("unlabeled", "mine")
    assert mine["trace_ms"] == pytest.approx(1000.0)


def test_a_hundred_thousand_nested_trace_events_in_under_a_second(
        clock):
    """A serving program fires 10⁴–10⁵ trace events: the handler has
    to vanish beside the 12–15 s of tracing they time."""
    import time
    ledger = compiles.install(service="unit")
    began = time.perf_counter()
    for outer in range(100):                # 100 programs' worth
        base = outer * 10.0
        for call in range(333):             # children, each with two
            at = base + call * 0.03
            clock.fire(compiles.TRACE_EVENT, at + 0.010, 0.005)
            clock.fire(compiles.TRACE_EVENT, at + 0.020, 0.005)
            clock.fire(compiles.TRACE_EVENT, at + 0.025, 0.024)
        clock.fire(compiles.TRACE_EVENT, base + 9.995, 9.995)
    elapsed = time.perf_counter() - began
    clock.fire(compiles.BACKEND_EVENT, 1000.0, 0.001)
    assert elapsed < 1.0, f"100,000 events took {elapsed:.2f} s"
    snapshot = ledger.snapshot()
    assert snapshot["trace_ms_total"] == pytest.approx(100 * 9995.0)
    # 99,900 children went as their parents arrived: what is left is
    # booked time, an entry for each of the 99 traces nothing lowered
    # and one for the program the last of them led to
    assert len(compiles._TLS.stack) == 100 * compiles._ENTRY
    assert snapshot["programs_traced"] == 1


def test_the_stack_is_bounded_where_nothing_is_ever_lowered(clock):
    ledger = compiles.install(service="unit")
    for index in range(compiles._STACK_CAP + 10):
        clock.fire(compiles.TRACE_EVENT, index + 0.5, 0.25)
    assert len(compiles._TLS.stack) <= 10 * compiles._ENTRY
    assert ledger.snapshot()["trace_ms_total"] == pytest.approx(
        (compiles._STACK_CAP + 1) * 250.0)
    assert ledger.snapshot()["programs_traced"] == 0


# ---------------------------------------------------------------- #
# The collector's pauses
# ---------------------------------------------------------------- #

def test_full_collections_are_timed_and_young_ones_ignored():
    import gc
    found = list(gc.callbacks)
    ledger = compiles.install(service="unit")
    assert gc.callbacks == found + [compiles._on_gc]
    compiles.install(service="again")        # idempotent: one entry
    assert gc.callbacks == found + [compiles._on_gc]
    before = ledger.gc_full_pauses    # a collection of the process's
    gc.collect(0)                     # own may fall anywhere
    gc.collect(1)
    assert ledger.gc_full_pauses == before
    gc.collect()
    assert ledger.gc_full_pauses == before + 1
    snapshot = ledger.snapshot()
    assert snapshot["gc_full_pauses"] == before + 1
    assert snapshot["gc_full_pause_ms"] > 0
    compiles.uninstall()
    assert gc.callbacks == found
    compiles.uninstall()                     # and again: nothing to do
    assert gc.callbacks == found
    gc.collect()
    assert ledger.gc_full_pauses == before + 1


def test_a_long_pause_gets_a_line_with_the_label_then_set(
        monkeypatch):
    ledger = compiles.install(service="unit")
    ticks = iter((50.0, 50.04, 60.0, 60.25))
    monkeypatch.setattr(compiles, "_now", lambda: next(ticks))
    with compiles.label("serve_chunk", "s8"):
        for _ in range(2):
            compiles._on_gc("start", {"generation": 2})
            compiles._on_gc("stop", {"generation": 2})
    snapshot = ledger.snapshot()
    assert snapshot["gc_full_pauses"] == 2
    assert snapshot["gc_full_pause_ms"] == pytest.approx(290.0)
    # 40 ms is in the totals only; 250 ms is a stall with a time on it
    assert snapshot["pauses"] == [
        {"ts": 60.25, "ms": pytest.approx(250.0),
         "program": "serve_chunk", "signature": "s8"}]
    # records stay what their readers expect: programs
    assert snapshot["records"] == []


def test_steady_compile_fires_flight_capture(tmp_path):
    flight.install(out_dir=str(tmp_path), service="unit")
    ledger = compiles.install(service="unit")
    ledger.fence()
    with compiles.label("paged_prefill", "w64"):
        ledger.record_compile(40.0)
    bundles = sorted(tmp_path.glob("capture_*.json"))
    assert len(bundles) == 1
    bundle = json.loads(bundles[0].read_text())
    assert bundle["manifest"]["trigger"] == "compile"
    assert "paged_prefill[w64]" in bundle["manifest"]["reason"]
    section = bundle["compiles"]
    assert section["compiles_steady_state"] == 1
    assert section["records"][-1]["program"] == "paged_prefill"
    # the operator's view of the same section: the totals, the
    # collector's line and a row a program with its phases
    from aiko_services_tpu.tools import doctor
    report = doctor.render_report(bundle)
    assert "host phases: trace 0 ms + lower 0 ms over 0 programs" in report
    assert "backend compile 40 ms, cache load 0 ms" in report
    assert "collector: " in report and "full collections" in report
    row = next(line for line in report.splitlines()
               if line.lstrip().startswith("paged_prefill"))
    assert row.split()[:2] == ["paged_prefill", "w64"]
    assert row.rstrip().endswith("<< STEADY-STATE")


# ---------------------------------------------------------------- #
# Persistent compilation cache (real jax)
# ---------------------------------------------------------------- #

def test_persistent_cache_counters_via_real_cache(tmp_path):
    import jax
    import jax.numpy as jnp

    ledger = compiles.install(service="cache-unit")
    found = jax.config.jax_compilation_cache_dir
    with compiles.persistent_cache(str(tmp_path / "cache")):
        # Both halves start as a restart does.  An earlier test of
        # this worker may have left ``arange(16)``'s program in the
        # in-memory caches: the cold half then compiled one program,
        # the directory held one, and the "restart" compiled the
        # other afresh (the one red test of PR 35's run).
        jax.clear_caches()
        with compiles.label("unit", "t"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(16))
        assert ledger.cache_misses > 0
        compiles_cold = ledger.compiles
        assert compiles_cold > 0
        jax.clear_caches()     # drop in-memory jit caches: "restart"
        with compiles.label("unit", "t"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(16))
        assert ledger.cache_hits > 0
        # retrievals were NOT booked as compiles
        assert ledger.compiles == compiles_cold
        assert ledger.cache_load_ms > 0
    # the rig scope restores the setting it found
    assert jax.config.jax_compilation_cache_dir == found


def test_a_jit_calling_a_jit_is_one_record_within_the_calls_wall():
    """Real jax on the CPU: the inner jit and every ``jnp`` wrapper
    fire trace events of their own inside the outer program's, and
    the record's phases still fit inside the call that paid them."""
    import time

    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.where(x > 0, jnp.sin(x), jnp.cos(x)) * 2

    @jax.jit
    def outer(x):
        for _ in range(3):
            x = inner(x) + jnp.tanh(x)
        return x

    from jax._src import monitoring

    argument = jnp.arange(8.0)          # its own small program: before
    seen = []

    def witness(event, duration, **kwargs):
        seen.append((event, duration))

    ledger = compiles.install(service="unit")
    monitoring.register_event_duration_secs_listener(witness)
    try:
        began = time.perf_counter()
        with compiles.label("outer", "s8"):
            jax.block_until_ready(outer(argument))
        wall_ms = (time.perf_counter() - began) * 1e3
    finally:
        monitoring.unregister_event_duration_listener(witness)
    (record,) = ledger.snapshot()["records"]
    assert (record["program"], record["fun_name"]) == ("outer", "outer")
    assert record["trace_ms"] > 0 and record["lower_ms"] > 0
    assert record["trace_ms"] + record["lower_ms"] + record["wall_ms"] \
        <= wall_ms
    traces = [duration for event, duration in seen
              if event == compiles.TRACE_EVENT]
    outermost = max(traces)
    assert len(traces) >= 10             # nested: a sum counts twice
    assert sum(traces) > outermost
    assert record["trace_ms"] == pytest.approx(
        outermost * 1e3, abs=1.0)
    assert ledger.snapshot()["programs_traced"] == 1
    # the same program again: nothing traced, nothing booked
    jax.block_until_ready(outer(argument))
    assert len(ledger.snapshot()["records"]) == 1


# ---------------------------------------------------------------- #
# Invariant 15: jaxpr byte-identical with ledger + profiler on
# ---------------------------------------------------------------- #

def test_ledger_and_profiler_do_not_change_jaxpr(tmp_path):
    import jax

    from aiko_services_tpu.models import llama
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer,
    )

    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=32, chunk_steps=2)

    def traced():
        return str(jax.make_jaxpr(
            lambda state, cache: llama.serve_chunk_ragged(
                server.params, state, cache, 2, server.config,
                eos_id=-1, sampled=False))(server._state, server.cache))

    clean = traced()
    compiles.install(service="test")
    compiles.set_label("serve_chunk", "s2")
    profiler.PROFILER = profiler.DeviceProfiler(
        out_dir=str(tmp_path), steps=4, service="test")
    try:
        assert traced() == clean
    finally:
        compiles.clear_label()


# ---------------------------------------------------------------- #
# The pow2 bucket discipline as an executable check
# (the log-bound comment at orchestration/continuous.py prefill loop)
# ---------------------------------------------------------------- #

def test_paged_prefill_compiles_log_bounded_and_steady_clean():
    from aiko_services_tpu.orchestration.continuous import (
        DecodeRequest,
    )
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer,
    )

    ledger = compiles.install(service="bound")
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   chunk_steps=4, seed=0)
    rng = np.random.RandomState(0)

    def wave(tag):
        # ragged lengths straddling pow2 bucket edges on purpose
        for index, prompt_len in enumerate((5, 9, 17, 24, 31, 40)):
            server.submit(DecodeRequest(
                request_id=f"{tag}{index}",
                prompt=rng.randint(
                    1, 64, size=prompt_len).astype(np.int32),
                max_new_tokens=4))
        server.run_until_drained()

    wave("a")
    distinct = ledger.signatures("paged_prefill")
    bound = int(math.log2(server.max_seq)) + 1
    assert 0 < len(distinct) <= bound, \
        f"{len(distinct)} prefill shapes vs log bound {bound}: " \
        f"{distinct}"
    compiles_after_wave_a = ledger.compiles
    ledger.fence()
    wave("b")      # identical shape population: NOTHING may compile
    assert ledger.compiles == compiles_after_wave_a
    assert ledger.steady_compiles == 0
    # stats() exposes the ledger to telemetry / EC shares
    stats = server.stats()
    assert stats["compiles"] == compiles_after_wave_a
    assert stats["compiles_steady_state"] == 0


# ---------------------------------------------------------------- #
# On-demand device profiling on the live engine
# ---------------------------------------------------------------- #

def test_profile_bracket_measures_device_ms_and_lands_in_doctor(
        tmp_path):
    from aiko_services_tpu.orchestration.continuous import (
        DecodeRequest,
    )
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer,
    )
    from aiko_services_tpu.tools import doctor

    flight.install(out_dir=str(tmp_path / "flight"), service="prof")
    compiles.install(service="prof")
    steplog.install()      # doctor's tax table needs step events to
    # show the MEASURED device_step_ms annotation
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   chunk_steps=4, seed=0)
    rng = np.random.RandomState(0)

    def submit(tag, count=2):
        for index in range(count):
            server.submit(DecodeRequest(
                request_id=f"{tag}{index}",
                prompt=rng.randint(1, 64, size=12).astype(np.int32),
                max_new_tokens=8))

    submit("warm")
    server.run_until_drained()
    assert server.request_profile(steps=4, reason="test bracket",
                                  out_dir=str(tmp_path / "prof"))
    assert not server.request_profile(steps=4)        # busy: one at a
    submit("p")                                       # time
    server.run_until_drained()
    stats = server.stats()
    assert stats["profiles"] == 1
    assert stats["device_step_ms"] > 0
    manifest = profiler.LAST
    assert manifest is not None and manifest["ok"]
    assert manifest["steps"] >= 4
    assert manifest["artifacts"], "no profiler artifacts captured"
    assert profiler.PROFILER is None                  # auto-finished

    # the bracket fired a flight capture whose bundle carries the
    # profile section; doctor renders it and --json pins the schema
    bundles = sorted((tmp_path / "flight").glob("capture_*.json"))
    assert bundles, "profile bracket did not capture a bundle"
    bundle = json.loads(bundles[-1].read_text())
    assert bundle["manifest"]["trigger"] == "profile"
    assert bundle["profile"]["device_step_ms"] == \
        stats["device_step_ms"]
    report = doctor.render_report(bundle)
    assert "device profile" in report
    assert "MEASURED" in report

    summary = doctor.bundle_summary(bundle)
    assert set(summary) == {
        "path", "trigger", "reason", "trace_id", "service", "pid",
        "captured_unix", "spans", "steplog", "tax_table",
        "counters_moved", "compiles", "profile", "census"}
    assert summary["profile"]["ok"] is True
    assert summary["profile"]["device_step_ms"] > 0
    assert summary["compiles"] is not None
    payload = json.loads(json.dumps(
        {"format": doctor.JSON_FORMAT,
         "bundles": [summary]}))
    assert payload["format"] == 1


def test_actor_profile_command_reports_unsupported():
    """Every actor answers ``(profile …)``; only engine-carrying
    actors can run a bracket — others must reply ``unsupported``, not
    drop the command (the router fan-out expects one reply per
    process)."""
    from aiko_services_tpu.runtime.actor import Actor

    published = []

    class _FakeActor:
        name = "plain"
        server = None
        process = type("P", (), {"message": type(
            "M", (), {"publish": staticmethod(
                lambda topic, payload:
                published.append((topic, payload)))})()})()

    Actor.profile(_FakeActor(), steps=2, response_topic="resp/t")
    assert published and published[0][0] == "resp/t"
    assert "unsupported" in published[0][1]


# ---------------------------------------------------------------- #
# The loadgen cold-vs-warm compile-cache A/B gate
# ---------------------------------------------------------------- #

def test_compile_cache_ab_warm_beats_cold():
    """PR-12's restart gate extended to compile time: warm restart
    must strictly beat cold on time-to-first-compiled-step (asserted
    inside the harness, with bit-exact tokens and > 0 cache hits)."""
    from aiko_services_tpu.tools.loadgen import run_compile_cache_ab

    cold, warm = run_compile_cache_ab(prompt_len=16, max_new_tokens=4)
    assert warm.elapsed_s < cold.elapsed_s
    assert warm.compile_cache["cache_hits"] > 0
    assert cold.compile_cache["compiles"] > 0
    assert warm.compile_cache["compiles"] < \
        cold.compile_cache["compiles"]
    # what the cache saved is measured: the warm arm's loads against
    # the cold arm's compiles
    assert 0 < warm.compile_cache["cache_load_ms_total"] < \
        cold.compile_cache["compile_wall_ms_total"]


@pytest.mark.slow
def test_chaos_compile_gate_zero_steady_compiles():
    """The full chaos rig under the compile gate: warmup wave, fence,
    fault schedule (replica kill mid-decode), and ZERO steady-state
    compiles — failover work must land on warmed or cache-served
    programs (asserted inside run_chaos)."""
    from aiko_services_tpu.tools.loadgen import run_chaos

    report = run_chaos(seed=1, n_requests=16, rate_hz=200.0,
                       compile_gate=True)
    assert report.lost == 0
    assert report.compiles_steady_state == 0
    assert report.warmup_compiles > 0
    assert report.warmup_s > 0
    assert report.steady_tokens_per_sec > 0
    assert "steady" in repr(report)


@pytest.mark.multichip
def test_mesh2d_sp_compiles_log_bounded_and_steady_clean(
        virtual_mesh_devices):
    """The pow2 bucket discipline survives the 2-D mesh: on tp=2 ×
    sp=2 the sp-window path adds ONE prefill signature per admission
    cap (not one per offset), so distinct prefill shapes stay
    log-bounded; the ladder pre-warm + a ragged warmup wave cover the
    whole shape space, and after the fence an identical second wave —
    including a mid-flight cancel + resubmit, the failover shape of
    work — compiles NOTHING."""
    from aiko_services_tpu.orchestration.continuous import (
        DecodeRequest,
    )
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer,
    )
    from aiko_services_tpu.parallel.mesh import ReplicaMesh

    ledger = compiles.install(service="mesh2d")
    server = PagedContinuousServer(config_name="tiny_tp", slots=2,
                                   chunk_steps=3, seed=0,
                                   block_size=16, max_seq=256,
                                   chunk_prefill_tokens=32,
                                   replica_mesh=ReplicaMesh(tp=2,
                                                            sp=2))
    assert server.warm_prefill_ladder() > 0       # sp-chunk ladder walk
    rng = np.random.RandomState(0)

    def wave(tag):
        # ragged lengths straddling bucket edges, two long enough
        # that the sp window (sp * cap = 64 tokens) fires
        for index, plen in enumerate((5, 24, 40, 90, 150)):
            server.submit(DecodeRequest(
                request_id=f"{tag}{index}",
                prompt=rng.randint(
                    1, 64, size=plen).astype(np.int32),
                max_new_tokens=4))
        server.run_until_drained()

    wave("a")
    assert server.counters["sp_prefill_dispatches"] > 0
    distinct = ledger.signatures("paged_prefill")
    # pow2 ladder + the single sp-window shape: log-bounded in sp
    # chunk count, NOT multiplied by it.
    bound = int(math.log2(server.max_seq)) + 2
    assert 0 < len(distinct) <= bound, \
        f"{len(distinct)} prefill shapes vs bound {bound}: {distinct}"
    assert any(sig.startswith("sp2") for _, sig in distinct), distinct
    compiles_after_wave_a = ledger.compiles
    ledger.fence()
    wave("b")      # identical shape population: NOTHING may compile
    # kill/failover-shaped churn: cancel a request mid-prefill and
    # resubmit it — the redispatch must land on warmed programs
    victim = DecodeRequest(
        request_id="kill", prompt=rng.randint(
            1, 64, size=150).astype(np.int32), max_new_tokens=4)
    server.submit(victim)
    server.step()
    assert server.cancel("kill")
    server.submit(DecodeRequest(request_id="kill2",
                                prompt=victim.prompt,
                                max_new_tokens=4))
    server.run_until_drained()
    assert ledger.compiles == compiles_after_wave_a
    assert ledger.steady_compiles == 0
    stats = server.stats()
    assert stats["compiles_steady_state"] == 0
