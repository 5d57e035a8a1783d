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
  ``doctor --json`` (schema pinned here).
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
    # signed saved-time accumulates raw (can be negative)
    compiles._on_duration("/jax/compilation_cache/compile_time_saved",
                          -0.001)
    assert ledger.cache_saved_ms == pytest.approx(-1.0)


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


# ---------------------------------------------------------------- #
# Persistent compilation cache (real jax)
# ---------------------------------------------------------------- #

def test_persistent_cache_counters_via_real_cache(tmp_path):
    import jax
    import jax.numpy as jnp

    ledger = compiles.install(service="cache-unit")
    found = jax.config.jax_compilation_cache_dir
    with compiles.persistent_cache(str(tmp_path / "cache")):
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
    # the rig scope restores the setting it found
    assert jax.config.jax_compilation_cache_dir == found


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
