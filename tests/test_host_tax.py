"""Host-tax elimination on the decode hot loop (PR 16).

Four contracts:

- **Compact dirty-row uploads** (``_sync_dirty`` gathers only dirty
  mirror rows into a pow2-bucketed packet and row-scatters it into the
  resident state) are BITWISE equivalent to the legacy full-mirror
  masked merge — plain, composed (chunked admission + prefix cache +
  int8 KV), speculated, and TP=4.
- **Steady state uploads nothing**: between admission waves the decode
  loop records zero ``state_upload`` events, and every compact-upload
  compile signature is a pow2 bucket (bounded program count).
- **The adaptive in-flight ring** widens when the device starves,
  shrinks under host backlog, and clamps to ``[ring_min, ring_max]``.
- **Device-resident sampling edits** (``update_sampling``) ride the
  dirty-row path: no restart, mid-flight budget shrink retires cleanly.
"""

import numpy as np
import pytest

from aiko_services_tpu.models import llama
from aiko_services_tpu.obs import attrib, compiles, steplog
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.orchestration.serving import TELEMETRY_KEYS

import jax.numpy as jnp


def _requests(config, spec, seed=9, prefix=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, config.vocab_size, prefix).astype(np.int32)
    out = []
    for i, (plen, new) in enumerate(spec):
        tail = rng.integers(1, config.vocab_size, plen).astype(np.int32)
        prompt = np.concatenate([shared, tail]) if prefix else tail
        out.append(DecodeRequest(request_id=f"r{i}", prompt=prompt,
                                 max_new_tokens=new))
    return out


def _run(server, requests):
    for request in requests:
        server.submit(request)
    finished = server.run_until_drained()
    assert all(r.error is None for r in finished), finished
    return {r.request_id: list(r.tokens) for r in finished}


def _paged(compact, **overrides):
    kw = dict(config_name="tiny", slots=2, max_seq=96, chunk_steps=4,
              seed=3, block_size=16, compact_upload=compact)
    kw.update(overrides)
    return PagedContinuousServer(**kw)


# ---------------------------------------------------------------- #
# Compact upload ≡ legacy merge, bitwise, under every composition
# ---------------------------------------------------------------- #

def test_compact_vs_legacy_parity_plain():
    """Same requests through the compact scatter path and the legacy
    full-mirror merge: identical greedy tokens, and both paths account
    their uploads (the compact one row-exactly)."""
    spec = [(7, 6), (12, 5), (4, 8), (9, 4)]
    outs, counters = {}, {}
    for compact in (True, False):
        server = _paged(compact)
        outs[compact] = _run(server, _requests(server.config, spec))
        counters[compact] = dict(server.counters)
    assert outs[True] == outs[False]
    for compact in (True, False):
        assert counters[compact]["state_uploads"] >= 1
        assert counters[compact]["dirty_rows_uploaded"] \
            >= counters[compact]["state_uploads"]


def test_compact_vs_legacy_parity_composed():
    """Chunked admission + prefix cache + int8 KV on top: the compact
    packet carries the paged block tables too, so the composition is
    where a missed leaf would show up as divergence."""
    spec = [(40, 5), (40, 4), (7, 6)]
    outs = {}
    for compact in (True, False):
        server = _paged(compact, max_seq=128,
                        enable_prefix_cache=True,
                        chunk_prefill_tokens=32, quantize_kv=True)
        outs[compact] = _run(
            server, _requests(server.config, spec, prefix=32))
        assert server.stats()["prefix_hits"] > 0
    assert outs[True] == outs[False]


def test_compact_vs_legacy_parity_speculated():
    """Speculation (paired draft — high acceptance) over the compact
    path: spec rounds consume the same resident state chain, so parity
    here locks the spec ring entries' interaction with row scatters."""
    spec = [(7, 6), (12, 8)]
    outs = {}
    for compact in (True, False):
        server = _paged(compact, draft_config_name="tiny", spec_k=3)
        server._draft["params"] = server.params
        server._draft["config"] = server.config
        outs[compact] = _run(server, _requests(server.config, spec))
    assert outs[True] == outs[False]


@pytest.mark.multichip
def test_compact_vs_legacy_parity_tp4(virtual_mesh_devices):
    """TP=4: the packet is replicated onto the replica mesh before the
    scatter, so the merged state stays a replicated jax.Array that
    shard_map accepts — and tokens stay bitwise equal to legacy."""
    from aiko_services_tpu.parallel.mesh import ReplicaMesh

    spec = [(7, 5), (12, 4)]
    outs = {}
    for compact in (True, False):
        server = _paged(compact, config_name="tiny_tp", max_seq=128,
                        replica_mesh=ReplicaMesh(tp=4))
        outs[compact] = _run(server, _requests(server.config, spec))
        assert server.stats()["tp_degree"] == 4
    assert outs[True] == outs[False]


# ---------------------------------------------------------------- #
# Steady state: no uploads, pow2-bounded scatter programs
# ---------------------------------------------------------------- #

def test_steady_state_records_no_state_upload_events():
    """After the admission wave the decode loop must never touch the
    host→device state path: zero ``state_upload`` step-log events and
    a flat ``state_uploads`` counter until drain."""
    server = _paged(True)
    for request in _requests(server.config, [(7, 24), (9, 24)]):
        server.submit(request)
    server.step()                       # admit + first dispatches
    uploads = server.counters["state_uploads"]
    recorder = steplog.install()
    try:
        while server.busy:
            server.step()
        events = [name for _t, name, _f in recorder.events()]
    finally:
        steplog.uninstall()
    assert "state_upload" not in events, events
    assert server.counters["state_uploads"] == uploads


def test_compact_upload_compiles_are_pow2_bucketed():
    """Every ``scatter_rows`` compile signature is a pow2 row-count
    bucket — the ledger would otherwise show one program per distinct
    dirty count (a shape leak the fence turns into a capture)."""
    ledger_owned = compiles.LEDGER is None
    ledger = compiles.install(service="test-host-tax")
    # The ledger sees a scatter only when one COMPILES: drop what an
    # earlier test of this process left in the function's jit cache.
    llama.scatter_state_rows.clear_cache()
    try:
        server = _paged(True)
        _run(server, _requests(server.config, [(7, 4), (9, 5), (4, 3)]))
        labels = [signature for program, signature
                  in ledger.signatures("scatter_rows")]
    finally:
        if ledger_owned:
            compiles.uninstall()
    assert labels, "compact path never compiled a scatter"
    for label in labels:
        bucket = int(label.lstrip("r"))
        assert bucket & (bucket - 1) == 0, labels


# ---------------------------------------------------------------- #
# Adaptive in-flight ring
# ---------------------------------------------------------------- #

def test_ring_policy_widens_on_starvation():
    policy = ContinuousBatchingServer._ring_policy
    assert policy(2, 2, 6, wait_ema=0.1, dispatch_ema=1.0,
                  starved_streak=2) == 3
    # one isolated starved pass is noise, not a trend
    assert policy(2, 2, 6, wait_ema=0.1, dispatch_ema=1.0,
                  starved_streak=1) == 2


def test_ring_policy_shrinks_on_backlog():
    policy = ContinuousBatchingServer._ring_policy
    assert policy(4, 2, 6, wait_ema=5.0, dispatch_ema=1.0,
                  starved_streak=0) == 3


def test_ring_policy_clamps_and_handles_cold_start():
    policy = ContinuousBatchingServer._ring_policy
    # shrink pressure at the floor stays at the floor
    assert policy(2, 2, 6, wait_ema=9.0, dispatch_ema=1.0,
                  starved_streak=0) == 2
    # widen pressure at the ceiling stays at the ceiling
    assert policy(6, 2, 6, wait_ema=0.0, dispatch_ema=1.0,
                  starved_streak=9) == 6
    # no EMAs yet (cold start): hold, but still clamp
    assert policy(9, 2, 6, wait_ema=None, dispatch_ema=None,
                  starved_streak=0) == 6


def test_ring_max_below_floor_rejected():
    with pytest.raises(ValueError):
        ContinuousBatchingServer(config_name="tiny", slots=2,
                                 max_seq=64, chunk_steps=2, seed=3,
                                 lookahead=3, ring_max=2)


def test_ring_depth_stays_clamped_and_telemetered():
    server = _paged(True, ring_max=5)
    for request in _requests(server.config, [(7, 10), (9, 10)]):
        server.submit(request)
    while server.busy:
        server.step()
        assert (server.ring_min <= server.stats()["ring_depth"]
                <= server.ring_max)
    stats = server.stats()
    for key in ("ring_depth", "ring_starved_steps",
                "dirty_rows_uploaded"):
        assert key in TELEMETRY_KEYS
        assert key in stats


# ---------------------------------------------------------------- #
# Listening for arrivals while a chunk that has just begun runs
# ---------------------------------------------------------------- #

@pytest.mark.parametrize("ring, wait_ms, want", [
    ((), 90.0, 0.0),           # nothing in flight: an idle device
    (("chunk",), None, 0.0),   # cold start: no wait measured yet
    (("chunk",), 4.0, 0.0005),  # an eighth of the host's wait
    (("chunk",), 90.0, 0.010),  # capped
])
def test_arrival_hold_follows_the_ring_and_the_wait(ring, wait_ms, want):
    server = ContinuousBatchingServer.__new__(ContinuousBatchingServer)
    server._ring, server._ema_wait_ms = list(ring), wait_ms
    assert server.arrival_hold_s() == pytest.approx(want)


def test_arrival_hold_is_zero_once_the_ring_has_drained():
    server = _paged(True)
    for request in _requests(server.config, [(7, 10)]):
        server.submit(request)
    held = []
    while server.busy:
        server.step()
        held.append((bool(server._ring), server.arrival_hold_s()))
    assert any(ring for ring, _ in held)
    assert all(0.0 <= hold <= server.ARRIVAL_HOLD_MAX_S
               for _, hold in held)
    assert all(hold == 0.0 for ring, hold in held if not ring)
    assert server.arrival_hold_s() == 0.0


# ---------------------------------------------------------------- #
# Device-resident sampling-param edits
# ---------------------------------------------------------------- #

def test_update_sampling_budget_shrink_retires_cleanly():
    """Shrinking a live request's budget mid-flight delivers a prefix
    of the untouched run and frees the slot — no restart, no error."""
    server = _paged(True)
    [request] = _requests(server.config, [(7, 20)])
    baseline = _run(_paged(True), _requests(server.config, [(7, 20)]))
    server.submit(request)
    server.step()
    server.step()
    assert server.update_sampling(request.request_id, max_new_tokens=3)
    server.run_until_drained()
    assert request.error is None
    assert 3 <= len(request.tokens) < 20
    assert list(request.tokens) == \
        baseline[request.request_id][:len(request.tokens)]
    assert server.stats()["slots_active"] == 0


def test_update_sampling_marks_slot_dirty_and_queued_edits():
    server = _paged(True)
    live, queued = _requests(server.config, [(7, 12), (9, 6)])
    server.submit(live)
    server.step()                       # live admitted
    server.submit(queued)               # stays queued (slot budget ok,
    # but edit BEFORE admission must not touch device state)
    assert server.update_sampling(queued.request_id, top_p=0.5)
    assert queued.top_p == 0.5
    assert server.update_sampling(live.request_id, temperature=0.0,
                                  top_p=0.9)
    slot = next(s for s, r in enumerate(server._requests) if r is live)
    # Sampling-only edits ride the sampling-leaf scatter (the slot may
    # have chunks in flight), never the full-row structural upload.
    assert server._dirty_sampling[slot]
    assert not server._dirty[slot]
    assert server._top_ps[slot] == pytest.approx(0.9)
    assert not server.update_sampling("no-such-id", temperature=1.0)
    server.run_until_drained()


# ---------------------------------------------------------------- #
# Attribution: admission compute stays out of the decode loop
# ---------------------------------------------------------------- #

def test_attrib_classifies_post_admission_dispatch():
    events = [
        (0.000, "admission", {"slots": 2}),
        (0.010, "dispatch", {"ring": 1, "after_admission": 1}),
        (0.020, "dispatch", {"ring": 2}),
        (0.030, "sync", {"wait_ms": 2.0, "steps": 4}),
    ]
    table = attrib.attribute_steps(events, wall_ms=30.0)
    by_name = {row.component: row for row in table.rows}
    assert by_name["post_admission_dispatch"].ms == pytest.approx(10.0)
    assert by_name["dispatch"].ms == pytest.approx(10.0)
    assert table.within(0.10)


def test_scatter_state_rows_duplicate_padding_benign():
    """The pow2 pad repeats the last dirty row: duplicate indices with
    identical payloads must merge order-independently, and host dtypes
    are cast to the resident leaf's dtype."""
    state = {"token": jnp.zeros((4, 1), jnp.int32),
             "temps": jnp.zeros((4,), jnp.float32)}
    rows = jnp.asarray(np.array([1, 3, 3, 3], np.int32))
    packet = {"token": np.array([[5], [7], [7], [7]], np.int64),
              "temps": np.array([0.5, 0.25, 0.25, 0.25], np.float64)}
    merged = llama.scatter_state_rows(state, rows, packet)
    np.testing.assert_array_equal(
        np.asarray(merged["token"]).ravel(), [0, 5, 0, 7])
    np.testing.assert_allclose(
        np.asarray(merged["temps"]), [0.0, 0.5, 0.0, 0.25])
    assert merged["token"].dtype == jnp.int32


def test_a_fresh_ledger_finds_no_label_an_engine_left_on_the_thread():
    """An engine that served under an earlier ledger leaves its last
    dispatch's label (``serve_chunk``) on the thread; a ledger
    installed afterwards must not book its first compile under it.
    Dealt to one xdist worker before ``test_compiles.py``, this file
    made ``test_two_threads_stacks_do_not_mix`` read ``'serve_chunk'
    != 'unlabeled'`` (PERF.md section 7, after PR 38 (0))."""
    previous = compiles.LEDGER
    if previous is not None:
        compiles.uninstall()
    try:
        compiles.set_label("serve_chunk", "s8")
        compiles.install(service="fresh")
        assert compiles.current_label() == ("unlabeled", "")
    finally:
        compiles.uninstall()
        if previous is not None:
            compiles.install(ledger=previous)
