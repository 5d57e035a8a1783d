"""Tiered KV cache: host-RAM demotion tier with async restore.

The three gates of ARCHITECTURE invariant 10:

* **Bit-exactness** — a chain demoted to host RAM and restored into
  freshly allocated pool blocks produces greedy decode BITWISE equal
  to the never-evicted chain, for bf16 and int8 pools, single-chip
  and TP meshes, including cross-replica export served from the host
  tier.  Host rows are the pool bytes verbatim (never re-quantized),
  which is the whole mechanism.
* **No stalls** — restores land asynchronously (``_producing`` miss
  semantics, bounded blocks per engine step); active decode slots
  keep emitting tokens while a multi-block restore is in flight, and
  the traced serve-chunk program is byte-identical before and after a
  demote/restore cycle (invariant 7: host branches never enter jitted
  modules).
* **Capacity** — a long-tail workload whose prefix working set
  overflows the HBM pool gets strictly higher prefix hit rate AND
  lower mean TTFT with the tier on than off (slow test).
"""

import ast
import pathlib
import statistics

import numpy as np
import pytest

from aiko_services_tpu.kvstore import chain_keys_hex, digest_encode
from aiko_services_tpu.kvstore.directory import PrefixDirectory
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import (
    RESTORING, PagedContinuousServer,
)
from aiko_services_tpu.parallel.mesh import ReplicaMesh
from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
from aiko_services_tpu.utils.sexpr import generate

from .test_kvstore import _router_rig, _warm, make_server

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "aiko_services_tpu"

BOTH_DTYPES = pytest.mark.parametrize("quantize_kv", [False, True],
                                      ids=["bf16", "int8"])


def _demote_all(server):
    """Leaf-first demote every zero-ref cached block (what pool
    pressure would eventually do), returning how many moved."""
    before = server.kv_demotions
    while server._evict_one():
        pass
    return server.kv_demotions - before


# ---------------------------------------------------------------- #
# Bit-exactness: restored chain == never-evicted chain
# ---------------------------------------------------------------- #

@BOTH_DTYPES
def test_restored_chain_greedy_bit_exact(quantize_kv):
    prompt = np.arange(1, 50, dtype=np.int32)       # 3 shareable blocks
    server = make_server(quantize_kv=quantize_kv, host_tier_blocks=16)
    want = _warm(server, prompt)

    assert _demote_all(server) == 3
    stats = server.stats()
    assert stats["kv_host_blocks"] == 3
    assert stats["kv_host_bytes"] > 0
    assert stats["prefix_evictions"] == 0           # demoted, not lost

    got = _warm(server, prompt)
    stats = server.stats()
    assert got == want
    assert stats["kv_restores"] == 3
    assert stats["prefix_hits_host"] == 1
    assert stats["kv_host_blocks"] == 0             # promoted back
    assert stats["restore_queue_depth"] == 0

    # Never-evicted reference: a cold server's first decode.
    cold = make_server(quantize_kv=quantize_kv)
    assert got == _warm(cold, prompt)


def test_demote_restore_preserves_chain_identity():
    """A demoted key keeps its depth/parent linkage and hit counters;
    restore re-indexes the same key bytes (no re-hash, no re-seed)."""
    prompt = np.arange(1, 50, dtype=np.int32)
    server = make_server(host_tier_blocks=16)
    _warm(server, prompt)
    keys = list(server._index)
    depths = {key: server._depth[key] for key in keys}

    _demote_all(server)
    for key in keys:
        assert key in server._host and key not in server._index
        assert server._depth[key] == depths[key]    # identity survives
    _warm(server, prompt)
    for key in keys:
        assert key in server._index and key not in server._host

    # Host overflow is the true eviction: identity goes with it.
    tiny = make_server(host_tier_blocks=1)
    _warm(tiny, prompt)
    _demote_all(tiny)
    assert tiny.stats()["kv_host_blocks"] == 1
    assert tiny.stats()["prefix_evictions"] == 2    # overflowed chain tail


@pytest.mark.multichip
@BOTH_DTYPES
def test_tp4_restore_bit_exact(virtual_mesh_devices, quantize_kv):
    """Demote/restore through the TP gather/re-pin paths: full
    kv-head-width host rows, scatter re-pinned to the pool sharding —
    greedy decode equals both the TP never-evicted run and the
    single-chip restored run."""
    prompt = np.arange(1, 66, dtype=np.int32)       # 4 shareable blocks

    def run(tp):
        kw = dict(config_name="tiny_tp", slots=2, max_seq=128,
                  chunk_steps=3, seed=5, block_size=16,
                  enable_prefix_cache=True, chunk_prefill_tokens=32,
                  quantize_kv=quantize_kv, host_tier_blocks=16,
                  restore_blocks_per_step=2)
        if tp:
            kw["replica_mesh"] = ReplicaMesh(tp=tp)
        server = PagedContinuousServer(**kw)
        first = _warm(server, prompt)
        assert _demote_all(server) == 4
        second = _warm(server, prompt)
        assert server.stats()["kv_restores"] == 4
        assert server.stats()["prefix_hits_host"] == 1
        return first, second

    tp_first, tp_second = run(4)
    chip_first, chip_second = run(None)
    assert tp_second == tp_first                    # restore == resident
    assert tp_second == chip_second == chip_first   # TP == single chip


# ---------------------------------------------------------------- #
# Cross-replica export served FROM the host tier
# ---------------------------------------------------------------- #

@BOTH_DTYPES
def test_export_serves_host_tier_without_promotion(quantize_kv):
    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server(quantize_kv=quantize_kv, host_tier_blocks=16)
    want = _warm(owner, prompt)
    assert _demote_all(owner) == 3

    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    assert payload is not None and len(payload["kv_keys"]) == 3
    stats = owner.stats()
    assert stats["kv_host_blocks"] == 3             # NOT promoted
    assert stats["kv_restores"] == 0

    importer = make_server(quantize_kv=quantize_kv)
    assert importer.kv_import_payload(
        decode_swag(encode_swag(payload))) == 3
    got = _warm(importer, prompt)
    cold = make_server(quantize_kv=quantize_kv)
    assert got == want == _warm(cold, prompt)


def test_export_splices_mixed_hbm_and_host_sources():
    """A chain straddling tiers (leaf demoted, ancestors resident)
    exports as one payload — per-position source splicing."""
    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server(host_tier_blocks=16)
    want = _warm(owner, prompt)
    assert owner._evict_one()                       # deepest leaf only
    assert owner.stats()["kv_host_blocks"] == 1

    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    assert payload is not None and len(payload["kv_keys"]) == 3
    importer = make_server()
    assert importer.kv_import_payload(payload) == 3
    assert _warm(importer, prompt) == want


# ---------------------------------------------------------------- #
# No stalls: decode keeps producing while a restore is in flight
# ---------------------------------------------------------------- #

def test_active_slots_produce_during_multiblock_restore():
    # Pool sized so the 4-block restore fits WHILE the active slot
    # holds its blocks — the overlap this gate is about.
    server = make_server(host_tier_blocks=16, restore_blocks_per_step=1,
                         total_blocks=24)
    prompt_a = np.arange(1, 66, dtype=np.int32)     # 4 shareable blocks
    want_a = _warm(server, prompt_a)
    assert _demote_all(server) == 4

    active = DecodeRequest(request_id="active",
                           prompt=np.arange(200, 220, dtype=np.int32),
                           max_new_tokens=16)
    server.submit(active)
    for _ in range(8):                              # admit + first token
        server.step()
        if active.tokens:
            break
    assert len(active.tokens) > 0

    restored = DecodeRequest(request_id="restored", prompt=prompt_a,
                             max_new_tokens=4)
    server.submit(restored)
    produced_during_restore = False
    for _ in range(40):
        depth_before = server.stats()["restore_queue_depth"]
        emitted_before = len(active.tokens)
        server.step()
        if depth_before > 0 and len(active.tokens) > emitted_before:
            produced_during_restore = True
        if not server.busy:
            break
    # 4 blocks at 1 block/step guarantee several such steps.
    assert produced_during_restore
    assert restored.tokens == want_a                # bit-exact through it all
    assert server.stats()["kv_restores"] == 4
    assert server.stats()["prefix_hits_host"] == 1


def test_restore_sentinel_never_collides_with_slot_owner():
    """RESTORING must stay outside the slot-id space ``_producing``
    uses for in-flight prefills — cancel/finish paths match owners by
    slot id and must never clear a restore in flight."""
    assert RESTORING == -1
    server = make_server(host_tier_blocks=16)
    assert all(slot >= 0 for slot in range(server.slots))


def test_restore_under_pool_pressure_converges():
    """When the pool can't immediately host the restored chain
    (everything else pinned), admission defers behind the filler and
    resolves once blocks free — never a livelock, never half a chain,
    and the answer is bit-exact regardless of which path produced it."""
    server = make_server(total_blocks=7, host_tier_blocks=16)
    prompt = np.arange(1, 50, dtype=np.int32)
    want = _warm(server, prompt)
    _demote_all(server)
    # Pin the pool with an unrelated request large enough that the
    # 3-block chain can't fit alongside it.
    filler = DecodeRequest(request_id="filler",
                           prompt=np.arange(100, 140, dtype=np.int32),
                           max_new_tokens=24)
    server.submit(filler)
    server.submit(DecodeRequest(request_id="again", prompt=prompt,
                                max_new_tokens=4))
    finished = server.run_until_drained()
    tokens = {r.request_id: r.tokens for r in finished}
    assert tokens["again"] == want                  # exact either way
    assert server.stats()["restore_queue_depth"] == 0


# ---------------------------------------------------------------- #
# Invariant 7: the tier never touches traced programs
# ---------------------------------------------------------------- #

def test_demote_restore_does_not_change_serve_chunk_jaxpr():
    import jax

    from aiko_services_tpu.models import llama

    prompt = np.arange(1, 50, dtype=np.int32)
    server = make_server(host_tier_blocks=16)
    _warm(server, prompt)

    def trace():
        return str(jax.make_jaxpr(
            lambda state, pool: llama.serve_chunk_paged(
                server.params, state, pool, 2, server.config,
                eos_id=-1, sampled=False))(server._state, server.pool))

    clean = trace()
    _demote_all(server)
    assert trace() == clean
    _warm(server, prompt)                           # restores
    assert server.stats()["kv_restores"] == 3
    assert trace() == clean


def test_no_tier_references_in_traced_modules():
    """models/ and ops/ build the jitted programs; the host tier is
    orchestration-side bookkeeping and must never leak in."""
    banned = ("demote", "restore", "host_tier", "RESTORING")
    for directory in ("models", "ops"):
        for path in sorted((PKG / directory).glob("*.py")):
            tree = ast.parse(path.read_text())
            for node in ast.walk(tree):
                name = getattr(node, "id", None) \
                    or getattr(node, "attr", None)
                if isinstance(name, str):
                    assert not any(word in name for word in banned), \
                        f"{path.name}:{node.lineno}: {name}"


# ---------------------------------------------------------------- #
# Directory + router: tier-aware advertisement and scoring
# ---------------------------------------------------------------- #

def test_matched_detail_counts_host_blocks():
    directory = PrefixDirectory(lease_s=30.0)
    keys = [f"{i:016x}" for i in range(4)]
    entries = [(key, depth + 1, 0, 1, 1 if depth >= 2 else 0)
               for depth, key in enumerate(keys)]
    directory.update("ra", digest_encode(16, "decode", entries),
                     now=0.0)
    assert directory.matched_blocks("ra", keys, now=1.0) == 4
    assert directory.matched_detail("ra", keys, now=1.0) == (4, 2)
    # Only matched ancestors count toward the host tally.
    assert directory.matched_detail("ra", keys[:2], now=1.0) == (2, 0)
    assert directory.matched_detail("ra", ["ff" * 8], now=1.0) == (0, 0)


def test_router_prefers_hbm_owner_over_host_owner(engine):
    """Equal depth, equal queue: the replica holding the chain in HBM
    wins over the one that would have to restore it; a host owner
    still wins over no owner (and counts as host-routed)."""
    router, topics, pr = _router_rig(engine, "kvtier")
    prompt = np.arange(1, 50, dtype=np.int32)
    keys = chain_keys_hex(prompt, 16)

    def advertise(topic, tier):
        entries = [(key, depth + 1, 0, 1, tier)
                   for depth, key in enumerate(keys)]
        pr.message.publish(
            f"{topic}/state",
            generate("update", ["kv_prefixes",
                                digest_encode(16, "decode", entries)]))

    advertise(topics[0], tier=1)                    # host copy
    advertise(topics[1], tier=0)                    # HBM copy
    engine.drain()

    payload = encode_swag({"tokens": prompt})
    assert router.route("m1", "test/resp", dict(payload))
    assert router._inflight["m1"]["replica"] == topics[1]
    engine.drain()
    assert router.counters["prefix_routed"] == 1
    assert router.counters.get("prefix_routed_host", 0) == 0

    # HBM owner gone: the host owner is still far better than a
    # recompute — routed there, tallied as a host-tier route.
    pr.message.publish(f"{topics[1]}/state",
                       generate("update", ["lifecycle", "unhealthy"]))
    engine.drain()
    assert router.route("m2", "test/resp", dict(payload))
    assert router._inflight["m2"]["replica"] == topics[0]
    engine.drain()
    assert router.counters["prefix_routed_host"] == 1


def test_replica_digest_advertises_tiers():
    from aiko_services_tpu.kvstore import digest_decode

    server = make_server(host_tier_blocks=16)
    prompt = np.arange(1, 50, dtype=np.int32)
    _warm(server, prompt)
    tiers = {entry[4] for entry in digest_decode(server.prefix_digest())[2]}
    assert tiers == {0}
    assert server._evict_one()                      # demote one leaf
    entries = digest_decode(server.prefix_digest())[2]
    assert {entry[4] for entry in entries} == {0, 1}
    assert sum(1 for entry in entries if entry[4] == 1) == 1


# ---------------------------------------------------------------- #
# Capacity gate (slow): tier-on beats tier-off under overflow
# ---------------------------------------------------------------- #

def test_longtail_tier_capacity_gate():
    """The HBM pool holds 52 blocks; the longtail working set needs
    ~144.  With the tier on, demoted chains restore instead of
    recomputing: strictly higher prefix hit rate AND lower mean TTFT
    at the same pool size."""
    from aiko_services_tpu.tools.loadgen import run_longtail

    tier_on = run_longtail(host_tier_blocks=160, seed=0)
    tier_off = run_longtail(host_tier_blocks=0, seed=0)
    for report in (tier_on, tier_off):
        assert report.lost == 0 and report.timeouts == 0

    assert (tier_on.prefix_hit_rate or 0.0) \
        > (tier_off.prefix_hit_rate or 0.0)
    assert tier_on.prefix_hit_rate_host == 1.0      # every hit via tier
    assert statistics.fmean(tier_on.ttfts_ms) \
        < statistics.fmean(tier_off.ttfts_ms)
    stats = tier_on.server_stats
    assert stats["kv_restores"] > 0
    assert stats["prefix_routed_host"] > 0
    assert tier_off.server_stats["kv_demotions"] == 0
