"""Distributed KV-cache subsystem: chain-key identity, the digest/
directory protocol, cross-replica block transfer exactness (bf16 and
int8), prefix-aware routing, disaggregated prefill/decode, telemetry
flow into the dashboard, and the jaxpr guard pinning transfers out of
traced serve-chunk programs."""

import ast
import pathlib

import numpy as np
import pytest

from aiko_services_tpu.kvstore import (
    PrefixDirectory, chain_keys, chain_keys_hex, digest_decode,
    digest_encode, export_payload, import_payload, payload_bytes,
    pool_signature, seed_chain, shareable_blocks,
)
from aiko_services_tpu.kvstore.directory import HEX_KEY_CHARS
from aiko_services_tpu.orchestration.continuous import (
    ContinuousReplica, DecodeRequest,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
from aiko_services_tpu.registry import Registrar
from aiko_services_tpu.runtime import (
    Process, actor_args, compose_instance,
)
from aiko_services_tpu.utils.sexpr import generate, parse

from .test_continuous import reference_greedy

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "aiko_services_tpu"


def make_server(**kwargs):
    defaults = dict(config_name="tiny", slots=2, max_seq=96,
                    chunk_steps=4, seed=0, block_size=16,
                    enable_prefix_cache=True)
    defaults.update(kwargs)
    return PagedContinuousServer(**defaults)


def make_process(engine, pid, broker):
    return Process(namespace="test", hostname="h", pid=str(pid),
                   engine=engine, broker=broker)


# ---------------------------------------------------------------- #
# Chain keys & digest wire format
# ---------------------------------------------------------------- #

def test_chain_keys_shared_definition_with_server():
    """The router-side hashing (kvstore) and the server's admission
    walk must produce byte-identical keys from tokens alone — the
    property that makes a digest advertised by one process matchable
    by any other."""
    server = make_server()
    prompt = np.arange(1, 50, dtype=np.int32)
    assert server._chain_keys(prompt) == chain_keys(prompt, 16)
    # Adapter-seeded chains diverge from base chains on the SAME
    # tokens — cross-adapter sharing is structurally impossible.
    assert chain_keys(prompt, 16, adapter_id=1) != chain_keys(prompt, 16)


def test_shareable_blocks_excludes_admission_seed_block():
    # Last prompt position's row is rewritten at admission, so the
    # block containing position prompt_len-1 is never shareable.
    assert shareable_blocks(16, 16) == 0
    assert shareable_blocks(17, 16) == 1
    assert shareable_blocks(33, 16) == 2
    assert shareable_blocks(0, 16) == 0
    prompt = np.arange(1, 34, dtype=np.int32)       # len 33
    assert len(chain_keys_hex(prompt, 16)) == 2
    assert all(len(k) == HEX_KEY_CHARS for k in chain_keys_hex(prompt, 16))


def test_digest_roundtrip_and_malformed():
    # 4-field entries stay valid wire (pre-tier replicas); decode
    # always returns 8-tuples with tier/adopted/migrating/adapter 0
    # appended.
    entries = [("ab12cd34ef567890", 3, 1, 7),
               ("ffee001122334455", 2, 0, 1)]
    text = digest_encode(16, "decode", entries)
    assert digest_decode(text) == (
        16, "decode", [entry + (0, 0, 0, 0) for entry in entries])
    # Host-tier entries carry a 5th field; tier 0 encodes 4-field
    # (the wire only grows where the tier is actually in play).
    tiered = [("ab12cd34ef567890", 3, 1, 7, 0),
              ("ffee001122334455", 2, 0, 1, 1)]
    text = digest_encode(16, "decode", tiered)
    assert "ab12cd34ef567890/3/1/7," in text     # tier 0 stays 4-field
    assert text.endswith("/2/0/1/1")             # tier 1 appends
    assert digest_decode(text) == (
        16, "decode", [("ab12cd34ef567890", 3, 1, 7, 0, 0, 0, 0),
                       ("ffee001122334455", 2, 0, 1, 1, 0, 0, 0)])
    # Spilled entries carry the adopted 6th field; a zero flag keeps
    # the 5-field tier wire (same back-compat move tier made).
    spilled = [("ab12cd34ef567890", 3, 1, 7, 2, 0),
               ("ffee001122334455", 2, 0, 1, 2, 1)]
    text = digest_encode(16, "decode", spilled)
    assert "ab12cd34ef567890/3/1/7/2," in text   # adopted 0: 5-field
    assert text.endswith("/2/0/1/2/1")           # adopted 1 appends
    assert digest_decode(text) == (
        16, "decode", [entry + (0, 0) for entry in spilled])
    # S-expression safe: survives the EC-share broadcast wire.
    command, params = parse(generate("update", ["kv_prefixes", text]))
    assert (command, params[1]) == ("update", text)
    for bad in ("", "16;decode", "x;decode;a/1/2/3",
                "16;decode;nodepth", None, "16;d;a/b/c/d",
                "16;decode;ab/1/2/3/4/5/6/7/8"):
        assert digest_decode(bad) is None


def test_digest_migrating_flag_back_compat_matrix():
    """The 7th (``migrating``) field composes with every older wire
    format: a zero flag leaves the 4/5/6-field encodings byte-for-
    byte unchanged (pre-migration routers parse them untouched), a
    set flag forces the full positional 7-field entry, and the
    publisher-level ``migrating=1`` kwarg ORs into every entry."""
    four = ("ab12cd34ef567890", 3, 1, 7)
    five = ("ffee001122334455", 2, 0, 1, 1)
    six = ("0123456789abcdef", 1, 0, 2, 2, 1)
    # Zero flag: encodings identical to the pre-migration wire.
    assert digest_encode(16, "decode", [four + (0, 0, 0)]) \
        == digest_encode(16, "decode", [four])
    assert digest_encode(16, "decode", [five + (0, 0)]) \
        == digest_encode(16, "decode", [five])
    assert digest_encode(16, "decode", [six + (0,)]) \
        == digest_encode(16, "decode", [six])
    # Set flag: the full 7-field entry, zeros written positionally.
    text = digest_encode(16, "decode", [four + (0, 0, 1)])
    assert text.endswith("/3/1/7/0/0/1")
    assert digest_decode(text) == (16, "decode",
                                   [four + (0, 0, 1, 0)])
    # Publisher-level flag ORs into every entry, whatever its arity.
    text = digest_encode(16, "decode", [four, five, six], migrating=1)
    _, _, decoded = digest_decode(text)
    assert [entry[6] for entry in decoded] == [1, 1, 1]
    assert decoded[1][:5] == five                # payload untouched
    # Decode matrix: every arity 4..8 parses to the padded 8-tuple.
    for arity, wire in ((4, "aa" * 8 + "/3/1/7"),
                        (5, "aa" * 8 + "/3/1/7/1"),
                        (6, "aa" * 8 + "/3/1/7/1/1"),
                        (7, "aa" * 8 + "/3/1/7/1/1/1"),
                        (8, "aa" * 8 + "/3/1/7/1/1/1/1")):
        decoded = digest_decode(f"16;decode;{wire}")
        assert decoded is not None, arity
        entry = decoded[2][0]
        assert len(entry) == 8
        assert entry[:4] == ("aa" * 8, 3, 1, 7)


def test_digest_adapter_flag_back_compat_matrix():
    """The 8th (``adapter``) field composes with every older wire
    format: a zero flag leaves the 4/5/6/7-field encodings
    byte-identical (pre-adapter routers parse them untouched), and a
    set flag forces the full positional 8-field entry."""
    four = ("ab12cd34ef567890", 3, 1, 7)
    five = ("ffee001122334455", 2, 0, 1, 1)
    six = ("0123456789abcdef", 1, 0, 2, 2, 1)
    seven = ("aa" * 8, 1, 0, 2, 0, 0, 1)
    # Zero flag: encodings byte-identical to the pre-adapter wire.
    assert digest_encode(16, "decode", [four + (0, 0, 0, 0)]) \
        == digest_encode(16, "decode", [four])
    assert digest_encode(16, "decode", [five + (0, 0, 0)]) \
        == digest_encode(16, "decode", [five])
    assert digest_encode(16, "decode", [six + (0, 0)]) \
        == digest_encode(16, "decode", [six])
    assert digest_encode(16, "decode", [seven + (0,)]) \
        == digest_encode(16, "decode", [seven])
    # Set flag: the full positional 8-field entry.
    text = digest_encode(16, "decode", [four + (0, 0, 0, 1)])
    assert text.endswith("/3/1/7/0/0/0/1")
    assert digest_decode(text) == (16, "decode",
                                   [four + (0, 0, 0, 1)])
    # Adapter + tier compose: a host-demoted adapter page entry.
    demoted = ("ab12cd34ef567890", 1, 0, 4, 1, 0, 0, 1)
    text = digest_encode(16, "decode", [demoted])
    assert text.endswith("/1/0/4/1/0/0/1")
    assert digest_decode(text) == (16, "decode", [demoted])


def test_directory_adapter_residency_queries():
    """``adapter_tier`` / ``adapter_owners`` read the 8th field:
    per-replica tier lookup, warmest-first owner ordering, dead
    replicas excluded by the lease, KV entries never counted."""
    directory = PrefixDirectory(lease_s=30.0)
    hexkey = "aa" * 8
    directory.update("ra", digest_encode(
        16, "decode", [(hexkey, 1, 0, 3, 0, 0, 0, 1)]), now=0.0)
    directory.update("rb", digest_encode(
        16, "decode", [(hexkey, 1, 0, 3, 1, 0, 0, 1)]), now=0.0)
    directory.update("rc", digest_encode(
        16, "decode", [(hexkey, 1, 0, 3, 0, 0, 0, 0)]), now=0.0)
    assert directory.adapter_tier("ra", hexkey, now=1.0) == 0
    assert directory.adapter_tier("rb", hexkey, now=1.0) == 1
    # A plain KV advertisement of the same key is NOT residency.
    assert directory.adapter_tier("rc", hexkey, now=1.0) is None
    assert directory.adapter_owners(hexkey, now=1.0) == [
        ("ra", 0), ("rb", 1)]
    assert directory.adapter_owners(hexkey, now=1.0,
                                    exclude=("ra",)) == [("rb", 1)]
    # Leases apply: an expired replica is not an owner.
    assert directory.adapter_owners(hexkey, now=100.0) == []


def test_directory_migrating_flag_tracks_advertisements():
    """``PrefixDirectory.migrating`` follows the replica's LAST
    advertisement (set -> cleared across updates) and eviction."""
    directory = PrefixDirectory(lease_s=30.0)
    entries = [("aa" * 8, 1, 0, 3)]
    directory.update("ra", digest_encode(16, "decode", entries),
                     now=0.0)
    assert not directory.migrating("ra")
    directory.update(
        "ra", digest_encode(16, "decode", entries, migrating=1),
        now=1.0)
    assert directory.migrating("ra")
    # The blocks stay matchable while migrating (the source must
    # remain exportable mid-flight).
    assert directory.matched_blocks("ra", ["aa" * 8], now=2.0) == 1
    directory.update("ra", digest_encode(16, "decode", entries),
                     now=3.0)
    assert not directory.migrating("ra")         # flag clears
    directory.update(
        "ra", digest_encode(16, "decode", entries, migrating=1),
        now=4.0)
    directory.evict_replica("ra")
    assert not directory.migrating("ra")         # unknown -> False


def test_directory_lease_matching_and_eviction():
    directory = PrefixDirectory(lease_s=30.0)
    keys = [f"{i:016x}" for i in range(4)]
    entries = [(k, depth + 1, 0, depth) for depth, k in enumerate(keys)]
    assert directory.update("ra", digest_encode(16, "decode", entries),
                            now=0.0)
    assert not directory.update("rb", "garbage", now=0.0)
    # Deepest advertised key wins; missing leaf falls back shallower.
    assert directory.matched_blocks("ra", keys, now=1.0) == 4
    assert directory.matched_blocks("ra", keys[:2] + ["ffff" * 4],
                                    now=1.0) == 2
    assert directory.matched_blocks("ra", ["ffff" * 4], now=1.0) == 0
    owner, depth = directory.best_owner(keys, now=1.0)
    assert (owner, depth) == ("ra", 4)
    # Lease expiry: queries skip, purge reclaims, update re-arms.
    assert directory.matched_blocks("ra", keys, now=31.0) == 0
    assert directory.best_owner(keys, now=31.0) == (None, 0)
    directory.purge_expired(now=31.0)
    assert directory.size == 0
    directory.update("ra", digest_encode(16, "prefill", entries),
                     now=40.0)
    assert directory.role("ra") == "prefill"
    assert directory.block_size("ra") == 16
    directory.evict_replica("ra")
    assert directory.size == 0 and directory.replicas() == []


def test_best_owner_tie_breaks_by_hotness():
    directory = PrefixDirectory()
    key = "aa" * 8
    directory.update("cold", digest_encode(16, "decode",
                                           [(key, 1, 0, 1)]), now=0.0)
    directory.update("hot", digest_encode(16, "decode",
                                          [(key, 1, 0, 9)]), now=0.0)
    assert directory.best_owner([key], now=1.0)[0] == "hot"


# ---------------------------------------------------------------- #
# Block transfer: exactness + rejection
# ---------------------------------------------------------------- #

def _warm(server, prompt, max_new=4):
    server.submit(DecodeRequest(request_id="warm", prompt=prompt,
                                max_new_tokens=max_new))
    finished = server.run_until_drained()
    return finished[0].tokens


@pytest.mark.parametrize("quantize_kv", [False, True],
                         ids=["bf16", "int8"])
def test_transferred_prefix_decode_bit_exact(quantize_kv):
    """ARCHITECTURE invariant 6: greedy decode after an IMPORTED
    prefix exactly equals local prefill — for both pool dtypes, and
    through the real wire codec."""
    prompt = np.arange(1, 50, dtype=np.int32)       # 3 shareable blocks
    owner = make_server(quantize_kv=quantize_kv)
    want = _warm(owner, prompt)

    keys = owner.prefix_keys_hex(prompt)
    assert len(keys) == 3
    payload = owner.kv_export_payload(keys, 0)
    assert payload is not None
    nbytes = payload_bytes(payload)
    assert nbytes > 0 and owner.kv_transfer_bytes == nbytes

    wire = decode_swag(encode_swag(payload))        # real codec pass
    importer = make_server(quantize_kv=quantize_kv)
    assert importer.kv_import_payload(wire) == 3
    assert importer.kv_transfer_bytes == nbytes

    got = _warm(importer, prompt)
    cold = make_server(quantize_kv=quantize_kv)
    assert got == want == _warm(cold, prompt)
    stats = importer.stats()
    assert stats["prefix_remote_hits"] == 1
    assert stats["prefix_blocks_reused"] >= 3
    assert cold.stats()["prefix_remote_hits"] == 0


def test_import_rejects_layout_and_linkage_mismatches():
    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server()
    _warm(owner, prompt)
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)

    other_dtype = make_server(quantize_kv=True)
    assert other_dtype.kv_import_payload(dict(payload)) == 0
    assert pool_signature(owner) != pool_signature(other_dtype)

    wrong_block = dict(payload, kv_block_size=32)
    assert make_server().kv_import_payload(wrong_block) == 0

    # start_depth > 0 whose parent the importer doesn't hold: the
    # local prefix was evicted between request and response.
    broken = dict(payload, kv_start_depth=2,
                  kv_parent="cd" * 32)
    assert make_server().kv_import_payload(broken) == 0

    truncated = {k: v for k, v in payload.items()
                 if not k.startswith("kv_l1_")}
    fresh = make_server()
    free_before = len(fresh._free)
    assert fresh.kv_import_payload(truncated) == 0
    assert len(fresh._free) == free_before      # allocation rolled back


def test_export_unknown_prefix_returns_none_and_counts():
    server = make_server()
    assert export_payload(server, ["ab" * 8], 0) is None
    assert server.kv_export_payload(["ab" * 8], 0) is None
    assert server.stats()["kv_transfer_failures"] == 1


def test_import_lease_release_and_spill_accounting(engine):
    """Imported blocks stay ref-pinned until the lease expires, then
    become evictable; imports that evict cached prefixes count as
    evictions (no host tier) or demotions (host tier configured)."""
    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server()
    _warm(owner, prompt)
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)

    importer = make_server()
    evictable_before = len(importer._evictable)
    assert importer.kv_import_payload(dict(payload), engine=engine,
                                      lease_s=5.0) == 3
    assert len(importer._evictable) == evictable_before
    engine.advance(6.0)
    engine.drain()
    assert len(importer._evictable) == evictable_before + 3

    # A tiny pool already full of cached prefixes must evict to
    # accept the import — deletions without a host tier, demotions
    # with one.
    small = make_server(total_blocks=5)
    _warm(small, np.arange(100, 149, dtype=np.int32))
    assert len(small._evictable) > 0          # cached prefix occupies pool
    assert small.kv_import_payload(dict(payload)) == 3
    assert small.stats()["prefix_evictions"] > 0
    assert small.stats()["kv_demotions"] == 0

    tiered = make_server(total_blocks=5, host_tier_blocks=8)
    _warm(tiered, np.arange(100, 149, dtype=np.int32))
    assert tiered.kv_import_payload(dict(payload)) == 3
    stats = tiered.stats()
    assert stats["kv_demotions"] > 0
    assert stats["kv_host_blocks"] > 0 and stats["kv_host_bytes"] > 0


def test_seed_chain_registers_without_prefill():
    server = make_server(max_seq=96)
    tokens = np.arange(1, 66, dtype=np.int32)       # 4 shareable blocks
    assert seed_chain(server, tokens) == 4
    keys = chain_keys_hex(tokens, 16)
    payload = export_payload(server, keys, 0)
    assert payload is not None and len(payload["kv_keys"]) == 4


# ---------------------------------------------------------------- #
# Telemetry flow: stats -> serving_telemetry -> EC share -> dashboard
# ---------------------------------------------------------------- #

def test_kv_counters_flow_to_dashboard_plugins():
    from aiko_services_tpu.orchestration.serving import (
        TELEMETRY_KEYS, serving_telemetry,
    )
    from aiko_services_tpu.tools.dashboard_plugins import (
        model_replica_plugin, replica_router_plugin,
    )

    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server()
    _warm(owner, prompt)
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    importer = make_server()
    importer.kv_import_payload(payload)
    _warm(importer, prompt)

    stats = importer.stats()
    for key in ("prefix_remote_hits", "kv_transfer_bytes",
                "kv_transfer_ms", "kv_transfer_failures",
                "kv_demotions", "kv_restores", "kv_host_blocks",
                "kv_host_bytes", "restore_queue_depth",
                "prefix_hits_host"):
        assert key in stats and key in TELEMETRY_KEYS
    telemetry = serving_telemetry(stats)
    assert telemetry["prefix_remote_hits"] == 1
    assert telemetry["kv_transfer_bytes"] > 0

    class Fields:
        name, topic_path = "replica_x", "t/replica_x"
        protocol = "model_replica"

    variables = {key: str(value) for key, value in telemetry.items()}
    variables.update(slots="2", prefix_hits="1")
    lines = "\n".join(model_replica_plugin(Fields, variables))
    assert "kv xfer" in lines and "1 remote hits" in lines

    class RouterFields:
        name, topic_path = "router", "t/router"
        protocol = "replica_router"

    lines = "\n".join(replica_router_plugin(RouterFields, {
        "kv_directory_size": "12", "prefix_routed": "7",
        "kv_remote_hints": "2"}))
    assert "12 advertised blocks" in lines
    assert "7 prefix-routed" in lines and "2 transfer hints" in lines


# ---------------------------------------------------------------- #
# Router: prefix-aware scoring, hints, directory maintenance
# ---------------------------------------------------------------- #

def _router_rig(engine, broker, n_replicas=2, **router_kwargs):
    from aiko_services_tpu.orchestration.serving import (
        ModelReplica, ReplicaRouter,
    )
    p0 = make_process(engine, 1, broker)
    Registrar(process=p0)
    engine.advance(4.0)
    topics = []
    for i in range(n_replicas):
        p = make_process(engine, 10 + i, broker)
        replica = compose_instance(
            ModelReplica, actor_args(f"replica_{i}"), process=p,
            infer=lambda payload: {"ok": 1})
        topics.append(replica.topic_path)
    pr = make_process(engine, 99, broker)
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr, **router_kwargs)
    engine.drain()
    assert router.share["replicas"] == n_replicas
    return router, topics, pr


def _advertise(process, replica_topic, prompt, hotness=1,
               role="decode"):
    keys = chain_keys_hex(prompt, 16)
    entries = [(key, depth + 1, 0, hotness)
               for depth, key in enumerate(keys)]
    process.message.publish(
        f"{replica_topic}/state",
        generate("update", ["kv_prefixes",
                            digest_encode(16, role, entries)]))


def test_router_prefix_affinity_beats_round_robin(engine):
    """A prompt matching one replica's advertisement routes there
    repeatedly (affinity), while unmatched prompts keep the exact
    PR-4 fallback."""
    router, topics, pr = _router_rig(engine, "kvaff")
    prompt = np.arange(1, 50, dtype=np.int32)
    _advertise(pr, topics[0], prompt)
    engine.drain()
    assert router.share["kv_directory_size"] == 3

    payload = encode_swag({"tokens": prompt})
    picks = []
    for i in range(4):
        assert router.route(f"m{i}", "test/resp", dict(payload))
        picks.append(router._inflight[f"m{i}"]["replica"])
        engine.drain()
    assert picks == [topics[0]] * 4
    assert router.counters["prefix_routed"] == 4

    # Unmatched prompt: exact fallback (round-robin while load is
    # unknown) — the non-kvstore fleet behavior, unchanged.
    other = encode_swag({"tokens": np.arange(500, 549, dtype=np.int32)})
    targets = set()
    for i in range(2):
        router.route(f"u{i}", "test/resp", dict(other))
        targets.add(router._inflight[f"u{i}"]["replica"])
        engine.drain()
    assert targets == set(topics)


def test_router_load_beats_affinity_and_hints_transfer(engine):
    """When the owner's queue outweighs alpha·match the router picks
    the less-loaded replica and (kv_transfer=True) attaches a
    kv_source hint pointing at the owner."""
    router, topics, pr = _router_rig(engine, "kvhint",
                                     kv_transfer=True)
    prompt = np.arange(1, 50, dtype=np.int32)
    _advertise(pr, topics[0], prompt)
    for topic, depth in ((topics[0], 50), (topics[1], 0)):
        pr.message.publish(f"{topic}/state",
                           generate("update", ["queue_depth",
                                               str(depth)]))
    engine.drain()

    delivered = []
    pr.add_message_handler(
        lambda _t, m: delivered.append(parse(m)), f"{topics[1]}/in")
    assert router.route("h1", "test/resp",
                        encode_swag({"tokens": prompt}))
    picked = router._inflight["h1"]["replica"]
    engine.drain()
    assert picked == topics[1]
    assert router.counters["kv_remote_hints"] == 1
    infer = [p for c, p in delivered if c == "infer"]
    assert infer and infer[0][2]["kv_source"] == f"s:{topics[0]}"


def test_router_evicts_dead_and_unhealthy_owners(engine):
    router, topics, pr = _router_rig(engine, "kvdead")
    prompt = np.arange(1, 50, dtype=np.int32)
    _advertise(pr, topics[0], prompt)
    _advertise(pr, topics[1], prompt)
    engine.drain()
    assert router.share["kv_directory_size"] == 6

    pr.message.publish(f"{topics[0]}/state",
                       generate("update", ["lifecycle", "unhealthy"]))
    engine.drain()
    assert router.share["kv_directory_size"] == 3
    assert topics[0] not in router.directory.replicas()

    # Directory-advertised lease expiry also stops attracting routes.
    engine.advance(31.0)
    router.directory.purge_expired(router.process.event.now())
    assert router.directory.size == 0


# ---------------------------------------------------------------- #
# Wire: warm-start fetch, timeout fallback, disaggregated mode
# ---------------------------------------------------------------- #

def _drive(engine, predicate, steps=4000, dt=0.01):
    for _ in range(steps):
        engine.advance(dt)
        engine.drain()
        if predicate():
            return
    raise AssertionError("wire rig did not converge")


def _paged_replica(engine, pid, broker, name, **kwargs):
    process = make_process(engine, pid, broker)
    server = make_server()
    replica = compose_instance(ContinuousReplica, actor_args(name),
                               process=process, server=server,
                               **kwargs)
    return process, server, replica


def test_wire_warm_start_via_kv_source(engine):
    """Replica B, handed a kv_source hint, pulls A's blocks over the
    wire and produces EXACTLY A's greedy tokens; transfer counters
    move on both ends."""
    prompt = np.arange(1, 50, dtype=np.int32)
    pa, server_a, replica_a = _paged_replica(engine, 2, "warm", "ra")
    pb, server_b, replica_b = _paged_replica(engine, 3, "warm", "rb")

    responses = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            responses.append((params[0], decode_swag(params[1])))

    pa.add_message_handler(handler, "test/warm/resp")
    pa.message.publish(
        replica_a.topic_in,
        generate("infer", ["w1", "test/warm/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 4})]))
    _drive(engine, lambda: len(responses) == 1)

    pb.message.publish(
        replica_b.topic_in,
        generate("infer", ["w2", "test/warm/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 4,
                                        "kv_source":
                                        replica_a.topic_path})]))
    _drive(engine, lambda: len(responses) == 2)
    (id1, out1), (id2, out2) = responses
    assert list(out1["tokens_out"]) == list(out2["tokens_out"])
    assert server_b.prefix_remote_hits == 1
    assert server_b.kv_transfer_bytes > 0
    assert server_b.kv_transfer_bytes == server_a.kv_transfer_bytes
    assert server_b.kv_transfer_failures == 0
    # The EC share carries the counters a dashboard consumer reads.
    assert int(replica_b.share["kv_transfer_bytes"]) > 0
    assert int(replica_b.share["prefix_remote_hits"]) == 1


def test_digest_refreshes_at_a_bounded_rate_while_busy(engine):
    """The digest walks the whole index, so a busy replica rebuilds it
    at most every ``KV_DIGEST_S``; the pump that leaves the server idle
    always does, and so does a turn of the ``migrating`` flag."""
    process, server, replica = _paged_replica(engine, 2, "rate", "rr")
    calls = []
    digest = server.prefix_digest
    server.prefix_digest = lambda **kw: calls.append(kw) or digest(**kw)
    responses = []
    process.add_message_handler(
        lambda _t, payload: responses.append(parse(payload)[0]),
        "test/rate/resp")
    process.message.publish(
        replica.topic_in,
        generate("infer", ["r1", "test/rate/resp",
                           encode_swag({"tokens": np.arange(1, 50,
                                                            dtype=np.int32),
                                        "max_new_tokens": 24})]))
    replica.KV_DIGEST_S = 3600.0
    _drive(engine, lambda: "infer_response" in responses)
    assert server.counters["dispatches"] >= 6      # several busy pumps
    # One at the first busy pump, one at the pump that ended idle.
    assert len(calls) == 2
    assert digest_decode(replica.share["kv_prefixes"])[2]  # the chain
    replica._migrating_ids.add("r2")
    server._queue.append(None)                     # busy, nothing due
    replica._share_telemetry()
    server._queue.clear()
    assert len(calls) == 3 and calls[-1]["migrating"] is True


def test_wire_kv_fetch_timeout_falls_back_to_local(engine):
    """A kv_source pointing at a dead owner must NOT lose the request:
    the fetch times out and the replica prefills locally."""
    prompt = np.arange(1, 50, dtype=np.int32)
    pb, server_b, replica_b = _paged_replica(engine, 3, "dead", "rb",
                                             kv_fetch_timeout_s=2.0)
    responses = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            responses.append(decode_swag(params[1]))

    pb.add_message_handler(handler, "test/dead/resp")
    pb.message.publish(
        replica_b.topic_in,
        generate("infer", ["d1", "test/dead/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 4,
                                        "kv_source":
                                        "test/h/77/1/gone"})]))
    _drive(engine, lambda: bool(responses))
    assert "error" not in responses[0]
    want = reference_greedy(server_b, prompt, 4)
    assert list(responses[0]["tokens_out"]) == want
    assert server_b.kv_transfer_failures == 1
    assert server_b.prefix_remote_hits == 0


def test_disaggregated_prefill_decode_exact_over_wire(engine):
    """Opt-in disaggregation: prefill replica computes the prompt KV,
    decode replica pulls it and generates — client-visible tokens are
    identical to single-phase serving and the prefill leg's one-token
    answer is never forwarded."""
    from aiko_services_tpu.orchestration.serving import ReplicaRouter

    broker = "disagg"
    p0 = make_process(engine, 1, broker)
    Registrar(process=p0)
    engine.advance(4.0)
    pp, server_p, replica_p = _paged_replica(engine, 2, broker,
                                             "prefiller",
                                             prefill_only=True)
    pd, server_d, replica_d = _paged_replica(engine, 3, broker,
                                             "decoder")
    pr = make_process(engine, 99, broker)
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr, kv_transfer=True,
                              disaggregate=True)
    engine.drain()
    assert router.share["replicas"] == 2
    # Roles arrive via the periodic kv advertisement.
    engine.advance(6.0)
    engine.drain()
    assert router.directory.role(replica_p.topic_path) == "prefill"
    assert router.directory.role(replica_d.topic_path) == "decode"

    responses, partials = [], []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            responses.append(decode_swag(params[1]))
        elif command == "infer_partial":
            partials.append(decode_swag(params[1]))

    pr.add_message_handler(handler, "test/disagg/resp")
    prompt = np.arange(1, 41, dtype=np.int32)
    pr.message.publish(
        f"{router.topic_path}/in",
        generate("infer", ["g1", "test/disagg/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 5,
                                        "stream": 1})]))
    _drive(engine, lambda: bool(responses))
    want = reference_greedy(server_d, prompt, 5)
    assert list(responses[0]["tokens_out"]) == want
    streamed = [t for p in partials for t in p.get("tokens_out", [])]
    assert streamed == want            # prefill partials suppressed
    # The decode replica really pulled the prefill replica's blocks.
    assert server_d.prefix_remote_hits == 1
    assert server_d.kv_transfer_bytes > 0
    assert server_p.stats()["dispatches"] == 1   # prefill leg really ran
    assert router.counters["kv_remote_hints"] == 1


@pytest.mark.multichip
def test_disaggregated_per_role_tp_degrees_exact(engine,
                                                 virtual_mesh_devices):
    """DistServe's per-role parallelism argument end to end: a TP=4
    prefill replica paired with a TP=2 decode replica through the
    disaggregated router.  The KV wire format is degree-agnostic
    (full kv-head width), so the cross-degree handoff is exact —
    client tokens equal the single-chip greedy oracle."""
    from aiko_services_tpu.orchestration.serving import ReplicaRouter
    from aiko_services_tpu.parallel.mesh import ReplicaMesh

    broker = "xdegree"
    p0 = make_process(engine, 1, broker)
    Registrar(process=p0)
    engine.advance(4.0)

    def tp_replica(pid, name, tp, **kwargs):
        process = make_process(engine, pid, broker)
        server = PagedContinuousServer(
            config_name="tiny_tp", slots=2, max_seq=96, chunk_steps=4,
            seed=0, block_size=16, enable_prefix_cache=True,
            replica_mesh=ReplicaMesh(tp=tp))
        replica = compose_instance(ContinuousReplica, actor_args(name),
                                   process=process, server=server,
                                   **kwargs)
        return process, server, replica

    pp, server_p, replica_p = tp_replica(2, "prefiller4", 4,
                                         prefill_only=True)
    pd, server_d, replica_d = tp_replica(3, "decoder2", 2)
    pr = make_process(engine, 99, broker)
    router = compose_instance(ReplicaRouter, actor_args("router"),
                              process=pr, kv_transfer=True,
                              disaggregate=True)
    engine.drain()
    assert router.share["replicas"] == 2
    engine.advance(6.0)
    engine.drain()
    assert router.directory.role(replica_p.topic_path) == "prefill"
    assert router.directory.role(replica_d.topic_path) == "decode"
    assert server_p.stats()["tp_degree"] == 4
    assert server_d.stats()["tp_degree"] == 2

    responses = []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_response":
            responses.append(decode_swag(params[1]))

    pr.add_message_handler(handler, "test/xdegree/resp")
    prompt = np.arange(1, 41, dtype=np.int32)
    pr.message.publish(
        f"{router.topic_path}/in",
        generate("infer", ["x1", "test/xdegree/resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 5})]))
    _drive(engine, lambda: bool(responses))
    # Oracle from a SINGLE-CHIP server with the same seed/config —
    # the cross-degree pair must be bitwise equal to one chip.
    single = PagedContinuousServer(config_name="tiny_tp", slots=2,
                                   max_seq=96, chunk_steps=4, seed=0,
                                   block_size=16)
    want = reference_greedy(single, prompt, 5)
    assert list(responses[0]["tokens_out"]) == want
    # The TP=2 decoder really imported the TP=4 prefiller's blocks.
    assert server_d.prefix_remote_hits == 1
    assert server_d.kv_transfer_bytes > 0
    assert server_p.stats()["dispatches"] == 1


# ---------------------------------------------------------------- #
# Chaos: killing an advertised prefix owner loses nothing
# ---------------------------------------------------------------- #

def test_chaos_dead_prefix_owner_zero_lost():
    """The chaos gate now runs with prefix routing + transfer ON:
    the schedule kills replica_a mid-run AFTER it has advertised the
    shared system prefix — every request still reaches a terminal
    state."""
    from aiko_services_tpu.tools.loadgen import run_chaos

    report = run_chaos(seed=2, n_requests=8, rate_hz=200.0)
    assert report.lost == 0, report
    assert report.timeouts == 0, report
    stats = report.server_stats
    assert stats["replica_deaths_observed"] == 1
    assert stats["prefix_hits"] + stats["prefix_misses"] > 0
    assert report.prefix_hit_rate is not None


# ---------------------------------------------------------------- #
# Jaxpr + AST guards: transfers never enter traced programs
# ---------------------------------------------------------------- #

def test_kv_import_does_not_change_serve_chunk_jaxpr():
    """The paged serve-chunk's traced program is bit-identical before
    and after an import — transfers are host-side pool writes, never
    traced logic."""
    import jax

    from aiko_services_tpu.models import llama

    prompt = np.arange(1, 50, dtype=np.int32)
    owner = make_server()
    _warm(owner, prompt)
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    server = make_server()
    _warm(server, np.arange(60, 77, dtype=np.int32))  # build state

    def trace():
        return str(jax.make_jaxpr(
            lambda state, pool: llama.serve_chunk_paged(
                server.params, state, pool, 2, server.config,
                eos_id=-1, sampled=False))(server._state, server.pool))

    clean = trace()
    assert server.kv_import_payload(payload) == 3
    assert trace() == clean


def test_no_kvstore_references_in_traced_modules():
    """models/ and ops/ (everything that builds jitted programs) must
    not import or reference kvstore — the transfer path lives entirely
    in orchestration host code."""
    for directory in ("models", "ops"):
        for path in sorted((PKG / directory).glob("*.py")):
            tree = ast.parse(path.read_text())
            for node in ast.walk(tree):
                if isinstance(node, ast.Name):
                    assert "kvstore" not in node.id, \
                        f"{path.name}:{node.lineno}"
                if isinstance(node, (ast.Import, ast.ImportFrom)):
                    names = [alias.name for alias in node.names]
                    module = getattr(node, "module", "") or ""
                    assert not any("kvstore" in n
                                   for n in names + [module]), \
                        f"{path.name}:{node.lineno} imports kvstore"


# ---------------------------------------------------------------- #
# shared_prefix workload
# ---------------------------------------------------------------- #

def test_shared_prefix_workload_deterministic_and_interleaved():
    from aiko_services_tpu.tools.loadgen import shared_prefix_payloads

    fn1 = shared_prefix_payloads(n_conversations=3, turns=4,
                                 system_len=32, seed=7)
    fn2 = shared_prefix_payloads(n_conversations=3, turns=4,
                                 system_len=32, seed=7)
    payloads = [fn1(i) for i in range(12)]
    assert all((payloads[i]["tokens"] == fn2(i)["tokens"]).all()
               for i in range(12))
    # Every request shares the system prompt; consecutive requests hit
    # different conversations; a conversation's next turn extends its
    # previous prompt exactly.
    system = payloads[0]["tokens"][:32]
    assert all((p["tokens"][:32] == system).all() for p in payloads)
    for conversation in range(3):
        turn0 = payloads[conversation]["tokens"]
        turn1 = payloads[conversation + 3]["tokens"]
        assert len(turn1) == len(turn0) + 8
        assert (turn1[:len(turn0)] == turn0).all()
    different_seed = shared_prefix_payloads(n_conversations=3, turns=4,
                                            system_len=32, seed=8)(0)
    assert not (different_seed["tokens"] == payloads[0]["tokens"]).all()
