"""Tensor-parallel serving replicas: one replica = one mesh.

The exactness contract (ARCHITECTURE invariant 9): a TP replica's
collectives are all-gathers only — pure data movement, no floating-
point reduction reorder — so greedy decode on a TP=k mesh is BITWISE
equal to the single-chip server, with the prefix cache, int8 KV, and
chunked prefill composed on top.  These tests run on the virtual
8-device CPU mesh the conftest provisions.
"""

import numpy as np
import pytest

import jax

from aiko_services_tpu.models import llama, llama_tp
from aiko_services_tpu.orchestration.autoscaler import (
    AutoscalerPolicy, FleetSnapshot, ReplicaView, decide,
)
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.parallel.mesh import ReplicaMesh

pytestmark = pytest.mark.multichip


def _requests(config, spec, seed=9, prefix=0):
    """``prefix`` > 0 prepends the SAME ``prefix`` tokens to every
    prompt so the prefix cache has something to hit."""
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
    return {r.request_id: r.tokens for r in finished}


def _paged(tp, **overrides):
    kw = dict(config_name="tiny_tp", slots=2, max_seq=128,
              chunk_steps=3, seed=5, block_size=16,
              enable_prefix_cache=True, chunk_prefill_tokens=32,
              quantize=True, quantize_kv=True)
    kw.update(overrides)
    if tp:
        kw["replica_mesh"] = ReplicaMesh(tp=tp)
    return PagedContinuousServer(**kw)


# ---------------------------------------------------------------- #
# The exact-equality gate: TP == single chip, everything composed
# ---------------------------------------------------------------- #

@pytest.mark.parametrize("tp", [4, 8])
def test_tp_paged_greedy_equals_single_chip_composed(
        virtual_mesh_devices, tp):
    """Greedy TP=4 / TP=8 ≡ single-chip greedy on the paged server
    with prefix cache + int8 KV + chunked prefill composed: shared-
    prefix admissions hit the cache, the 40-token tails ride the mixed
    prefill/decode dispatch (chunk_prefill_tokens=32), and every
    emitted token matches bitwise."""
    spec = [(40, 5), (40, 4), (7, 6), (19, 5)]
    outs = {}
    for degree in (None, tp):
        server = _paged(degree)
        outs[degree] = _run(server,
                            _requests(server.config, spec, prefix=32))
        stats = server.stats()
        assert stats["prefix_hits"] > 0        # the cache really hit
        assert stats["tp_degree"] == (degree or 1)
    assert outs[tp] == outs[None]


def test_tp_state_upload_parity(virtual_mesh_devices):
    """TP changes WHERE compute runs, not the host protocol: the
    steady-state decode loop performs the same (admission-only) state
    uploads as the single-chip server — no per-chunk re-upload snuck
    into the shard_map path."""
    spec = [(7, 6), (19, 5), (4, 8)]
    counts = {}
    for degree in (None, 2):
        server = _paged(degree)
        _run(server, _requests(server.config, spec))
        counts[degree] = (server.counters["state_uploads"],
                          server.counters["dispatches"])
    assert counts[2] == counts[None]


def test_tp_base_server_greedy_parity(virtual_mesh_devices):
    """The contiguous-layout server under a replica mesh (GSPMD path:
    sharded weights, replicated cache) matches single-chip greedy."""
    spec = [(7, 5), (13, 4), (4, 8)]
    outs = {}
    for degree in (None, 2):
        kw = dict(config_name="tiny_tp", slots=2, max_seq=64,
                  chunk_steps=3, seed=5)
        if degree:
            kw["replica_mesh"] = ReplicaMesh(tp=degree)
        server = ContinuousBatchingServer(**kw)
        outs[degree] = _run(server, _requests(server.config, spec))
    assert outs[2] == outs[None]


# ---------------------------------------------------------------- #
# Jaxpr guards: the pool is sharded and NEVER gathered
# ---------------------------------------------------------------- #

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            yield from _sub_eqns(value)


def _sub_eqns(value):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield from _iter_eqns(value.jaxpr)
    elif isinstance(value, Jaxpr):
        yield from _iter_eqns(value)
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _sub_eqns(item)


def test_tp_pool_sharded_and_never_gathered(virtual_mesh_devices):
    """(1) After a decode chunk the pool buffers still carry the
    kv-head sharding (donation preserved it); (2) the traced serve
    program's all_gather operands are all small activation tensors —
    rank < 4 and nowhere near pool size — so the paged pool never
    crosses the interconnect whole."""
    server = _paged(4)
    _run(server, _requests(server.config, [(7, 6), (19, 5)]))
    axis = server.replica_mesh.axis
    for name, buf in server.pool[0].items():
        spec = tuple(buf.sharding.spec)
        assert axis in spec, (name, spec)

    engine = server._tp_engine
    pool_rows = server.pool[0]["k"].shape[0]
    jaxpr = jax.make_jaxpr(
        lambda p, s, kv: engine.serve_chunk_paged(p, s, kv, 3))(
            server.params, server._state, server.pool)
    gathers = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
               if eqn.primitive.name == "all_gather"]
    assert gathers, "TP serve program must gather activations"
    for eqn in gathers:
        for var in eqn.invars:
            aval = var.aval
            assert aval.ndim < 4, (aval,)
            assert aval.shape[0] != pool_rows, (aval,)
            assert aval.size < 1_000_000, (aval,)


# ---------------------------------------------------------------- #
# Cross-TP-degree block transfer
# ---------------------------------------------------------------- #

@pytest.mark.parametrize("quantize_kv", [False, True],
                         ids=["bf16", "int8"])
def test_tp_cross_degree_transfer_bit_exact(virtual_mesh_devices,
                                            quantize_kv):
    """TP=2 → TP=4 prefix handoff: the wire format is the full
    kv-head width, so replicas with different TP degrees exchange
    blocks directly, and greedy decode after the imported prefix is
    bit-exact against local prefill — both pool dtypes."""
    prompt = np.arange(1, 50, dtype=np.int32)       # 3 shareable blocks

    def make(tp):
        return _paged(tp, quantize=False, quantize_kv=quantize_kv,
                      chunk_prefill_tokens=0)

    owner = make(2)
    want = _run(owner, [DecodeRequest(request_id="warm", prompt=prompt,
                                      max_new_tokens=4)])["warm"]
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    assert payload is not None

    importer = make(4)
    assert importer.kv_import_payload(dict(payload)) == 3
    # The import's scatter must not have de-sharded the pool.
    axis = importer.replica_mesh.axis
    assert axis in tuple(importer.pool[0]["k"].sharding.spec)
    got = _run(importer,
               [DecodeRequest(request_id="warm", prompt=prompt,
                              max_new_tokens=4)])["warm"]
    assert got == want
    assert importer.stats()["prefix_remote_hits"] == 1

    # And down-degree: TP=2 exporter → single-chip importer.
    single = make(None)
    assert single.kv_import_payload(dict(payload)) == 3
    got = _run(single,
               [DecodeRequest(request_id="warm", prompt=prompt,
                              max_new_tokens=4)])["warm"]
    assert got == want


# ---------------------------------------------------------------- #
# Mixed prefill/decode smoke + config validation
# ---------------------------------------------------------------- #

def test_tp2_mixed_prefill_decode_smoke(virtual_mesh_devices):
    """Fast gate: a TP=2 replica with a long prompt admitted through
    chunked prefill WHILE another slot decodes — the mixed dispatch —
    drains clean and matches single-chip."""
    spec = [(4, 10), (72, 4)]
    outs = {}
    for degree in (None, 2):
        server = _paged(degree, quantize=False, quantize_kv=False)
        outs[degree] = _run(server, _requests(server.config, spec))
        assert server.counters["prefill_tokens"] > 0
    assert outs[2] == outs[None]


def test_replica_mesh_validation():
    config = llama.CONFIGS["tiny_tp"]
    ReplicaMesh(tp=8).validate(config)              # divides everything
    with pytest.raises(ValueError, match="n_kv_heads"):
        ReplicaMesh(tp=16).validate(config)
    with pytest.raises(ValueError, match="divisible"):
        ReplicaMesh(tp=3).validate(config)
    with pytest.raises(ValueError, match="needs"):
        ReplicaMesh(tp=1024).build()
    with pytest.raises(ValueError, match="tp must be"):
        ReplicaMesh(tp=0).build()


def test_tp_rejects_unsupported_compositions(virtual_mesh_devices):
    # Speculative decoding now COMPOSES with replica_mesh (draft
    # replicated on the mesh) — the PR 3 rejection is gone.
    server = ContinuousBatchingServer(config_name="tiny_tp",
                                      replica_mesh=ReplicaMesh(tp=2),
                                      draft_config_name="tiny_tp")
    assert server._draft is not None and server.tp_degree == 2
    # The TP×LoRA rejection is gone too (PR 20): factors replicate on
    # the contiguous layout (tiny, exact) or column-shard on the paged
    # one, so the composition constructs — exactness is gated by
    # tests/test_multitenant.py.
    from aiko_services_tpu.models.lora import LoRAConfig
    lora_server = ContinuousBatchingServer(
        config_name="tiny_tp", replica_mesh=ReplicaMesh(tp=2),
        lora_config=LoRAConfig(rank=2))
    assert lora_server.tp_degree == 2


def test_tp_param_and_pool_specs():
    """The sharding rule in one place: every 2-D weight leaf shards on
    its LAST axis, pool k/v on the kv-head axis (dim 2), scale planes
    on their trailing kv-head axis."""
    from jax.sharding import PartitionSpec as P
    config = llama.CONFIGS["tiny_tp"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    specs = llama_tp.tp_param_specs(params)
    assert specs["embed"] == P(None, "tp")
    assert specs["layers"][0]["wq"] == P(None, "tp")
    assert specs["final_norm"] == P()
    pool = llama.init_paged_cache(config, 5, 16, quantize_kv=True)
    pool_specs = llama_tp.tp_pool_specs(pool)
    assert pool_specs[0]["k"] == P(None, None, "tp", None)
    assert pool_specs[0]["ks"] == P(None, None, "tp")


# ---------------------------------------------------------------- #
# Autoscaler: a TP=k replica is k chips in the capacity ledger
# ---------------------------------------------------------------- #

def _policy(**overrides):
    defaults = dict(target=1, min_replicas=1, max_replicas=8,
                    cooldown_s=10.0,
                    breach_windows=10 ** 6, clear_windows=10 ** 6)
    defaults.update(overrides)
    return AutoscalerPolicy(**defaults)


def test_autoscaler_counts_tp_replica_as_k_chips():
    """One adopted TP=4 replica satisfies a 4-chip target outright —
    no spawns, no drain."""
    view = ReplicaView(slot="decode1", tp_degree=4)
    actions, state = decide(
        FleetSnapshot(now=0.0, replicas=(view,)), _policy(target=4))
    assert actions == []
    assert state.chips == {"decode1": 4}


def test_autoscaler_drain_prefers_fitting_replica():
    """Surplus of 1 chip over target: drain the TP=1 replica, never
    the TP=4 one (draining 4 chips to shed 1 overshoots)."""
    policy = _policy(target=4)
    big = ReplicaView(slot="decode1", tp_degree=4, queue_depth=0)
    small = ReplicaView(slot="decode2", tp_degree=1, queue_depth=0)
    _, state = decide(FleetSnapshot(now=0.0, replicas=(big, small)),
                      policy)
    actions, state = decide(
        FleetSnapshot(now=1.0, replicas=(big, small)), policy, state)
    drains = [a for a in actions if a.kind == "drain"]
    assert [a.slot for a in drains] == ["decode2"]


def test_autoscaler_tp1_ledger_unchanged():
    """Every weight 1 ⇒ the chip ledger IS the old replica count:
    bootstrap to target spawns exactly target replicas."""
    actions, state = decide(FleetSnapshot(now=0.0), _policy(target=2))
    assert [a.kind for a in actions] == ["spawn", "spawn"]


def test_autoscaler_per_role_tp_degrees_flow_into_spawns():
    """DistServe's per-role parallelism argument as config wiring:
    ``prefill_tp=4, decode_tp=2`` makes every spawn action carry its
    role's degree, books it in the chip ledger, and closes chip
    targets with the RIGHT number of replicas — a 4-chip decode
    target takes two TP=2 spawns, a 4-chip prefill target one TP=4
    spawn."""
    policy = _policy(target=4, decode_tp=2,
                     prefill_target=4, prefill_tp=4)
    assert policy.role_tp("decode") == 2
    assert policy.role_tp("prefill") == 4
    actions, state = decide(FleetSnapshot(now=0.0), policy)
    spawns = [a for a in actions if a.kind == "spawn"]
    decode = [a for a in spawns if a.role == "decode"]
    prefill = [a for a in spawns if a.role == "prefill"]
    assert [a.tp_degree for a in decode] == [2, 2]
    assert [a.tp_degree for a in prefill] == [4]
    assert sorted(state.chips[a.slot] for a in spawns) == [2, 2, 4]


def test_autoscaler_respawn_carries_role_degree():
    """A dead slot's replacement spawn re-carries the policy degree
    (the chips entry was dropped with the death)."""
    policy = _policy(target=2, decode_tp=2, backoff_base_s=0.0)
    actions, state = decide(FleetSnapshot(now=0.0), policy)
    slot = actions[0].slot
    view = ReplicaView(slot=slot, tp_degree=2)
    _, state = decide(FleetSnapshot(now=1.0, replicas=(view,)),
                      policy, state)
    # the replica dies: no live view, respawn after backoff
    actions, state = decide(FleetSnapshot(now=60.0), policy, state)
    respawns = [a for a in actions
                if a.kind == "spawn" and a.reason == "replace"]
    assert respawns and respawns[0].tp_degree == 2
    assert state.chips[respawns[0].slot] == 2
