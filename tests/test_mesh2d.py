"""2-D replica meshes (ISSUE 18): tensor-parallel × a SECOND axis.

The exactness contract is ARCHITECTURE invariant 19 — the second
axis's collectives are pure data movement (tiled all-gathers, no
floating-point reduction reorder), so serving on a ``tp × sp`` or
``tp × ep`` mesh stays BITWISE equal to the single-chip server with
the whole invariant-9 composition on top (int8 KV, chunked admission,
prefix cache):

* ``sp`` — sequence-parallel chunked prefill: one admission dispatch
  carries ``sp`` prompt chunks, each shard prefills its own chunk and
  all-gathers the window's K/V so every (sp-replicated) pool copy
  stays identical.
* ``ep`` — expert-parallel MoE: the expert tree shards at rest over
  ``(ep, tp)`` and is all-gathered per layer into the IDENTICAL
  single-chip ``moe_ffn`` program — bitwise by construction, and the
  old blanket ``validate()`` MoE rejection is gone.

Runs on the virtual 8-device CPU mesh the conftest provisions.
"""

import numpy as np
import pytest

import jax

from aiko_services_tpu.models import llama
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.parallel.mesh import ReplicaMesh

pytestmark = pytest.mark.multichip


def _requests(config, spec, seed=9, prefix=0):
    """``prefix`` > 0 prepends the SAME tokens to every prompt so the
    prefix cache has something to hit."""
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


def _paged(mesh, **overrides):
    kw = dict(config_name="tiny_tp", slots=2, max_seq=256,
              chunk_steps=3, seed=5, block_size=16,
              enable_prefix_cache=True, chunk_prefill_tokens=32,
              quantize=True, quantize_kv=True)
    kw.update(overrides)
    if mesh is not None:
        kw["replica_mesh"] = mesh
    return PagedContinuousServer(**kw)


# ---------------------------------------------------------------- #
# Sequence parallelism: tp × sp ≡ single chip, everything composed
# ---------------------------------------------------------------- #

def test_sp_prefill_greedy_equals_single_chip_composed(
        virtual_mesh_devices):
    """The acceptance gate: tp=2 × sp=2 AND tp=2 × sp=4 greedy output
    is bitwise identical to single-chip under int8 KV + int8 weights +
    chunked admission + prefix cache, with prompts long enough that
    the sp-window path actually fires."""
    spec = [(150, 5), (40, 4), (150, 6)]
    single = _paged(None)
    want = _run(single, _requests(single.config, spec, prefix=32))
    assert single.counters["sp_prefill_dispatches"] == 0
    for sp in (2, 4):
        server = _paged(ReplicaMesh(tp=2, sp=sp))
        got = _run(server, _requests(server.config, spec, prefix=32))
        assert got == want, f"sp={sp} diverged from single chip"
        stats = server.stats()
        assert stats["sp_prefill_dispatches"] > 0, \
            "sp window never fired — the test exercised nothing"
        assert stats["tp_degree"] == 2
        assert stats["sp_degree"] == sp
        assert stats["mesh_shape"] == f"tp=2,sp={sp}"


def test_sp_pool_sharded_on_tp_replicated_on_sp(virtual_mesh_devices):
    """The pool layout rule on a 2-D mesh: k/v shard on the kv-head
    dim over ``tp`` and REPLICATE over ``sp`` (every sp shard holds a
    full bitwise-identical pool copy), and the census/accountant walk
    stays coherent while serving."""
    server = _paged(ReplicaMesh(tp=2, sp=2))
    _run(server, _requests(server.config, [(150, 4), (20, 3)]))
    spec = tuple(server.pool[0]["k"].sharding.spec)
    assert "tp" in spec
    assert "sp" not in spec
    census = server.pool_census()
    assert census["total_blocks"] == server.total_blocks
    assert census["tiers"]["hbm"]["blocks"] <= census["total_blocks"]
    assert census["block_bytes"] > 0


def test_sp_mesh_kv_export_import_cross_mesh_exact(
        virtual_mesh_devices):
    """Transfer re-pinning is mesh-agnostic: blocks exported from a
    tp=2 × sp=2 replica import into a single-chip replica (and decode
    after the imported prefix is exact) — the wire format carries the
    full kv-head width regardless of mesh rank."""
    prompt = np.arange(1, 50, dtype=np.int32)
    owner = _paged(ReplicaMesh(tp=2, sp=2), chunk_prefill_tokens=0)
    want = _run(owner, [DecodeRequest(request_id="w", prompt=prompt,
                                      max_new_tokens=4)])["w"]
    payload = owner.kv_export_payload(owner.prefix_keys_hex(prompt), 0)
    assert payload is not None
    importer = _paged(None, chunk_prefill_tokens=0)
    assert importer.kv_import_payload(dict(payload)) == 3
    got = _run(importer,
               [DecodeRequest(request_id="w", prompt=prompt,
                              max_new_tokens=4)])["w"]
    assert got == want
    assert importer.stats()["prefix_remote_hits"] == 1


# ---------------------------------------------------------------- #
# Expert parallelism: tp × ep serves MoE, bitwise vs single chip
# ---------------------------------------------------------------- #

def _moe_paged(mesh, config_name="moe_tiny", **overrides):
    kw = dict(config_name=config_name, slots=2, max_seq=128,
              chunk_steps=3, seed=5, block_size=16,
              chunk_prefill_tokens=32, quantize=True,
              quantize_kv=True)
    kw.update(overrides)
    if mesh is not None:
        kw["replica_mesh"] = mesh
    return PagedContinuousServer(**kw)


def test_moe_ep_serving_greedy_equals_single_chip(
        virtual_mesh_devices):
    """tp × ep meshes serve MoE configs through TPEngine with greedy
    output bitwise equal to single-chip: the expert tree is gathered
    per layer into the IDENTICAL single-chip moe_ffn program (weight-
    gathered EP — sharding the COMPUTE is not bitwise-safe because
    XLA does not guarantee the re-decomposed graph reproduces the
    fused program's bits)."""
    spec = [(40, 5), (17, 4), (33, 6)]
    single = _moe_paged(None)
    want = _run(single, _requests(single.config, spec))
    for name, mesh in (("tp2ep2", ReplicaMesh(tp=2, ep=2)),
                       ("tp1ep4", ReplicaMesh(tp=1, ep=4)),
                       ("tp2ep4", ReplicaMesh(tp=2, ep=4))):
        server = _moe_paged(mesh)
        got = _run(server, _requests(server.config, spec))
        assert got == want, f"{name} diverged from single chip"
        stats = server.stats()
        assert stats["ep_degree"] == mesh.ep
        assert stats["mesh_shape"] == f"tp={mesh.tp},ep={mesh.ep}"


def test_moe_eight_experts_tp_ep_mesh_serves(virtual_mesh_devices):
    """The acceptance criterion verbatim: an ``n_experts=8`` config
    constructs a tp × ep ReplicaMesh (validate() no longer rejects
    MoE) and serves through TPEngine, exact vs single chip."""
    mesh = ReplicaMesh(tp=2, ep=4)
    config = llama.CONFIGS["moe_tiny8"]
    assert config.n_experts == 8
    mesh.validate(config)                      # old rejection is gone
    spec = [(40, 4), (17, 3)]
    single = _moe_paged(None, config_name="moe_tiny8")
    want = _run(single, _requests(single.config, spec))
    server = _moe_paged(mesh, config_name="moe_tiny8")
    got = _run(server, _requests(server.config, spec))
    assert got == want
    assert server.stats()["ep_degree"] == 4


# ---------------------------------------------------------------- #
# validate()/build(): the satellite's error-message contract
# ---------------------------------------------------------------- #

def test_mesh2d_validation_messages():
    dense = llama.CONFIGS["tiny_tp"]
    moe = llama.CONFIGS["moe_tiny"]
    # MoE rejection replaced by the ep-axis path: ep on a DENSE
    # config points at the ep axis's job, not a blanket "no MoE".
    with pytest.raises(ValueError, match="expert weights"):
        ReplicaMesh(ep=2).validate(dense)
    # Non-divisible expert count names the ep axis size.
    with pytest.raises(ValueError, match="'ep' axis size 3"):
        ReplicaMesh(ep=3).validate(moe)
    # Non-divisible tensor dims name the tp axis size.
    with pytest.raises(ValueError, match="'tp' axis size 3"):
        ReplicaMesh(tp=3).validate(dense)
    # At most 2-D, and the message says to pick one.
    with pytest.raises(ValueError, match="ONE second axis"):
        ReplicaMesh(sp=2, ep=2).validate(dense)
    with pytest.raises(ValueError, match="ONE second axis"):
        ReplicaMesh(sp=2, ep=2).build()
    # The happy paths.
    ReplicaMesh(tp=2, sp=4).validate(dense)
    ReplicaMesh(tp=2, ep=2).validate(moe)


def test_mesh2d_build_shapes(virtual_mesh_devices):
    mesh = ReplicaMesh(tp=2, sp=4).build()
    assert mesh.axis_names == ("tp", "sp")
    assert mesh.devices.shape == (2, 4)
    mesh = ReplicaMesh(tp=2, ep=2).build()
    assert mesh.axis_names == ("tp", "ep")
    assert mesh.devices.shape == (2, 2)
    assert ReplicaMesh(tp=2).build().axis_names == ("tp",)
    with pytest.raises(ValueError, match="needs"):
        ReplicaMesh(tp=4, sp=4).build()


# ---------------------------------------------------------------- #
# Warm ladder + overlap mode
# ---------------------------------------------------------------- #

def test_warm_prefill_ladder_counts_and_idle_guard(
        virtual_mesh_devices):
    """The sp-chunk shape ladder pre-warm: on an idle engine it
    dispatches every (bucket, width) prefill shape including the
    sp-window shapes; on a busy engine it refuses (warming against a
    live pool would scribble scratch writes into block 0 races)."""
    server = _paged(ReplicaMesh(tp=2, sp=2))
    warmed = server.warm_prefill_ladder()
    assert warmed > 0
    # Warming is idempotent and compile-free the second time, but the
    # dispatch count is the same — it is a shape walk, not a cache.
    assert server.warm_prefill_ladder() == warmed
    server.submit(DecodeRequest(
        request_id="busy",
        prompt=np.arange(1, 150, dtype=np.int32), max_new_tokens=3))
    server.step()
    with pytest.raises(RuntimeError, match="idle"):
        server.warm_prefill_ladder()
    server.run_until_drained()


def test_overlap_mode_dense_only_and_off_the_exact_path(
        virtual_mesh_devices):
    """``overlap=True`` (collective-matmul reduce-scatter down-proj)
    is a LOSSY-layout opt-in mode: it requires dense MLP weights and
    the exactness suite never enables it.  Quantized weights reject
    at engine construction; a dense server serves."""
    with pytest.raises(ValueError, match="dense"):
        _paged(ReplicaMesh(tp=2, overlap=True))       # quantize=True
    server = _paged(ReplicaMesh(tp=2, overlap=True), quantize=False)
    out = _run(server, _requests(server.config, [(20, 3)]))
    assert len(out["r0"]) == 3


# ---------------------------------------------------------------- #
# Telemetry: the 2-D degrees reach the share/dashboard key set
# ---------------------------------------------------------------- #

def test_mesh2d_telemetry_keys_flow():
    from aiko_services_tpu.orchestration.serving import TELEMETRY_KEYS
    for key in ("sp_degree", "ep_degree", "sp_prefill_dispatches",
                "mesh_shape"):
        assert key in TELEMETRY_KEYS, key
    server = _paged(None, max_seq=96)
    stats = server.stats()
    assert stats["sp_degree"] == 1 and stats["ep_degree"] == 1
    assert stats["sp_prefill_dispatches"] == 0
