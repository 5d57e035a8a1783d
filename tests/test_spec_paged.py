"""Speculative decoding on the PAGED production path: the draft
proposes per live slot, one ragged verify pass writes the window's K/V
straight into table-resolved pool blocks, and each slot commits its own
accepted prefix with a mid-block rollback of the rest.  Greedy outputs
are BITWISE the plain paged server's under every composition (int8 KV,
chunked admission, prefix cache, TP) — invariant 11: speculation is a
latency optimization, never an approximation."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.models import llama
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import PagedContinuousServer

from .test_continuous import reference_greedy
from .test_paged_prefill import _iter_eqns

REPO = pathlib.Path(__file__).resolve().parents[1]
PKG = REPO / "aiko_services_tpu"

#: Mixed prompt lengths/budgets through 2 slots: queueing, slot reuse,
#: and ragged per-slot progress in every test below.
SHAPES = [(5, 12), (11, 9), (3, 14), (17, 8)]


def _requests(config, spec, seed=0):
    rng = np.random.default_rng(seed)
    return [DecodeRequest(
        f"r{i}", rng.integers(1, config.vocab_size, plen).astype(np.int32),
        new) for i, (plen, new) in enumerate(spec)]


def _prompts(config, spec, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, config.vocab_size, plen).astype(np.int32)
            for plen, _ in spec]


def _server(**kwargs):
    defaults = dict(config_name="tiny", slots=2, max_seq=96,
                    chunk_steps=4, block_size=16, seed=3)
    defaults.update(kwargs)
    return PagedContinuousServer(**defaults)


def _spec_server(paired=True, **kwargs):
    kwargs.setdefault("draft_config_name",
                      kwargs.get("config_name", "tiny"))
    kwargs.setdefault("spec_k", 3)
    server = _server(**kwargs)
    if paired:
        # Draft ≡ target: greedy proposals always match, so every round
        # multi-token-accepts — the high-acceptance ceiling.  The
        # default (paired=False) draft keeps its own random init:
        # acceptance ≈ 0, every round rolls the window back.
        server._draft["params"] = server.params
        server._draft["config"] = server.config
    return server


def _drain(server, spec, seed=0):
    requests = _requests(server.config, spec, seed=seed)
    for request in requests:
        server.submit(request)
    server.run_until_drained()
    return requests


def _outputs(requests):
    return {r.request_id: list(r.tokens) for r in requests}


def _assert_pool_balanced(server):
    assert (server.free_blocks + len(server._evictable)
            + len(server._producing) == server.total_blocks), (
        server.free_blocks, len(server._evictable),
        len(server._producing), server.total_blocks)


# --------------------------------------------------------------------------- #
# Invariant 11: bitwise-exact under every composition


def test_spec_paged_matches_plain_composed():
    """int8 KV + chunked admission + prefix cache, speculated: outputs
    are token-identical to the plain server with the same cache
    composition, and one request is additionally anchored to the
    per-request greedy oracle (bf16 control)."""
    base = _server(chunk_prefill_tokens=0, quantize_kv=True,
                   enable_prefix_cache=True)
    base_requests = _drain(base, SHAPES)
    spec = _spec_server(quantize_kv=True, enable_prefix_cache=True,
                        chunk_prefill_tokens=16)
    spec_requests = _drain(spec, SHAPES)
    assert _outputs(spec_requests) == _outputs(base_requests)
    stats = spec.stats()
    assert stats["spec_rounds"] > 0 and stats["spec_accepted"] > 0
    assert stats["spec_tokens_per_target_pass"] > 1.0
    _assert_pool_balanced(spec)
    _assert_pool_balanced(base)

    oracle = _spec_server()         # bf16: oracle comparison is exact
    oracle_requests = _drain(oracle, SHAPES)
    prompts = _prompts(oracle.config, SHAPES)
    assert list(oracle_requests[0].tokens) == reference_greedy(
        oracle, prompts[0], SHAPES[0][1])


def test_spec_ragged_per_slot_accept_histograms():
    """Every slot accepts its OWN prefix each round; the per-request
    histograms surface that raggedness and reconcile exactly with the
    server's accepted-token counter."""
    server = _spec_server()
    requests = _drain(server, SHAPES)
    hists = {r.request_id: r.spec_accepted_rounds for r in requests}
    assert all(h is not None and len(h) > 0 for h in hists.values())
    k = server._draft["k"]
    for hist in hists.values():
        assert all(0 <= int(a) <= k for a in hist)
    # Paired draft: full-k accepts happen.
    assert any(int(a) == k for h in hists.values() for a in h)
    # Ragged: different budgets finish in different round counts.
    assert len({len(h) for h in hists.values()}) > 1
    stats = server.stats()
    assert stats["spec_accepted"] == sum(
        int(a) for h in hists.values() for a in h)


def test_spec_rejection_rolls_back_blocks_without_leaking():
    """A degraded (random-init) draft rejects nearly everything: the
    verify window's speculative K/V rows — including rows that crossed
    into a freshly chained block — are logically rolled back, the
    rollback counter sees those block crossings, outputs stay exactly
    the plain server's, and the pool balance sheet still closes."""
    base = _server(chunk_prefill_tokens=0)
    base_requests = _drain(base, SHAPES)
    spec = _spec_server(paired=False)
    spec_requests = _drain(spec, SHAPES)
    assert _outputs(spec_requests) == _outputs(base_requests)
    stats = spec.stats()
    assert stats["spec_rounds"] > 0
    assert stats["spec_acceptance_rate"] < 0.5
    assert stats["spec_rollback_blocks"] > 0
    _assert_pool_balanced(spec)


def test_spec_prefix_cache_never_indexes_speculated_blocks():
    """Speculated blocks are invisible to the prefix cache: after a
    speculated run only full PROMPT blocks are indexed, and a repeat
    prompt takes a normal hit whose continuation is bit-identical."""
    server = _spec_server(enable_prefix_cache=True,
                          chunk_prefill_tokens=0)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, server.config.vocab_size, 40).astype(np.int32)
    first = DecodeRequest("a", prompt, 8)
    server.submit(first)
    server.run_until_drained()
    # Deepest indexed chain ≤ shareable prompt blocks — nothing the
    # verify pass wrote past the prompt ever reached the index.
    assert all(depth <= (40 - 1) // 16
               for depth in server._depth.values()), server._depth
    second = DecodeRequest("b", prompt, 8)
    server.submit(second)
    server.run_until_drained()
    assert server.prefix_hits == 1
    assert list(first.tokens) == list(second.tokens)
    _assert_pool_balanced(server)


def test_spec_composes_with_demoted_chain_restore():
    """Prefix chains demoted to the host tier restore under a
    speculated re-run: the hit adopts restored blocks and the
    continuation is bit-identical to the warm run."""
    server = _spec_server(enable_prefix_cache=True, host_tier_blocks=16,
                          chunk_prefill_tokens=0)
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, server.config.vocab_size, 40).astype(np.int32)
    first = DecodeRequest("a", prompt, 8)
    server.submit(first)
    server.run_until_drained()
    demoted = 0
    while server._evict_one():
        demoted += 1
    assert demoted > 0
    second = DecodeRequest("b", prompt, 8)
    server.submit(second)
    server.run_until_drained()
    stats = server.stats()
    assert stats["kv_restores"] > 0
    assert stats["spec_accepted"] > 0
    assert list(first.tokens) == list(second.tokens)


def test_spec_interleaves_with_chunked_admission():
    """Prompts longer than the chunk budget are admitted slice by slice
    while the other slot keeps speculating — mixed steps, standalone
    prefill steps, and spec rounds interleave and the result is still
    bitwise plain."""
    shapes = [(5, 10), (33, 8), (3, 12), (40, 6)]
    base = _server(chunk_prefill_tokens=0)
    base_requests = _drain(base, shapes)
    spec = _spec_server(chunk_prefill_tokens=16)
    spec_requests = _drain(spec, shapes)
    assert _outputs(spec_requests) == _outputs(base_requests)
    assert spec.stats()["spec_rounds"] > 0
    _assert_pool_balanced(spec)


def test_mixed_step_prefill_finish_keeps_new_request():
    """Regression: a chunked step whose prefill slice FINISHES the
    prompt activates the new occupant host-side mid-dispatch, bumping
    the slot serial inside ``_serve_chunk``.  The ring entry must carry
    the PRE-dispatch serials; snapshotting after the call judged the
    fresh occupant by an ``active_after`` flag computed while its lane
    was still a scratch row — silently retiring it with zero tokens."""
    shapes = [(5, 10), (11, 8), (3, 12), (17, 6)]
    server = _server(config_name="tiny_tp", chunk_steps=3, seed=5,
                     enable_prefix_cache=True, chunk_prefill_tokens=16,
                     total_blocks=24)
    requests = _drain(server, shapes)
    prompts = _prompts(server.config, shapes)
    for request, prompt, (_, new) in zip(requests, prompts, shapes):
        assert len(request.tokens) == new, request.request_id
        assert list(request.tokens) == reference_greedy(
            server, prompt, new), request.request_id
    _assert_pool_balanced(server)


def test_tp4_spec_bitwise_parity(virtual_mesh_devices):
    """TP=4: draft replicated, verify through the TP paged engine —
    outputs bitwise the SINGLE-CHIP plain server's with int8 KV +
    chunked admission + prefix cache composed, with real multi-token
    accepts."""
    from aiko_services_tpu.parallel.mesh import ReplicaMesh
    shapes = [(5, 10), (11, 8), (3, 12), (17, 6)]
    kwargs = dict(config_name="tiny_tp", slots=2, max_seq=96,
                  chunk_steps=3, block_size=16, seed=5,
                  enable_prefix_cache=True, quantize_kv=True,
                  chunk_prefill_tokens=16, total_blocks=24)
    base = PagedContinuousServer(**kwargs)
    base_requests = _drain(base, shapes)
    spec = PagedContinuousServer(replica_mesh=ReplicaMesh(tp=4),
                                 draft_config_name="tiny_tp", spec_k=3,
                                 **kwargs)
    spec._draft["params"] = spec.params
    spec._draft["config"] = spec.config
    spec_requests = _drain(spec, shapes)
    assert _outputs(spec_requests) == _outputs(base_requests)
    stats = spec.stats()
    assert stats["spec_accepted"] > 0
    assert stats["spec_tokens_per_target_pass"] > 1.0
    _assert_pool_balanced(spec)


@pytest.mark.slow
def test_spec_rollback_accounting_hundred_rounds():
    """~100+ consecutive rejecting rounds across slot reuse: every
    round appends a speculative window and rolls it back; afterwards
    the pool balance sheet closes to the block — nothing leaked."""
    shapes = [(p, 24) for p in (5, 9, 13, 17, 7, 11, 15, 3, 6, 10)]
    base = _server()
    base_requests = _drain(base, shapes)
    spec = _spec_server(paired=False)
    spec_requests = _drain(spec, shapes)
    assert _outputs(spec_requests) == _outputs(base_requests)
    stats = spec.stats()
    assert stats["spec_rounds"] >= 100
    assert stats["spec_rollback_blocks"] > 0
    _assert_pool_balanced(spec)


@pytest.mark.slow
@pytest.mark.chaos
def test_spec_chaos_bit_exact_under_kills():
    """Replica kills mid-spec-round: failover re-dispatch replays on a
    surviving speculated replica and the fleet's outputs are STILL
    bit-exact vs the plain chaos run — nothing lost, no duplicate
    finals (run_spec_ab raises on any token mismatch)."""
    from aiko_services_tpu.tools.loadgen import run_spec_ab
    base, spec = run_spec_ab(spec_k=3, n_requests=12, rate_hz=30.0,
                             seed=0, chaos=True)
    for report in (base, spec):
        assert report.lost == 0
        assert report.timeouts == 0
        assert report.duplicate_finals == 0
    assert spec.spec_stats is not None
    assert spec.spec_stats["spec_tokens_per_target_pass"] > 1.0
    assert spec.spec_accept_hist


# --------------------------------------------------------------------------- #
# jaxpr + AST guards: verify never gathers the pool; counters stay host-side


def _verify_jaxpr():
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, 9, 16)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    tokens = jnp.ones((2, 4), jnp.int32)
    active = jnp.ones((2,), bool)
    jaxpr = jax.make_jaxpr(
        lambda t, pl_, p: llama._verify_append_core(
            params, t, pl_, tables, p, active, config, kv_limit=4))(
        tokens, pool, jnp.asarray([5, 17], jnp.int32))
    return jaxpr, tuple(pool[0]["k"].shape)


def test_kernel_verify_never_gathers_pool(monkeypatch):
    """With the verify kernel dispatched, the traced program contains
    NO gather whose operand is the pool — cached prefix K/V is read in
    place by the kernel's block sweep, exactly like admission."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", "interpret")
    jaxpr, pool_shape = _verify_jaxpr()
    offenders = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert not offenders, (
        f"paged verify still gathers the pool: {offenders}")


def test_reference_verify_does_gather(monkeypatch):
    """Control: the jnp fallback DOES gather the pool view — the probe
    above can see what it asserts away."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", "reference")
    jaxpr, pool_shape = _verify_jaxpr()
    gathers = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert gathers, "reference verify path should gather the pool view"


# The verify window rides the append kernel's sweep at each row's OWN
# position: the same walk of the live band, started mid-block.
VERIFY_CASES = {
    # (cached_lens, chunk_lens, window, int8 pool)
    "mid_block_and_inactive_row": ((37, 100, 0, 14), (5, 3, 0, 5),
                                   None, False),
    "at_a_block_boundary": ((32, 96, 0, 16), (5, 5, 0, 1), None, False),
    "int8_mid_block_and_inactive_row": ((37, 150, 0, 14), (5, 3, 0, 5),
                                        None, True),
    "int8_window_drops_leading_steps": ((37, 170, 0, 140), (5, 5, 0, 2),
                                        24, True),
    "band_past_one_step": ((130, 131, 127, 0), (5, 1, 5, 0), None,
                           False),
}


@pytest.mark.parametrize("name", sorted(VERIFY_CASES))
def test_verify_kernel_matches_reference(name):
    """``paged_verify_attention`` in interpret mode against
    ``paged_prefill_reference``: outputs of every real query and every
    pool row an active slot appended; rows past a row's chunk length
    (all of an inactive row's) land in scratch block 0 only."""
    from aiko_services_tpu.ops import paged_prefill as pp
    from .test_paged_prefill import _case
    cached, chunk, window, quant = VERIFY_CASES[name]
    case = _case(batch=4, T=5, max_blocks=12, cached_blocks=(0,) * 4,
                 chunk_lens=chunk, quant=quant)
    tol = 1e-3 if quant else 2e-5

    def arm(fn, **kwargs):
        pool = {key: jnp.asarray(val) for key, val in case["pool"].items()}
        out, new_pool = fn(
            jnp.asarray(case["q"]), jnp.asarray(case["k_new"]),
            jnp.asarray(case["v_new"]), pool, jnp.asarray(case["tables"]),
            jnp.asarray(cached, jnp.int32), jnp.asarray(chunk, jnp.int32),
            window=window, **kwargs)
        return np.asarray(out, np.float32), {
            key: np.asarray(val, np.float32)
            for key, val in new_pool.items()}

    out_k, pool_k = arm(pp.paged_verify_attention, interpret=True)
    out_r, pool_r = arm(pp.paged_prefill_reference)
    for b in range(4):
        np.testing.assert_allclose(out_k[b, :chunk[b]],
                                   out_r[b, :chunk[b]], atol=tol,
                                   rtol=tol, err_msg=f"row {b}")
        for position in range(cached[b], cached[b] + chunk[b]):
            block = int(case["tables"][b, position // 16])
            for key in pool_k:
                np.testing.assert_allclose(
                    pool_k[key][block, position % 16],
                    pool_r[key][block, position % 16], atol=tol,
                    rtol=tol, err_msg=f"row {b} pos {position} {key}")
    # Nothing but the appended rows and scratch block 0 changed.
    for key, before in case["pool"].items():
        changed = np.flatnonzero(
            (pool_k[key] != np.asarray(before, np.float32)).reshape(
                before.shape[0], -1).any(axis=1))
        owned = {0} | {int(case["tables"][b, p // 16])
                       for b in range(4)
                       for p in range(cached[b], cached[b] + chunk[b])}
        assert set(changed) <= owned, (key, sorted(set(changed) - owned))


def test_spec_counters_stay_host_side():
    """Invariant 7: acceptance counters, rollback accounting, and
    per-request histograms are HOST bookkeeping — the traced model and
    kernel modules never touch them (no recompiles, no device
    round-trips on the hot path)."""
    banned = ("spec_rollback_blocks", "spec_accepted_rounds",
              "spec_accept_hist", "spec_acceptance_rate",
              "spec_tokens_per_target_pass", "SpecStats")
    targets = [PKG / "models" / "llama.py",
               PKG / "models" / "llama_tp.py",
               *sorted((PKG / "ops").glob("*.py"))]
    assert len(targets) > 2
    for path in targets:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Name):
                name = node.id
            elif isinstance(node, ast.Attribute):
                name = node.attr
            else:
                continue
            assert not any(word in name for word in banned), (
                f"{path.name}: traced module references host-side "
                f"spec counter {name!r}")


# --------------------------------------------------------------------------- #
# Telemetry: stats -> TELEMETRY_KEYS projection -> dashboard


def test_spec_telemetry_flows_to_dashboard():
    from aiko_services_tpu.orchestration.serving import (
        TELEMETRY_KEYS, serving_telemetry,
    )
    from aiko_services_tpu.tools.dashboard_plugins import (
        model_replica_plugin,
    )

    server = _spec_server()
    _drain(server, [(5, 8), (9, 6)])
    stats = server.stats()
    for key in ("spec_k", "spec_rounds", "spec_proposed",
                "spec_accepted", "spec_acceptance_rate",
                "spec_tokens_per_target_pass", "spec_rollback_blocks"):
        assert key in stats and key in TELEMETRY_KEYS
    telemetry = serving_telemetry(stats)
    assert telemetry["spec_rounds"] > 0
    assert telemetry["spec_k"] == server._draft["k"]

    class Fields:
        name, topic_path = "replica_x", "t/replica_x"
        protocol = "model_replica"

    variables = {key: str(value) for key, value in telemetry.items()}
    variables.update(slots="2", prefix_hits="0")
    lines = "\n".join(model_replica_plugin(Fields, variables))
    assert "spec:" in lines
    assert f"k={server._draft['k']}" in lines

    # Plain replicas advertise NO spec keys: the projection omits
    # absent counters, so dashboards only render the line on draft
    # replicas.
    plain = _server()
    _drain(plain, [(5, 4)])
    assert "spec_rounds" not in serving_telemetry(plain.stats())
