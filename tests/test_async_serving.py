"""Device-resident async serving: steady-state transfer counters,
greedy parity for both servers under async dispatch, cancellation with
chunks in flight, streaming increment ordering, and the CPU smoke the
tier-1 gate runs on every PR.

The engine contract under test (docs/SERVING.md): per-slot decode
state lives on device and is updated in-jit; the host uploads state
only when admission/retirement dirties a slot (counted by
``state_uploads``) and downloads only the tiny per-chunk
``(tokens, counts, active)`` result (counted by ``sync_elements``) —
never full logits.
"""

import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, ContinuousReplica, DecodeRequest,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from aiko_services_tpu.pipeline.codec import decode_swag, encode_swag
from aiko_services_tpu.runtime import (
    Process, actor_args, compose_instance,
)
from aiko_services_tpu.utils.sexpr import generate, parse

import jax.numpy as jnp


def reference_greedy(server, prompt, max_new):
    """Per-request oracle: prefill + generate_tokens at batch 1 with
    the server's own params (same oracle as test_continuous)."""
    config = server.config
    prompt = jnp.asarray(np.asarray(prompt, np.int32))[None, :]
    prompt_len = prompt.shape[1]
    cache = llama.init_cache(config, 1, server.max_seq)
    logits, cache = llama.prefill(server.params, prompt, cache, config)
    first = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    if max_new == 1:
        return [int(first[0, 0])]
    tokens, _ = llama.generate_tokens(
        server.params, first, cache, jnp.int32(prompt_len),
        max_new - 1, config)
    return [int(first[0, 0])] + [int(t) for t in np.asarray(tokens)[0]]


def test_steady_state_no_per_step_uploads():
    """After the admission wave, the decode loop must run WITHOUT
    host→device state uploads: ``state_uploads`` counts dirty-slot
    merges (admission/retirement only), not steps.  The per-sync
    download stays far below one row of logits."""
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=96, chunk_steps=2,
                                      seed=3)
    rng = np.random.default_rng(0)
    for i in range(2):
        server.submit(DecodeRequest(
            f"r{i}", rng.integers(1, 500, 8).astype(np.int32), 30))
    server.step()                      # admit + first dispatches
    uploads_after_admission = server.stats()["state_uploads"]
    assert uploads_after_admission >= 1       # admission dirtied slots
    while server.busy:
        server.step()
    stats = server.stats()
    # Steady state: every later dispatch reused the resident state —
    # the only merges were the admission wave's (retirement marks
    # slots dirty too, but nothing dispatches after the last retire).
    assert stats["state_uploads"] == uploads_after_admission, stats
    assert stats["decode_steps"] >= 30
    # The host pulled (tokens, counts, active) per sync — not logits.
    per_sync = stats["sync_elements"] / max(stats["host_syncs"], 1)
    assert per_sync < server.config.vocab_size / 4, stats
    assert stats["tokens_committed"] == 60


def test_paged_greedy_parity_with_prefix_sharing():
    """Paged server with the prefix cache on: shared-prefix requests
    (admitted in one wave, blocks shared mid-flight) match the
    per-request oracle byte-for-byte, and the cache counters record
    the first request as a miss, later ones as hits."""
    server = PagedContinuousServer(
        config_name="tiny", slots=3, max_seq=96, chunk_steps=4,
        seed=5, block_size=8, enable_prefix_cache=True)
    rng = np.random.default_rng(7)
    prefix = rng.integers(1, 500, 17).astype(np.int32)
    requests = []
    for i, (tail_len, new) in enumerate([(4, 6), (9, 5), (6, 8)]):
        tail = rng.integers(1, 500, tail_len).astype(np.int32)
        requests.append(DecodeRequest(
            f"p{i}", np.concatenate([prefix, tail]), new))
    for request in requests:
        server.submit(request)
    server.run_until_drained()
    for request in requests:
        want = reference_greedy(server, request.prompt,
                                request.max_new_tokens)
        assert request.tokens == want, (request.request_id,
                                        request.tokens, want)
    assert server.prefix_misses >= 1          # first arrival: cold
    assert server.prefix_hits >= 1            # later arrivals: shared
    stats = server.stats()
    assert stats["prefix_hits"] == server.prefix_hits
    assert stats["prefix_misses"] == server.prefix_misses


def test_prefix_cache_hits_across_buckets():
    """Bucket-insensitive matching: the SAME prompt resubmitted with a
    different decode budget (different padded shapes downstream) still
    hits — keys hash prompt content, never bucket geometry."""
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=128, chunk_steps=4,
        seed=6, block_size=8, enable_prefix_cache=True)
    prompt = np.arange(1, 20, dtype=np.int32)       # 2 full blocks
    server.submit(DecodeRequest("cold", prompt.copy(), 4))
    server.run_until_drained()
    assert server.prefix_hits == 0
    server.submit(DecodeRequest("warm", prompt.copy(), 40))
    server.run_until_drained()
    assert server.prefix_hits == 1, vars(server)
    assert server.prefix_blocks_reused >= 2


def test_cancel_mid_decode_with_chunks_in_flight():
    """Cancelling a decoding request while the async ring holds
    undelivered chunks drains them first: the partial tokens delivered
    are an exact prefix of the oracle, and the surviving request is
    untouched."""
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=96, chunk_steps=2,
                                      seed=7, lookahead=4)
    rng = np.random.default_rng(11)
    victim = DecodeRequest(
        "victim", rng.integers(1, 500, 8).astype(np.int32), 20)
    keeper = DecodeRequest(
        "keeper", rng.integers(1, 500, 11).astype(np.int32), 6)
    server.submit(victim)
    server.submit(keeper)
    server.step()                       # ring fills with in-flight work
    assert server.stats()["in_flight"] >= 1
    assert server.cancel("victim")
    finished = server.run_until_drained()
    by_id = {r.request_id: r for r in finished}
    assert by_id["victim"].error == "cancelled"
    assert 0 < len(by_id["victim"].tokens) < 20
    assert by_id["victim"].tokens == reference_greedy(
        server, victim.prompt, 20)[:len(by_id["victim"].tokens)]
    assert by_id["keeper"].error is None
    assert by_id["keeper"].tokens == reference_greedy(
        server, keeper.prompt, 6)


def test_streaming_ordering_under_async_dispatch(engine):
    """With several chunks in flight per pump (lookahead=3), streamed
    increments still arrive in decode order and concatenate to exactly
    the final (oracle) sequence — consume order is ring order."""
    process = Process(namespace="test", hostname="h", pid="88",
                      engine=engine, broker="async_stream")
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=96, chunk_steps=3,
                                      seed=6, lookahead=3)
    replica = compose_instance(
        ContinuousReplica, actor_args("cba"), process=process,
        server=server)
    partials, finals = [], []

    def handler(_topic, payload):
        command, params = parse(payload)
        if command == "infer_partial":
            partials.append(
                list(decode_swag(params[1])["tokens_out"]))
        elif command == "infer_response":
            finals.append(decode_swag(params[1]))

    process.add_message_handler(handler, "test/async_resp")
    prompt = np.arange(1, 12, dtype=np.int32)
    process.message.publish(
        replica.topic_in,
        generate("infer", ["s1", "test/async_resp",
                           encode_swag({"tokens": prompt,
                                        "max_new_tokens": 12,
                                        "stream": 1})]))
    for _ in range(5000):
        engine.advance(0.001)
        if finals:
            break
    assert finals, "no final infer_response"
    want = reference_greedy(server, prompt, 12)
    assert list(finals[0]["tokens_out"]) == want
    joined = [t for increment in partials for t in increment]
    assert joined == want               # in-order, gapless, complete


def test_pump_listens_longer_while_a_chunk_is_in_flight(engine):
    """Between steps the pump yields a millisecond to other mailbox
    traffic, and the server's ``arrival_hold_s`` while a chunk that
    has just begun keeps the device busy."""
    process = Process(namespace="test", hostname="h", pid="89",
                      engine=engine, broker="async_hold")
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=64, chunk_steps=2, seed=6)
    replica = compose_instance(
        ContinuousReplica, actor_args("cbh"), process=process,
        server=server)
    delays = []
    replica._post_message = (
        lambda mailbox, message, delay=0.0: delays.append(delay))
    replica._schedule_pump()
    server._ring.append(object())
    server._ema_wait_ms = 80.0
    replica._schedule_pump()
    server._ema_wait_ms = 2.0
    replica._schedule_pump()
    server._ring.clear()
    assert delays == [0.001, server.ARRIVAL_HOLD_MAX_S, 0.001]


def test_serving_smoke_counters_monotone():
    """Fast CPU smoke for the async loop (tier-1): run BOTH servers a
    few steps and check every cumulative counter is monotone
    non-decreasing, the ring empties at drain, and the derived rates
    are sane."""
    monotone = ("dispatches", "decode_steps", "tokens_committed",
                "host_syncs", "sync_elements", "state_uploads",
                "admission_deferred")
    servers = [
        ContinuousBatchingServer(config_name="tiny", slots=2,
                                 max_seq=64, chunk_steps=2, seed=9),
        PagedContinuousServer(config_name="tiny", slots=2, max_seq=64,
                              chunk_steps=2, seed=9, block_size=8,
                              enable_prefix_cache=True),
    ]
    rng = np.random.default_rng(3)
    for server in servers:
        for i in range(4):              # 4 requests > 2 slots: queueing
            server.submit(DecodeRequest(
                f"m{i}", rng.integers(1, 500, 6).astype(np.int32), 5))
        previous = server.stats()
        steps = 0
        while server.busy and steps < 200:
            server.step()
            steps += 1
            stats = server.stats()
            for key in monotone:
                assert stats[key] >= previous[key], (key, stats)
            previous = stats
        assert not server.busy
        final = server.stats()
        assert final["in_flight"] == 0
        assert final["slots_active"] == 0
        assert final["tokens_committed"] == 4 * 5
        assert final["decode_steps_per_sec"] >= 0.0
        assert final["sync_stalls_per_100_steps"] >= 0.0
