"""Generation by block passes through the paged engine
(``models/sdar.py``): the kernels under the block-causal mask against
their jnp forms, what the engine refuses for the module, its counters,
span fields and compile label, retirement by length and by the
end-of-sequence id, per-request schedules on the wire, a budget edit in
flight, sampling, and streaming over the loopback transport.  The
arithmetic against the plain reference is
``tests/benchmark/test_sdar.py``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aiko_services_tpu.models import llama, sdar
from aiko_services_tpu.obs import compiles, steplog
from aiko_services_tpu.ops import paged_attention, paged_prefill
from aiko_services_tpu.orchestration.continuous import DecodeRequest
from aiko_services_tpu.orchestration.paged import PagedContinuousServer


def _server(**more):
    options = dict(config_name="sdar_tiny", slots=2, max_seq=256,
                   chunk_steps=3, block_size=16, total_blocks=40,
                   chunk_prefill_tokens=32)
    options.update(more)
    return PagedContinuousServer(**options)


def _request(name, prompt_len, answer, seed=0, **more):
    rng = np.random.default_rng([seed, prompt_len])
    return DecodeRequest(
        request_id=name, max_new_tokens=answer,
        prompt=rng.integers(1, 1000, prompt_len).astype(np.int32), **more)


def _serve(server, requests):
    for request in requests:
        server.submit(request)
    server.run_until_drained()
    return requests


# --- the kernels under the block-causal mask ----------------------------- #


def _slice(start, width=32, kv=2, group=2, hd=32, blocks=9):
    keys = jax.random.split(jax.random.PRNGKey(start + 1), 5)
    q = jax.random.normal(keys[0], (1, width, kv, group, hd), jnp.float32)
    k = jax.random.normal(keys[1], (1, width, kv, hd), jnp.float32)
    v = jax.random.normal(keys[2], (1, width, kv, hd), jnp.float32)
    pool = {"k": jax.random.normal(keys[3], (blocks, 16, kv, hd)),
            "v": jax.random.normal(keys[4], (blocks, 16, kv, hd))}
    tables = jnp.asarray([[3, 1, 4, 7, 5, 2, 0, 0]], jnp.int32)
    lens = (jnp.asarray([start], jnp.int32),
            jnp.asarray([width], jnp.int32))
    return q, k, v, pool, tables, lens


@pytest.mark.parametrize("start", [0, 48])
def test_the_append_kernel_under_the_block_causal_mask(start):
    """Interpreted, against the jnp form, for a slice at the start of a
    row and one behind 48 cached positions; and the jnp form against
    attention written out with the mask ``j // 4 <= i // 4``."""
    q, k, v, pool, tables, (cached, chunk) = _slice(start)
    got, new_pool = paged_prefill.paged_prefill_attention(
        q, k, v, pool, tables, cached, chunk, interpret=True, mask_block=4)
    wanted, ref_pool = paged_prefill.paged_prefill_reference(
        q, k, v, pool, tables, cached, chunk, mask_block=4)
    np.testing.assert_allclose(got, wanted, atol=2e-5, rtol=0)
    for name in ("k", "v"):
        np.testing.assert_array_equal(new_pool[name], ref_pool[name])
    causal, _ = paged_prefill.paged_prefill_reference(
        q, k, v, pool, tables, cached, chunk)
    assert np.abs(np.asarray(causal) - np.asarray(wanted)).max() > 1e-2
    # Written out: the row's keys are its cached blocks, then the slice.
    rows = start + 32
    keys = np.asarray(ref_pool["k"])[np.asarray(tables[0])].reshape(
        -1, 2, 32)[:rows]
    values = np.asarray(ref_pool["v"])[np.asarray(tables[0])].reshape(
        -1, 2, 32)[:rows]
    i = start + np.arange(32)
    seen = np.arange(rows)[None, :] // 4 <= i[:, None] // 4
    scores = np.einsum("qkgd,skd->kgqs", np.asarray(q[0]), keys) / 32 ** .5
    scores = np.where(seen, scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    plain = np.einsum("kgqs,skd->qkgd", weights, values)
    np.testing.assert_allclose(wanted[0], plain, atol=2e-5, rtol=0)
    with pytest.raises(ValueError, match="must divide the pool block"):
        paged_prefill.paged_prefill_attention(
            q, k, v, pool, tables, cached, chunk, interpret=True,
            mask_block=5)


def test_a_blocks_queries_ride_one_decode_call_beside_the_group():
    """The pass's attention: ``B`` queries that see the same keys are
    ``B x group`` query rows of a kv head, the position the block's
    last; the interpreted decode kernel against its jnp form and
    against a query at a time."""
    B, kv, group, hd = 4, 2, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (3, B, kv, group, hd), jnp.float32)
    k_pool = jax.random.normal(keys[1], (9, 16, kv, hd))
    v_pool = jax.random.normal(keys[2], (9, 16, kv, hd))
    tables = jnp.asarray([[3, 1, 4, 0], [7, 5, 0, 0], [2, 0, 0, 0]],
                         jnp.int32)
    base = jnp.asarray([36, 16, 0], jnp.int32)
    stacked = q.transpose(0, 2, 1, 3, 4).reshape(3, kv, B * group, hd)
    got = paged_attention.paged_decode_attention(
        stacked, k_pool, v_pool, tables, base + B - 1, interpret=True)
    wanted = paged_attention.paged_decode_reference(
        stacked, k_pool, v_pool, tables, base + B - 1)
    np.testing.assert_allclose(got, wanted, atol=2e-5, rtol=0)
    each = jnp.stack([paged_attention.paged_decode_reference(
        q[:, b], k_pool, v_pool, tables, base + B - 1)
        for b in range(B)], axis=1)
    np.testing.assert_allclose(
        np.asarray(got).reshape(3, kv, B, group, hd).transpose(
            0, 2, 1, 3, 4), each, atol=2e-5, rtol=0)


# --- what the engine refuses --------------------------------------------- #


REFUSED = {
    "replica_mesh": (dict(replica_mesh=object()), "shard_map"),
    "adapters": (dict(adapters={"a": {}}, lora_config=object()),
                 "LoRA factors"),
    "speculation": (dict(draft_config_name="tiny"), "acceptance rule"),
    "grammar": (dict(automata={"g": object()}), "acceptance rule"),
    "prefix_cache": (dict(enable_prefix_cache=True), "block is final"),
    "host_tier": (dict(host_tier_blocks=4), "block to be final"),
    "spill": (dict(spill_dir="/nonexistent/spill"), "final block"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_each_refusal_raises_at_construction_and_names_its_piece(feature):
    options, piece = REFUSED[feature]
    with pytest.raises(ValueError, match=piece) as caught:
        _server(**options)
    assert "generation by block passes (sdar)" in str(caught.value)


def test_what_is_refused_at_the_call_and_the_int8_pool():
    server = _server()
    request = _request("r", 20, 4)
    with pytest.raises(ValueError, match="block in progress"):
        server.publish_live_chain(request)
    with pytest.raises(ValueError, match="leaves out the block"):
        server.kv_export_payload([], 0)
    with pytest.raises(ValueError, match="no int8 scale append"):
        _server(quantize_kv=True)
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer)
    with pytest.raises(ValueError, match="contiguous-cache programs"):
        ContinuousBatchingServer(config_name="sdar_tiny", slots=2,
                                 max_seq=128)
    with pytest.raises(ValueError, match="sharding rule"):
        ContinuousBatchingServer(config_name="sdar_tiny", slots=2,
                                 max_seq=128, mesh=object())
    assert set(sdar.UNSUPPORTED[1]) == {
        "mesh", "replica_mesh", "adapters", "speculation", "prefix_cache",
        "host_tier", "spill", "kv_transfer", "migration",
        "contiguous_layout"}


# --- counters, spans, labels --------------------------------------------- #


def test_counters_stats_span_fields_and_the_compile_label():
    owned = compiles.LEDGER is None
    ledger = compiles.install(service="test-sdar")
    steplog.install(capacity=4096)
    try:
        sdar.serve_chunk_paged.clear_cache()
        server = _server()
        # Count blocks as the chip's path does (the CPU's jnp form
        # reads the whole table a pass).
        server.decode_attention_path = "kernel"
        _serve(server, [_request("a", 40, 8, denoise_steps=2,
                                 denoise_rule="static"),
                        _request("b", 17, 6, denoise_steps=2,
                                 denoise_rule="static")])
        events = steplog.RECORDER.events()
        labels = ledger.signatures("serve_block_chunk")
    finally:
        steplog.uninstall()
        if owned:
            compiles.uninstall()
    counters, stats = server.counters, server.stats()
    for name in ("block_pass_rows", "block_store_rows", "blocks_finished",
                 "moe_pairs", "moe_pairs_here", "moe_experts_hit"):
        assert counters[name] > 0 and stats[name] == counters[name]
    assert counters["tokens_committed"] == 14
    # 40 + 8: blocks 40 and 44, both whole and both stored but the
    # last; 17 + 6: block 16 (3 open) stored, block 20 (3 of 4) cut.
    assert counters["blocks_finished"] == counters["block_store_rows"] == 2
    # Two static passes a block, whole or not, and the stores.
    assert counters["block_pass_rows"] == 2 * 4 + 2
    # Passes, and every dispatch a whole chunk of them.
    assert counters["decode_steps"] == 3 * counters["dispatches"]
    assert counters["decode_steps"] >= counters["block_pass_rows"] // 2
    # A pass reads a slot's blocks once, up to its block's last row:
    # at most the 3 blocks that 48 positions fill, not 4 queries' worth.
    assert 0 < counters["decode_blocks_read"] <= \
        counters["decode_steps"] * 2 * 3
    # All 8 experts are here: every row's top-2 falls on them.
    assert counters["moe_pairs_here"] == counters["moe_pairs"]
    dispatch = [fields for _, event, fields in events
                if event == "dispatch"]
    assert dispatch and all(
        fields["passes"] == fields["steps"] == 3
        and 1 <= fields["live_rows"] <= 2 for fields in dispatch)
    assert [signature for _, signature in labels] == ["p3"]
    assert not ledger.signatures("serve_chunk")


# --- retirement, schedules, edits ---------------------------------------- #


def test_an_end_of_sequence_id_ends_the_request_in_its_block():
    free = _serve(_server(), [_request("free", 21, 12)])[0]
    assert len(free.tokens) == 12
    eos = free.tokens[5]
    first = free.tokens.index(eos)
    server = _server(eos_id=eos)
    ended = _serve(server, [_request("free", 21, 12)])[0]
    assert ended.error is None
    assert ended.tokens == free.tokens[:first + 1]
    assert server.free_blocks == server.total_blocks
    assert not server.active.any()


def test_schedules_a_request_may_and_may_not_ask_for():
    server = _server()
    bad = [_request("steps", 20, 4, denoise_steps=5),
           _request("zero", 20, 4, denoise_steps=0),
           _request("rule", 20, 4, denoise_rule="sequential"),
           _request("long", 200, 53)]
    _serve(server, bad)
    assert [r.error for r in bad] == ["bad_denoise_steps",
                                     "bad_denoise_steps",
                                     "bad_denoise_rule", "prompt_too_long"]
    fits = _serve(server, [_request("fits", 200, 52)])[0]
    assert fits.error is None and len(fits.tokens) == 52
    plain = PagedContinuousServer(config_name="tiny", slots=2, max_seq=128)
    asked = _serve(plain, [_request("steps", 20, 4, denoise_steps=2)])[0]
    assert asked.error == "no_block_passes"
    # The same prompt under two schedules: other passes, and the
    # config's own (4 passes, dynamic) where the request names none.
    one, four, own = _serve(_server(), [
        _request("one", 24, 8, denoise_steps=1, denoise_rule="static"),
        _request("four", 24, 8, denoise_steps=4, denoise_rule="static"),
        _request("own", 24, 8)])
    assert len(one.tokens) == len(four.tokens) == len(own.tokens) == 8
    state = _server()._block_state
    assert (state["denoise_steps"][0], state["dynamic"][0],
            round(float(state["threshold"][0]), 2)) == (4, True, 0.9)


def test_a_budget_edit_and_a_cancel_in_flight():
    server = _server()
    kept = _request("kept", 24, 40)
    gone = _request("gone", 17, 40)
    server.submit(kept)
    server.submit(gone)
    for _ in range(4):
        server.step()
    assert 0 < len(kept.tokens) < 40
    assert server.update_sampling("kept", max_new_tokens=18)
    assert server.cancel("gone")
    server.run_until_drained()
    assert kept.error is None and len(kept.tokens) == 18
    assert gone.error == "cancelled" and len(gone.tokens) < 40
    assert server.free_blocks == server.total_blocks
    # What the edit delivered is what an unedited request of that
    # length gets: the mirrors the edit uploaded were the device's.
    whole = _serve(_server(), [_request("kept", 24, 18)])[0]
    assert kept.tokens[:16] == whole.tokens[:16]


def test_sampled_requests_draw_by_the_servers_seed():
    params = sdar.init_params(sdar.CONFIGS["sdar_tiny"],
                              jax.random.PRNGKey(0))

    def run(seed):
        server = _server(seed=seed, params=params)
        return _serve(server, [
            _request("hot", 20, 10, temperature=0.9, top_p=0.8),
            _request("cold", 33, 10)])
    hot, cold = run(1)
    again, same = run(1)
    other, _ = run(2)
    assert len(hot.tokens) == len(cold.tokens) == 10
    assert hot.tokens == again.tokens and cold.tokens == same.tokens
    assert hot.tokens != other.tokens
    greedy = _serve(_server(params=params), [_request("cold", 33, 10)])[0]
    assert cold.tokens == greedy.tokens


def test_the_wire_carries_the_schedule_and_streams_the_prefix():
    """``(infer ...)`` over loopback with a schedule in its swag: the
    partials are the committed prefix as it grows, and add up to the
    final tokens."""
    import time
    import uuid
    from aiko_services_tpu.orchestration.client import InferClient
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousReplica)
    from aiko_services_tpu.runtime import (Process, actor_args,
                                           compose_instance)
    from aiko_services_tpu.runtime.event import EventEngine
    server = _server()
    engine = EventEngine()
    thread = engine.run_in_thread()
    broker = f"sdar-{uuid.uuid4().hex[:6]}"
    processes = [Process(namespace="t", hostname="h", pid=str(pid),
                         engine=engine, broker=broker) for pid in (2, 9)]
    try:
        replica = compose_instance(ContinuousReplica, actor_args("replica"),
                                   process=processes[0], server=server)
        client = InferClient(processes[1], replica.topic_in)
        prompt = _request("w", 26, 14).prompt
        partials = []
        future = client.submit(prompt, max_new_tokens=14, stream=True,
                               on_partial=partials.append,
                               denoise_steps=1, denoise_rule="static")
        deadline = time.monotonic() + 120
        while not future.done and time.monotonic() < deadline:
            time.sleep(0.01)
        assert future.done and future.error is None
    finally:
        for process in reversed(processes):
            process.terminate()
        engine.terminate()
        thread.join(timeout=10)
    direct = _serve(_server(), [DecodeRequest(
        request_id="d", prompt=prompt, max_new_tokens=14,
        denoise_steps=1, denoise_rule="static")])[0]
    assert future.tokens == direct.tokens and len(future.tokens) == 14
    assert [t for part in partials for t in part] == future.tokens
    assert len(partials) > 1


def test_a_causal_models_programs_do_not_see_the_mask_mode():
    """A module that sets no ``mask_block`` traces the append kernel's
    call as before: the same jaxpr with the argument left out and with
    it None, and none of the block leaves in its slot state."""
    q, k, v, pool, tables, (cached, chunk) = _slice(16)

    def call(**more):
        return str(jax.make_jaxpr(lambda q, pool: (
            paged_prefill.paged_prefill_call(
                q, pool, tables, cached, window=None, sm_scale=0.25,
                q_tile=32, kv_blocks=8, interpret=True, **more)))(q, pool))

    assert call() == call(mask_block=None) != call(mask_block=4)
    plain = PagedContinuousServer(config_name="tiny", slots=2, max_seq=128)
    assert plain._block_length == 0 and plain._block_state == {}
    assert set(plain._state) == {"token", "positions", "active",
                                 "remaining", "temps", "tops",
                                 "adapter_ids", "tables"}
    assert "block_pass_rows" not in plain.counters
    assert not hasattr(llama, "block_slot_state")
