"""The hybrid model module (Mamba-2 + attention + latent experts)
through the paged engine at a tiny size with all three layer kinds:
state carried through prefill slices, padded buckets, idle rows and
reused slots; what the engine refuses for a model with recurrent
state; the expert layer's share of a deployment."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu import models
from aiko_services_tpu.models import moe, nemotron_h
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer

F32 = dataclasses.replace(nemotron_h.CONFIGS["nemotron_tiny"],
                          dtype=jnp.float32, chunk_size=32)
nemotron_h.CONFIGS["nemotron_tiny_f32"] = F32


def make_server(**kwargs):
    options = dict(config_name="nemotron_tiny_f32", slots=4, max_seq=256,
                   chunk_steps=8, block_size=16, total_blocks=64,
                   chunk_prefill_tokens=32, seed=3)
    options.update(kwargs)
    return PagedContinuousServer(**options)


def greedy(server, prompt, count):
    """Token by token through the full-sequence forward: no cache, no
    state carried."""
    tokens = list(prompt)
    for _ in range(count):
        logits = nemotron_h.forward(
            server.params, jnp.asarray([tokens], jnp.int32), F32)
        tokens.append(int(np.asarray(logits)[0, -1].argmax()))
    return tokens[len(prompt):]


def request(name, prompt, count):
    return DecodeRequest(request_id=name,
                         prompt=np.asarray(prompt, np.int32),
                         max_new_tokens=count)


def test_the_engine_binds_the_module_that_registered_the_config():
    module, config = models.serving_model("nemotron_tiny")
    assert module is nemotron_h and config.pattern == "*EMEM"
    module, _ = models.serving_model("tiny")
    assert module is models.llama
    with pytest.raises(KeyError, match="no serving config"):
        models.serving_model("no_such_config")
    server = make_server()
    assert server._model is nemotron_h
    stats = server.stats()
    assert stats["layer_kinds"] == "mamba=2,attention=1,experts=2"
    # Two Mamba layers: a 3-row window of 320 channels and an
    # (8, 32, 16) float32 state each.
    assert stats["state_bytes_per_slot"] == 2 * (3 * 320 * 4
                                                 + 8 * 32 * 16 * 4)
    assert PagedContinuousServer(config_name="tiny", slots=2).stats()[
        "state_bytes_per_slot"] == 0


@pytest.mark.parametrize("lengths", [
    (40, 5, 70, 33),          # slices of 32: whole, padded, three, 1 over
    (17, 90, 64, 31)])
def test_slices_padding_and_reused_slots_serve_the_forward(lengths):
    """Six requests on four slots: prompts on both sides of the slice
    width, each in a padded bucket, slots reused; every request's
    tokens are what the full-sequence forward gives."""
    server = make_server()
    rng = np.random.default_rng(sum(lengths))
    requests = [request(f"r{i}", rng.integers(1, 1024, n), 16 if i % 2
                        else 8)
                for i, n in enumerate(lengths + (23, 45))]
    for item in requests:
        server.submit(item)
    server.run_until_drained()
    for item in requests:
        assert item.error is None
        assert item.tokens == greedy(server, item.prompt,
                                     item.max_new_tokens), item.request_id
    counters = server.counters
    assert counters["ssm_state_resets"] == len(requests)
    assert counters["ssm_prefill_tokens"] == sum(
        len(item.prompt) - 1 for item in requests)
    # Every decode row routes top-4 of 16 in each of the 2 E layers,
    # all experts held here.
    committed = sum(item.max_new_tokens for item in requests)
    assert counters["moe_pairs"] == committed * 2 * 4
    assert counters["moe_pairs_here"] == counters["moe_pairs"]
    assert 0 < counters["moe_experts_hit"] <= counters["moe_pairs_here"]


def test_a_reused_slot_gives_what_a_fresh_server_gives():
    rng = np.random.default_rng(5)
    first, second = rng.integers(1, 1024, 60), rng.integers(1, 1024, 41)
    used = make_server(slots=1)
    used.submit(request("a", first, 16))
    used.run_until_drained()
    used.submit(request("b", second, 16))
    reused = used.run_until_drained()[0].tokens
    fresh = make_server(slots=1)
    fresh.submit(request("b", second, 16))
    assert reused == fresh.run_until_drained()[0].tokens


def _state_bits(pool):
    return [np.asarray(leaf).view(np.uint32 if leaf.dtype == jnp.float32
                                  else np.uint16).copy()
            for layer in pool["ssm"] for leaf in (layer["conv"],
                                                  layer["state"])]


def test_idle_rows_and_padding_leave_a_state_bit_identical():
    server = make_server()
    rng = np.random.default_rng(9)
    for index in range(3):
        server.submit(request(f"r{index}", rng.integers(1, 1024, 20), 8))
    server.run_until_drained()          # every slot's state is non-zero
    before = _state_bits(server.pool)
    assert all(bits[:3].any() for bits in before)
    # One live row decodes; three slots idle.
    server.submit(request("live", rng.integers(1, 1024, 20), 16))
    server.run_until_drained()
    after = _state_bits(server.pool)
    live = [slot for slot in range(4)
            if any((a[slot] != b[slot]).any()
                   for a, b in zip(after, before))]
    assert len(live) == 1
    # A slice none of whose tokens count (all padding) on a slot that
    # carries a state: not at position 0, so nothing is reset either.
    slot = (live[0] + 1) % 4
    _, server.pool = nemotron_h.prefill_append_paged(
        server.params, jnp.asarray(rng.integers(1, 1024, (1, 32)),
                                   jnp.int32),
        server.pool, jnp.zeros((1, 16), jnp.int32), jnp.int32(32), F32,
        compute_logits=False, state_row=jnp.int32(slot),
        valid_len=jnp.int32(0))
    for padded, held in zip(_state_bits(server.pool), after):
        assert np.array_equal(padded, held)


def test_two_slices_of_a_padded_bucket_give_the_forwards_logits():
    """45 tokens in a bucket of 64: a whole slice of 32, then one
    whose last 19 positions are padding.  Logits of both slices equal
    the full-sequence forward's, and so does the state behind them (a
    decode step from it gives the forward's next-token logits)."""
    params = nemotron_h.init_params(F32, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, 1024, 45).astype(np.int32)
    wanted = np.asarray(nemotron_h.forward(params, jnp.asarray(
        prompt[None]), F32))[0]
    pool = nemotron_h.init_paged_cache(F32, 9, 16, slots=2)
    tables = jnp.asarray([[3, 5, 7, 8]], jnp.int32)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :45] = prompt
    padded[0, 45:] = rng.integers(1, 1024, 19)        # garbage, not zeros
    got = []
    for start in (0, 32):
        logits, pool = nemotron_h.prefill_append_paged(
            params, jnp.asarray(padded[:, start:start + 32]), pool,
            tables, jnp.int32(start), F32, kv_limit=4,
            state_row=jnp.int32(1),
            valid_len=jnp.int32(min(32, 44 - start)))
        got.append(np.asarray(logits)[0])
    got = np.concatenate(got)
    np.testing.assert_allclose(got[:44], wanted[:44], atol=1e-4, rtol=0)
    # Position 44 is the decode step's: the state stands before it.
    state = dict(token=jnp.asarray([[0], [prompt[44]]], jnp.int32),
                 positions=jnp.asarray([0, 44], jnp.int32),
                 active=jnp.asarray([False, True]),
                 remaining=jnp.asarray([0, 1], jnp.int32),
                 temps=jnp.zeros((2,)), tops=jnp.ones((2,)),
                 adapter_ids=jnp.zeros((2,), jnp.int32),
                 tables=jnp.concatenate([jnp.zeros((1, 4), jnp.int32),
                                         tables]))
    tokens, counts, _, _, chunk_counters = nemotron_h.serve_chunk_paged(
        params, state, pool, 1, F32)
    assert int(np.asarray(tokens)[1, 0]) == int(wanted[44].argmax())
    assert np.asarray(counts).tolist() == [0, 1]
    assert int(chunk_counters["moe_pairs"]) == 2 * 4


REFUSED_AT_CONSTRUCTION = {
    "prefix cache": dict(enable_prefix_cache=True),
    "host tier": dict(host_tier_blocks=8),
    "spill": dict(spill_dir="/tmp/never-created-by-this-test"),
    "draft model": dict(draft_config_name="tiny"),
    "n-gram self-draft": dict(draft_mode="ngram"),
    "lora": dict(adapters={"a": {}}, lora_config=object()),
}


@pytest.mark.parametrize("what", sorted(REFUSED_AT_CONSTRUCTION))
def test_the_engine_refuses_what_needs_a_state_snapshot(what):
    with pytest.raises(ValueError, match="recurrent state .*needs"):
        make_server(**REFUSED_AT_CONSTRUCTION[what])


def test_transfer_migration_and_the_contiguous_layout_are_refused():
    server = make_server()
    with pytest.raises(ValueError, match="kv_transfer .*snapshot"):
        server.kv_export_payload(["00"], 0)
    with pytest.raises(ValueError, match="kv_transfer"):
        server.kv_import_payload({})
    with pytest.raises(ValueError, match="migration .*live recurrent"):
        server.publish_live_chain("r0")
    with pytest.raises(ValueError, match="contiguous_layout"):
        ContinuousBatchingServer(config_name="nemotron_tiny_f32",
                                 slots=2)


LATENT = moe.MoEConfig(d_model=32, d_ff=48, n_experts=8, top_k=3,
                       capacity_factor=None, dtype=jnp.float32,
                       scoring="sigmoid", routed_scale=2.5,
                       activation="relu2", d_latent=16, d_shared=64)


@pytest.mark.parametrize("field", [
    {"held": (0, 2)}, {"scoring": "sigmoid"}, {"routed_scale": 2.5}])
def test_the_capacity_dispatch_refuses_what_it_does_not_serve(field):
    with pytest.raises(ValueError, match="capacity_factor=None"):
        moe.MoEConfig(n_experts=8, top_k=2, **field)
    moe.MoEConfig(n_experts=8, top_k=2, capacity_factor=None, **field)


def test_sigmoid_routing_picks_by_biased_score_and_gates_by_score():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0, 0.5, 0.4, 0.3, 0.2]])
    bias = jnp.zeros((8,)).at[3].set(5.0)
    ids, gate = moe.route(logits, LATENT, bias)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 1, 3]
    picked = np.asarray(jax.nn.sigmoid(logits))[0, np.asarray(ids)[0]]
    np.testing.assert_allclose(np.asarray(gate)[0],
                               2.5 * picked / picked.sum(), rtol=1e-6)


def test_the_shares_of_an_expert_layer_add_up_to_the_layer():
    """Four chips of two experts each, the shared expert and the
    latent projections on every one: the routed parts add up to the
    uncut layer's, with what every chip computes alike counted once."""
    params = moe.init_moe_params(LATENT, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 32), jnp.float32)
    whole, counts = moe.moe_layer(params, x, LATENT)
    assert np.asarray(counts).tolist()[::2] == [2 * 9 * 3, 2 * 9]
    flat = x.reshape(-1, 32)
    shared = np.asarray(jnp.square(jax.nn.relu(
        flat @ params["shared_up"])) @ params["shared_down"])
    total = np.zeros_like(shared)
    pairs = 0
    for first in range(0, 8, 2):
        config = dataclasses.replace(LATENT, held=(first, 2))
        part = dict(params, w_up=params["w_up"][first:first + 2],
                    w_down=params["w_down"][first:first + 2])
        out, counts = moe.moe_layer(part, x, config)
        total += np.asarray(out).reshape(-1, 32) - shared
        pairs += int(counts[0])
    assert pairs == 2 * 9 * 3
    np.testing.assert_allclose(total + shared,
                               np.asarray(whole).reshape(-1, 32),
                               atol=2e-5, rtol=0)


def test_the_heap_is_frozen_when_programs_are_traced_and_not_after():
    """``_settle_heap`` follows the model module's own jit caches: a
    server whose dispatches trace programs freezes after those steps,
    one that finds every program traced freezes once (its first step)
    and never again, and no server hands a frozen heap back."""
    import gc
    prompt = np.random.default_rng(9).integers(1, 1024, 53)

    def serve(server, rounds):
        for index in range(rounds):
            server.submit(request(f"r{index}", prompt, 16))
            server.run_until_drained()
        return server.counters["heap_freezes"]

    # Five-step chunks: decode programs no other test has traced.
    first = make_server(slots=1, chunk_steps=5)
    frozen = gc.get_freeze_count()
    settled = serve(first, 1)
    assert settled >= 1 and gc.get_freeze_count() > frozen
    assert serve(first, 2) == settled    # warm: no step freezes
    frozen = gc.get_freeze_count()
    second = make_server(slots=1, chunk_steps=5)
    assert gc.get_freeze_count() >= frozen
    assert serve(second, 2) == 1
