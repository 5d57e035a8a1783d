"""A cache of two kinds of row through the paged engine
(``models/evabyte.py``): the module's whole-sequence form against the
plain reference (all prediction heads) and, inside one window, against
``llama.forward`` and the accepted dense reference on the same weights;
the served path (slices, then decode through the composed table across
a window's end in prefill and one in decode, a prompt whose last chunk
is partial, a padded bucket) against the reference's full forward, with
float and int8 pools, kernels interpreted and the jnp forms; the blocks
a slot holds, the refusals, the counters and the step-log event.

Tiny widths: ``window_size`` 32, ``chunk_size`` 4 (so the pool block is
4), seeded weights.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aiko_services_tpu.models import evabyte, llama
from aiko_services_tpu.obs import steplog
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer
from benchmark.reference import dense_gqa, eva_window_chunks

CONFIG = evabyte.CONFIGS["evabyte_tiny"]
BLOCK = CONFIG.chunk_size
#: The reference's view of the tiny config.
CFG = dict(hidden_size=CONFIG.d_model, num_attention_heads=CONFIG.n_heads,
           num_key_value_heads=CONFIG.n_kv_heads,
           num_hidden_layers=CONFIG.n_layers, vocab_size=CONFIG.vocab_size,
           num_pred_heads=CONFIG.n_pred_heads,
           window_size=CONFIG.window_size, chunk_size=CONFIG.chunk_size,
           rope_theta=CONFIG.rope_theta, rms_norm_eps=CONFIG.norm_eps)


@pytest.fixture(scope="module")
def params():
    """Seeded weights whose norm offsets, ``phi`` and ``mu`` all carry
    information."""
    tree = evabyte.init_params(CONFIG, jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(8)
    for index, layer in enumerate(tree["layers"]):
        for slot, name in enumerate(("attn_norm", "mlp_norm")):
            layer[name] = 0.1 * jax.random.normal(
                jax.random.fold_in(key, 2 * index + slot),
                layer[name].shape)
    tree["final_norm"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 99), tree["final_norm"].shape)
    return tree


class Weights:
    """The module's parameter tree as the plain reference takes it."""

    def __init__(self, tree):
        self.tree = jax.tree.map(lambda leaf: leaf.astype(jnp.float32),
                                 tree)

    def top(self):
        return {name: self.tree[name]
                for name in ("embed", "final_norm", "lm_head")}

    def layer(self, index):
        return self.tree["layers"][index]


def _tokens(count, seed=0):
    return np.random.default_rng([seed, count]).integers(
        0, CONFIG.vocab_size, count).astype(np.int32)


def _reference(params, tokens, all_heads=False):
    return eva_window_chunks.run(CFG, Weights(params), [tokens],
                                 [(0, len(tokens))], all_heads)[0]


# --- (a), (b): the whole-sequence form ------------------------------------ #


@pytest.mark.parametrize("length", [19, 90])
def test_forward_is_the_reference_on_all_prediction_heads(params, length):
    tokens = _tokens(length)
    got = np.asarray(evabyte.forward(params, jnp.asarray(tokens[None]),
                                     CONFIG))[0]
    want = _reference(params, tokens, all_heads=True)
    assert got.shape == want.shape == (length, CONFIG.n_pred_heads,
                                       CONFIG.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_inside_one_window_the_model_is_dense_causal_attention(params):
    """No chunk is behind a query of the first window: the layer IS
    causal softmax attention with as many kv heads as heads, so the
    module equals ``llama.forward`` and the accepted dense reference on
    the same weights (a norm's weight there is ``1 + g``)."""
    tokens = _tokens(CONFIG.window_size)
    wide = CONFIG.n_pred_heads * CONFIG.vocab_size
    dense = llama.LlamaConfig(
        vocab_size=wide, d_model=CONFIG.d_model, n_layers=CONFIG.n_layers,
        n_heads=CONFIG.n_heads, n_kv_heads=CONFIG.n_kv_heads,
        d_ff=CONFIG.d_ff, rope_theta=CONFIG.rope_theta,
        norm_eps=CONFIG.norm_eps, dtype=jnp.float32)

    def as_dense(tree):
        out = dict(tree, final_norm=1.0 + tree["final_norm"])
        out["layers"] = [
            dict({name: leaf for name, leaf in layer.items()
                  if name not in ("phi", "mu")},
                 attn_norm=1.0 + layer["attn_norm"],
                 mlp_norm=1.0 + layer["mlp_norm"])
            for layer in tree["layers"]]
        return out

    got = np.asarray(evabyte.forward(params, jnp.asarray(tokens[None]),
                                     CONFIG))[0].reshape(len(tokens), wide)
    served = np.asarray(llama.forward(as_dense(params),
                                      jnp.asarray(tokens[None]), dense,
                                      use_flash=False))[0]
    np.testing.assert_allclose(got, served, atol=2e-4)
    plain = dense_gqa.run(
        dict(CFG, sliding_window=None), Weights(as_dense(params)),
        [tokens], [(0, len(tokens))])[0]
    np.testing.assert_allclose(got, plain, atol=2e-4)


# --- (c): the served path against the reference's full forward ----------- #


def _through_the_cache(params, tokens, prompt_len, quantize_kv, slice_width,
                       CONFIG=CONFIG):
    """Next-byte logits at positions ``prompt_len - 1 ..`` of ``tokens``:
    the prompt in slices (its bucket padded), then one decode step a
    position with the true byte fed back, in slot 1 of 2."""
    max_seq = 128
    width = evabyte.table_blocks(CONFIG, max_seq, BLOCK)
    pool = evabyte.init_paged_cache(CONFIG, 1 + width, BLOCK,
                                    quantize_kv=quantize_kv)
    tables = np.zeros((2, width), np.int32)
    tables[1] = np.arange(1, width + 1)
    tables = jnp.asarray(tables)
    bucket = -(-prompt_len // slice_width) * slice_width
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = tokens[:prompt_len]
    for start in range(0, prompt_len, slice_width):
        _, pool = evabyte.prefill_append_paged(
            params, jnp.asarray(padded[:, start:start + slice_width]),
            pool, tables[1:2], jnp.int32(start), CONFIG, kv_limit=99,
            compute_logits=False)
    state = dict(
        token=jnp.zeros((2, 1), jnp.int32),
        positions=jnp.asarray([0, prompt_len - 1], jnp.int32),
        active=jnp.asarray([False, True]),
        remaining=jnp.asarray([0, 1000], jnp.int32),
        temps=jnp.zeros((2,)), tops=jnp.ones((2,)),
        adapter_ids=jnp.zeros((2,), jnp.int32), tables=tables)
    logits = []
    for position in range(prompt_len - 1, len(tokens)):
        token = jnp.asarray([[0], [int(tokens[position])]], jnp.int32)
        step, pool = evabyte._decode_core(
            params, token, pool, tables,
            jnp.asarray([0, position], jnp.int32), state["active"], CONFIG)
        logits.append(np.asarray(step[1, 0]))
    return np.stack(logits)


@pytest.mark.parametrize("mode", ["reference", "interpret"])
@pytest.mark.parametrize("quantize_kv", [False, True])
def test_slices_then_decode_through_the_composed_cache(
        params, monkeypatch, mode, quantize_kv):
    """90 positions, window 32: a window's end (32) is reached in
    prefill, one (64) in decode; the prompt's last chunk is partial
    (53 = 13 chunks and a byte) and its bucket padded to 64."""
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", mode)
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", mode)
    jax.clear_caches()
    tokens, prompt_len = _tokens(90), 53
    got = _through_the_cache(params, tokens, prompt_len, quantize_kv, 16)
    want = _reference(params, tokens)[prompt_len - 1:]
    jax.clear_caches()
    assert np.abs(got - want).max() < (0.08 if quantize_kv else 2e-4)


@pytest.mark.parametrize("quantize_kv", [False, True])
def test_decode_at_32_heads_takes_the_all_heads_form(monkeypatch,
                                                     quantize_kv):
    """EvaByte's own head count (no grouped queries): a block of 4 keys
    is one lane row of (key, head) pairs, so the decode kernel attends
    over every head of a key at once — through the composed table,
    across a window's end in prefill and one in decode, it gives what
    the jnp form gives."""
    import dataclasses

    from aiko_services_tpu.ops.paged_attention import decode_attend_form
    config = dataclasses.replace(CONFIG, d_model=128, n_heads=32,
                                 n_kv_heads=32)
    assert decode_attend_form(1, config.n_kv_heads, BLOCK,
                              jnp.int8 if quantize_kv
                              else config.dtype) == "all_heads"
    wide = evabyte.init_params(config, jax.random.PRNGKey(11))
    tokens, prompt_len = _tokens(80), 37
    got = {}
    for mode in ("reference", "interpret"):
        monkeypatch.setenv("AIKO_DECODE_ATTENTION", mode)
        monkeypatch.setenv("AIKO_PREFILL_ATTENTION", "reference")
        jax.clear_caches()
        got[mode] = _through_the_cache(wide, tokens, prompt_len,
                                       quantize_kv, 16, config)
    jax.clear_caches()
    assert np.abs(got["interpret"] - got["reference"]).max() < 2e-4


# --- the engine ------------------------------------------------------------ #


def _server(**more):
    options = dict(config_name="evabyte_tiny", slots=2, max_seq=128,
                   chunk_steps=4, block_size=BLOCK, total_blocks=40,
                   chunk_prefill_tokens=16, seed=3)
    options.update(more)
    return PagedContinuousServer(**options)


def _request(name, prompt_len, answer):
    return DecodeRequest(request_id=name, max_new_tokens=answer,
                         prompt=_tokens(prompt_len, seed=5))


def _serve(server, requests, every_step=None):
    for request in requests:
        server.submit(request)
    for _ in range(2000):
        if all(request.finished_ts is not None for request in requests):
            return
        server.step()
        if every_step is not None:
            every_step()
    raise AssertionError("requests did not finish")


@pytest.mark.parametrize("quantize_kv", [False, True])
def test_served_tokens_are_the_references_choice(quantize_kv):
    server = _server(quantize_kv=quantize_kv)
    requests = [_request("a", 53, 24), _request("b", 30, 40),
                _request("c", 7, 12), _request("d", 64, 8)]
    _serve(server, requests)
    for request in requests:
        assert request.error is None
        assert len(request.tokens) == request.max_new_tokens
        tokens = np.concatenate([request.prompt, request.tokens])
        logits = _reference(server.params, tokens)[
            len(request.prompt) - 1:-1]
        gaps = logits.max(-1) - logits[np.arange(len(request.tokens)),
                                       request.tokens]
        assert gaps.max() <= (0.05 if quantize_kv else 1e-4)


def test_blocks_held_by_kind_and_all_returned():
    """A slot holds its ring and 8 (here 2) summary blocks for each
    window its request can reach, from admission to release; what it
    has IN USE grows by a window's summaries at every window's end; the
    census names the two kinds and balances."""
    server = _server()
    ring, per_window = evabyte.block_kinds(CONFIG, BLOCK)
    assert (ring, per_window) == (8, 2)
    request = _request("a", 53, 40)         # 93 positions: 3 windows
    assert server._worst_case_blocks(53, 40) == ring + 3 * per_window
    seen = []

    def check():
        census = server.pool_census()
        kinds, states = census["kinds"], census["states"]
        assert kinds["window"] + kinds["summary"] == states["private"]
        assert states["free"] + states["private"] == server.total_blocks
        if server._requests[0] is request:
            assert len(server._owned[0]) == ring + 3 * per_window
            assert kinds == dict(window=ring, summary=3 * per_window)
            rows = int(server.positions[0])
            in_use = ring + per_window * (rows // CONFIG.window_size + 1)
            assert in_use <= len(server._owned[0])
            seen.append(in_use)

    _serve(server, [request], every_step=check)
    assert seen and sorted(set(seen)) == [ring + per_window * n
                                          for n in (1, 2, 3)]
    assert server.free_blocks == server.total_blocks
    assert server.pool_census()["kinds"] == dict(window=0, summary=0)
    assert not server.tables.any()
    # A query never sees more rows than a window and a row a chunk.
    positions = np.arange(0, 40 * CONFIG.window_size, 7)
    composed, held = evabyte.cache_rows(CONFIG, positions, BLOCK)
    assert (composed + 1
            <= CONFIG.window_size + positions // CONFIG.chunk_size).all()
    assert (composed == evabyte.composed_positions(CONFIG,
                                                   positions)).all()
    assert (held["eva_rows_held"] >= composed + 1).all()
    assert set(held) < set(evabyte.CACHE_COUNTERS)


def test_counters_add_up_and_a_windows_end_is_logged():
    steplog.install(capacity=4096)
    try:
        server = _server()
        # Count blocks as the chip's path does (the CPU's jnp form
        # reads the whole table a step).
        server.decode_attention_path = "kernel"
        requests = [_request("a", 53, 24), _request("b", 30, 40)]
        _serve(server, requests)
        events = [fields for _, event, fields
                  in steplog.RECORDER.events() if event == "window_end"]
    finally:
        steplog.uninstall()
    counters, stats = server.counters, server.stats()
    for name in evabyte.CACHE_COUNTERS:
        assert counters[name] > 0 and stats[name] == counters[name]
    rows = [53 + 24 - 1, 30 + 40 - 1]       # rows each slot has written
    assert counters["eva_chunks_summarised"] == sum(
        n // CONFIG.chunk_size for n in rows)
    assert counters["eva_windows_closed"] == sum(
        n // CONFIG.window_size for n in rows) == len(events)
    assert counters["eva_blocks_returned"] == 8 * len(events)
    assert all(event["eva_windows_closed"] == 1 for event in events)
    assert 0 < counters["decode_summary_blocks_read"] \
        < counters["decode_blocks_read"]
    # Fewer rows than positions once a window is behind, never fewer
    # than a sixteenth (here a quarter) of them.
    assert counters["eva_positions_held"] // CONFIG.chunk_size \
        < counters["eva_rows_held"] < counters["eva_positions_held"]


REFUSED = {
    "adapters": (dict(adapters={"a": {}}, lora_config=object()),
                 "LoRA factors"),
    "speculation": (dict(draft_config_name="tiny"), "rollback across"),
    "prefix_cache": (dict(enable_prefix_cache=True),
                     "two block kinds"),
    "host_tier": (dict(host_tier_blocks=4), "demotion of a chain"),
    "spill": (dict(spill_dir="/nonexistent/spill"), "spilled chains"),
    "replica_mesh": (dict(replica_mesh=object()), "shard_map engine"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_each_refusal_raises_at_construction_and_names_its_piece(feature):
    options, piece = REFUSED[feature]
    with pytest.raises(ValueError, match=piece) as caught:
        _server(**options)
    assert "summaries of the chunks behind it (evabyte)" in str(
        caught.value)


def test_what_is_refused_at_the_call_and_the_layouts():
    server = _server()
    with pytest.raises(ValueError, match="migration snapshot"):
        server.publish_live_chain(_request("r", 20, 4))
    with pytest.raises(ValueError, match="an export of a chain"):
        server.kv_export_payload([], 0)
    with pytest.raises(ValueError, match="contiguous-cache programs"):
        ContinuousBatchingServer(config_name="evabyte_tiny", slots=2,
                                 max_seq=128)
    with pytest.raises(ValueError, match="sharding rule"):
        ContinuousBatchingServer(config_name="evabyte_tiny", slots=2,
                                 max_seq=128, mesh=object())
    with pytest.raises(ValueError, match="must be chunk_size"):
        _server(block_size=16, total_blocks=20)
    with pytest.raises(ValueError, match="never straddles"):
        _server(chunk_prefill_tokens=64)
    assert set(evabyte.UNSUPPORTED[1]) == {
        "mesh", "replica_mesh", "adapters", "speculation", "prefix_cache",
        "host_tier", "spill", "kv_transfer", "migration",
        "contiguous_layout"}


def test_older_modules_programs_are_as_they_were():
    """The hooks are declared by this module alone: a Llama-family
    server has no composed table, none of its counters, and its
    admission arithmetic is the row count it always was."""
    server = PagedContinuousServer(
        config_name="tiny", slots=2, max_seq=128, chunk_steps=4,
        block_size=16, total_blocks=20)
    assert not server._composed
    assert not set(evabyte.CACHE_COUNTERS) & set(server.counters)
    assert server.tables.shape[1] == 128 // 16
    assert server._worst_case_blocks(20, 30) == -(-(32 + 30) // 16)
    assert server.pool_census()["kinds"] is None
    assert dataclasses.is_dataclass(server.config)
    from aiko_services_tpu import models
    hooks = ("check_layout", "table_blocks", "slot_blocks", "block_kinds",
             "composed_tables", "composed_positions", "cache_rows",
             "cache_events", "CACHE_COUNTERS")
    for module in models.SERVING_MODULES:
        assert all(hasattr(module, hook) for hook in hooks) \
            == (module is evabyte)
        assert not any(hasattr(module, hook) for hook in hooks) \
            == (module is not evabyte)
