"""The latent-attention model module (MLA + routed SwiGLU experts
beside a shared one) through the paged engine at a tiny size, float32:
absorbed against expanded attention, prefill in slices, a prefix hit on
shared latent blocks, decode through the cache, reused slots and
evicted documents; the kernels in interpret mode against their jnp
forms; what the engine refuses for a latent pool.

The tiny config's original context is 64 positions, so every prompt
here reaches past it: both YaRN's blended frequencies and the query
scale ``1 + beta ln(1 + floor(p / 64))`` act."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu import models
from aiko_services_tpu.models import mistral4, moe
from aiko_services_tpu.ops import latent_attention as la
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer

F32 = dataclasses.replace(mistral4.CONFIGS["mistral4_tiny"],
                          dtype=jnp.float32)
mistral4.CONFIGS["mistral4_tiny_f32"] = F32


def make_server(**kwargs):
    options = dict(config_name="mistral4_tiny_f32", slots=4, max_seq=256,
                   chunk_steps=4, block_size=16, total_blocks=64,
                   chunk_prefill_tokens=32, seed=3,
                   enable_prefix_cache=True)
    options.update(kwargs)
    return PagedContinuousServer(**options)


def forward_logits(params, tokens, config=F32):
    return np.asarray(mistral4.forward(
        params, jnp.asarray([tokens], jnp.int32), config))[0]


def greedy(server, prompt, served):
    """What the full-sequence forward (expanded attention, no cache)
    picks behind ``prompt`` followed by each prefix of ``served``: the
    served tokens themselves, by induction, when every entry agrees."""
    logits = forward_logits(server.params, list(prompt) + list(served))
    return logits[len(prompt) - 1:-1].argmax(-1).tolist()


def request(name, prompt, count):
    return DecodeRequest(request_id=name,
                         prompt=np.asarray(prompt, np.int32),
                         max_new_tokens=count)


@pytest.fixture(params=["reference", "interpret"])
def kernels(request, monkeypatch):
    """Both attention paths: the jnp forms, and the Pallas kernels
    interpreted."""
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", request.param)
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", request.param)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


def test_the_engine_binds_the_module_and_sizes_a_latent_pool():
    module, config = models.serving_model("mistral4_tiny")
    assert module is mistral4 and config.n_experts == 16
    server = make_server()
    stats = server.stats()
    assert stats["layer_kinds"] == "latent_attention=2,experts=2"
    assert stats["state_bytes_per_slot"] == 0
    # A row needs 32 + 16 values; the pool pads it to one lane row:
    # 2 layers x 128 values x 4 bytes a position, 16 positions a block.
    assert mistral4.cache_row_values(F32) == 48
    assert stats["kv_bytes_per_position"] == 2 * 128 * 4
    assert server._block_nbytes() == 16 * 2 * 128 * 4
    assert stats["kv_pool_bytes"] == 65 * server._block_nbytes()
    assert server.pool_census()["block_bytes"] == server._block_nbytes()
    assert [sorted(layer) for layer in server.pool] == [["c"], ["c"]]
    assert server.pool[0]["c"].shape == (65, 16, 128)


def test_the_scalings_act_past_the_original_context():
    assert F32.rope_original_max == 64 and F32.rope_factor == 8.0
    plain = dataclasses.replace(F32, rope_factor=1.0)
    assert F32.sm_scale == pytest.approx(
        32 ** -0.5 * (0.1 * np.log(8.0) + 1.0) ** 2)
    assert plain.sm_scale == pytest.approx(32 ** -0.5)
    blended, unscaled = mistral4._inv_freq(F32), mistral4._inv_freq(plain)
    # The fastest pair keeps its frequency, the slowest is stretched.
    assert float(blended[0]) == pytest.approx(float(unscaled[0]))
    assert float(blended[-1]) == pytest.approx(float(unscaled[-1]) / 8.0)
    scale = np.asarray(mistral4._query_scale(
        jnp.asarray([0, 63, 64, 200]), F32))
    np.testing.assert_allclose(
        scale, [1.0, 1.0, 1 + 0.1 * np.log(2.0), 1 + 0.1 * np.log(4.0)],
        rtol=1e-6)
    # And the forward's logits depend on both.
    params = mistral4.init_params(F32, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(1, 1024, 100)
    base = forward_logits(params, tokens)
    for changed in (plain, dataclasses.replace(F32,
                                               llama4_scaling_beta=0.0)):
        other = np.asarray(mistral4.forward(
            params, jnp.asarray(tokens[None]), changed))[0]
        assert np.abs(other[70:] - base[70:]).max() > 1e-3


def test_absorbed_attention_is_expanded_attention(kernels):
    """One layer over 150 positions: the expanded form of the forward,
    against the absorbed form over a latent pool (a slice of 64 at
    position 0, one of 64 behind it, then decode steps)."""
    params = mistral4.init_params(F32, jax.random.PRNGKey(4))
    layer = params["layers"][0]
    normed = jax.random.normal(jax.random.PRNGKey(5), (1, 150, 128))
    wanted = np.asarray(mistral4._attention_expanded(layer, F32, normed))[0]
    pool = mistral4.init_paged_cache(F32, 12, 16)[0]
    table = jnp.asarray([4, 2, 9, 1, 7, 3, 8, 5, 10, 6], jnp.int32)
    got = []
    for start in (0, 64):
        out, pool = mistral4._attention_append(
            layer, F32, normed[:, start:start + 64], pool, table,
            jnp.int32(start))
        got.append(np.asarray(out)[0])
    for position in range(128, 150):
        out, pool = mistral4._attention_decode(
            layer, F32, normed[:, position:position + 1], pool,
            table[None], jnp.asarray([position], jnp.int32))
        got.append(np.asarray(out)[0])
    np.testing.assert_allclose(np.concatenate(got), wanted, atol=1e-5,
                               rtol=0)
    # The cache holds c_kv and k_r and zeros: nothing per head.
    rows = np.asarray(pool["c"])[np.asarray(table)].reshape(-1, 128)
    assert np.abs(rows[:150, :48]).min() > 0
    assert not rows[:, 48:].any()


def test_slices_a_prefix_hit_and_decode_give_the_forwards_logits(kernels):
    """A 200-token document prefilled in slices of 32 and a question
    behind it; the same document asked again hits its 12 latent
    blocks and prefills the new question alone; the logits of both
    prompts' slices and of the decode steps behind them are the
    full-sequence forward's."""
    params = mistral4.init_params(F32, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    document = rng.integers(1, 1024, 192).astype(np.int32)
    asks = [np.concatenate([document, rng.integers(1, 1024, n)]).astype(
        np.int32) for n in (40, 23)]
    pool = mistral4.init_paged_cache(F32, 40, 16)
    shared = list(range(1, 13))                 # the document's blocks
    tables = [shared + [20, 21, 22, 23], shared + [30, 31, 32, 33]]
    for prompt, table, first in zip(asks, tables, (0, 192)):
        wanted = forward_logits(params, prompt)
        padded = np.zeros((1, 256), np.int32)
        padded[0, :len(prompt)] = prompt
        table = jnp.asarray([table], jnp.int32)
        for start in range(first, 256, 32):
            logits, pool = mistral4.prefill_append_paged(
                params, jnp.asarray(padded[:, start:start + 32]), pool,
                table, jnp.int32(start), F32)
            stop = min(start + 32, len(prompt) - 1)
            if stop > start:
                np.testing.assert_allclose(
                    np.asarray(logits)[0, :stop - start],
                    wanted[start:stop], atol=1e-5, rtol=0)
        # The prompt's last token is the first decode step's.
        last = len(prompt) - 1
        state = dict(token=jnp.asarray([[0], [prompt[last]]], jnp.int32),
                     positions=jnp.asarray([0, last], jnp.int32),
                     active=jnp.asarray([False, True]),
                     remaining=jnp.asarray([0, 3], jnp.int32),
                     temps=jnp.zeros((2,)), tops=jnp.ones((2,)),
                     adapter_ids=jnp.zeros((2,), jnp.int32),
                     tables=jnp.concatenate(
                         [jnp.zeros((1, 16), jnp.int32), table]))
        tokens, counts, _, pool, chunk_counters = \
            mistral4.serve_chunk_paged(params, state, pool, 3, F32)
        served = np.asarray(tokens)[1].tolist()
        sequence = list(prompt)
        for token in served:
            logits = forward_logits(params, sequence)[-1]
            assert logits.max() - logits[token] <= 1e-5
            sequence.append(token)
        assert np.asarray(counts).tolist() == [0, 3]
        assert int(chunk_counters["moe_pairs"]) == 3 * 2 * 4
        assert int(chunk_counters["moe_pairs_here"]) == 3 * 2 * 4


# --- a slice riding the chunk's first step ------------------------------- #

#: Three layers, so that "every layer but the last" is more than one.
F32_L3 = dataclasses.replace(F32, n_layers=3)


@functools.partial(jax.jit, static_argnames=("num_steps", "eos_id",
                                             "sampled"))
def _slice_then_chunk(params, state, pool, tokens, row, start, key,
                      num_steps, eos_id, sampled):
    """The mixed program as it was before the slice rode the first step:
    the slice to its end, then the chunk."""
    _, pool = mistral4._prefill_core(params, tokens, pool,
                                     state["tables"][row], start, F32_L3,
                                     False)
    return mistral4._serve(params, state, pool, num_steps, F32_L3, eos_id,
                           sampled, key)


@functools.lru_cache(maxsize=None)
def _mixed_scene(width):
    """Five slots over a pool of seeded rows: idle, live, PREFILLING
    (its first slice cached, its second the mixed one), live (greedy:
    the row the test retires by EOS), live with one token of budget
    left.  Returns ``(params, state, pool, the slice's tokens, what
    slot 3 emits first)``."""
    params = mistral4.init_params(F32_L3, jax.random.PRNGKey(21))
    rng = np.random.default_rng(width)
    rows = rng.normal(size=(3, 60, 16, 128)).astype(np.float32)
    rows[..., 48:] = 0
    tables = np.zeros((5, 16), np.int32)
    tables[1:, :14] = 1 + rng.permutation(59)[:56].reshape(4, 14)
    # Slot 3's context is its prompt's own rows, so that the forward
    # names the token its first step emits.
    asked = rng.integers(1, 1024, 48).astype(np.int32)
    pool = [{"c": jnp.asarray(layer)} for layer in rows]
    _, pool = mistral4.prefill_append_paged(
        params, jnp.asarray(asked[None]), pool, jnp.asarray(tables[3:4]),
        jnp.int32(0), F32_L3, compute_logits=False)
    first = int(forward_logits(params, asked[:37], F32_L3)[-1].argmax())
    state = dict(
        token=jnp.asarray([[0], [5], [0], [asked[36]], [7]], jnp.int32),
        positions=jnp.asarray([0, 69, 0, 36, 89], jnp.int32),
        active=jnp.asarray([False, True, False, True, True]),
        remaining=jnp.asarray([0, 20, 0, 20, 1], jnp.int32),
        temps=jnp.asarray([0.0, 0.8, 0.0, 0.0, 0.7]),
        tops=jnp.asarray([1.0, 0.9, 1.0, 1.0, 0.95]),
        adapter_ids=jnp.zeros((5,), jnp.int32),
        tables=jnp.asarray(tables))
    tokens = jnp.asarray(rng.integers(1, 1024, (1, width)), jnp.int32)
    return params, state, pool, tokens, first


#: (kernels, slice width, steps, sampled): the jnp forms over every
#: combination, the interpreted kernels (half a minute of compiling each
#: on the CPU) at both widths.
MIXED_CASES = [("reference", width, steps, sampled)
               for width in (16, 64) for steps in (1, 2, 8)
               for sampled in (False, True)] + [
    ("interpret", 16, 2, True), ("interpret", 64, 8, False)]


@pytest.mark.parametrize(
    "kernels,width,num_steps,sampled", MIXED_CASES, indirect=["kernels"],
    ids=[f"{k}-w{w}-s{n}-{'sampled' if d else 'greedy'}"
         for k, w, n, d in MIXED_CASES])
def test_a_slice_riding_the_first_step_is_the_slice_then_the_chunk(
        kernels, width, num_steps, sampled):
    """The mixed program against the slice followed by the chunk on the
    same inputs: tokens, emit counts, state, pool (the rows both write;
    to rounding where a hidden row went through a pass of another
    height) and the three counters of the decode rows.  Slot 3 emits
    the EOS id in the merged step itself; slot 4's budget ends there."""
    params, state, pool, tokens, eos_id = _mixed_scene(width)
    key = jax.random.PRNGKey(17)
    copy = functools.partial(jax.tree.map, jnp.copy)
    row, start = jnp.int32(2), jnp.int32(width)
    wanted = _slice_then_chunk(params, state, copy(pool), tokens, row,
                               start, key, num_steps=num_steps,
                               eos_id=eos_id, sampled=sampled)
    got = mistral4.serve_chunk_mixed(
        params, state, copy(pool), tokens, row, start, num_steps, F32_L3,
        eos_id=eos_id, sampled=sampled, rng_key=key)
    for name, mine, theirs in zip(("tokens", "emitted"), got, wanted):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs),
                                      err_msg=name)
    emitted = np.asarray(got[1]).tolist()
    assert emitted[:3] == [0, num_steps, 0] and emitted[3:] == [1, 1]
    assert np.asarray(got[0])[3, 0] == eos_id
    for name in wanted[2]:
        np.testing.assert_array_equal(np.asarray(got[2][name]),
                                      np.asarray(wanted[2][name]),
                                      err_msg=name)
    slice_blocks = np.asarray(state["tables"])[2, width // 16:width // 8]
    for before, mine, theirs in zip(pool, got[3], wanted[3]):
        np.testing.assert_allclose(np.asarray(mine["c"]),
                                   np.asarray(theirs["c"]), atol=1e-5,
                                   rtol=0)
        # The slice left its rows in every layer, the last included.
        assert not np.array_equal(
            np.asarray(mine["c"])[slice_blocks],
            np.asarray(before["c"])[slice_blocks])
    counters, old = got[4], wanted[4]
    for name in ("moe_pairs", "moe_pairs_here", "moe_experts_hit"):
        assert int(counters[name]) == int(old[name]), name
    assert int(counters["moe_pairs"]) == (num_steps + 2) * 3 * 4
    assert int(counters["moe_slice_rows_merged"]) == width * 2
    assert int(old["moe_slice_rows_merged"]) == 0
    assert sorted(counters) == sorted(mistral4.COUNTERS)


@pytest.mark.parametrize("lengths", [(100, 70, 133, 65), (81, 97, 64, 190)])
def test_slices_hits_and_reused_slots_serve_the_forward(lengths, kernels):
    """Requests on both sides of the slice width, two of them asking
    about a document a third has cached, on slots that are reused:
    every request's tokens are the forward's own."""
    server = make_server()
    rng = np.random.default_rng(sum(lengths))
    document = rng.integers(1, 1024, 96)
    prompts = [rng.integers(1, 1024, n) for n in lengths] + [
        np.concatenate([document, rng.integers(1, 1024, n)])
        for n in (20, 37, 9)]
    requests = [request(f"r{i}", prompt, 8 if i % 2 else 12)
                for i, prompt in enumerate(prompts)]
    for item in requests[:5]:
        server.submit(item)
    server.run_until_drained()
    for item in requests[5:]:
        server.submit(item)
    server.run_until_drained()
    for item in requests:
        assert item.error is None
        assert len(item.tokens) == item.max_new_tokens
        assert item.tokens == greedy(server, item.prompt,
                                     item.tokens), item.request_id
    # The document's six blocks were reused by the two later askings.
    assert server.prefix_blocks_reused >= 2 * 6
    counters = server.counters
    committed = sum(item.max_new_tokens for item in requests)
    assert counters["moe_pairs"] == committed * 2 * 4
    assert counters["moe_pairs_here"] == counters["moe_pairs"]
    # Every mixed slice (16 or 32 tokens here) rode a decode step
    # through the one layer before the last.
    assert 16 * counters["prefill_slices_mixed"] \
        <= counters["moe_slice_rows_merged"] \
        <= 32 * counters["prefill_slices_mixed"]
    assert counters["prefill_slices_mixed"] > 0
    assert counters["prefill_key_blocks"] > 0
    assert counters["decode_blocks_read"] > 0
    assert (server.decode_attention_path, server.prefill_attention_path) \
        == (("kernel", "kernel") if kernels == "interpret"
            else ("reference", "reference"))


def test_an_evicted_document_is_prefilled_again_and_serves_the_same():
    """A pool too small for two documents (a prompt of 150 is admitted
    in a bucket of 16 blocks and leaves 9 cached): the second evicts
    blocks of the first, the first is asked again, and its tokens are
    those of its first asking and of the forward."""
    server = make_server(slots=1, total_blocks=20)
    rng = np.random.default_rng(11)
    first, second = (rng.integers(1, 1024, 150) for _ in range(2))
    served = []
    for name, prompt in (("a", first), ("b", second), ("c", first)):
        server.submit(request(name, prompt, 8))
        done = server.run_until_drained()[0]
        assert done.error is None
        served.append(done.tokens)
    assert server.prefix_evictions > 0
    assert served[0] == served[2] == greedy(server, first, served[0])
    assert served[1] == greedy(server, second, served[1])
    assert server.free_blocks + len(server._evictable) == 20


def test_an_asking_waits_for_its_document_in_flight_and_shares_it():
    """Two askings of one document admitted together: the second finds
    the first's blocks still being produced.  It waits at the head of
    the queue until they land (the slice queue serves the oldest
    prefill first, so a second prefill of the document could not start
    sooner anyway) and then shares them; before PR 31 it took them for
    a miss, prefilled the document again, and indexed its question's
    blocks under a chain it did not hold, which no leaf-first eviction
    could then reach (``pop from empty list`` in ``_reserve_slot``,
    first chip run of PR 31).  Afterwards every cached block can be
    evicted: a prompt that needs the pool gets it."""
    server = make_server(slots=2, total_blocks=54, max_seq=512)
    rng = np.random.default_rng(13)
    document = rng.integers(1, 1024, 96)
    short, long = (request(name, np.concatenate(
        [document, rng.integers(1, 1024, 40)]), count)
        for name, count in (("a", 4), ("b", 40)))
    server.submit(short)
    server.submit(long)
    server.step()
    assert server._prefilling and len(server._queue) == 1
    assert server.counters["admission_deferred"] >= 1
    finished = []
    while not finished:
        finished = server.step()
    assert [item.request_id for item in finished] == ["a"]
    assert (short.shared_tokens, long.shared_tokens) == (0, 96)
    assert server.prefix_blocks_reused == 6
    third = request("c", rng.integers(1, 1024, 300), 4)
    server.submit(third)
    server.run_until_drained()
    for item in (short, long, third):
        assert item.error is None
        assert item.tokens == greedy(server, item.prompt, item.tokens)
    assert server.free_blocks + len(server._evictable) == 54
    while server._evict_one():
        pass
    assert server.free_blocks == 54 and not server._index


def test_a_reused_slot_gives_what_a_fresh_server_gives():
    rng = np.random.default_rng(5)
    first, second = rng.integers(1, 1024, 90), rng.integers(1, 1024, 71)
    used = make_server(slots=1, enable_prefix_cache=False)
    used.submit(request("a", first, 12))
    used.run_until_drained()
    used.submit(request("b", second, 12))
    reused = used.run_until_drained()[0].tokens
    fresh = make_server(slots=1, enable_prefix_cache=False)
    fresh.submit(request("b", second, 12))
    assert reused == fresh.run_until_drained()[0].tokens


# --- the kernels against their jnp forms -------------------------------- #


def _pool(rng, blocks=40, width=128):
    return jnp.asarray(rng.normal(size=(blocks, 16, width)), jnp.float32)


#: Keys one iteration of the decode loop covers for the tests' 4 heads.
DECODE_KEYS = la.keys_per_iteration(4, 16)

#: name -> (context lengths a row, table width): what a loop that covers
#: many blocks an iteration can get wrong.  A length 1 row on a table of
#: zeros is an idle slot on the scratch block.
DECODE_CONTEXTS = {
    "shorter_than_a_block": ([6], 12),
    "one_iteration_exactly": ([DECODE_KEYS], DECODE_KEYS // 16 + 8),
    "one_iteration_less_a_key": ([DECODE_KEYS - 1], DECODE_KEYS // 16 + 8),
    "one_iteration_and_a_key": ([DECODE_KEYS + 1], DECODE_KEYS // 16 + 8),
    "iterations_and_a_ragged_tail": (
        [2 * DECODE_KEYS + 300, DECODE_KEYS + 16 * 9 + 5, 131],
        3 * DECODE_KEYS // 16),
    "table_narrower_than_an_iteration": ([6, 131, 192], 12),
    "idle_row_beside_live_rows": ([1, DECODE_KEYS + 77, 1, 40],
                                  DECODE_KEYS // 16 + 8),
}


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32_pool", "bf16_pool"])
@pytest.mark.parametrize("case", sorted(DECODE_CONTEXTS))
def test_the_decode_kernel_interpreted_is_its_jnp_form(case, pool_dtype):
    lengths, width = DECODE_CONTEXTS[case]
    assert DECODE_KEYS > 12 * 16         # the narrow table is narrower
    rng = np.random.default_rng(len(case))
    n_blocks = 1 + len(lengths) * width
    pool = _pool(rng, blocks=n_blocks).astype(pool_dtype)
    tables = rng.permutation(np.arange(1, n_blocks)).reshape(
        len(lengths), width)
    for row, length in enumerate(lengths):
        if length == 1:
            tables[row] = 0
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(lengths, jnp.int32) - 1
    q = jnp.asarray(rng.normal(size=(len(lengths), 4, 128)), jnp.float32)
    wanted = la.latent_decode_reference(q, pool, tables, positions,
                                        rank=32, sm_scale=0.3)
    got = la.latent_decode_attention(q, pool, tables, positions, rank=32,
                                     sm_scale=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(wanted),
                               atol=1e-5, rtol=0)


#: Keys one pass of the prefill loop covers for a whole tile of the
#: tests' 4 heads.
PREFILL_KEYS = la.keys_per_iteration(la.PREFILL_Q_TILE * 4, 16)

#: name -> (slice tokens, cached positions, table width[, pool dtype,
#: query dtype: f32 unless given]): what a pass of many blocks can get
#: wrong at prefill.  The first five are PR 31's.
PREFILL_CONTEXTS = {
    "16_tokens_nothing_cached": (16, 0, 30),
    "32_tokens_one_block": (32, 16, 30),
    "64_tokens_8_blocks": (64, 128, 30),
    "128_tokens_2_blocks": (128, 32, 30),
    "256_tokens_9_blocks": (256, 144, 30),
    "four_tiles_nothing_cached": (256, 0, 30),
    "three_tiles_192_tokens": (192, 32, 30),
    "prefix_shorter_than_a_pass": (64, 48, PREFILL_KEYS // 16 + 8),
    "one_pass_exactly": (64, PREFILL_KEYS, PREFILL_KEYS // 16 + 8),
    "one_pass_and_a_block": (64, PREFILL_KEYS + 16,
                             PREFILL_KEYS // 16 + 8),
    "passes_and_a_ragged_last": (256, 2 * PREFILL_KEYS + 16 * 9,
                                 3 * PREFILL_KEYS // 16),
    "three_passes_16_tokens": (16, 3 * PREFILL_KEYS,
                               3 * PREFILL_KEYS // 16 + 1),
    "table_narrower_than_a_pass": (64, 80, 12),
    "bf16_pool_one_pass_and_a_block": (64, PREFILL_KEYS + 16,
                                       PREFILL_KEYS // 16 + 8,
                                       jnp.bfloat16),
    "bf16_pool_ragged_last_four_tiles": (256, PREFILL_KEYS + 16 * 5,
                                         2 * PREFILL_KEYS // 16,
                                         jnp.bfloat16),
    "bf16_pool_nothing_cached": (16, 0, 12, jnp.bfloat16),
    # The served path: bf16 queries are their own single MXU term and
    # the weights go to bf16 for the second product.
    "bf16_pool_bf16_queries": (64, 2 * PREFILL_KEYS + 16 * 3,
                               3 * PREFILL_KEYS // 16, jnp.bfloat16,
                               jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(PREFILL_CONTEXTS))
def test_the_prefill_kernel_interpreted_is_its_jnp_form(case):
    tokens, start, width, *dtypes = PREFILL_CONTEXTS[case]
    pool_dtype, q_dtype = (*dtypes, jnp.float32, jnp.float32)[:2]
    assert PREFILL_KEYS > 12 * 16        # the narrow table is narrower
    assert start % 16 == 0 and start + tokens <= width * 16
    rng = np.random.default_rng(tokens + start)
    n_blocks = max(40, width + 10)
    pool = _pool(rng, blocks=n_blocks).astype(pool_dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, n_blocks))[:width],
                        jnp.int32)
    q = jnp.asarray(rng.normal(size=(tokens, 4, 128)), q_dtype)
    own = jnp.asarray(rng.normal(size=(tokens, 128)), q_dtype)
    wanted = la.latent_prefill_reference(q, own, pool, table,
                                         jnp.int32(start), rank=32,
                                         sm_scale=0.3)
    got = la.latent_prefill_attention(q, own, pool, table,
                                      jnp.int32(start), rank=32,
                                      sm_scale=0.3, interpret=True)
    # bf16 queries: the result itself is bf16 (8 bits) and so are the
    # weights of the second product.
    atol = 1e-5 if q_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(wanted, np.float32),
                               atol=atol, rtol=0)


def test_the_append_kernel_writes_one_row_or_whole_blocks_in_place():
    rng = np.random.default_rng(3)
    pool = _pool(rng)
    rows = jnp.asarray(rng.normal(size=(3, 128)), jnp.float32)
    blocks = jnp.asarray([3, 0, 7], jnp.int32)     # the idle row: block 0
    offsets = jnp.asarray([2, 5, 15], jnp.int32)
    got = np.asarray(jax.jit(
        lambda p: la.latent_append(p, rows, blocks, offsets,
                                   interpret=True),
        donate_argnums=0)(pool + 0))
    wanted = np.asarray(pool).copy()
    wanted[3, 2], wanted[7, 15] = np.asarray(rows[0]), np.asarray(rows[2])
    np.testing.assert_array_equal(got, wanted)
    whole = jnp.asarray(rng.normal(size=(2, 16, 128)), jnp.float32)
    got = np.asarray(jax.jit(
        lambda p: la.latent_append(p, whole, jnp.asarray([4, 9]),
                                   interpret=True),
        donate_argnums=0)(pool + 0))
    wanted = np.asarray(pool).copy()
    wanted[4], wanted[9] = np.asarray(whole)
    np.testing.assert_array_equal(got, wanted)


def test_a_slice_sweeps_its_cached_blocks_once_a_query_tile():
    # 256 tokens at position 1024: four tiles of 64, each over the 64
    # cached blocks and its own rows up to its last query.
    assert la.latent_slice_key_blocks(1024, 256, 16) == \
        4 * 64 + 4 + 8 + 12 + 16
    assert la.latent_slice_key_blocks(0, 32, 16) == 2
    assert mistral4.slice_key_blocks(F32, 1024, 256, 16) == 296


# --- what a latent pool is refused -------------------------------------- #

REFUSED_AT_CONSTRUCTION = {
    "host tier": (dict(host_tier_blocks=8), "host_tier .*latent blocks"),
    "spill": (dict(spill_dir="/tmp/never-created-by-this-test"),
              "spill .*pool signature"),
    "draft model": (dict(draft_config_name="tiny"),
                    "speculation .*verify program"),
    "n-gram self-draft": (dict(draft_mode="ngram"), "speculation"),
    "lora": (dict(adapters={"a": {}}, lora_config=object()),
             "adapters .*LoRA"),
    "replica mesh": (dict(replica_mesh=object()),
                     "replica_mesh .*head axis"),
    "int8 cache": (dict(quantize_kv=True), "no int8 layout"),
}


@pytest.mark.parametrize("what", sorted(REFUSED_AT_CONSTRUCTION))
def test_the_engine_refuses_what_cannot_carry_a_latent_block(what):
    options, message = REFUSED_AT_CONSTRUCTION[what]
    with pytest.raises(ValueError, match=message):
        make_server(**options)


def test_transfer_migration_mesh_and_the_contiguous_layout_are_refused():
    server = make_server()
    with pytest.raises(ValueError, match="kv_transfer .*wire format"):
        server.kv_export_payload(["00"], 0)
    with pytest.raises(ValueError, match="kv_transfer"):
        server.kv_import_payload({})
    with pytest.raises(ValueError, match="migration .*latent block chain"):
        server.publish_live_chain("r0")
    with pytest.raises(ValueError, match="contiguous_layout"):
        ContinuousBatchingServer(config_name="mistral4_tiny_f32", slots=2)
    with pytest.raises(ValueError, match="mesh .*sharding rule"):
        ContinuousBatchingServer(config_name="mistral4_tiny_f32", slots=2,
                                 mesh=object())
    with pytest.raises(ValueError, match="latent block pool"):
        make_server(host_tier_blocks=4)


# --- the feed-forward --------------------------------------------------- #

SWIGLU = moe.MoEConfig(d_model=32, d_ff=48, n_experts=16, top_k=4,
                       capacity_factor=None, dtype=jnp.float32,
                       activation="swiglu", d_shared=40)


def _swiglu_layer(params, x, held=None):
    """The layer written out, one expert after another."""
    gates = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    out = np.zeros_like(x)
    for t, row in enumerate(gates):
        ids = np.argsort(-row)[:4]
        chosen = row[ids] / row[ids].sum()
        for expert, gate in zip(ids, chosen):
            if held is not None and not held[0] <= expert < sum(held):
                continue
            e = expert - (held[0] if held else 0)
            hidden = jax.nn.silu(x[t] @ params["w_gate"][e]) \
                * (x[t] @ params["w_up"][e])
            out[t] += gate * np.asarray(hidden @ params["w_down"][e])
    shared = (jax.nn.silu(x @ params["shared_gate"])
              * (x @ params["shared_up"])) @ params["shared_down"]
    return out, np.asarray(shared)


def test_the_shared_expert_is_a_swiglu_of_three_matrices():
    params = moe.init_moe_params(SWIGLU, jax.random.PRNGKey(7))
    assert params["shared_gate"].shape == (32, 40)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (24, 32)))
    with jax.default_matmul_precision("highest"):
        routed, shared = _swiglu_layer(params, x)
        got, counts = moe.moe_layer(params, jnp.asarray(x)[None], SWIGLU)
    np.testing.assert_allclose(np.asarray(got)[0], routed + shared,
                               atol=1e-5, rtol=0)
    assert np.asarray(counts).tolist()[0::2] == [24 * 4, 24]
    # relu2 keeps its two matrices.
    two = moe.init_moe_params(dataclasses.replace(
        SWIGLU, activation="relu2"), jax.random.PRNGKey(7))
    assert "shared_gate" not in two


def test_the_four_shares_of_a_layer_add_up_to_the_layer():
    """Held 0-3 ... 12-15 of 16, the shared expert counted once."""
    whole = moe.init_moe_params(SWIGLU, jax.random.PRNGKey(9))
    x = jax.random.normal(jax.random.PRNGKey(10), (1, 40, 32))
    with jax.default_matmul_precision("highest"):
        wanted, _ = moe.moe_layer(whole, x, SWIGLU)
        _, shared = _swiglu_layer(whole, np.asarray(x)[0])
        total, pairs = shared.copy(), 0
        for first in range(0, 16, 4):
            config = dataclasses.replace(SWIGLU, held=(first, 4))
            share = dict(whole, **{name: whole[name][first:first + 4]
                                   for name in ("w_gate", "w_up",
                                                "w_down")})
            out, counts = moe.moe_layer(share, x, config)
            total += np.asarray(out)[0] - shared
            pairs += int(counts[0])
    np.testing.assert_allclose(total, np.asarray(wanted)[0], atol=1e-5,
                               rtol=0)
    assert pairs == 40 * 4
