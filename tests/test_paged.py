"""Paged-KV continuous batching: exactness vs the per-request greedy
oracle and the contiguous server, block accounting, and admission
deferral under pool pressure."""

import numpy as np

from aiko_services_tpu.models import llama
from aiko_services_tpu.orchestration.continuous import (
    ContinuousBatchingServer, DecodeRequest,
)
from aiko_services_tpu.orchestration.paged import PagedContinuousServer

from .test_continuous import reference_greedy


def _requests(config, spec, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i, (plen, new) in enumerate(spec):
        prompt = rng.integers(1, config.vocab_size, plen).astype(np.int32)
        out.append(DecodeRequest(request_id=f"r{i}", prompt=prompt,
                                 max_new_tokens=new))
    return out


def test_paged_matches_per_request_greedy():
    """Requests through 2 slots with queueing + slot/block reuse: every
    output matches the per-request greedy oracle exactly."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=96, chunk_steps=4, seed=3,
                                   block_size=16)
    requests = _requests(server.config,
                         [(5, 6), (11, 3), (3, 9), (17, 5), (24, 7)])
    for request in requests:
        server.submit(request)
    finished = server.run_until_drained()
    assert sorted(r.request_id for r in finished) == \
        sorted(r.request_id for r in requests)
    for request in requests:
        want = reference_greedy(server, request.prompt,
                                request.max_new_tokens)
        assert request.tokens == want, (request.request_id,
                                        request.tokens, want)


def test_paged_matches_contiguous_server():
    """Same request stream through both layouts → identical outputs
    (paging changes memory shape only)."""
    spec = [(7, 5), (13, 4), (4, 8)]
    outs = {}
    for cls in (ContinuousBatchingServer, PagedContinuousServer):
        server = cls(config_name="tiny", slots=2, max_seq=64,
                     chunk_steps=3, seed=5)
        for request in _requests(server.config, spec, seed=9):
            server.submit(request)
        finished = server.run_until_drained()
        outs[cls.__name__] = {r.request_id: r.tokens for r in finished}
    assert outs["ContinuousBatchingServer"] == \
        outs["PagedContinuousServer"]


def test_paged_lookahead_outputs_identical():
    """Lookahead chains decode_chunk_paged calls device-side (pool and
    block tables unchanged between chunks); outputs stay identical to
    the sync-every-chunk paged server."""
    spec = [(7, 5), (13, 4), (4, 8), (19, 6)]
    outs = {}
    for lookahead in (1, 3):
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=64, chunk_steps=3,
            seed=5, lookahead=lookahead)
        for request in _requests(server.config, spec, seed=9):
            server.submit(request)
        finished = server.run_until_drained()
        outs[lookahead] = {r.request_id: r.tokens for r in finished}
    assert outs[1] == outs[3]


def test_paged_block_accounting_and_reuse():
    """Blocks are reserved worst-case at admission and ALL return to
    the pool at retirement."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64, chunk_steps=4,
                                   block_size=16, total_blocks=8)
    assert server.free_blocks == 8
    [request] = _requests(server.config, [(10, 6)])
    server.submit(request)
    server.step()
    # bucket(10)=16 rows + 6 new = 22 rows -> 2 blocks of 16.
    assert server.free_blocks == 6
    assert np.count_nonzero(server.tables[0]) == 2
    server.run_until_drained()
    assert server.free_blocks == 8
    assert not server.tables.any()


def test_paged_admission_defers_until_blocks_free():
    """With a pool sized for ONE request, the second stays queued (not
    errored) until the first retires, then completes with oracle-exact
    output."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64, chunk_steps=4,
                                   block_size=16, total_blocks=2)
    requests = _requests(server.config, [(10, 6), (9, 5)])
    for request in requests:
        server.submit(request)
    server.step()
    # Only r0 admitted (2 blocks); r1 deferred in queue.
    assert server.free_blocks == 0
    assert len(server._queue) == 1
    finished = server.run_until_drained()
    assert sorted(r.request_id for r in finished) == ["r0", "r1"]
    for request in requests:
        want = reference_greedy(server, request.prompt,
                                request.max_new_tokens)
        assert request.tokens == want


def test_paged_quantized_kv_composes():
    """int8 KV pool: same requests complete; outputs match the
    quantized contiguous server exactly (identical quantized math,
    different memory shape)."""
    spec = [(6, 5), (12, 4)]
    outs = {}
    for cls in (ContinuousBatchingServer, PagedContinuousServer):
        server = cls(config_name="tiny", slots=2, max_seq=64,
                     chunk_steps=3, seed=2, quantize_kv=True)
        for request in _requests(server.config, spec, seed=4):
            server.submit(request)
        finished = server.run_until_drained()
        outs[cls.__name__] = {r.request_id: r.tokens for r in finished}
    assert outs["ContinuousBatchingServer"] == \
        outs["PagedContinuousServer"]


def test_paged_bucket_overshoot_still_admits():
    """A request whose power-of-2 prompt bucket + budget overshoots
    max_seq must still admit (reservation is capped at max_seq rows) —
    regression: this livelocked the whole queue."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64, chunk_steps=4,
                                   block_size=16)
    [request] = _requests(server.config, [(33, 30)])  # bucket 64+30>64
    server.submit(request)
    finished = server.run_until_drained(max_chunks=100)
    assert [r.request_id for r in finished] == ["r0"]
    assert request.tokens == reference_greedy(server, request.prompt, 30)


def test_paged_large_block_size_aligns_buckets():
    """block_size larger than the default 16-row bucket floor raises
    the floor so prefill buckets stay block-aligned — regression: this
    crashed mid-admission and leaked the reserved blocks."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64, chunk_steps=4,
                                   block_size=32)
    [request] = _requests(server.config, [(5, 4)])
    server.submit(request)
    finished = server.run_until_drained(max_chunks=100)
    assert finished[0].tokens == reference_greedy(server,
                                                  request.prompt, 4)
    assert server.free_blocks == server.total_blocks


def test_paged_rejects_request_exceeding_pool():
    """A request whose worst case can NEVER fit the pool fails at
    submit (error response) instead of starving the queue forever."""
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=64, chunk_steps=4,
                                   block_size=16, total_blocks=2)
    big, ok = _requests(server.config, [(33, 10), (5, 4)])
    server.submit(big)      # bucket 64 rows -> 4 blocks > 2 total
    server.submit(ok)
    finished = server.run_until_drained(max_chunks=100)
    by_id = {r.request_id: r for r in finished}
    assert by_id["r0"].error == "request_exceeds_pool"
    assert by_id["r1"].error is None
    assert by_id["r1"].tokens == reference_greedy(server, ok.prompt, 4)


# --------------------------------------------------------------------------- #
# Automatic prefix caching

def test_prefix_cache_exact_and_reuses_blocks():
    """Three requests sharing a 32-token system prefix: outputs equal
    the non-cached server exactly; the 2nd and 3rd admissions reuse
    the cached prefix blocks and skip the prefix prefill."""
    rng = np.random.default_rng(12)
    system = rng.integers(1, 1024, 32).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(1, 1024, 7).astype(np.int32)])
               for _ in range(3)]

    outs = {}
    for enabled in (False, True):
        server = PagedContinuousServer(
            config_name="tiny", slots=1, max_seq=96, chunk_steps=4,
            block_size=16, enable_prefix_cache=enabled)
        for i, prompt in enumerate(prompts):
            server.submit(DecodeRequest(request_id=f"r{i}",
                                        prompt=prompt,
                                        max_new_tokens=5))
        finished = server.run_until_drained()
        outs[enabled] = {r.request_id: r.tokens for r in finished}
        if enabled:
            # Prefix = full blocks before position len(prompt)-1 =
            # (39-1)//16 = 2 blocks; hit by requests 2 and 3.
            assert server.prefix_hits == 2
            assert server.prefix_blocks_reused == 4
    assert outs[True] == outs[False]


def test_prefix_cache_blocks_survive_retirement_and_accounting():
    """Cached blocks stay out of the free list after retirement
    (evictable, still indexed); free + evictable always equals the
    whole pool when no request is live."""
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, 1024, 33).astype(np.int32)
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=96, chunk_steps=4,
        block_size=16, enable_prefix_cache=True)
    server.submit(DecodeRequest(request_id="a", prompt=prompt,
                                max_new_tokens=4))
    server.run_until_drained()
    cached = len(server._evictable)
    assert cached == 2                      # (33-1)//16 full blocks
    assert server.free_blocks + cached == server.total_blocks
    # Same prompt again: hits the cache, nothing re-registered twice.
    server.submit(DecodeRequest(request_id="b", prompt=prompt,
                                max_new_tokens=4))
    server.run_until_drained()
    assert server.prefix_hits == 1
    assert len(server._index) == 2
    assert server.free_blocks + len(server._evictable) \
        == server.total_blocks


def test_prefix_cache_eviction_under_pressure():
    """A tiny pool: cached blocks from a retired request are evicted
    (LRU) to admit a new, different request — never deadlocks."""
    rng = np.random.default_rng(14)
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=64, chunk_steps=4,
        block_size=16, total_blocks=4, enable_prefix_cache=True)
    first = rng.integers(1, 1024, 33).astype(np.int32)
    second = rng.integers(1, 1024, 40).astype(np.int32)
    server.submit(DecodeRequest(request_id="a", prompt=first,
                                max_new_tokens=8))
    server.run_until_drained()
    assert len(server._evictable) == 2
    server.submit(DecodeRequest(request_id="b", prompt=second,
                                max_new_tokens=8))
    finished = server.run_until_drained()
    assert finished[0].error is None
    # The second prompt needed the whole pool: cached blocks evicted.
    assert len(server._index) <= 2


def test_prefix_cache_concurrent_slots_share_blocks():
    """Two LIVE slots reading the same shared prefix blocks at once:
    refcounts track both, outputs match the non-cached server, and one
    retiring early does not free blocks the other still reads."""
    rng = np.random.default_rng(16)
    system = rng.integers(1, 1024, 32).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(1, 1024, 6).astype(np.int32)])
               for _ in range(2)]
    outs = {}
    for enabled in (False, True):
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=96, chunk_steps=2,
            block_size=16, total_blocks=12,
            enable_prefix_cache=enabled)
        # Different budgets so one slot retires chunks earlier.
        for i, (prompt, new) in enumerate(zip(prompts, (3, 9))):
            server.submit(DecodeRequest(request_id=f"r{i}",
                                        prompt=prompt,
                                        max_new_tokens=new))
        server.step()       # both admitted in one pass; both live
        if enabled:
            shared = server._owned[1][:2]
            assert server._owned[0][:2] == shared
            assert all(server._refs[b] == 2 for b in shared)
        finished = server.run_until_drained()
        outs[enabled] = {r.request_id: r.tokens for r in finished}
    assert outs[True] == outs[False]


def test_prefix_cache_with_quantized_kv_matches():
    """Prefix sharing composes with the int8 KV pool: cached-path
    outputs equal the non-cached quantized server."""
    rng = np.random.default_rng(15)
    system = rng.integers(1, 1024, 32).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(1, 1024, 5).astype(np.int32)])
               for _ in range(2)]
    outs = {}
    for enabled in (False, True):
        server = PagedContinuousServer(
            config_name="tiny", slots=1, max_seq=96, chunk_steps=3,
            block_size=16, quantize_kv=True,
            enable_prefix_cache=enabled)
        for i, prompt in enumerate(prompts):
            server.submit(DecodeRequest(request_id=f"r{i}",
                                        prompt=prompt,
                                        max_new_tokens=4))
        finished = server.run_until_drained()
        outs[enabled] = {r.request_id: r.tokens for r in finished}
    assert outs[True] == outs[False]


def test_prefix_cache_evicts_leaf_first_preserving_roots():
    """Eviction under mild pressure frees chain LEAVES, not whole
    chains: after losing one block, the surviving prefix root still
    produces cache hits."""
    rng = np.random.default_rng(18)
    long_prompt = rng.integers(1, 1024, 65).astype(np.int32)  # 4 keys
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=128, chunk_steps=4,
        block_size=16, total_blocks=9, enable_prefix_cache=True)
    server.submit(DecodeRequest(request_id="a", prompt=long_prompt,
                                max_new_tokens=4))   # 8 blocks reserved
    server.run_until_drained()
    # 4 shareable blocks cached ((65-1)//16); the other 4 went free.
    assert len(server._evictable) == 4
    assert server.free_blocks == 5
    # Unrelated request needing 7 blocks (bucket 32 + 66 rows): 5 free
    # + exactly TWO leaf evictions; the chain root survives.
    other = rng.integers(1, 1024, 30).astype(np.int32)
    server.submit(DecodeRequest(request_id="b", prompt=other,
                                max_new_tokens=66))
    server.run_until_drained()
    assert len(server._evictable) >= 2 + 1   # 2 survivors + b's 1 key
    # The surviving keys are the chain's FIRST two (leaf-first evicted
    # from the tail) — the root was preserved.
    chain = server._chain_keys(long_prompt)
    assert chain[0] in server._index and chain[1] in server._index
    assert chain[3] not in server._index
    # The surviving prefix still hits (2 found, pow2 pins 2).
    server.submit(DecodeRequest(request_id="c", prompt=long_prompt,
                                max_new_tokens=4))
    server.run_until_drained()
    assert server.prefix_hits >= 1
    assert server.prefix_blocks_reused >= 2


def test_prefix_cache_pow2_truncation_leaks_nothing():
    """A 3-block shareable prefix is pow2-truncated to 2 pinned hits;
    the found-but-unpinned 3rd key must keep its original binding
    (no overwrite-leak), and the pool stays fully accounted across
    repeated admissions of the same prompt."""
    rng = np.random.default_rng(17)
    prompt = rng.integers(1, 1024, 55).astype(np.int32)  # shareable 3
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=128, chunk_steps=4,
        block_size=16, total_blocks=16, enable_prefix_cache=True)
    for round_index in range(3):
        server.submit(DecodeRequest(request_id=f"r{round_index}",
                                    prompt=prompt, max_new_tokens=4))
        server.run_until_drained()
        assert (server.free_blocks + len(server._evictable)
                == server.total_blocks), round_index
    assert server.prefix_hits == 2
    assert len(server._index) == 3          # k1,k2,k3 — no duplicates


def test_paged_pool_smaller_than_contiguous():
    """The default pool is half the contiguous reservation (the whole
    point); per-layer pool rows = (total_blocks+1) * block_size."""
    server = PagedContinuousServer(config_name="tiny", slots=4,
                                   max_seq=128, block_size=16)
    contiguous_rows = 4 * 128
    pool_rows = server.pool[0]["k"].shape[0] * server.block_size
    assert pool_rows <= contiguous_rows // 2 + server.block_size

def test_victim_selection_is_sequential_leaf_first_eviction():
    """``_select_victims`` walks the LRU order once; what it returns is
    what that many ``_evict_one`` calls would evict, in their order —
    over chains released root first, chains that share a prefix, and
    cached blocks whose child is still pinned (never reachable)."""
    from collections import OrderedDict
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   total_blocks=8,
                                   enable_prefix_cache=True)
    rng = np.random.default_rng(41)
    for trial in range(40):
        count = int(rng.integers(1, 60))
        keys = [bytes([trial, i]) for i in range(count)]
        parent = {}
        for i in range(1, count):
            if rng.random() < 0.85:         # chains, some branching
                parent[keys[i]] = keys[int(rng.integers(max(0, i - 3), i))]
        children = {}
        for child, above in parent.items():
            children[above] = children.get(above, 0) + 1
        pinned = {key for key in keys if rng.random() < 0.15}
        order = [keys[i] for i in rng.permutation(count)
                 if keys[i] not in pinned]
        server._evictable = OrderedDict(
            (key, 100 + keys.index(key)) for key in order)
        server._parent, server._children = dict(parent), dict(children)
        want = int(rng.integers(1, count + 2))
        got = server._select_victims(want)
        # The same by the definition: restart the walk for every pick.
        wanted, taken, pending = [], set(), {}
        while len(wanted) < want:
            pick = next((key for key in order if key not in taken
                         and children.get(key, 0) == pending.get(key, 0)),
                        None)
            if pick is None:
                break
            wanted.append((pick, 100 + keys.index(pick)))
            taken.add(pick)
            if pick in parent:
                pending[parent[pick]] = pending.get(parent[pick], 0) + 1
        assert got == wanted
