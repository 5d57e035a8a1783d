"""Ragged paged append-attention kernel (ops/paged_prefill.py) and the
chunked mixed prefill/decode admission built on it.

Everything runs in ``interpret=True`` / CPU-reference mode, so the
suite is CPU-green: kernel-vs-oracle parity across ragged chunk
lengths, mid-block chunk tails, GQA group sizes, sliding window and
int8 KV; llama-level parity of ``prefill_append_paged`` against the
contiguous prefill; jaxpr + behavioral guards that admission never
gathers the pool or scatters a bucket back; end-to-end greedy
exactness of chunked (mixed-step) admission; and the serving/loadgen
telemetry the feature reports."""

import ast
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aiko_services_tpu.ops import paged_prefill as pp

RNG = np.random.default_rng(11)


# --------------------------------------------------------------------------- #
# Kernel vs jnp oracle parity


def _case(batch=3, kv=2, group=4, hd=32, bs=16, max_blocks=4,
          cached_blocks=(0, 1, 2), T=32, chunk_lens=(32, 17, 5),
          quant=False, pool_dtype=np.float32):
    """Random pool + shuffled block tables + a ragged append chunk.
    ``cached_blocks[b]`` full blocks of prefix are already resident for
    row ``b`` (append starts block-aligned by construction); row ``b``
    appends ``chunk_lens[b]`` real tokens inside the ``T``-padded
    slab."""
    n_blocks = batch * max_blocks + 1
    q = RNG.standard_normal((batch, T, kv, group, hd)).astype(np.float32)
    k_new = RNG.standard_normal((batch, T, kv, hd)).astype(np.float32)
    v_new = RNG.standard_normal((batch, T, kv, hd)).astype(np.float32)
    ids = list(range(1, n_blocks))
    RNG.shuffle(ids)
    tables = np.array(ids[:batch * max_blocks],
                      np.int32).reshape(batch, max_blocks)
    if quant:
        pool = dict(
            k=RNG.integers(-127, 128, (n_blocks, bs, kv, hd)).astype(
                np.int8),
            v=RNG.integers(-127, 128, (n_blocks, bs, kv, hd)).astype(
                np.int8),
            ks=np.abs(RNG.standard_normal((n_blocks, bs, kv))).astype(
                np.float32) / 127.0 + 1e-3,
            vs=np.abs(RNG.standard_normal((n_blocks, bs, kv))).astype(
                np.float32) / 127.0 + 1e-3)
    else:
        pool = dict(
            k=jnp.asarray(RNG.standard_normal((n_blocks, bs, kv, hd)),
                          pool_dtype),
            v=jnp.asarray(RNG.standard_normal((n_blocks, bs, kv, hd)),
                          pool_dtype))
    cached_lens = np.array([c * bs for c in cached_blocks], np.int32)
    return dict(q=q, k_new=k_new, v_new=v_new, pool=pool,
                tables=tables, cached_lens=cached_lens,
                chunk_lens=np.array(chunk_lens, np.int32), bs=bs)


def _run(case, path, window=None, kv_limit=None):
    """One parity arm on a FRESH pool copy (the kernel aliases the
    pool buffers in and out — reusing a consumed input would fail)."""
    pool = {key: jnp.asarray(val) for key, val in case["pool"].items()}
    args = (jnp.asarray(case["q"]), jnp.asarray(case["k_new"]),
            jnp.asarray(case["v_new"]), pool,
            jnp.asarray(case["tables"]),
            jnp.asarray(case["cached_lens"]),
            jnp.asarray(case["chunk_lens"]))
    if path == "reference":
        out, new_pool = pp.paged_prefill_reference(*args, window=window)
    else:
        out, new_pool = pp.paged_prefill_attention(*args, window=window,
                                                   interpret=True,
                                                   kv_limit=kv_limit)
    return np.asarray(out, np.float32), {
        key: np.asarray(val, np.float32) for key, val in new_pool.items()}


def _parity(case, tol, window=None, kv_limit=None):
    out_k, pool_k = _run(case, "kernel", window=window,
                         kv_limit=kv_limit)
    out_r, pool_r = _run(case, "reference", window=window)
    bs = case["bs"]
    for b in range(out_k.shape[0]):
        chunk = int(case["chunk_lens"][b])
        cached = int(case["cached_lens"][b])
        # Outputs: only the row's REAL queries (pad rows attend over
        # pad keys and are discarded by every caller).
        np.testing.assert_allclose(out_k[b, :chunk], out_r[b, :chunk],
                                   atol=tol, rtol=tol, err_msg=f"row {b}")
        # Pool content: every appended row landed identically (walk
        # the block table position by position).
        for position in range(cached, cached + chunk):
            block = int(case["tables"][b, position // bs])
            offset = position % bs
            for key in pool_k:
                np.testing.assert_allclose(
                    pool_k[key][block, offset],
                    pool_r[key][block, offset], atol=tol, rtol=tol,
                    err_msg=f"row {b} pos {position} pool[{key}]")


def test_append_matches_reference_ragged_chunks():
    _parity(_case(), 2e-5)


def test_append_mid_block_boundaries():
    """Chunks ending mid-block and one token past a block edge, over
    cached prefixes at different block counts."""
    _parity(_case(cached_blocks=(1, 2, 0), chunk_lens=(17, 16, 31)),
            2e-5)
    _parity(_case(batch=2, cached_blocks=(0, 1), T=16,
                  chunk_lens=(1, 15)), 2e-5)


@pytest.mark.parametrize("heads,kv_heads",
                         [(1, 1), (4, 1), (8, 2), (32, 2)])
def test_append_gqa_group_sizes(heads, kv_heads):
    group = heads // kv_heads
    _parity(_case(kv=kv_heads, group=group), 2e-5)


@pytest.mark.parametrize("window", [3, 16, 40])
def test_append_sliding_window(window):
    _parity(_case(), 2e-5, window=window)


def test_append_int8_kv_parity():
    _parity(_case(quant=True), 1e-3)
    _parity(_case(quant=True, cached_blocks=(2, 1, 0),
                  chunk_lens=(9, 32, 23)), 1e-3, window=19)


# The sweep takes P = 128 / block_size pool blocks (128 keys) a step
# over a tile's live band of table entries; each case below puts one
# edge of that walk under the oracle.
SWEEP_CASES = {
    # 11 live blocks: one full step of 8 and a clamped, masked step
    "band_not_multiple_of_step": dict(
        case=dict(max_blocks=12, cached_blocks=(9, 3, 0))),
    "band_of_two_full_steps": dict(
        case=dict(max_blocks=16, cached_blocks=(14, 6, 0))),
    # window 20 at 10 cached blocks: the band starts in block 8, the
    # whole first step's worth of entries is never visited
    "window_drops_leading_steps": dict(
        case=dict(max_blocks=12, cached_blocks=(10, 9, 0)), window=20),
    "window_inside_one_block": dict(
        case=dict(max_blocks=12, cached_blocks=(10, 9, 1),
                  quant=True), window=5, tol=1e-3),
    # the table is longer than the row can be: the sweep is bounded
    "kv_limit_shorter_than_table": dict(
        case=dict(max_blocks=24, cached_blocks=(6, 2, 0)), kv_limit=8),
    "row_without_tokens": dict(
        case=dict(max_blocks=12, cached_blocks=(9, 4, 0),
                  chunk_lens=(32, 0, 5))),
    "int8_row_without_tokens": dict(
        case=dict(max_blocks=12, cached_blocks=(9, 4, 0),
                  chunk_lens=(32, 0, 5), quant=True), tol=1e-3),
    # the contiguous view's geometry: one 128-key block a step
    "block_128_one_block_a_step": dict(
        case=dict(bs=128, T=128, max_blocks=3,
                  cached_blocks=(0, 1, 2), chunk_lens=(128, 70, 5))),
    "block_128_int8": dict(
        case=dict(bs=128, T=128, max_blocks=3, cached_blocks=(2, 1, 0),
                  chunk_lens=(128, 70, 5), quant=True), tol=1e-3),
    "block_64_two_blocks_a_step": dict(
        case=dict(bs=64, T=64, max_blocks=5, cached_blocks=(3, 1, 0),
                  chunk_lens=(64, 33, 5))),
    "group_16_int8": dict(
        case=dict(kv=2, group=16, max_blocks=12,
                  cached_blocks=(9, 3, 0), quant=True), tol=1e-3),
}


@pytest.mark.parametrize("name", sorted(SWEEP_CASES))
def test_append_sweep_walks_the_live_band(name):
    spec = SWEEP_CASES[name]
    _parity(_case(**spec["case"]), spec.get("tol", 2e-5),
            window=spec.get("window"), kv_limit=spec.get("kv_limit"))


@pytest.mark.parametrize("pool_dtype,q_dtype,tol", [
    ("float32", "float32", 2e-5),       # MXU_PRECISION over f32 rows
    ("bfloat16", "float32", 2e-5),      # q in three bf16 terms
    ("bfloat16", "bfloat16", 1e-2),     # q is its own single term
    ("int8", "bfloat16", 1e-2),         # the serving pair
])
def test_append_pool_and_query_dtypes(pool_dtype, q_dtype, tol):
    """One contraction per pool dtype, at f32 contract precision: the
    oracle is given the pool's values in f32, so what is compared is
    the kernel's arithmetic and (bf16 queries) its output rounding."""
    case = _case(max_blocks=12, cached_blocks=(9, 3, 0),
                 quant=pool_dtype == "int8",
                 pool_dtype=jnp.dtype(pool_dtype)
                 if pool_dtype != "int8" else np.float32)
    for key in ("q", "k_new", "v_new"):
        case[key] = jnp.asarray(case[key], q_dtype)
    out_k, _ = _run(case, "kernel")
    exact = dict(case, q=jnp.asarray(case["q"], jnp.float32))
    if pool_dtype == "bfloat16":
        exact["pool"] = {key: jnp.asarray(val, jnp.float32)
                         for key, val in case["pool"].items()}
        exact["k_new"] = jnp.asarray(case["k_new"], jnp.bfloat16)
        exact["v_new"] = jnp.asarray(case["v_new"], jnp.bfloat16)
    out_r, _ = _run(exact, "reference")
    for b in range(out_k.shape[0]):
        chunk = int(case["chunk_lens"][b])
        np.testing.assert_allclose(out_k[b, :chunk], out_r[b, :chunk],
                                   atol=tol, rtol=tol, err_msg=f"row {b}")


def test_prefill_key_blocks_counts_the_kernels_band():
    """The host's count of a slice's work is the kernel's own band:
    summed over tiles, ``last - first + 1`` of ``_live_bands``."""
    heads, group = 8, 4
    for start, width, bs, window in ((0, 256, 16, None),
                                     (768, 256, 16, None),
                                     (4096, 256, 16, 4096),
                                     (6144, 256, 16, 4096),
                                     (512, 64, 16, 100),
                                     (256, 128, 128, None)):
        q_tile = pp._q_tile_size(width, heads, 2, group)
        _, first, last = pp._live_bands(
            jnp.asarray([start], jnp.int32), width // q_tile,
            q_tile=q_tile, block_size=bs, window=window,
            kv_blocks=10**6)
        want = int(jnp.sum(last - first + 1))
        assert pp.prefill_key_blocks(start, width, bs, window,
                                     heads=heads, group=group,
                                     itemsize=2) == want
    # 256 tokens at position 768, tiles of 128: blocks 0..55 and 0..63
    assert pp.prefill_key_blocks(768, 256, 16, None, heads=32, group=4,
                                 itemsize=2) == 56 + 64


def test_append_zero_cached_equals_fresh_prefill():
    """cached_lens=0 everywhere: pure chunked self-attention (the
    first slice of every admission)."""
    _parity(_case(cached_blocks=(0, 0, 0), chunk_lens=(32, 20, 7)),
            2e-5)


# --------------------------------------------------------------------------- #
# llama-level: append prefill == contiguous prefill


def _tiny_setup(seed=1, prompt_len=32, bs=16):
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(seed))
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (1, prompt_len), 1,
        config.vocab_size), np.int32)
    n_blocks = prompt_len // bs * 2 + 1
    pool = llama.init_paged_cache(config, n_blocks, bs)
    max_blocks = prompt_len // bs
    tables = jnp.arange(1, max_blocks + 1,
                        dtype=jnp.int32)[None, :]
    return llama, config, params, prompt, pool, tables


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_prefill_append_matches_contiguous(monkeypatch, mode):
    """One-shot append admission == contiguous prefill: identical
    last-position logits AND identical KV rows in the pool."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", mode)
    llama, config, params, prompt, pool, tables = _tiny_setup()
    prompt_len = prompt.shape[1]
    logits, new_pool = llama.prefill_append_paged(
        params, jnp.asarray(prompt), pool, tables, jnp.int32(0),
        config, kv_limit=tables.shape[1])
    cache = llama.init_cache(config, 1, 64)
    logits_ref, cache_ref = llama.prefill(params, jnp.asarray(prompt),
                                          cache, config)
    np.testing.assert_allclose(
        np.asarray(logits[0, prompt_len - 1]),
        np.asarray(logits_ref[0, -1]), atol=2e-4, rtol=2e-4)
    bs = 16
    for layer in range(config.n_layers):
        for key in ("k", "v"):
            got = np.asarray(new_pool[layer][key])[1:1 + prompt_len // bs]
            got = got.reshape(prompt_len, *got.shape[2:])
            want = np.asarray(cache_ref[layer][key])[0, :prompt_len]
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5,
                                       err_msg=f"layer {layer} {key}")


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_prefill_append_two_slices_match_one_shot(monkeypatch, mode):
    """Appending 16+16 (two slices, cached_len advancing) writes the
    same pool content as the single 32-token admission — the chunked
    path's core invariant."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", mode)
    llama, config, params, prompt, pool, tables = _tiny_setup(seed=4)
    _, pool_one = llama.prefill_append_paged(
        params, jnp.asarray(prompt), pool, tables, jnp.int32(0),
        config, kv_limit=2, compute_logits=False)
    pool2 = llama.init_paged_cache(config, 5, 16)
    for start in (0, 16):
        _, pool2 = llama.prefill_append_paged(
            params, jnp.asarray(prompt[:, start:start + 16]), pool2,
            tables, jnp.int32(start), config, kv_limit=2,
            compute_logits=False)
    for layer in range(config.n_layers):
        for key in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(pool_one[layer][key])[1:3],
                np.asarray(pool2[layer][key])[1:3], atol=2e-5,
                rtol=2e-5, err_msg=f"layer {layer} {key}")


# --------------------------------------------------------------------------- #
# jaxpr + behavioral guards: admission reads/writes the pool in place


def _iter_eqns(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subjaxprs(val):
        if isinstance(val, Jaxpr):
            yield val
        elif isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, (list, tuple)):
            for item in val:
                yield from subjaxprs(item)

    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                yield from _iter_eqns(sub)


def _admission_jaxpr():
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, 9, 16)
    tables = jnp.arange(1, 5, dtype=jnp.int32)[None, :]
    tokens = jnp.ones((1, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda t, pl_, s: llama._prefill_append_core(
            params, t, pl_, tables, s, config, kv_limit=4,
            compute_logits=False))(tokens, pool, jnp.int32(0))
    return jaxpr, tuple(pool[0]["k"].shape)


def test_kernel_admission_never_gathers_pool(monkeypatch):
    """With the append kernel dispatched, the traced admission program
    contains NO gather whose operand is the pool — prefix KV is read
    in place by the kernel's block sweep, not copied out."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", "interpret")
    jaxpr, pool_shape = _admission_jaxpr()
    offenders = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert not offenders, (
        f"append admission still gathers the pool: {offenders}")


def test_reference_admission_does_gather(monkeypatch):
    """Control: the jnp fallback DOES gather the pool view — proving
    the probe above can see what it asserts away."""
    monkeypatch.setenv("AIKO_PREFILL_ATTENTION", "reference")
    jaxpr, pool_shape = _admission_jaxpr()
    gathers = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert gathers, "reference append path should gather the pool view"


def test_admission_never_calls_bucket_gather_scatter(monkeypatch):
    """Behavioral lock on the tentpole: a prefix-hit admission (the
    old gather→contiguous-prefill→scatter worst case) completes with
    the legacy bucket helpers booby-trapped — the server no longer
    copies cached blocks out or scatters a bucket back."""
    from aiko_services_tpu.models import llama
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    from .test_continuous import reference_greedy

    def _boom(*args, **kwargs):
        raise AssertionError(
            "bucket gather/scatter reached from paged admission")

    monkeypatch.setattr(llama, "paged_gather_blocks", _boom)
    monkeypatch.setattr(llama, "paged_scatter_blocks", _boom)
    rng = np.random.default_rng(21)
    system = rng.integers(1, 1024, 32).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(1, 1024, 7).astype(np.int32)])
               for _ in range(2)]
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=96, chunk_steps=4,
        block_size=16, enable_prefix_cache=True,
        chunk_prefill_tokens=0)
    for i, prompt in enumerate(prompts):
        server.submit(DecodeRequest(request_id=f"r{i}", prompt=prompt,
                                    max_new_tokens=5))
    finished = server.run_until_drained()
    assert server.prefix_hits == 1
    for request in finished:
        want = reference_greedy(server, request.prompt, 5)
        assert request.tokens == want


# --------------------------------------------------------------------------- #
# End-to-end: chunked (mixed-step) admission is exact and the default


def _submit_all(server, spec, seed):
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    rng = np.random.default_rng(seed)
    requests = []
    for i, (plen, new) in enumerate(spec):
        prompt = rng.integers(1, server.config.vocab_size,
                              plen).astype(np.int32)
        request = DecodeRequest(request_id=f"r{i}", prompt=prompt,
                                max_new_tokens=new)
        requests.append(request)
        server.submit(request)
    return requests


def test_chunked_admission_is_paged_default():
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    server = PagedContinuousServer(config_name="tiny", slots=1,
                                   max_seq=64)
    assert server.chunk_prefill_tokens == \
        PagedContinuousServer.DEFAULT_CHUNK_PREFILL_TOKENS == 256
    off = PagedContinuousServer(config_name="tiny", slots=1,
                                max_seq=64, chunk_prefill_tokens=0)
    assert off.chunk_prefill_tokens == 0


def test_chunk_width_must_align_to_blocks():
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    with pytest.raises(ValueError, match="multiple of block_size"):
        PagedContinuousServer(config_name="tiny", slots=1, max_seq=64,
                              block_size=32, chunk_prefill_tokens=16)


def test_chunked_outputs_exactly_equal_nonchunked():
    """Greedy outputs through mixed prefill/decode steps == whole-
    bucket admission == the per-request oracle, with decode live
    during the chunked prefills (slots=2 keeps a decoding slot active
    while the long prompts admit slice by slice)."""
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    from .test_continuous import reference_greedy
    spec = [(5, 6), (33, 5), (17, 4), (40, 7)]
    outs = {}
    for chunk in (0, 16):
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=96, chunk_steps=3,
            seed=6, block_size=16, chunk_prefill_tokens=chunk)
        requests = _submit_all(server, spec, seed=19)
        finished = server.run_until_drained()
        assert sorted(r.request_id for r in finished) == \
            sorted(r.request_id for r in requests)
        outs[chunk] = {r.request_id: r.tokens for r in finished}
        if chunk:
            for request in requests:
                want = reference_greedy(server, request.prompt,
                                        request.max_new_tokens)
                assert request.tokens == want, request.request_id
    assert outs[0] == outs[16]


def test_chunked_composes_with_prefix_cache_and_int8():
    """Chunked admission + prefix cache + quantized pool: outputs
    equal the non-chunked, non-cached quantized server exactly.  The
    in-flight producer walk (blocks being chunk-prefilled are cache
    MISSES until finished) keeps same-prefix streams correct."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    rng = np.random.default_rng(23)
    system = rng.integers(1, 1024, 32).astype(np.int32)
    prompts = [np.concatenate([system,
                               rng.integers(1, 1024, 9).astype(np.int32)])
               for _ in range(3)]
    outs = {}
    for chunked, cached in ((False, False), (True, True)):
        server = PagedContinuousServer(
            config_name="tiny", slots=2, max_seq=96, chunk_steps=3,
            block_size=16, quantize_kv=True,
            enable_prefix_cache=cached,
            chunk_prefill_tokens=16 if chunked else 0)
        for i, prompt in enumerate(prompts):
            server.submit(DecodeRequest(request_id=f"r{i}",
                                        prompt=prompt,
                                        max_new_tokens=5))
        finished = server.run_until_drained()
        outs[chunked] = {r.request_id: r.tokens for r in finished}
    assert outs[True] == outs[False]


def test_chunked_cancel_mid_prefill_releases_blocks():
    """Cancelling a request while its chunked prefill is in flight
    returns every block (registered prefix keys purged, not leaked)
    and the pool stays fully accounted."""
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    rng = np.random.default_rng(29)
    prompt = rng.integers(1, 1024, 40).astype(np.int32)
    server = PagedContinuousServer(
        config_name="tiny", slots=1, max_seq=96, chunk_steps=4,
        block_size=16, enable_prefix_cache=True,
        chunk_prefill_tokens=16)
    server.submit(DecodeRequest(request_id="a", prompt=prompt,
                                max_new_tokens=5))
    server.step()                     # admits; prefill still chunking
    assert server._prefilling
    assert server.cancel("a")
    assert not server._prefilling and not server._producing
    assert server.free_blocks + len(server._evictable) == \
        server.total_blocks
    # The pool is reusable: a fresh request completes normally.
    server.submit(DecodeRequest(request_id="b", prompt=prompt,
                                max_new_tokens=4))
    finished = server.run_until_drained()
    assert [r.request_id for r in finished if r.error is None] == ["b"]


def test_speculative_chunked_guard_is_gone():
    """The PR 3 spec+chunked "speculative-incompatibility guard" is
    REPLACED by the real composition: constructing a chunked-prefill
    server with a draft succeeds (spec rounds interleave with
    standalone prefill slices — exactness covered in
    tests/test_spec_paged.py), and the old guard text is gone from the
    module source.  Still-unsupported combos keep loud errors."""
    import inspect

    from aiko_services_tpu.orchestration import continuous as mod
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer)
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=64,
                                      chunk_prefill_tokens=16,
                                      draft_config_name="tiny")
    assert server._draft is not None
    assert server.chunk_prefill_tokens == 16
    source = inspect.getsource(mod)
    assert "does not compose with chunked-prefill" not in source
    assert "pass chunk_prefill_tokens=0 with a draft" not in source
    # The loud errors that REMAIN: GSPMD mesh= has no draft placement.
    with pytest.raises(ValueError, match="draft placement"):
        import jax
        from jax.sharding import Mesh
        ContinuousBatchingServer(
            config_name="tiny", slots=1, max_seq=64,
            mesh=Mesh(np.asarray(jax.devices()[:1]), ("tp",)),
            draft_config_name="tiny")


# --------------------------------------------------------------------------- #
# Telemetry + guards


def test_prefill_telemetry_counters():
    from aiko_services_tpu.orchestration.paged import (
        PagedContinuousServer)
    from aiko_services_tpu.orchestration.serving import (
        serving_telemetry)
    server = PagedContinuousServer(config_name="tiny", slots=2,
                                   max_seq=96, chunk_steps=3,
                                   block_size=16,
                                   chunk_prefill_tokens=16)
    _submit_all(server, [(33, 4), (6, 3)], seed=31)
    server.run_until_drained()
    stats = server.stats()
    assert stats["prefill_attention_path"] in ("kernel", "reference")
    assert server.counters["prefill_tokens"] >= 33 + 16
    # Slices of 16 tokens over 16-key blocks, one tile each: a slice at
    # position s visits blocks 0 .. s/16.  The 33-token prompt takes
    # three slices (1 + 2 + 3), the 6-token one a single piece.
    assert server.counters["prefill_key_blocks"] == 6 + 1
    assert stats["prefill_key_blocks"] == 7
    assert stats["prefill_tokens_per_sec"] > 0
    assert stats["prefill_queue_depth"] == 0
    telemetry = serving_telemetry(stats)
    assert telemetry["prefill_tokens_per_sec"] == \
        stats["prefill_tokens_per_sec"]
    assert telemetry["prefill_attention_path"] == \
        stats["prefill_attention_path"]
    assert "prefill_queue_depth" in telemetry


def test_load_report_ttft_tail():
    from aiko_services_tpu.tools.loadgen import LoadReport
    report = LoadReport(sent=3, completed=3, errors=0, timeouts=0,
                        elapsed_s=1.0, latencies_ms=[5.0, 6.0, 7.0],
                        ttfts_ms=[10.0, 30.0, 20.0])
    assert report.ttft_p50_ms == 20.0
    assert report.ttft_p95_ms == 30.0
    assert "ttft_p50=20.0/p95=30.0" in repr(report)
    empty = LoadReport(sent=0, completed=0, errors=0, timeouts=0,
                       elapsed_s=0.0, latencies_ms=[])
    assert empty.ttft_p95_ms == 0.0 and "ttft" not in repr(empty)


def test_append_kernel_covered_by_interpret_knob_guard():
    """ops/paged_prefill.py is inside the ops-wide AST guard's glob
    AND actually contains Pallas kernels — the guard is covering
    something real here, not vacuously passing."""
    path = (pathlib.Path(__file__).resolve().parent.parent
            / "aiko_services_tpu" / "ops" / "paged_prefill.py")
    tree = ast.parse(path.read_text())
    pallas_fns = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if any(isinstance(sub, ast.Attribute)
               and sub.attr == "pallas_call"
               for sub in ast.walk(node)):
            pallas_fns.append(node)
    assert len(pallas_fns) >= 2      # KV-append writer + attention
    for node in pallas_fns:
        names = [a.arg for a in (node.args.args
                                 + node.args.kwonlyargs)]
        assert "interpret" in names, node.name
