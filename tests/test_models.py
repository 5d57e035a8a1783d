"""Model + ops numeric tests (CPU, tiny configs; 8 virtual devices for
sharding)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aiko_services_tpu.ops.attention import (
    attention_reference, flash_attention,
)
from aiko_services_tpu.parallel import make_mesh, ring_attention_sharded
from aiko_services_tpu.models import llama


def test_flash_attention_matches_reference_interpret():
    key = jax.random.PRNGKey(0)
    q, k, v = [jax.random.normal(s, (2, 4, 128, 64), jnp.float32)
               for s in jax.random.split(key, 3)]
    for causal in (True, False):
        ref = attention_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=64, block_k=64)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_ring_attention_matches_reference():
    mesh = make_mesh(sp=8)
    key = jax.random.PRNGKey(1)
    q, k, v = [jax.random.normal(s, (1, 2, 256, 32), jnp.float32)
               for s in jax.random.split(key, 3)]
    for causal in (True, False):
        ref = attention_reference(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, axis="sp",
                                     causal=causal)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_ring_attention_gqa_native_matches_reference():
    """GQA ring: q has 4x the kv heads; only kv heads rotate, output
    equals the repeated-K/V dense reference."""
    mesh = make_mesh(sp=8)
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (2, 8, 128, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 128, 32), jnp.float32)
    for causal in (True, False):
        ref = attention_reference(q, jnp.repeat(k, 4, axis=1),
                                  jnp.repeat(v, 4, axis=1),
                                  causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, axis="sp",
                                     causal=causal)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


@pytest.fixture(scope="module")
def tiny():
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    return config, params


def test_llama_forward_shapes(tiny):
    config, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.forward(params, tokens, config, use_flash=False)
    assert logits.shape == (2, 16, config.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_llama_decode_matches_forward(tiny):
    """prefill + decode_step must agree with the full forward pass — the
    KV-cache path is numerically the same computation."""
    config, params = tiny
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (1, 12), 0, config.vocab_size)
    full = llama.forward(params, tokens, config, use_flash=False)

    prompt, rest = tokens[:, :8], tokens[:, 8:]
    cache = llama.init_cache(config, batch=1, max_seq=32)
    logits, cache = llama.prefill(params, prompt, cache, config)
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), np.asarray(full[:, 7]),
        rtol=2e-2, atol=2e-2)
    for step in range(rest.shape[1]):
        token = rest[:, step:step + 1]
        index = jnp.int32(8 + step)
        logits, cache = llama.decode_step(params, token, cache, index,
                                          config)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, 8 + step]),
            rtol=2e-2, atol=2e-2)


def test_llama_tp_sharded_forward_matches(tiny):
    """Forward under a dp*tp mesh with megatron shardings must equal the
    single-device result."""
    config, params = tiny
    mesh = make_mesh(dp=2, tp=4)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                                config.vocab_size)
    expected = llama.forward(params, tokens, config, use_flash=False)

    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = llama.param_specs(config)
    sharded_params = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf,
                                          NamedSharding(mesh, spec)),
        params, specs,
        is_leaf=lambda x: isinstance(x, jnp.ndarray))
    sharded_tokens = jax.device_put(
        tokens, NamedSharding(mesh, P("dp", None)))
    out = llama.forward(sharded_params, sharded_tokens, config,
                        use_flash=False)
    # bf16 + different reduction order under sharding: allow small noise,
    # and require (near-)identical next-token decisions.
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=6e-2, atol=6e-2)
    agree = (np.asarray(out).argmax(-1) ==
             np.asarray(expected).argmax(-1)).mean()
    assert agree > 0.99


def test_mesh_spec_wildcard():
    from aiko_services_tpu.parallel import MeshSpec
    assert MeshSpec(dp=-1, tp=4).resolve(8) == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolve(8)


# --------------------------------------------------------------------------- #
# Int8 weight-only quantization

def test_int8_matmul_pallas_matches_fallback():
    from aiko_services_tpu.ops.quant import int8_matmul, quantize_int8
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    qw = quantize_int8(w)
    got = int8_matmul(x, qw["q"], qw["s"], interpret=True)
    want = (x @ (qw["q"].astype(jnp.float32) * qw["s"]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_quantize_int8_roundtrip_error_small():
    from aiko_services_tpu.ops.quant import dequantize, quantize_int8
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(128, 128)) * 0.05, jnp.float32)
    qw = quantize_int8(w)
    err = np.abs(np.asarray(dequantize(qw, jnp.float32)) - np.asarray(w))
    # Max error is half a quantization bucket: scale/2 per column.
    assert err.max() <= float(np.asarray(qw["s"]).max())


def test_llama_quantized_forward_close(tiny):
    """Quantized forward vs the SAME dequantized weights run dense —
    isolates kernel correctness from quantization error."""
    from aiko_services_tpu.ops.quant import dequantize, is_quantized
    config, params = tiny
    qparams = llama.quantize_params(params)
    deq = jax.tree_util.tree_map(
        lambda leaf: dequantize(leaf, config.dtype)
        if is_quantized(leaf) else leaf,
        qparams, is_leaf=is_quantized)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    got = llama.forward(qparams, tokens, config)
    want = llama.forward(deq, tokens, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


def test_llama_quantized_decode_runs(tiny):
    config, dense = tiny
    params = llama.quantize_params(dense)
    tokens = jnp.zeros((2, 16), jnp.int32)
    cache = llama.init_cache(config, 2, 64)
    logits, cache = llama.prefill(params, tokens, cache, config)
    token = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    generated, _ = llama.generate_tokens(
        params, token, cache, jnp.int32(16), 8, config)
    assert generated.shape == (2, 8)
    assert np.isfinite(np.asarray(logits)).all()


# --------------------------------------------------------------------------- #
# Int4 weight-only quantization (nibble-packed, grouped scales)

def test_quantize_int4_roundtrip_error_small():
    from aiko_services_tpu.ops.quant import dequantize_int4, quantize_int4
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(256, 128)) * 0.05, jnp.float32)
    qw = quantize_int4(w, group_size=128)
    assert qw["q4"].shape == (128, 128) and qw["q4"].dtype == jnp.int8
    assert qw["s"].shape == (2, 128)
    err = np.abs(np.asarray(dequantize_int4(qw, jnp.float32))
                 - np.asarray(w))
    # Max error is half a bucket: group scale / 2.
    assert err.max() <= float(np.asarray(qw["s"]).max())


def test_int4_matmul_fallback_matches_dequantized_dense():
    from aiko_services_tpu.ops.quant import (
        dequantize_int4, int4_matmul, quantize_int4,
    )
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)  # m > 64
    qw = quantize_int4(w)
    got = int4_matmul(x, qw["q4"], qw["s"])
    want = x @ dequantize_int4(qw, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_int4_matmul_pallas_matches_fallback():
    from aiko_services_tpu.ops.quant import (
        dequantize_int4, int4_matmul, quantize_int4,
    )
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    qw = quantize_int4(w, group_size=128)
    got = int4_matmul(x, qw["q4"], qw["s"], interpret=True)
    # Interpret mode computes in f32 (CPU has no bf16 dot); on TPU the
    # kernel feeds the MXU bf16 weights — within the same tolerance.
    want = x @ dequantize_int4(qw, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_llama_int4_forward_close(tiny):
    """Int4-quantized forward vs the SAME dequantized weights run dense
    — isolates the matmul paths from quantization error."""
    from aiko_services_tpu.ops.quant import (
        dequantize, dequantize_int4, is_quantized, is_quantized_int4,
    )
    config, params = tiny
    qparams = llama.quantize_params(params, bits=4)

    def deq(leaf):
        if is_quantized_int4(leaf):
            return dequantize_int4(leaf, config.dtype)
        if is_quantized(leaf):
            return dequantize(leaf, config.dtype)
        return leaf
    dense = jax.tree_util.tree_map(deq, qparams, is_leaf=is_quantized)
    tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    got = llama.forward(qparams, tokens, config)
    want = llama.forward(dense, tokens, config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


def test_llama_int4_decode_runs(tiny):
    config, dense = tiny
    params = llama.quantize_params(dense, bits=4)
    tokens = jnp.zeros((2, 16), jnp.int32)
    cache = llama.init_cache(config, 2, 64)
    logits, cache = llama.prefill(params, tokens, cache, config)
    token = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    generated, _ = llama.generate_tokens(
        params, token, cache, jnp.int32(16), 8, config)
    assert generated.shape == (2, 8)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_int4_moe_forward_runs():
    """bits=4 must compose with MoE configs: the 2-D router quantizes
    to {"q4","s"} and moe_ffn must dispatch it to int4_matmul."""
    config = llama.CONFIGS["moe_tiny"]
    params = llama.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(0)), bits=4)
    assert "q4" in params["layers"][0]["moe"]["router"]
    logits = llama.forward(params, jnp.zeros((1, 8), jnp.int32), config)
    assert np.isfinite(np.asarray(logits)).all()


# --------------------------------------------------------------------------- #
# Model-level sequence parallelism (ring attention inside the forward)

def test_forward_sequence_parallel_matches_plain(tiny):
    """The whole-MODEL sp forward (ring attention per layer over an
    sp=8 mesh, GQA repeated per shard) must match the single-device
    forward."""
    config, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 64),
                                0, config.vocab_size, jnp.int32)
    want = llama.forward(params, tokens, config, use_flash=False)
    mesh = make_mesh(sp=8)
    got = llama.forward_sequence_parallel(params, tokens, config, mesh)
    # bf16 activations accumulate in different orders across the ring;
    # logits of magnitude ~2 land within a few centi-units.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=4e-2)


def test_forward_sequence_parallel_ulysses_matches_plain(tiny):
    """The Ulysses (all-to-all) variant of the sp forward must match
    the single-device forward (tiny has 4 heads -> sp=4 mesh)."""
    config, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(10), (2, 32),
                                0, config.vocab_size, jnp.int32)
    want = llama.forward(params, tokens, config, use_flash=False)
    mesh = make_mesh(dp=2, sp=4)
    got = llama.forward_sequence_parallel(params, tokens, config, mesh,
                                          attention="ulysses")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=4e-2)
    with pytest.raises(ValueError, match="divisible"):
        llama.forward_sequence_parallel(
            params, jax.random.randint(jax.random.PRNGKey(0), (1, 64),
                                       0, 10, jnp.int32),
            config, make_mesh(sp=8), attention="ulysses")


def test_forward_sequence_parallel_ulysses_kv_native(tiny):
    """When kv heads divide the sp size the all-to-all moves only the
    kv heads (repeat happens locally after the scatter) — output still
    matches the plain forward."""
    config, params = tiny                     # 4 heads, 2 kv heads
    tokens = jax.random.randint(jax.random.PRNGKey(11), (2, 32),
                                0, config.vocab_size, jnp.int32)
    want = llama.forward(params, tokens, config, use_flash=False)
    mesh = make_mesh(dp=4, sp=2)              # kv 2 % sp 2 == 0
    got = llama.forward_sequence_parallel(params, tokens, config, mesh,
                                          attention="ulysses")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=4e-2)


def test_forward_sequence_parallel_sliding_window_ring():
    """SP × sliding window (the Mistral-class long-context composition):
    ring attention with global-position window masking must match the
    single-device windowed forward — at seq 64 >> window 16 the mask
    crosses several shard boundaries of the sp=8 mesh AND whole shards
    fall below the window (exercising the dead-shard skip)."""
    config = llama.CONFIGS["mistral_tiny"]   # window 16
    params = llama.init_params(config, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 64),
                                0, config.vocab_size, jnp.int32)
    want = llama.forward(params, tokens, config, use_flash=False)
    got = llama.forward_sequence_parallel(params, tokens, config,
                                          make_mesh(sp=8))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=4e-2)


def test_forward_sequence_parallel_sliding_window_ulysses():
    """Ulysses variant of SP × sliding window: after the head scatter
    the full sequence is local, so windowed masking must be globally
    correct with no offset bookkeeping."""
    config = llama.CONFIGS["mistral_tiny"]   # 4 heads, window 16
    params = llama.init_params(config, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(13), (2, 64),
                                0, config.vocab_size, jnp.int32)
    want = llama.forward(params, tokens, config, use_flash=False)
    got = llama.forward_sequence_parallel(params, tokens, config,
                                          make_mesh(dp=2, sp=4),
                                          attention="ulysses")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-2, atol=4e-2)


def test_sp_prefill_decode_handoff(tiny):
    """SP-prefill → decode handoff: prefill sharded over sp=8 into a
    replicated cache, then greedy-decode single-program from the
    gathered cache — tokens must exactly match the plain prefill +
    decode path."""
    config, params = tiny
    seq, new = 64, 24
    tokens = jax.random.randint(jax.random.PRNGKey(14), (2, seq),
                                0, config.vocab_size, jnp.int32)
    # Oracle: plain single-program prefill + decode.
    cache = llama.init_cache(config, 2, seq + new + 8)
    logits, cache = llama.prefill(params, tokens, cache, config)
    first = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    want, _ = llama.generate_tokens(params, first, cache,
                                    jnp.int32(seq), new, config)
    # SP prefill over the mesh, then the identical decode tail.
    mesh = make_mesh(sp=8)
    cache_sp = llama.init_cache(config, 2, seq + new + 8)
    logits_sp, cache_sp = llama.prefill_sequence_parallel(
        params, tokens, cache_sp, config, mesh)
    np.testing.assert_allclose(np.asarray(logits_sp),
                               np.asarray(logits[:, -1]),
                               rtol=3e-2, atol=4e-2)
    first_sp = logits_sp.argmax(-1).astype(jnp.int32)[:, None]
    got, _ = llama.generate_tokens(params, first_sp, cache_sp,
                                   jnp.int32(seq), new, config)
    assert (np.asarray(got) == np.asarray(want)).mean() >= 0.95


def test_sp_prefill_decode_handoff_windowed_rolling():
    """The full long-context composition: SP-windowed prefill (ring)
    into a ROLLING (ring-buffer) cache, then windowed decode from the
    wrapped cache — must track the full-cache windowed oracle."""
    config = llama.CONFIGS["mistral_tiny"]   # window 16
    params = llama.init_params(config, jax.random.PRNGKey(5))
    seq, new = 64, 16
    tokens = jax.random.randint(jax.random.PRNGKey(15), (1, seq),
                                0, config.vocab_size, jnp.int32)
    cache = llama.init_cache(config, 1, seq + new + 8)
    logits, cache = llama.prefill(params, tokens, cache, config)
    first = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    want, _ = llama.generate_tokens(params, first, cache,
                                    jnp.int32(seq), new, config)
    mesh = make_mesh(sp=8)
    rolling = llama.init_cache(config, 1, rolling=True)
    logits_sp, rolling = llama.prefill_sequence_parallel(
        params, tokens, rolling, config, mesh)
    np.testing.assert_allclose(np.asarray(logits_sp),
                               np.asarray(logits[:, -1]),
                               rtol=3e-2, atol=4e-2)
    first_sp = logits_sp.argmax(-1).astype(jnp.int32)[:, None]
    got, _ = llama.generate_tokens(params, first_sp, rolling,
                                   jnp.int32(seq), new, config)
    assert (np.asarray(got) == np.asarray(want)).mean() >= 0.9


# --------------------------------------------------------------------------- #
# Sliding-window attention (Mistral-class)

def test_flash_attention_sliding_window_matches_reference():
    """Windowed flash kernel (two-sided block skipping) must equal the
    windowed jnp reference at shapes that exercise skipping on both
    sides of the band, incl. GQA."""
    from aiko_services_tpu.ops.attention import (
        attention_reference, flash_attention,
    )
    key = jax.random.PRNGKey(11)
    for (h, kv, q_len, k_len, window) in [
            (4, 4, 512, 512, 128),     # interior blocks fully skipped
            (4, 2, 384, 384, 128),     # GQA + window
            (2, 2, 256, 256, 300),     # window wider than seq = causal
            (2, 2, 128, 512, 128),     # q shorter than k (suffix)
    ]:
        ks = jax.random.split(jax.random.fold_in(key, window + q_len), 3)
        q = jax.random.normal(ks[0], (2, h, q_len, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, kv, k_len, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, kv, k_len, 64), jnp.float32)
        group = h // kv
        ref = attention_reference(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            causal=True, window=window)
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_mistral_window_decode_matches_forward():
    """Cached decode with sliding-window masking must reproduce the
    full-sequence forward logits at every step PAST the window edge
    (teacher-forced), proving both paths apply the same window."""
    config = llama.CONFIGS["mistral_tiny"]   # window 16
    params = llama.init_params(config, jax.random.PRNGKey(3))
    seq = 24                                  # > window
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, seq),
                                0, config.vocab_size, jnp.int32)
    full = llama.forward(params, tokens, config, use_flash=False)

    cache = llama.init_cache(config, 1, 64)
    _, cache = llama.prefill(params, tokens[:, :8], cache, config)
    for pos in range(8, seq):
        logits, cache = llama.decode_step(
            params, tokens[:, pos:pos + 1], cache, jnp.int32(pos),
            config)
        np.testing.assert_allclose(
            np.asarray(logits[0, -1]), np.asarray(full[0, pos]),
            rtol=4e-2, atol=4e-2)


def test_rolling_cache_matches_full_cache_windowed_decode():
    """Ring-buffer cache (rows = window) must reproduce the full-cache
    windowed decode exactly past several wraparounds: greedy tokens
    equal, logits match to float tolerance (row permutation only
    changes summation order of exact-zero masked terms)."""
    config = llama.CONFIGS["mistral_tiny"]   # window 16
    params = llama.init_params(config, jax.random.PRNGKey(3))
    seq = 24
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, seq),
                                0, config.vocab_size, jnp.int32)

    outs = {}
    for rolling in (False, True):
        cache = llama.init_cache(config, 1, 96, rolling=rolling)
        if rolling:
            assert cache[0]["k"].shape[1] == config.sliding_window
        logits, cache = llama.prefill(params, tokens, cache, config)
        tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        generated, _ = llama.generate_tokens(
            params, tok, cache, jnp.int32(seq), 40, config)  # wraps 2x
        outs[rolling] = (np.asarray(logits), np.asarray(generated))
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(outs[True][1], outs[False][1])


def test_rolling_cache_quantized_kv_composes():
    """int8 KV + ring buffer together: decode runs and tracks the
    full-cache quantized decode."""
    config = llama.CONFIGS["mistral_tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 20),
                                0, config.vocab_size, jnp.int32)
    outs = {}
    for rolling in (False, True):
        cache = llama.init_cache(config, 2, 80, quantize_kv=True,
                                 rolling=rolling)
        logits, cache = llama.prefill(params, tokens, cache, config)
        tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        generated, _ = llama.generate_tokens(
            params, tok, cache, jnp.int32(20), 24, config)
        outs[rolling] = np.asarray(generated)
    assert (outs[True] == outs[False]).mean() >= 0.9


def test_rolling_cache_requires_window(tiny):
    config, _ = tiny
    with pytest.raises(ValueError, match="sliding_window"):
        llama.init_cache(config, 1, 64, rolling=True)


def test_prefill_chunk_rejects_rolling_cache_for_wide_chunks():
    """Chunked prefill with K > 1 on a ring-buffer cache would slab-
    write rows still inside earlier chunk queries' windows (silently
    wrong logits) — it must refuse loudly; K=1 stays supported and
    matches generate_tokens' row layout."""
    config = llama.CONFIGS["mistral_tiny"]   # window 16
    params = llama.init_params(config, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (1, 8),
                                0, config.vocab_size, jnp.int32)
    rolling = llama.init_cache(config, 1, 64, rolling=True)
    with pytest.raises(ValueError, match="rolling"):
        llama.prefill_chunk(params, tokens, rolling, jnp.int32(0),
                            config)
    # K=1 token-by-token chunked prefill on the ring matches the
    # full-cache chunked prefill logits.
    full = llama.init_cache(config, 1, 64)
    out_full = []
    for i in range(tokens.shape[1]):
        lg, full = llama.prefill_chunk(params, tokens[:, i:i + 1],
                                       full, jnp.int32(i), config)
        out_full.append(np.asarray(lg[:, -1]))
    out_ring = []
    for i in range(tokens.shape[1]):
        lg, rolling = llama.prefill_chunk(params, tokens[:, i:i + 1],
                                          rolling, jnp.int32(i), config)
        out_ring.append(np.asarray(lg[:, -1]))
    np.testing.assert_allclose(np.asarray(out_ring),
                               np.asarray(out_full),
                               rtol=2e-2, atol=2e-2)


def test_mistral_window_changes_output_vs_full_causal():
    """Sanity: with seq > window the windowed model must NOT equal the
    unwindowed one (the mask actually bites)."""
    config = llama.CONFIGS["mistral_tiny"]
    dense_config = dataclasses.replace(config, sliding_window=None)
    params = llama.init_params(config, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 48),
                                0, config.vocab_size, jnp.int32)
    windowed = llama.forward(params, tokens, config, use_flash=False)
    full = llama.forward(params, tokens, dense_config, use_flash=False)
    assert not np.allclose(np.asarray(windowed[0, -1]),
                           np.asarray(full[0, -1]), atol=1e-3)


# --------------------------------------------------------------------------- #
# Int8 KV-cache quantization

def test_llama_kv8_decode_close_to_bf16(tiny):
    """Decode with an int8 KV cache must track the bf16-cache decode:
    per-(token, head) absmax scales keep the dequantization error under
    1% of the score scale, so short greedy horizons agree."""
    config, params = tiny
    tokens = jnp.asarray([[5, 17, 200, 3, 9, 41, 77, 8]], jnp.int32)

    outs = {}
    for quantize_kv in (False, True):
        cache = llama.init_cache(config, 1, 64, quantize_kv=quantize_kv)
        logits, cache = llama.prefill(params, tokens, cache, config)
        tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        # decode_step READS the (possibly quantized) cache — prefill
        # logits never do (prefill attends over the fresh bf16 k/v).
        step_logits, cache = llama.decode_step(params, tok, cache,
                                               jnp.int32(8), config)
        generated, _ = llama.generate_tokens(
            params, tok, cache, jnp.int32(8), 8, config)
        outs[quantize_kv] = (np.asarray(step_logits),
                            np.asarray(generated))
    ref = np.abs(outs[False][0]).max()
    assert np.abs(outs[True][0] - outs[False][0]).max() <= 0.05 * ref
    assert (outs[True][1] == outs[False][1]).mean() >= 0.75


def test_llama_kv8_chunked_prefill_matches_full(tiny):
    """The slab write (full prefill) and chunked prefill must build the
    SAME int8 cache: decoding after either yields identical tokens."""
    config, params = tiny
    tokens = jnp.asarray([[5, 17, 200, 3, 9, 41, 77, 8]], jnp.int32)

    cache_a = llama.init_cache(config, 1, 64, quantize_kv=True)
    logits_a, cache_a = llama.prefill(params, tokens, cache_a, config)

    cache_b = llama.init_cache(config, 1, 64, quantize_kv=True)
    lg1, cache_b = llama.prefill_chunk(params, tokens[:, :4], cache_b,
                                       jnp.int32(0), config)
    lg2, cache_b = llama.prefill_chunk(params, tokens[:, 4:], cache_b,
                                       jnp.int32(4), config)
    np.testing.assert_allclose(np.asarray(logits_a[:, -1]),
                               np.asarray(lg2[:, -1]),
                               rtol=4e-2, atol=4e-2)
    for la, lb in zip(cache_a, cache_b):
        # bf16 k-projection rounding differs between the 8-wide and
        # 4-wide matmuls, so codes may land one bucket apart.
        code_diff = np.abs(np.asarray(la["k"][:, :8], np.int32)
                           - np.asarray(lb["k"][:, :8], np.int32))
        assert code_diff.max() <= 1
        np.testing.assert_allclose(
            np.asarray(la["ks"][:, :8]), np.asarray(lb["ks"][:, :8]),
            rtol=1e-2)


def test_continuous_batching_kv8_matches_unquantized_cache():
    """The continuous-batching server with an int8 KV cache completes
    the same requests with closely-tracking outputs."""
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer, DecodeRequest,
    )
    prompts = [[5, 17, 200], [3, 9, 41, 77, 8, 12]]
    results = {}
    for quantize_kv in (False, True):
        server = ContinuousBatchingServer(
            "tiny", slots=2, max_seq=64, chunk_steps=4,
            quantize_kv=quantize_kv)
        for i, prompt in enumerate(prompts):
            server.submit(DecodeRequest(request_id=str(i),
                                        prompt=np.asarray(prompt),
                                        max_new_tokens=8))
        finished = server.run_until_drained()
        results[quantize_kv] = {
            r.request_id: r.tokens for r in finished}
    assert set(results[True]) == set(results[False]) == {"0", "1"}
    for rid in results[True]:
        a = np.asarray(results[True][rid])
        b = np.asarray(results[False][rid])
        assert a.shape == b.shape
        # Greedy decode: once one token differs the tails diverge, so
        # the honest closeness metric is the agreeing PREFIX length.
        disagree = np.nonzero(a != b)[0]
        prefix = disagree[0] if disagree.size else a.size
        assert prefix >= 4, (rid, a, b)


def test_llama_int4_tp_sharded_matches(tiny):
    """Int4 params sharded megatron-style over tp must reproduce the
    unsharded int4 forward (packed rows cover contiguous original rows,
    so row-parallel sharding of the packed matrix stays correct)."""
    from jax.sharding import NamedSharding
    config, dense = tiny
    qparams = llama.quantize_params(dense, bits=4)
    expected = llama.forward(qparams, jnp.zeros((2, 8), jnp.int32),
                             config, use_flash=False)
    mesh = make_mesh(dp=2, tp=4)
    specs = llama.quantized_param_specs(config, bits=4)
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(
                jax.tree_util.tree_map(lambda _: 0, qparams)))
    sharded = jax.tree.map(
        lambda leaf, spec: jax.device_put(
            leaf, NamedSharding(mesh, spec)),
        qparams, specs)
    out = llama.forward(sharded, jnp.zeros((2, 8), jnp.int32), config,
                        use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=6e-2, atol=6e-2)


# --------------------------------------------------------------------------- #
# Collective matmuls (latency-hiding TP primitives)

def test_allgather_matmul_exact():
    from aiko_services_tpu.parallel import (
        allgather_matmul_sharded, make_mesh,
    )
    mesh = make_mesh(tp=8)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    got = allgather_matmul_sharded(x, w, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_matmul_reducescatter_exact():
    from aiko_services_tpu.parallel import (
        matmul_reducescatter_sharded, make_mesh,
    )
    mesh = make_mesh(tp=8)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    got = matmul_reducescatter_sharded(x, w, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_llama_quantized_tp_sharded_matches(tiny):
    """Quantized params sharded megatron-style over tp must reproduce
    the unsharded quantized forward."""
    from jax.sharding import NamedSharding
    config, dense = tiny
    qparams = llama.quantize_params(dense)
    expected = llama.forward(qparams, jnp.zeros((2, 8), jnp.int32),
                             config, use_flash=False)
    mesh = make_mesh(dp=2, tp=4)
    specs = llama.quantized_param_specs(config)
    sharded = jax.tree.map(
        lambda leaf, spec: jax.device_put(
            leaf, NamedSharding(mesh, spec)),
        qparams, specs)
    out = llama.forward(sharded, jnp.zeros((2, 8), jnp.int32), config,
                        use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=6e-2, atol=6e-2)


def test_flash_attention_gqa_matches_reference():
    """GQA path (kv_heads < heads) via BlockSpec index mapping must
    equal the repeated-K/V reference."""
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 8, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 2, 128, 64), jnp.float32)
    for causal in (True, False):
        ref = attention_reference(q, jnp.repeat(k, 4, axis=1),
                                  jnp.repeat(v, 4, axis=1),
                                  causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True,
                              block_q=64, block_k=64)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


# --------------------------------------------------------------------------- #
# Mixture-of-Experts + expert parallelism

def test_moe_matches_per_token_reference():
    from aiko_services_tpu.models import moe
    config = moe.MoEConfig(d_model=32, d_ff=64, n_experts=4, top_k=2,
                           dtype=jnp.float32)
    params = moe.init_moe_params(config, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32), jnp.float32)
    got = np.asarray(moe.moe_ffn(params, x, config))
    want = moe.moe_ffn_reference(params, x, config)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_moe_ep_sharded_matches_unsharded():
    from jax.sharding import NamedSharding
    from aiko_services_tpu.models import moe
    config = moe.MoEConfig(d_model=32, d_ff=64, n_experts=8, top_k=2,
                           dtype=jnp.float32)
    params = moe.init_moe_params(config, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32), jnp.float32)
    expected = np.asarray(moe.moe_ffn(params, x, config))
    mesh = make_mesh(ep=8)
    specs = moe.moe_param_specs()
    sharded = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf,
                                          NamedSharding(mesh, spec)),
        params, specs, is_leaf=lambda s: isinstance(s, jnp.ndarray))
    got = np.asarray(moe.moe_ffn(sharded, x, config))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-4)


def test_moe_capacity_drop_passthrough():
    """Tokens over capacity get zero combine weight (residual handles
    them); output must stay finite and bounded."""
    from aiko_services_tpu.models import moe
    config = moe.MoEConfig(d_model=16, d_ff=32, n_experts=2, top_k=1,
                           capacity_factor=0.25, dtype=jnp.float32)
    params = moe.init_moe_params(config, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 16), jnp.float32)
    out = np.asarray(moe.moe_ffn(params, x, config))
    assert np.isfinite(out).all()
    # Most tokens dropped at capacity_factor=0.25: many rows exactly 0.
    zero_rows = (np.abs(out[0]).sum(-1) == 0).sum()
    assert zero_rows > 0


def test_llama_moe_decode_matches_forward():
    """MoE-MLP llama: prefill + cached decode must agree with the full
    forward (same routing decisions at same hidden states)."""
    config = llama.CONFIGS["moe_tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0,
                                config.vocab_size)
    full = llama.forward(params, tokens, config, use_flash=False)
    assert bool(jnp.isfinite(full).all())
    cache = llama.init_cache(config, 1, 32)
    logits, cache = llama.prefill(params, tokens[:, :8], cache, config)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, 7]),
                               rtol=3e-2, atol=3e-2)
    for step in range(4):
        logits, cache = llama.decode_step(
            params, tokens[:, 8 + step:9 + step], cache,
            jnp.int32(8 + step), config)
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(full[:, 8 + step]),
                                   rtol=3e-2, atol=3e-2)


def test_llama_moe_quantized_forward_runs():
    """quantize_params must compose with MoE configs (router becomes
    int8; 3-D expert weights stay dense)."""
    config = llama.CONFIGS["moe_tiny"]
    params = llama.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(0)))
    logits = llama.forward(params, jnp.zeros((1, 8), jnp.int32), config,
                           use_flash=False)
    assert bool(jnp.isfinite(logits).all())


# --------------------------------------------------------------------------- #
# Pipeline parallelism (GPipe microbatching over a pp mesh axis)

def test_pipeline_parallel_matches_sequential():
    from aiko_services_tpu.parallel import (
        pipeline_apply_sharded, stack_stages,
    )
    rng = np.random.default_rng(7)
    n_stages, d = 8, 16

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    per_stage = [{"w": jnp.asarray(rng.normal(size=(d, d)) * 0.5,
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(d,)) * 0.1,
                                   jnp.float32)}
                 for _ in range(n_stages)]
    stages = stack_stages(per_stage)
    x = jnp.asarray(rng.normal(size=(16, d)), jnp.float32)

    expected = x
    for params in per_stage:
        expected = stage_fn(params, expected)

    mesh = make_mesh(pp=n_stages)
    for n_micro in (1, 2, 4, 8):
        got = pipeline_apply_sharded(stage_fn, stages, x, mesh,
                                     n_microbatches=n_micro)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(expected),
                                   rtol=1e-5, atol=1e-5)


def test_llama_pipeline_parallel_forward_matches(tiny):
    """pp-staged llama forward equals the plain forward (GPipe is a
    pure re-scheduling)."""
    config, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(9), (4, 16), 0,
                                config.vocab_size)
    expected = llama.forward(params, tokens, config, use_flash=False)
    mesh = make_mesh(pp=2, tp=4)   # tiny has 2 layers -> 1 per stage
    got = llama.pipeline_forward(params, tokens, config, mesh,
                                 n_microbatches=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=6e-2, atol=6e-2)
    agree = (np.asarray(got).argmax(-1) ==
             np.asarray(expected).argmax(-1)).mean()
    assert agree > 0.99


def test_quantized_specs_compose_with_moe():
    """quantize_params turns the 2-D MoE router into {"q","s"}; the spec
    tree must mirror that or any tree_map over (params, specs) raises a
    structure mismatch (ADVICE r1)."""
    from jax.sharding import NamedSharding
    config = llama.CONFIGS["moe_tiny"]
    params = llama.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(3)))
    specs = llama.quantized_param_specs(config)
    mesh = make_mesh(tp=2, ep=4)
    sharded = jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf,
                                          NamedSharding(mesh, spec)),
        params, specs)
    out = llama.forward(sharded, jnp.zeros((2, 8), jnp.int32), config,
                        use_flash=False)
    assert bool(jnp.isfinite(out).all())


def test_flash_attention_causal_skip_shapes():
    """Causal block-skipping (pl.when + clamped K/V index maps) must be
    exact at square and rectangular shapes and across block sizes."""
    key = jax.random.PRNGKey(11)
    shapes = [   # (q_len, k_len, block_q, block_k)
        (256, 256, 64, 64),
        (256, 256, 64, 128),
        (64, 256, 64, 64),     # short q over long k (decode-extend)
        (128, 128, 128, 64),
    ]
    for q_len, k_len, block_q, block_k in shapes:
        ks = jax.random.split(jax.random.fold_in(key, q_len * k_len), 3)
        q = jax.random.normal(ks[0], (1, 2, q_len, 32), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, k_len, 32), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, k_len, 32), jnp.float32)
        ref = attention_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True,
                              block_q=block_q, block_k=block_k)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5, \
            (q_len, k_len, block_q, block_k)


def test_ring_attention_causal_skip_matches():
    """Ring attention with causal step-skipping stays exact (the
    skipped steps are exactly the fully-masked ones)."""
    mesh = make_mesh(sp=8)
    key = jax.random.PRNGKey(12)
    q, k, v = [jax.random.normal(s, (2, 2, 128, 16), jnp.float32)
               for s in jax.random.split(key, 3)]
    ref = attention_reference(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_llama_moe_decode_matches_forward():
    """MoE (EP) cached decode must agree with the full-sequence forward
    — no-drop capacity (cf = E/k) makes routing order-independent, so
    the KV-cache path is the same computation (VERDICT r1 #10)."""
    config = llama.CONFIGS["moe_tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(21))
    tokens = jax.random.randint(jax.random.PRNGKey(22), (2, 10), 0,
                                config.vocab_size)
    full = llama.forward(params, tokens, config, use_flash=False)
    cache = llama.init_cache(config, 2, 16)
    logits, cache = llama.prefill(params, tokens[:, :6], cache, config)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, 5]),
                               rtol=2e-2, atol=2e-2)
    for step in range(6, 10):
        logits, cache = llama.decode_step(params, tokens[:, step:step + 1],
                                          cache, jnp.int32(step), config)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, 9]),
                               rtol=2e-2, atol=2e-2)


def test_llama_moe_int8_generates():
    """Quantized MoE (int8 router + dense experts + int8 attention/head
    weights) runs the full prefill+scan-decode path."""
    config = llama.CONFIGS["moe_tiny"]
    params = llama.quantize_params(
        llama.init_params(config, jax.random.PRNGKey(23)))
    cache = llama.init_cache(config, 1, 24)
    logits, cache = llama.prefill(
        params, jnp.zeros((1, 8), jnp.int32), cache, config)
    token = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    generated, _ = llama.generate_tokens(params, token, cache,
                                         jnp.int32(8), 6, config)
    assert generated.shape == (1, 6)
    assert bool((np.asarray(generated) >= 0).all())


def test_ulysses_attention_matches_reference():
    """Ulysses all-to-all SP is exact vs dense attention (heads
    divisible by axis size; both causal and bidirectional)."""
    from aiko_services_tpu.parallel import ulysses_attention_sharded
    mesh = make_mesh(sp=8)
    key = jax.random.PRNGKey(31)
    q, k, v = [jax.random.normal(s, (2, 8, 128, 32), jnp.float32)
               for s in jax.random.split(key, 3)]
    for causal in (True, False):
        ref = attention_reference(q, k, v, causal=causal)
        out = ulysses_attention_sharded(q, k, v, mesh, axis="sp",
                                        causal=causal)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_ulysses_rejects_indivisible_heads():
    from aiko_services_tpu.parallel import ulysses_attention_sharded
    mesh = make_mesh(sp=8)
    key = jax.random.PRNGKey(32)
    q, k, v = [jax.random.normal(s, (1, 6, 64, 16), jnp.float32)
               for s in jax.random.split(key, 3)]
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, k, v, mesh, axis="sp")


def test_pipeline_parallel_training_grads_match():
    """The scan-based GPipe schedule is differentiable: loss and grads
    through the pp=2 pipeline match the plain single-program training
    loss/grads (same params) up to bf16 stage-boundary rounding."""
    from aiko_services_tpu.parallel.train import (
        make_pp_train_step, to_pp_params, cross_entropy,
    )
    import optax
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(40))
    tokens = jax.random.randint(jax.random.PRNGKey(41), (4, 17), 0,
                                config.vocab_size)
    mesh = make_mesh(pp=2, tp=4)

    def plain_loss(p):
        logits = llama.forward(p, tokens[:, :-1], config,
                               use_flash=False)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:][..., None], -1)
        return -jnp.mean(picked)

    plain_l, plain_g = jax.value_and_grad(plain_loss)(params)

    pp_params = to_pp_params(params, config, pp=2)
    optimizer = optax.sgd(0.0)
    step = make_pp_train_step(config, optimizer, mesh,
                              n_microbatches=2)
    opt_state = optimizer.init(pp_params)
    new_params, _, pp_l = step(pp_params, opt_state, tokens)
    assert abs(float(pp_l) - float(plain_l)) < 2e-2, (
        float(pp_l), float(plain_l))
    # Compare a few grad leaves: embed and one early/late layer weight.
    pp_l2, pp_g = jax.value_and_grad(
        lambda p: cross_entropy(
            llama.pipeline_forward(
                {"embed": p["embed"], "final_norm": p["final_norm"],
                 "lm_head": p["lm_head"], "layers": []},
                tokens[:, :-1], config, mesh, n_microbatches=2,
                stages=p["stages"]),
            tokens[:, 1:]))(pp_params)
    per_stage = config.n_layers // 2
    for stage in (0, 1):
        for j in range(per_stage):
            layer_index = stage * per_stage + j
            got = pp_g["stages"]["wq"][stage, j]
            want = plain_g["layers"][layer_index]["wq"]
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
            assert err <= 0.15 * max(scale, 1e-3), (
                layer_index, err, scale)
    err_embed = float(jnp.max(jnp.abs(
        pp_g["embed"].astype(jnp.float32)
        - plain_g["embed"].astype(jnp.float32))))
    assert err_embed < 0.2, err_embed


def test_sample_logits_top_k_top_p():
    """top_k keeps only the k best ids; top_p keeps the minimal nucleus
    (always including the best id); temperature→0 approaches argmax."""
    from aiko_services_tpu.models.llama import sample_logits
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    keys = jax.random.split(jax.random.PRNGKey(0), 200)
    # top_k=2: only ids 0/1 ever sampled.
    samples = {int(sample_logits(logits, key, 1.0, top_k=2)[0])
               for key in keys[:100]}
    assert samples <= {0, 1} and 0 in samples
    # top_p=0.6: nucleus {0.5, 0.3} -> ids 0/1.
    samples = {int(sample_logits(logits, key, 1.0, top_p=0.6)[0])
               for key in keys[100:]}
    assert samples <= {0, 1} and 0 in samples
    # Tiny temperature: effectively argmax.
    assert int(sample_logits(logits, keys[0], 1e-4)[0]) == 0
    # top_p very small: still returns the single best id.
    assert int(sample_logits(logits, keys[1], 1.0, top_p=0.01)[0]) == 0


def test_generate_tokens_sampled_with_truncation():
    config = llama.CONFIGS["tiny"]
    params = llama.init_params(config, jax.random.PRNGKey(60))
    tokens = jnp.ones((2, 8), jnp.int32)
    cache = llama.init_cache(config, 2, 32)
    logits, cache = llama.prefill(params, tokens, cache, config)
    first = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
    out, _ = llama.generate_tokens(
        params, first, cache, jnp.int32(8), 6, config,
        temperature=0.8, rng_key=jax.random.PRNGKey(61), top_k=40,
        top_p=0.95)
    assert out.shape == (2, 6)
    assert bool((out >= 0).all()) and bool(
        (out < config.vocab_size).all())


def test_llama3_70b_tp8_sharding_consistent():
    """The 70B TP=8 configuration is validated WITHOUT materializing
    80 layers: jax.eval_shape traces the forward over abstract params,
    and every param spec maps onto an 8-way tp mesh with divisible
    dimensions (the real-pod deployment contract for BASELINE config
    5's chat stage)."""
    from jax.sharding import NamedSharding
    config = llama.CONFIGS["llama3_70b"]
    specs = llama.param_specs(config)
    mesh = make_mesh(tp=8)

    # The REAL init tree, abstractly (no 70B memory, stays in sync
    # with init_params by construction).
    params = jax.eval_shape(lambda k: llama.init_params(config, k),
                            jax.random.PRNGKey(0))
    # 1. Spec tree mirrors the param tree and every sharded dim divides.
    def check(leaf, spec):
        sharding = NamedSharding(mesh, spec)
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            assert leaf.shape[dim] % mesh.shape[axis] == 0, (
                leaf.shape, spec)
        return sharding
    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    # 2. The forward traces at 70B scale (no FLOPs, no memory).
    out = jax.eval_shape(
        lambda p, t: llama.forward(p, t, config, use_flash=False),
        params, jax.ShapeDtypeStruct((1, 32), jnp.int32))
    assert out.shape == (1, 32, config.vocab_size)


def test_grad_accumulation_matches_full_batch():
    """accum_steps=4 training step == full-batch step: same loss, same
    updated params (up to f32-accumulation vs bf16 rounding)."""
    import optax
    from aiko_services_tpu.parallel.train import (
        init_train_state, make_train_step,
    )
    config = llama.CONFIGS["tiny"]
    optimizer = optax.sgd(1e-2)
    params, opt_state = init_train_state(config, jax.random.PRNGKey(70),
                                         optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(71), (8, 24), 0,
                                config.vocab_size)
    full = jax.jit(make_train_step(config, optimizer))
    accum = jax.jit(make_train_step(config, optimizer, accum_steps=4))
    p_full, _, loss_full = full(params, opt_state, tokens)
    p_accum, _, loss_accum = accum(params, opt_state, tokens)
    assert abs(float(loss_full) - float(loss_accum)) < 5e-3
    for leaf_full, leaf_accum in zip(jax.tree.leaves(p_full),
                                     jax.tree.leaves(p_accum)):
        err = float(jnp.max(jnp.abs(
            leaf_full.astype(jnp.float32)
            - leaf_accum.astype(jnp.float32))))
        assert err < 5e-3, err


def test_remat_train_step_matches():
    """remat=True recomputes activations in the backward; the numbers
    must not change."""
    import optax
    from aiko_services_tpu.parallel.train import (
        init_train_state, make_train_step,
    )
    config = llama.CONFIGS["tiny"]
    optimizer = optax.sgd(1e-2)
    params, opt_state = init_train_state(config, jax.random.PRNGKey(72),
                                         optimizer)
    tokens = jax.random.randint(jax.random.PRNGKey(73), (4, 16), 0,
                                config.vocab_size)
    plain = jax.jit(make_train_step(config, optimizer))
    remat = jax.jit(make_train_step(config, optimizer, remat=True))
    p1, _, l1 = plain(params, opt_state, tokens)
    p2, _, l2 = remat(params, opt_state, tokens)
    assert abs(float(l1) - float(l2)) < 1e-5
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))) < 1e-4


def test_int4_dispatch_envelope():
    """Kernel dispatch envelope: shapes beyond the hardware-validated
    tile classes must NOT reach the repeat kernel; the grouped-unroll
    fallback stays reachable for large-K small-m shapes within its
    VMEM budget."""
    from aiko_services_tpu.ops.quant import (
        _pick_block_int4, _pick_block_repeat,
    )
    # Validated: 8B shapes (hardware dispatch, interpret=False).
    assert _pick_block_repeat(2048, 14336, False) == 256
    assert _pick_block_repeat(7168, 4096, False) == 128
    # Unvalidated khalf classes never dispatch on hardware...
    assert _pick_block_repeat(14336, 4096, False) == 0
    assert _pick_block_repeat(4096, 4096, False) == 0   # interpolated
    # ...but interpret mode (no Mosaic compile) stays permissive.
    assert _pick_block_repeat(4096, 4096, True) == 128
    # ...but the VMEM-gated unroll fallback covers small-m decode...
    assert _pick_block_int4(8, 14336, 4096, 224) > 0
    # ...and rejects tiles whose working set cannot fit the budget.
    assert _pick_block_int4(64, 28_672, 4096, 448) == 0


def test_int4_matmul_large_k_fallback_correct():
    """A 70B-shaped K (beyond the repeat envelope) still computes
    correctly through whichever fallback the dispatch picks."""
    from aiko_services_tpu.ops.quant import (
        dequantize_int4, int4_matmul, quantize_int4,
    )
    rng = np.random.default_rng(20)
    w = jnp.asarray(rng.normal(size=(28_672, 128)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.normal(size=(8, 28_672)), jnp.bfloat16)
    qw = quantize_int4(w, 128)
    got = np.asarray(int4_matmul(x, qw["q4"], qw["s"], interpret=True),
                     np.float32)
    want = np.asarray(
        jnp.dot(x, dequantize_int4(qw, jnp.bfloat16),
                preferred_element_type=jnp.float32).astype(x.dtype),
        np.float32)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert rel < 0.02, rel


def test_jitted_modules_read_only_the_attention_mode_variables():
    """``ops/`` and ``models/`` choose a lowering from what they can
    observe (backend, shapes, ``interpret``).  The two attention mode
    variables, read by ``ops.paged_attention.kernel_mode``, are the
    only environment they consult: a switch that only a measuring rig
    sets has no place in the program."""
    import ast
    import pathlib

    import aiko_services_tpu

    package = pathlib.Path(aiko_services_tpu.__file__).parent
    allowed = {"AIKO_DECODE_ATTENTION", "AIKO_PREFILL_ATTENTION"}
    reads, modes = [], set()
    for path in sorted((package / "ops").rglob("*.py")) + \
            sorted((package / "models").rglob("*.py")):
        where = path.relative_to(package).as_posix()
        for top in ast.parse(path.read_text(), filename=str(path)).body:
            for node in ast.walk(top):
                names = {getattr(node, "attr", None),
                         getattr(node, "id", None)}
                names.update(getattr(alias, "name", None)   # imports
                             for alias in getattr(node, "names", ()))
                if names & {"environ", "environb", "getenv", "putenv"}:
                    reads.append((where, getattr(top, "name", None),
                                  node.lineno))
                if isinstance(node, ast.Call) and \
                        getattr(node.func, "id", None) == "kernel_mode":
                    modes.update(
                        arg.value if isinstance(arg, ast.Constant)
                        else ast.dump(arg) for arg in node.args)
    assert [(where, function) for where, function, _ in reads] == \
        [("ops/paged_attention.py", "kernel_mode")], reads
    assert modes == allowed
