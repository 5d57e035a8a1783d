"""Pallas ragged paged decode-attention kernel (ops/paged_attention.py).

Everything here runs the kernel in ``interpret=True`` mode, so the suite
is CPU-green: parity vs the jnp oracle across ragged lengths, GQA group
sizes, sliding window, block-boundary edges, and int8 KV; jaxpr-level
assertions that the kv8 fallback never materializes a full-cache float
copy and that the kernel-path paged decode never gathers the pool; and
the collection-time guard that every ops/ Pallas kernel exposes an
``interpret`` knob.
"""

import ast
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from aiko_services_tpu.ops import paged_attention as pa
from aiko_services_tpu.ops.attention import attention_reference

RNG = np.random.default_rng(7)


def _quantize(rows):
    r32 = np.asarray(rows, np.float32)
    amax = np.abs(r32).max(-1)
    scale = np.where(amax == 0, 1.0, amax / 127.0)
    q = np.clip(np.round(r32 / scale[..., None]), -127, 127)
    return jnp.asarray(q, jnp.int8), jnp.asarray(scale, jnp.float32)


def _pool_case(batch=3, kv=2, group=4, hd=32, bs=16, max_blocks=4,
               quant=False, dtype=jnp.float32):
    """Random pool + shuffled (non-contiguous) block tables."""
    n_blocks = batch * max_blocks + 1
    q = jnp.asarray(RNG.standard_normal((batch, kv, group, hd)), dtype)
    k = RNG.standard_normal((n_blocks, bs, kv, hd))
    v = RNG.standard_normal((n_blocks, bs, kv, hd))
    ids = list(range(1, n_blocks))
    RNG.shuffle(ids)
    tables = jnp.asarray(
        np.array(ids[:batch * max_blocks]).reshape(batch, max_blocks),
        jnp.int32)
    if quant:
        kq, ks = _quantize(k)
        vq, vs = _quantize(v)
        return q, kq, vq, tables, dict(ks=ks, vs=vs)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype), tables,
            {})


def _parity(q, k, v, tables, positions, tol, window=None, **kv_args):
    positions = jnp.asarray(positions, jnp.int32)
    out = pa.paged_decode_attention(q, k, v, tables, positions,
                                    window=window, interpret=True,
                                    **kv_args)
    ref = pa.paged_decode_reference(q, k, v, tables, positions,
                                    window=window, **kv_args)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_kernel_matches_reference_ragged_lengths():
    q, k, v, tables, kv_args = _pool_case()
    _parity(q, k, v, tables, [0, 17, 63], 2e-5, **kv_args)


@pytest.mark.parametrize("heads,kv_heads", [(1, 1), (4, 1), (8, 1),
                                            (8, 2)])
def test_kernel_gqa_group_sizes(heads, kv_heads):
    group = heads // kv_heads
    q, k, v, tables, kv_args = _pool_case(kv=kv_heads, group=group)
    _parity(q, k, v, tables, [5, 33, 63], 2e-5, **kv_args)


@pytest.mark.parametrize("window", [None, 3, 16, 40])
def test_kernel_sliding_window(window):
    q, k, v, tables, kv_args = _pool_case()
    _parity(q, k, v, tables, [2, 30, 63], 2e-5, window=window,
            **kv_args)


def test_kernel_block_boundary_edges():
    q, k, v, tables, kv_args = _pool_case(bs=16)
    # Exactly at / adjacent to block edges, and single-block rows.
    _parity(q, k, v, tables, [15, 16, 17], 2e-5, **kv_args)
    q1, k1, v1, tables1, kv1 = _pool_case(max_blocks=1, bs=16)
    _parity(q1, k1, v1, tables1, [0, 7, 15], 2e-5, **kv1)


def test_kernel_int8_kv_parity():
    q, k, v, tables, kv_args = _pool_case(quant=True)
    _parity(q, k, v, tables, [4, 29, 63], 1e-4, **kv_args)
    _parity(q, k, v, tables, [11, 50, 63], 1e-4, window=13, **kv_args)


def test_kernel_matches_attention_reference():
    """Acceptance oracle: the kernel on a contiguous (degenerate
    iota-table) layout == plain attention_reference at q_len=1."""
    batch, kv, group, hd, bs, blocks = 2, 2, 3, 32, 16, 4
    seq = bs * blocks
    q = jnp.asarray(RNG.standard_normal((batch, kv, group, hd)),
                    jnp.float32)
    k = jnp.asarray(RNG.standard_normal((batch, seq, kv, hd)),
                    jnp.float32)
    v = jnp.asarray(RNG.standard_normal((batch, seq, kv, hd)),
                    jnp.float32)
    pool_k = k.reshape(batch * blocks, bs, kv, hd)
    pool_v = v.reshape(batch * blocks, bs, kv, hd)
    tables = (jnp.arange(batch, dtype=jnp.int32)[:, None] * blocks
              + jnp.arange(blocks, dtype=jnp.int32)[None, :])
    positions = jnp.full((batch,), seq - 1, jnp.int32)
    for window in (None, 11):
        out = pa.paged_decode_attention(q, pool_k, pool_v, tables,
                                        positions, window=window,
                                        interpret=True)
        # attention_reference layout: (batch, heads, len, hd).
        q_r = q.reshape(batch, kv * group, 1, hd)
        k_r = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1)
        v_r = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
        ref = attention_reference(q_r, k_r, v_r, causal=True,
                                  window=window)
        np.testing.assert_allclose(
            np.asarray(out.reshape(batch, kv * group, hd)),
            np.asarray(ref[:, :, 0]), atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------- #
# jaxpr-level assertions


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


def test_kv8_decode_never_materializes_full_cache(monkeypatch):
    """The kv8 regression fix: no convert_element_type anywhere in the
    quantized decode program turns a FULL-cache int8 buffer into
    floats (dequantization runs one span at a time)."""
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "reference")
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    batch, max_seq = 2, 64
    params = llama.init_params(config, jax.random.PRNGKey(0))
    cache = llama.init_cache(config, batch, max_seq, quantize_kv=True)
    token = jnp.zeros((batch, 1), jnp.int32)
    positions = jnp.full((batch,), 3, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda t, c, p: llama._decode_core_ragged(params, t, c, p,
                                                  config))(
        token, cache, positions)
    full_shape = tuple(cache[0]["k"].shape)
    offenders = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "convert_element_type"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) == full_shape
        and eqn.invars[0].aval.dtype == jnp.int8
        and jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.floating)]
    assert not offenders, (
        f"kv8 decode materializes a full-cache float copy: {offenders}")


def test_kernel_paged_decode_path_never_gathers_pool(monkeypatch):
    """With the kernel dispatched, steady-state paged decode walks the
    block table in the kernel — the program contains NO gather whose
    operand is the pool (the gather-then-attend bucket is gone)."""
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "interpret")
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    batch, bs, max_blocks = 2, 16, 4
    n_blocks = batch * max_blocks + 1
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, n_blocks, bs)
    tables = (jnp.arange(batch, dtype=jnp.int32)[:, None] * max_blocks
              + jnp.arange(max_blocks, dtype=jnp.int32)[None, :] + 1)
    token = jnp.zeros((batch, 1), jnp.int32)
    positions = jnp.full((batch,), 3, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda t, pl_, p: llama._decode_core_paged(
            params, t, pl_, tables, p, config))(token, pool, positions)
    pool_shape = tuple(pool[0]["k"].shape)
    offenders = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert not offenders, (
        f"kernel-path paged decode still gathers the pool: {offenders}")


def test_reference_paged_decode_path_does_gather(monkeypatch):
    """Control for the test above: the reference path DOES gather —
    proving the jaxpr probe can see the gather it asserts away."""
    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "reference")
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    batch, bs, max_blocks = 2, 16, 4
    n_blocks = batch * max_blocks + 1
    params = llama.init_params(config, jax.random.PRNGKey(0))
    pool = llama.init_paged_cache(config, n_blocks, bs)
    tables = (jnp.arange(batch, dtype=jnp.int32)[:, None] * max_blocks
              + jnp.arange(max_blocks, dtype=jnp.int32)[None, :] + 1)
    token = jnp.zeros((batch, 1), jnp.int32)
    positions = jnp.full((batch,), 3, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda t, pl_, p: llama._decode_core_paged(
            params, t, pl_, tables, p, config))(token, pool, positions)
    pool_shape = tuple(pool[0]["k"].shape)
    gathers = [
        eqn for eqn in _iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "gather"
        and tuple(getattr(eqn.invars[0].aval, "shape", ())) ==
        pool_shape]
    assert gathers, "reference paged decode should gather the pool"


# --------------------------------------------------------------------------- #
# End-to-end: llama decode through the kernel == through the oracle


@pytest.mark.parametrize("quantize_kv", [False, True])
def test_llama_decode_kernel_vs_reference(monkeypatch, quantize_kv):
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS["tiny"]
    batch, max_seq = 2, 64
    params = llama.init_params(config, jax.random.PRNGKey(1))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (batch, 8), 1,
                                config.vocab_size)

    def greedy(mode):
        monkeypatch.setenv("AIKO_DECODE_ATTENTION", mode)
        cache = llama.init_cache(config, batch, max_seq,
                                 quantize_kv=quantize_kv)
        logits, cache = llama.prefill(params, prompt, cache, config)
        token = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        positions = jnp.full((batch,), 8, jnp.int32)
        out = []
        for _ in range(3):
            logits, cache = llama._decode_core_ragged(
                params, token, cache, positions, config)
            token = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
            out.append(np.asarray(token))
            positions = positions + 1
        return np.concatenate(out, axis=1)

    np.testing.assert_array_equal(greedy("reference"),
                                  greedy("interpret"))


# --------------------------------------------------------------------------- #
# Guards


def test_every_ops_pallas_kernel_exposes_interpret_knob():
    """Collection-time guard: any ops/ function that issues a
    pallas_call must take an ``interpret`` argument, so every kernel
    stays CPU-testable."""
    ops_dir = (pathlib.Path(__file__).resolve().parent.parent
               / "aiko_services_tpu" / "ops")
    offenders = []
    for path in sorted(ops_dir.glob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            calls_pallas = any(
                isinstance(sub, ast.Attribute)
                and sub.attr == "pallas_call"
                for sub in ast.walk(node))
            if not calls_pallas:
                continue
            args = node.args
            names = [a.arg for a in (args.args + args.kwonlyargs)]
            if "interpret" not in names:
                offenders.append(f"{path.name}:{node.name}")
    assert not offenders, (
        f"Pallas kernels without an interpret knob: {offenders}")


def test_serving_stats_decode_attention_counters():
    from aiko_services_tpu.orchestration.continuous import (
        ContinuousBatchingServer, DecodeRequest)
    from aiko_services_tpu.orchestration.serving import (
        serving_telemetry)
    server = ContinuousBatchingServer(config_name="tiny", slots=2,
                                      max_seq=64, chunk_steps=4)
    server.submit(DecodeRequest(
        request_id="r0",
        prompt=np.arange(1, 9, dtype=np.int32),
        max_new_tokens=4))
    server.run_until_drained()
    stats = server.stats()
    assert stats["decode_attention_path"] in ("kernel", "reference")
    assert stats["decode_blocks_read"] > 0
    assert stats["blocks_read_per_step"] > 0
    telemetry = serving_telemetry(stats)
    assert telemetry["decode_attention_path"] == \
        stats["decode_attention_path"]
    assert telemetry["blocks_read_per_step"] == pytest.approx(
        stats["blocks_read_per_step"], abs=0.01)
