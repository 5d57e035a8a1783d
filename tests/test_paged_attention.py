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


@pytest.mark.parametrize("max_blocks,positions", [
    (4, [0, 17, 63]), (72, [0, 545, 1151])])
def test_kernel_matches_reference_ragged_lengths(max_blocks, positions):
    q, k, v, tables, kv_args = _pool_case(max_blocks=max_blocks)
    _parity(q, k, v, tables, positions, 2e-5, **kv_args)


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


@pytest.mark.parametrize("max_blocks,positions", [
    (4, ([4, 29, 63], [11, 50, 63])),
    (68, ([510, 529, 1087], [511, 512, 1040]))])
def test_kernel_int8_kv_parity(max_blocks, positions):
    q, k, v, tables, kv_args = _pool_case(quant=True,
                                          max_blocks=max_blocks)
    _parity(q, k, v, tables, positions[0], 1e-4, **kv_args)
    _parity(q, k, v, tables, positions[1], 1e-4, window=13, **kv_args)


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
# The iteration space: one grid step per row, a loop over the row's live
# table entries, P pool blocks an iteration.  What a per-block grid could
# not get wrong and this can: a last group that is not full, a first group
# that starts mid-way, entries the row does not own.

DTYPES = {"int8": jnp.int8, "bf16": jnp.bfloat16, "f32": jnp.float32}


def _typed_case(dtype, bs, max_blocks, batch, kv=2, group=2, hd=32):
    """Pool + shuffled tables in ``dtype``; the oracle's pools hold the
    same values in f32, so the comparison sees the kernel's arithmetic
    and not the oracle's rounding of bf16 softmax weights."""
    q, k, v, tables, kv_args = _pool_case(
        batch=batch, kv=kv, group=group, hd=hd, bs=bs,
        max_blocks=max_blocks, quant=dtype == "int8",
        dtype=DTYPES[dtype] if dtype != "int8" else jnp.float32)
    q = q.astype(jnp.float32)
    exact = (k, v) if dtype == "int8" else (k.astype(jnp.float32),
                                            v.astype(jnp.float32))
    return q, k, v, exact, tables, kv_args


def _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype,
                  window=None, oracle_tables=None):
    positions = jnp.asarray(positions, jnp.int32)
    out = pa.paged_decode_attention(q, k, v, tables, positions,
                                    window=window, interpret=True,
                                    **kv_args)
    ref = pa.paged_decode_reference(
        q, exact[0], exact[1],
        tables if oracle_tables is None else oracle_tables, positions,
        window=window, **kv_args)
    tol = 1e-4 if dtype == "int8" else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("bs", [16, 32, 128])
def test_blocks_per_iteration_follows_block_size(bs):
    """A group of copies is 128 keys whatever the block; the keys an
    iteration covers are whole groups, from what the call can see."""
    per_group = pa.blocks_per_group(bs)
    assert per_group == {16: 8, 32: 4, 128: 1}[bs]
    assert per_group * bs == pa.KEYS_PER_GROUP
    wide = pa.decode_keys_per_iteration(4096, bs, 2)
    assert wide == pa.MAX_DECODE_KEYS_PER_ITERATION == 512
    assert wide % (per_group * bs) == 0
    assert pa.decode_tiles(bs, wide) == {16: (16, 128, 512),
                                         32: (32, 128, 512),
                                         128: (128, 512)}[bs]
    assert pa.decode_tiles(bs, 128) == ((bs, 128) if bs < 128 else (128,))


@pytest.mark.parametrize("table_keys,kv,wide", [
    # What the table can hold caps the width, in whole groups.
    (64, 2, 128), (128, 1, 128), (320, 2, 384), (320, 4, 256),
    # Eight head tiles an iteration: the benchmark's cells (sdar's 4
    # kv heads, nemotron_h's 2, mistral's and mixtral's 8) ...
    (2064, 4, 256), (2304, 2, 512), (2560, 8, 128),
    # ... never past 512 keys, never under a group.
    (4096, 1, 512), (4096, 3, 256), (4096, 16, 128), (4096, 32, 128),
    (4096, 5, 128), (4096, 40, 128)])
def test_keys_per_iteration_follows_the_call(table_keys, kv, wide):
    assert pa.decode_keys_per_iteration(table_keys, 16, kv) == wide
    for form in ("per_head", "word_rows"):
        assert pa.decode_keys_per_iteration(table_keys, 16, kv,
                                            form) == wide


@pytest.mark.parametrize("table_keys,kv,bs,wide", [
    # evabyte.files' table; fewer and more heads; a table of one block;
    # the contiguous view; a block of 32 keys.
    (2944, 32, 16, 128), (4096, 8, 16, 128), (64, 32, 16, 128),
    (4096, 128, 16, 128), (2048, 16, 128, 128), (4096, 4, 32, 128)])
def test_keys_per_iteration_of_the_all_heads_form(table_keys, kv, bs, wide):
    """One query row a kv head: a pass is one group whatever the heads
    (the attend takes a tile whole, and the tile holds only copied
    keys)."""
    assert pa.decode_attend_form(1, kv, bs, jnp.int8) == "all_heads"
    assert pa.decode_keys_per_iteration(table_keys, bs, kv,
                                        "all_heads") == wide
    assert pa.decode_tiles(bs, wide)[-1] == wide


def test_prefill_kernel_keeps_a_group_of_keys_a_step():
    """The prefill kernel shares the decode kernel's helpers and not
    its width: a step's key buffers hold 128 keys."""
    from aiko_services_tpu.ops import paged_prefill as pp
    batch, T, kv, group, hd, bs, max_blocks = 1, 32, 2, 2, 16, 16, 40
    pool = {"k": jnp.zeros((max_blocks + 1, bs, kv, hd), jnp.bfloat16),
            "v": jnp.zeros((max_blocks + 1, bs, kv, hd), jnp.bfloat16)}
    q = jnp.zeros((batch, T, kv, group, hd), jnp.bfloat16)
    tables = jnp.arange(1, max_blocks + 1, dtype=jnp.int32)[None]
    jaxpr = jax.make_jaxpr(
        lambda q, pool: pp.paged_prefill_call(
            q, pool, tables, jnp.asarray([512], jnp.int32), window=None,
            sm_scale=0.25, q_tile=T, kv_blocks=max_blocks,
            interpret=True))(q, pool)
    call, = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    key_buffers = [aval.shape for aval in call.params[
        "grid_mapping"].scratch_avals if len(aval.shape) == 3
        and aval.shape[1:] == (kv, hd)]
    assert key_buffers == [(pa.KEYS_PER_GROUP, kv, hd)] * 2
    # ... while a decode call over the same table holds 512.
    assert pa.decode_keys_per_iteration(max_blocks * bs, bs, kv) == 512


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("bs", [16, 32, 128])
def test_kernel_live_blocks_not_a_multiple_of_group(bs, dtype):
    """Rows holding 1, P-1, P, P+1 and 2P+1 live blocks (P = blocks an
    iteration), each ending mid-block: the last group is clamped and
    masked, never short."""
    per_iter = pa.blocks_per_group(bs)
    counts = sorted({1, max(per_iter - 1, 1), per_iter, per_iter + 1,
                     2 * per_iter + 1})
    case = _typed_case(dtype, bs, max_blocks=2 * per_iter + 2,
                       batch=len(counts))
    positions = [n * bs - 1 - (3 * i) % bs for i, n in enumerate(counts)]
    assert [p // bs + 1 for p in positions] == counts
    q, k, v, exact, tables, kv_args = case
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("max_blocks,positions", [
    (20, [300, 0, 5, 319]), (72, [1100, 0, 5, 530])])
def test_kernel_idle_rows_beside_long_rows(dtype, max_blocks, positions):
    """A row at position 0 and an inactive-style row (zero table,
    position < block_size: what serve_chunk_paged hands over for an
    idle slot) between rows of several groups (a table of 320 keys,
    one wide tile of 384) and of several wide iterations (512)."""
    bs = 16
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=4)
    tables = tables.at[2].set(0)
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("max_blocks,positions,window", [
    (24, [383, 250, 37, 129], 40), (24, [383, 250, 37, 129], 100),
    (24, [383, 250, 37, 129], 129), (24, [383, 250, 37, 129], 200),
    (80, [1279, 900, 37, 641], 200), (80, [1279, 900, 37, 641], 530),
    (80, [1279, 900, 37, 641], 1030)])
def test_kernel_window_starts_mid_group(max_blocks, positions, window,
                                        dtype):
    """Sliding windows whose first live block is neither a multiple of
    P nor at a block edge; rows shorter than the window beside them;
    at one wide tile a table (384 keys) and at several iterations of
    512."""
    bs = 16
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=4)
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype,
                  window=window)


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("window", [None, 50])
@pytest.mark.parametrize("max_blocks,lengths", [
    (20, (0, 5 * 16 + 3, 17 * 16 - 1)),
    (70, (0, 33 * 16 + 3, 67 * 16 - 1))])
def test_kernel_never_reads_entries_past_the_row(max_blocks, lengths,
                                                 window, dtype):
    """Table entries past a row's last live block hold out-of-range
    garbage.  The interpreter clamps an out-of-range block id to the
    pool's first or last block, and both hold NaN here (no row owns
    them), so one dereference would poison the output.  The wider
    table's rows end one block into a second wide iteration and three
    blocks into a third."""
    bs, batch = 16, 3
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks + 1, batch=batch)
    n_blocks = k.shape[0]
    owned = np.asarray(tables)[:, :max_blocks]
    assert 0 not in owned
    # A block no row owns, and not one of the two poisoned ones.
    spare = int(next(b for b in np.asarray(tables)[:, max_blocks]
                     if b != n_blocks - 1))
    owned = np.where(owned == n_blocks - 1, spare, owned)

    def poison(pool):
        if pool.dtype == jnp.int8:
            return pool
        return pool.at[0].set(jnp.nan).at[n_blocks - 1].set(jnp.nan)

    k, v = poison(k), poison(v)
    kv_args = {key: poison(val) for key, val in kv_args.items()}
    positions = list(lengths)
    live = np.asarray(positions)[:, None] // bs
    column = np.arange(max_blocks)[None, :]
    garbage = np.where(column % 2, 2 ** 30, -7)
    tables = jnp.asarray(np.where(column <= live, owned, garbage),
                         jnp.int32)
    clean = jnp.asarray(np.where(column <= live, owned, spare),
                        jnp.int32)
    exact = (poison(exact[0]), poison(exact[1]))
    out = pa.paged_decode_attention(q, k, v, tables,
                                    jnp.asarray(positions, jnp.int32),
                                    window=window, interpret=True,
                                    **kv_args)
    assert np.isfinite(np.asarray(out)).all()
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype,
                  window=window, oracle_tables=clean)


@pytest.mark.parametrize("bs,kv,row", [(16, 8, 128), (32, 8, 128),
                                       (128, 8, 128), (16, 16, 128),
                                       (32, 4, 128), (16, 4, 0),
                                       (16, 2, 0), (16, 12, 0)])
def test_scale_rows_need_whole_lane_rows(bs, kv, row):
    assert pa.decode_scale_row(bs, kv) == row


def test_int8_dispatch_follows_the_scale_rows(monkeypatch):
    """Compiled, an int8 pool whose smallest block fills no lane row of
    scales goes to the reference; interpreted, and for float pools,
    the rule is kernel_serves alone."""
    monkeypatch.setattr(pa, "decode_kernel_mode", lambda: (True, False))
    assert pa.decode_dispatch(128, 8, jnp.int8) == (True, False)
    assert pa.decode_dispatch(128, 4, jnp.int8) == (False, False)
    assert pa.decode_dispatch(128, 2, jnp.bfloat16) == (True, False)
    monkeypatch.setattr(pa, "decode_kernel_mode", lambda: (True, True))
    assert pa.decode_dispatch(32, 2, jnp.int8) == (True, True)


@pytest.mark.parametrize("dtype", list(DTYPES))
def test_call_keeps_what_the_trace_pattern_needs(dtype):
    """The benchmark finds the kernel in a device trace as an unnamed
    custom call with a 3-D (batch, heads, head_dim) result in the
    query's dtype and the int32 block table first
    (benchmark/layer_metrics/decode_attn_roofline.json); and the grid
    is one step per row, so the per-block grid cannot come back
    unseen."""
    batch, max_blocks = 3, 6
    q, k, v, _, tables, kv_args = _typed_case(dtype, 16, max_blocks,
                                              batch=batch, kv=2, group=4)
    q = q.astype(jnp.bfloat16)
    positions = jnp.asarray([5, 40, 90], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, t, p, kw: pa.paged_decode_attention(
            q, k, v, t, p, interpret=True, **kw))(
        q, k, v, tables, positions, kv_args)
    calls = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call = calls[0]
    assert call.params["name"] is None
    # XLA names the custom call after the innermost computation around
    # it: the kernel's own jit, which keeps the name the decode scan's
    # body gave it (the pattern starts with ^%closed_call\.).
    around = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
              if eqn.primitive.name in ("pjit", "jit")
              and any(sub is call for sub in _iter_eqns_of(eqn))]
    assert [eqn.params["name"] for eqn in around][-1] == "closed_call"
    assert call.params["grid_mapping"].grid == (batch,)
    first = call.invars[0].aval
    assert (first.shape, first.dtype) == ((batch, max_blocks), jnp.int32)
    assert len(call.outvars) == 1
    result = call.outvars[0].aval
    assert (result.shape, result.dtype) == ((batch, 8, 32), q.dtype)
    # No XLA-level loop around the call: decode_step_ms reads the
    # decode scan as the program's one %while.
    kernel_body = {id(eqn) for eqn in _iter_eqns_of(call)}
    assert not [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
                if eqn.primitive.name in ("while", "scan")
                and id(eqn) not in kernel_body]


# --------------------------------------------------------------------------- #
# jaxpr-level assertions


def _iter_eqns_of(eqn):
    """Equations nested anywhere under ``eqn``'s sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for val in eqn.params.values():
        for item in val if isinstance(val, (list, tuple)) else [val]:
            if isinstance(item, ClosedJaxpr):
                item = item.jaxpr
            if isinstance(item, Jaxpr):
                yield from _iter_eqns(item)


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        yield from _iter_eqns_of(eqn)


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


@pytest.mark.parametrize("config_name,carried", [("tiny", 3),
                                                 ("tiny_tp", 2)])
@pytest.mark.parametrize("quantize_kv", [False, True])
def test_paged_decode_scan_kernel_vs_reference(monkeypatch, quantize_kv,
                                               config_name, carried):
    """An 8-step paged decode scan through the kernels == through the
    oracle: tokens, and the pool it hands back.  With int8 KV whose
    blocks fill lane rows of scales (``tiny_tp``: 8 kv heads) the scan
    carries the scale planes as those rows, for the decode kernel's
    copies and the append kernel's patches (llama._scan_scale_rows);
    a geometry without whole rows (``tiny``: 2 kv heads) keeps planes
    and the scatter.  At the scan's exit they are planes again, with
    the scan's writes in them."""
    import functools
    from aiko_services_tpu.models import llama
    config = llama.CONFIGS[config_name]
    slots, bs, max_blocks, steps = 3, 16, 4, 8
    n_blocks = slots * max_blocks + 1
    params = llama.init_params(config, jax.random.PRNGKey(1))
    tables = (jnp.arange(slots, dtype=jnp.int32)[:, None] * max_blocks
              + jnp.arange(max_blocks, dtype=jnp.int32)[None, :] + 1)
    tokens = jnp.asarray([[3], [7], [11]], jnp.int32)
    positions = jnp.asarray([0, 12, 30], jnp.int32)  # crosses a block
    active = jnp.asarray([True, True, False])

    def run(mode):
        # The mode is read when the scan is traced: a jit of its own
        # per mode, not the module's cached one.
        monkeypatch.setenv("AIKO_DECODE_ATTENTION", mode)
        chunk = functools.partial(llama.decode_chunk_paged.__wrapped__,
                                  num_steps=steps, config=config)
        pool = llama.init_paged_cache(config, n_blocks, bs,
                                      quantize_kv=quantize_kv)
        carried = []
        real = llama._decode_core_paged

        def spy(params, token, pool, *args, **kwargs):
            carried.append({key: buf.ndim
                            for key, buf in pool[0].items()})
            return real(params, token, pool, *args, **kwargs)

        monkeypatch.setattr(llama, "_decode_core_paged", spy)
        out = jax.jit(chunk)(params, tokens, pool, tables, positions,
                             active)
        monkeypatch.setattr(llama, "_decode_core_paged", real)
        return out, carried[0]

    (want, _, want_pos, want_pool), ref_ndim = run("reference")
    (got, _, got_pos, got_pool), ndim = run("interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_pos),
                                  np.asarray(want_pos))
    if quantize_kv:
        assert (ndim["ks"], ndim["vs"]) == (carried, carried)
        assert (ref_ndim["ks"], ref_ndim["vs"]) == (3, 3)
    for layer, want_layer in zip(got_pool, want_pool):
        assert sorted(layer) == sorted(want_layer)
        for key, buf in layer.items():
            assert buf.shape == want_layer[key].shape
    # Layer 0's K/V rows depend on the tokens alone, so both paths
    # must have written the same bytes (deeper layers see each path's
    # own rounding of the attention before them) — but for the idle
    # slot's write into scratch block 0, which the append kernel skips.
    first = 1 if quantize_kv and carried == 2 else 0
    for key, buf in got_pool[0].items():
        np.testing.assert_array_equal(
            np.asarray(buf)[first:],
            np.asarray(want_pool[0][key])[first:])


# --------------------------------------------------------------------------- #
# The append kernel: a step's token into a layer whose scales ride as rows


def _append_config(kv_heads):
    import dataclasses
    from aiko_services_tpu.models import llama
    return dataclasses.replace(llama.CONFIGS["tiny_tp"],
                               n_heads=2 * kv_heads, n_kv_heads=kv_heads)


#: name -> (block size, kv heads, pool blocks, (block, offset) a row;
#: block 0 = an idle slot's scratch write), planes carried as
APPEND_CASES = {
    "block16_kv8": (16, 8, 9, [(3, 5), (0, 1), (8, 9), (5, 0)], 2),
    "contiguous_view_block128_kv8": (
        128, 8, 5, [(2, 127), (0, 3), (4, 0), (1, 77)], 2),
    "refused_geometry_kv2_falls_to_the_scatter": (
        16, 2, 9, [(3, 5), (0, 1), (8, 9), (5, 0)], 3),
    "all_rows_idle": (16, 8, 9, [(0, 0), (0, 1), (0, 2), (0, 3)], 2),
    "last_offset_of_a_block_beside_offset_0_of_a_fresh_one": (
        16, 8, 9, [(4, 15), (7, 0), (0, 2), (6, 15)], 2),
    "kv16_eight_keys_a_row": (16, 16, 9, [(3, 5), (4, 4), (0, 1)], 2),
}


@pytest.mark.parametrize("name", sorted(APPEND_CASES))
def test_append_kernel_matches_the_scatter(monkeypatch, name):
    """``llama._paged_write_rows`` on what a decode scan carries, the
    append kernel interpreted, against the same write on planes by the
    XLA scatter (the oracle, and the path of the CPU and the
    reference): K/V rows and scale planes bit-equal outside reserved
    scratch block 0, which the kernel leaves as it was."""
    from aiko_services_tpu.models import llama
    bs, kv, n_blocks, writes, carried = APPEND_CASES[name]
    config = _append_config(kv)
    hd, batch = config.head_dim, len(writes)
    rng = np.random.default_rng(11)
    layer = {
        "k": jnp.asarray(rng.integers(-127, 128, (n_blocks, bs, kv, hd)),
                         jnp.int8),
        "v": jnp.asarray(rng.integers(-127, 128, (n_blocks, bs, kv, hd)),
                         jnp.int8),
        "ks": jnp.asarray(rng.random((n_blocks, bs, kv)), jnp.float32),
        "vs": jnp.asarray(rng.random((n_blocks, bs, kv)), jnp.float32)}
    k = jnp.asarray(rng.standard_normal((batch, 1, kv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((batch, 1, kv, hd)), jnp.float32)
    # One table entry a row: position // bs == 0 picks the block.
    tables = jnp.asarray([[block] for block, _ in writes], jnp.int32)
    positions = jnp.asarray([offset for _, offset in writes], jnp.int32)

    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "reference")
    assert llama._scan_scale_rows([layer], config)[0]["ks"].ndim == 3
    want = llama._paged_write_rows(layer, k, v, tables, positions)

    monkeypatch.setenv("AIKO_DECODE_ATTENTION", "interpret")
    scan_layer = llama._scan_scale_rows([layer], config)[0]
    assert scan_layer["ks"].ndim == scan_layer["vs"].ndim == carried
    if carried == 2:
        assert scan_layer["ks"].shape == (n_blocks * bs * kv // 128, 128)
    got = llama._rest_scale_planes(
        [llama._paged_write_rows(scan_layer, k, v, tables, positions)])[0]

    assert sorted(got) == sorted(want)
    for key, buf in got.items():
        assert buf.shape == layer[key].shape
        np.testing.assert_array_equal(np.asarray(buf)[1:],
                                      np.asarray(want[key])[1:])
        # Scratch block 0: the scatter writes idle slots' rows there,
        # the kernel leaves it as it was.
        np.testing.assert_array_equal(
            np.asarray(buf)[0],
            np.asarray((layer if carried == 2 else want)[key])[0])
    live = [block for block, _ in writes if block]
    assert (np.asarray(got["ks"]) != np.asarray(layer["ks"])).any() \
        == bool(live)


def test_append_call_keeps_its_name_and_writes_in_place():
    """What a device trace and the TPU compiler see: ONE Pallas call
    behind a jit named ``paged_decode_append`` (XLA names the custom
    call after it: not the decode kernel's ``closed_call``, whose
    roofline metric must not count this call), all four pools aliased
    to its outputs, rows walked by the kernel's own loop and no XLA-level
    loop (``decode_step_ms`` reads the decode scan as the program's one
    ``%while``)."""
    n_blocks, batch, kv, hd = 9, 4, 8, 32
    pool = {"k": jnp.zeros((n_blocks, 16, kv, hd), jnp.int8),
            "v": jnp.zeros((n_blocks, 16, kv, hd), jnp.int8),
            "ks": jnp.zeros((n_blocks, 128), jnp.float32),
            "vs": jnp.zeros((n_blocks, 128), jnp.float32)}
    rows = {"k": jnp.ones((batch, kv, hd), jnp.int8),
            "v": jnp.ones((batch, kv, hd), jnp.int8),
            "ks": jnp.ones((batch, kv), jnp.float32),
            "vs": jnp.ones((batch, kv), jnp.float32)}
    ids = jnp.asarray([3, 0, 8, 5], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *args: pa.paged_decode_append(*args, interpret=True))(
        pool, rows, ids, ids)
    calls = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call = calls[0]
    around = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
              if eqn.primitive.name in ("pjit", "jit")
              and any(sub is call for sub in _iter_eqns_of(eqn))]
    assert [eqn.params["name"] for eqn in around][-1] == \
        "paged_decode_append"
    assert call.params["grid_mapping"].grid == (1,)
    # (3 prefetched scalars, 5 row operands, then ks, vs, k, v)
    assert tuple(call.params["input_output_aliases"]) == (
        (8, 0), (9, 1), (10, 2), (11, 3))
    assert [(out.aval.shape, out.aval.dtype) for out in call.outvars] \
        == [(pool[key].shape, pool[key].dtype)
            for key in ("ks", "vs", "k", "v")]
    kernel_body = {id(eqn) for eqn in _iter_eqns_of(call)}
    loops = [eqn for eqn in _iter_eqns(jaxpr.jaxpr)
             if eqn.primitive.name in ("while", "scan")]
    assert loops and all(id(eqn) in kernel_body for eqn in loops)


@pytest.mark.parametrize("dtype,bs,kv,mode,path", [
    (jnp.int8, 16, 8, (True, False), "kernel"),
    (jnp.int8, 128, 8, (True, False), "kernel"),
    (jnp.int8, 16, 8, (True, True), "kernel"),
    (jnp.int8, 16, 2, (True, True), "scatter"),
    (jnp.int8, 16, 4, (True, False), "scatter"),
    (jnp.int8, 16, 8, (False, False), "scatter"),
    (jnp.bfloat16, 16, 8, (True, False), "none"),
])
def test_append_path_follows_the_decode_dispatch(monkeypatch, dtype, bs,
                                                 kv, mode, path):
    """The kernel appends exactly where the scans carry lane rows: the
    decode kernel dispatched (``mode``: what AIKO_DECODE_ATTENTION and
    the backend say) on an int8 pool whose blocks fill whole rows."""
    monkeypatch.setattr(pa, "decode_kernel_mode", lambda: mode)
    assert pa.decode_scale_append_path(128, kv, dtype, bs) == path
    assert pa.decode_append_dispatch(128, kv, dtype, bs) == (
        path == "kernel", mode[1])


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
    assert stats["decode_scale_append_path"] == "none"      # float cache
    assert stats["decode_blocks_read"] > 0
    assert stats["blocks_read_per_step"] > 0
    # 2 slots x (64 / 64 =) 1 block a row; never above what is there.
    assert stats["decode_table_live_share"] == pytest.approx(
        stats["decode_blocks_read"] / (stats["decode_steps"] * 2 * 1),
        abs=1e-4)
    assert 0 < stats["decode_table_live_share"] <= 1
    if stats["decode_attention_path"] == "kernel":
        assert stats["decode_iterations"] >= stats["decode_steps"]
    else:
        assert stats["decode_iterations"] == 0
    assert stats["decode_wide_iteration_share"] == pytest.approx(
        stats["decode_wide_iterations"]
        / max(stats["decode_iterations"], 1), abs=1e-4)
    telemetry = serving_telemetry(stats)
    assert telemetry["decode_attention_path"] == \
        stats["decode_attention_path"]
    assert telemetry["decode_scale_append_path"] == "none"
    assert telemetry["blocks_read_per_step"] == pytest.approx(
        stats["blocks_read_per_step"], abs=0.01)
