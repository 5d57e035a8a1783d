"""The decode kernel's width (ops/paged_attention.py): rows whose lengths
sit on every edge of the tiles an iteration can take, for each width
:func:`decode_keys_per_iteration` can return and each pool dtype, the
benchmark cells' geometries among them; and the host's count of the
kernel's iterations.  Interpreted, so CPU-green; beside
tests/test_paged_attention.py, whose cases it shares, in a file of its
own so that two workers share the interpreter's time."""

import numpy as np
import pytest

import jax.numpy as jnp

from aiko_services_tpu.ops import paged_attention as pa

from .test_paged_attention import DTYPES, _typed_case, _typed_parity

#: name -> (pool dtype, kv heads, group, block size, the width W the
#: call gets): the geometries the width function tells apart, the
#: benchmark's cells among them.
WIDTH_CASES = {
    "f32": ("f32", 2, 2, 16, 512),
    "sdar_bf16_4x32": ("bf16", 4, 32, 16, 256),
    "nemotron_bf16_2x16": ("bf16", 2, 16, 16, 512),
    "mistral_int8_8x4": ("int8", 8, 4, 16, 128),
    "int8_2x8": ("int8", 2, 8, 16, 512),
    "block32_int8": ("int8", 4, 2, 32, 256),
    "contiguous_view_block128_bf16": ("bf16", 2, 2, 128, 512),
    "bf16_one_head": ("bf16", 1, 8, 16, 512),
    "f32_16_heads": ("f32", 16, 1, 16, 128),
    # one query row a kv head, the all-heads form (evabyte.files' 32
    # int8 heads among them)
    "int8_32x1": ("int8", 32, 1, 16, 128),
    "bf16_8x1": ("bf16", 8, 1, 16, 128),
}


def _edge_case(name):
    """Rows whose lengths sit on every tile edge of the geometry's
    width W — W - 1, W, W + 1, W + 16, 2W + 17 keys, one block, and an
    idle slot (zero table, a position inside scratch block 0)."""
    dtype, kv, group, bs, wide = WIDTH_CASES[name]
    lengths = [wide - 1, wide, wide + 1, wide + 16, 2 * wide + 17, bs, 6]
    max_blocks = -(-max(lengths) // bs) + 1
    assert pa.decode_keys_per_iteration(
        max_blocks * bs, bs, kv,
        pa.decode_attend_form(group, kv, bs, DTYPES[dtype])) == wide
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=len(lengths), kv=kv, group=group,
        hd=16)
    tables = tables.at[len(lengths) - 1].set(0)
    positions = [length - 1 for length in lengths]
    return dtype, wide, (q, k, v, exact, tables, positions, kv_args)


@pytest.mark.parametrize("name", sorted(WIDTH_CASES))
def test_kernel_row_lengths_at_every_tile_edge(name):
    dtype, _, (q, k, v, exact, tables, positions, kv_args) = \
        _edge_case(name)
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype)


@pytest.mark.parametrize("name", ["f32", "sdar_bf16_4x32",
                                  "mistral_int8_8x4", "int8_2x8",
                                  "int8_32x1", "bf16_8x1"])
@pytest.mark.parametrize("window", ["W-1", "W+24", 100])
def test_kernel_window_at_every_tile_edge(name, window):
    """The same rows under sliding windows that end a band one key
    short of a wide tile, a block and a half past it, and inside one
    group."""
    dtype, wide, (q, k, v, exact, tables, positions, kv_args) = \
        _edge_case(name)
    window = {"W-1": wide - 1, "W+24": wide + 24}.get(window, window)
    _typed_parity(q, k, v, exact, tables, positions, kv_args, dtype,
                  window=window)


@pytest.mark.parametrize("name", ["f32", "sdar_bf16_4x32", "int8_2x8",
                                  "contiguous_view_block128_bf16",
                                  "int8_32x1", "bf16_8x1", "f32_16_heads"])
@pytest.mark.parametrize("tail_blocks", [9, 17, 31])
def test_wide_tile_never_weighs_another_rows_values(name, tail_blocks):
    """A wide tile reaches past the groups its pass copied, into buffer
    rows an EARLIER row's copies left: that row's own V (and V scales)
    are NaN and Inf here, in blocks it owns and attends over, and the
    rows after it, which end 9 to 31 blocks into a wide tile (of 32
    blocks, or of sdar's 16), must not see them (a masked key weighs
    zero, and zero times NaN is NaN).  With one query row a kv head
    (the all-heads form) a pass is one group, every key of which it
    copied: the rows after it end inside a group whose clamped entries
    re-copy their own last block."""
    dtype, kv, group, bs, wide = WIDTH_CASES[name]
    keys = tail_blocks * bs - 3 if bs < wide else 2 * bs - 3
    lengths = [wide, keys, 2 * wide, wide + keys, keys]
    max_blocks = -(-max(lengths) // bs) + 1
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=len(lengths), kv=kv, group=group,
        hd=16)
    owned = np.asarray(tables)
    bad = np.concatenate([owned[0, :wide // bs], owned[2, :2 * wide // bs]])
    half = len(bad) // 2
    if dtype == "int8":
        vs = kv_args["vs"].at[bad[:half]].set(jnp.nan)
        kv_args = dict(kv_args, vs=vs.at[bad[half:]].set(jnp.inf))
    else:
        v = v.at[bad[:half]].set(jnp.nan).at[bad[half:]].set(jnp.inf)
        exact = (exact[0], v.astype(jnp.float32))
    positions = jnp.asarray([length - 1 for length in lengths], jnp.int32)
    out = np.asarray(pa.paged_decode_attention(
        q, k, v, tables, positions, interpret=True, **kv_args))
    ref = np.asarray(pa.paged_decode_reference(
        q, exact[0], exact[1], tables, positions, **kv_args))
    clean = [1, 3, 4]
    assert not np.isfinite(out[[0, 2]]).any()
    assert np.isfinite(out[clean]).all()
    tol = 1e-4 if dtype == "int8" else 2e-5
    np.testing.assert_allclose(out[clean], ref[clean], atol=tol, rtol=tol)


def test_bf16_queries_are_their_own_single_term():
    """Queries that ARE bf16 values ride the score matmul as one term;
    the same values as f32 ride as three, of which two are zero: the
    results agree bit for bit.  (Queries and keys are small whole
    numbers here, so that every score is exact in whatever order the
    interpreter's CPU matmul adds 32 or 96 rows' products; the MXU's
    order does not depend on the rows.)"""
    _, _, (q, k, v, _, tables, positions, kv_args) = _edge_case(
        "sdar_bf16_4x32")
    q16 = jnp.round(2 * q).astype(jnp.bfloat16)
    k = jnp.round(2 * k.astype(jnp.float32)).astype(k.dtype)
    positions = jnp.asarray(positions, jnp.int32)
    out16 = pa.paged_decode_attention(q16, k, v, tables, positions,
                                      interpret=True, **kv_args)
    out32 = pa.paged_decode_attention(q16.astype(jnp.float32), k, v,
                                      tables, positions, interpret=True,
                                      **kv_args)
    assert out16.dtype == jnp.bfloat16 and out32.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(out16), np.asarray(out32.astype(jnp.bfloat16)))


@pytest.mark.parametrize("window", [None, 200, 700])
@pytest.mark.parametrize("kv,form", [(2, "per_head"), (4, "per_head"),
                                     (8, "per_head"), (8, "all_heads"),
                                     (32, "all_heads"), (2, "word_rows"),
                                     (4, "word_rows"), (8, "word_rows")])
def test_host_iteration_counts_are_the_kernels_loop_bounds(kv, form,
                                                           window):
    """``decode_iterations`` / ``decode_wide_iterations`` as the host
    reckons them equal a walk of the kernel's own loop — its bounds,
    the live blocks each pass holds and the tile it takes — for a
    table of mixed lengths: idle, one block, tails of a block, a group
    and more, rows at a wide tile's edges, the table's last key."""
    bs, table_blocks = 16, 129
    positions = np.array([0, 5, 15, 16, 127, 128, 300, 511, 512, 513,
                          545, 639, 640, 1023, 1024, 1500, 2063])
    wide = pa.decode_keys_per_iteration(table_blocks * bs, bs, kv, form)
    tiles = pa.decode_tiles(bs, wide)
    per_pass = wide // bs
    want_all = want_wide = 0
    for pos in positions:
        first, last, iterations = (int(x) for x in pa.decode_loop_bounds(
            jnp.int32(pos), block_size=bs, table_blocks=table_blocks,
            wide_keys=wide, window=window))
        for c in range(iterations):
            held = min(last - first + 1 - c * per_pass, per_pass)
            assert held >= 1
            want_all += 1
            want_wide += int(pa.decode_tile_index(held, bs, tiles)
                             == len(tiles) - 1)
    rows, wide_rows = pa.decode_iteration_counts(
        positions, block_size=bs, table_blocks=table_blocks, kv_heads=kv,
        window=window, form=form)
    assert (int(rows.sum()), int(wide_rows.sum())) == (want_all,
                                                       want_wide)
    assert rows.shape == wide_rows.shape == positions.shape
    assert (wide_rows <= rows).all() and wide_rows.sum() > 0


# --------------------------------------------------------------------------- #
# One query row a kv head: every head of a key at once, in the buffer's
# own layout (``decode_attend_form`` == "all_heads").

#: name -> (pool dtype, kv heads, block size)
ALL_HEADS_CASES = {
    "int8_32": ("int8", 32, 16),
    "int8_8": ("int8", 8, 16),
    "bf16_32": ("bf16", 32, 16),
    "bf16_8": ("bf16", 8, 16),
    "f32_32": ("f32", 32, 16),
    "f32_8": ("f32", 8, 16),
    "f32_4_block32": ("f32", 4, 32),
    "int8_16_contiguous_view_block128": ("int8", 16, 128),
}


@pytest.mark.parametrize("q_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("window", [None, 150])
@pytest.mark.parametrize("name", sorted(ALL_HEADS_CASES))
def test_all_heads_form_matches_the_reference(name, window, q_dtype):
    """Rows that are idle (zero table, a scratch position), a lone
    block, a ragged group, whole passes and several with a tail;
    queries that are f32 values (three MXU terms against bf16 rows) and
    bf16 values (their own one)."""
    dtype, kv, bs = ALL_HEADS_CASES[name]
    assert pa.decode_attend_form(1, kv, bs, DTYPES[dtype]) == "all_heads"
    wide = pa.decode_keys_per_iteration(4096, bs, kv, "all_heads")
    assert wide == pa.KEYS_PER_GROUP
    lengths = [7, bs, 100, wide - 1, wide, wide + 1, 2 * wide + 81, 3]
    max_blocks = -(-max(lengths) // bs) + 1
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=len(lengths), kv=kv, group=1, hd=16)
    tables = tables.at[len(lengths) - 1].set(0)
    positions = jnp.asarray([length - 1 for length in lengths], jnp.int32)
    q = q.astype(DTYPES[q_dtype])
    out = pa.paged_decode_attention(q, k, v, tables, positions,
                                    window=window, interpret=True,
                                    **kv_args)
    assert out.dtype == q.dtype
    ref = pa.paged_decode_reference(
        q.astype(jnp.float32), exact[0], exact[1], tables, positions,
        window=window, **kv_args)
    tol = {"f32": 1e-4 if dtype == "int8" else 2e-5, "bf16": 2e-2}[q_dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


# --------------------------------------------------------------------------- #
# Float pools, head by head through the buffer's 32-bit word rows
# (``decode_attend_form`` == "word_rows").

#: name -> (pool dtype, kv heads, group, block size): sdar30b.fixedlen's
#: and nemotron3super.reason's geometries, a word row eight heads wide
#: (stride 4), f32 pools (a word a head), the contiguous view.
WORD_ROWS_CASES = {
    "sdar_bf16_4x32": ("bf16", 4, 32, 16),
    "nemotron_bf16_2x16": ("bf16", 2, 16, 16),
    "bf16_8x4": ("bf16", 8, 4, 16),
    "f32_4x2": ("f32", 4, 2, 16),
    "f32_1x8": ("f32", 1, 8, 16),
    "bf16_2x2_contiguous_view_block128": ("bf16", 2, 2, 128),
}


def _word_rows_case(name):
    """Rows of unequal length: idle (zero table, a scratch position), a
    lone block, one ragged group, a wide tile that reaches past the
    groups its pass copied, whole passes, and several with a tail."""
    dtype, kv, group, bs = WORD_ROWS_CASES[name]
    assert pa.decode_attend_form(group, kv, bs,
                                 DTYPES[dtype]) == "word_rows"
    wide = pa.decode_keys_per_iteration(4096, bs, kv, "word_rows")
    lengths = [7, bs, 100, 128 + 37, wide - 1, wide, wide + 1,
               2 * wide + 81, 3]
    max_blocks = -(-max(lengths) // bs) + 1
    q, k, v, exact, tables, kv_args = _typed_case(
        dtype, bs, max_blocks, batch=len(lengths), kv=kv, group=group,
        hd=16)
    tables = tables.at[len(lengths) - 1].set(0)
    positions = jnp.asarray([length - 1 for length in lengths], jnp.int32)
    return dtype, q, k, v, exact, tables, positions, kv_args


@pytest.mark.parametrize("q_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("window", [None, 150])
@pytest.mark.parametrize("name", sorted(WORD_ROWS_CASES))
def test_word_rows_form_matches_the_reference(name, window, q_dtype):
    """Queries that are f32 values (three MXU terms against bf16 rows)
    and bf16 values (their own one), with and without a window."""
    dtype, q, k, v, exact, tables, positions, kv_args = \
        _word_rows_case(name)
    q = q.astype(DTYPES[q_dtype])
    out = pa.paged_decode_attention(q, k, v, tables, positions,
                                    window=window, interpret=True,
                                    **kv_args)
    assert out.dtype == q.dtype
    ref = pa.paged_decode_reference(
        q.astype(jnp.float32), exact[0], exact[1], tables, positions,
        window=window, **kv_args)
    tol = {"f32": 2e-5, "bf16": 2e-2}[q_dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [None, 150])
@pytest.mark.parametrize("name", sorted(WORD_ROWS_CASES))
def test_word_rows_are_the_per_head_reads_rows(monkeypatch, name, window):
    """A head's rows through the word rows are the rows the sub-word
    read gives: the two bodies share everything after the read, so
    their results agree bit for bit."""
    _, q, k, v, _, tables, positions, _ = _word_rows_case(name)

    def call():
        return np.asarray(pa.closed_call.__wrapped__(
            q, k, v, tables, positions, None, None, window=window,
            sm_scale=0.25, interpret=True))
    got = call()
    monkeypatch.setattr(pa, "decode_attend_form", lambda *_: "per_head")
    np.testing.assert_array_equal(got, call())


@pytest.mark.parametrize("group,kv,bs,dtype,form", [
    # One query row a kv head and whole lane rows of (key, head) pairs
    # (evabyte.files' 32 int8 heads first).
    (1, 32, 16, "int8", "all_heads"), (1, 8, 16, "bf16", "all_heads"),
    (1, 16, 16, "f32", "all_heads"), (1, 4, 32, "int8", "all_heads"),
    (1, 1, 128, "bf16", "all_heads"), (1, 128, 16, "int8", "all_heads"),
    # Float pools whose heads fill whole words, head by head through
    # the buffer's word rows: sdar30b.fixedlen, nemotron3super.reason,
    # f32 pools, a bf16 pool at Mistral's heads, and one row a head
    # whose blocks fill no whole lane row.
    (32, 4, 16, "bf16", "word_rows"), (16, 2, 16, "bf16", "word_rows"),
    (2, 2, 16, "f32", "word_rows"), (8, 1, 16, "f32", "word_rows"),
    (4, 8, 16, "bf16", "word_rows"), (2, 32, 16, "bf16", "word_rows"),
    (1, 4, 16, "bf16", "word_rows"), (1, 3, 128, "f32", "word_rows"),
    # int8 pools keep the sub-word head read: mistral7b.chat and
    # mixtral8x7b.chat (8 heads x 4 rows), others, and one row a head
    # without whole lane rows; so does a bf16 pool whose heads fill no
    # whole word.
    (4, 8, 16, "int8", "per_head"), (16, 2, 16, "int8", "per_head"),
    (32, 4, 16, "int8", "per_head"), (2, 32, 16, "int8", "per_head"),
    (1, 4, 16, "int8", "per_head"), (1, 40, 16, "int8", "per_head"),
    (8, 1, 16, "bf16", "per_head"), (1, 3, 128, "bf16", "per_head")])
def test_attend_form_follows_the_geometry(group, kv, bs, dtype, form):
    assert pa.decode_attend_form(group, kv, bs, DTYPES[dtype]) == form


@pytest.mark.parametrize("config_name,quantize_kv,form", [
    ("tiny", False, "word_rows"), ("tiny", True, "per_head"),
    ("evabyte_tiny", False, "word_rows"),
    ("evabyte_tiny", True, "per_head"),
    ("evabyte_32_heads", False, "all_heads")])
def test_servers_attend_form_is_the_kernels(config_name, quantize_kv,
                                            form):
    """``stats()["decode_attend_form"]`` is the kernel's own deciding
    function at the server's geometry and pool dtype (``evabyte_tiny``:
    one row a head, but 4 heads of a 4-key block fill no lane row; an
    int8 pool keeps the sub-word head read), and the host's iteration
    counts take the width that form gets."""
    import dataclasses

    from aiko_services_tpu.models import evabyte, serving_model
    from aiko_services_tpu.orchestration.continuous import DecodeRequest
    from aiko_services_tpu.orchestration.paged import PagedContinuousServer
    from aiko_services_tpu.orchestration.serving import serving_telemetry
    if config_name == "evabyte_32_heads":
        # EvaByte's own head count at width 128: a block of 4 keys is
        # one lane row of (key, head) pairs.
        evabyte.CONFIGS[config_name] = dataclasses.replace(
            evabyte.CONFIGS["evabyte_tiny"], d_model=128, n_heads=32,
            n_kv_heads=32)
    _, config = serving_model(config_name)
    block = 16 if config_name == "tiny" else config.chunk_size
    try:
        server = PagedContinuousServer(
            config_name=config_name, slots=2, max_seq=128, chunk_steps=2,
            block_size=block, chunk_prefill_tokens=16, total_blocks=40,
            quantize_kv=quantize_kv)
    finally:
        evabyte.CONFIGS.pop("evabyte_32_heads", None)
    group = config.n_heads // config.n_kv_heads
    assert server.decode_attend_form == pa.decode_attend_form(
        group, config.n_kv_heads, block,
        jnp.int8 if quantize_kv else config.dtype) == form
    # Count as the chip's path does, at the width the form gets.
    server.decode_attention_path = "kernel"
    request = DecodeRequest(request_id="r", max_new_tokens=6,
                            prompt=np.arange(1, 40, dtype=np.int32))
    server.submit(request)
    for _ in range(200):
        if request.finished_ts is not None:
            break
        server.step()
    assert request.error is None and len(request.tokens) == 6
    stats = server.stats()
    assert stats["decode_attend_form"] == form
    assert serving_telemetry(stats)["decode_attend_form"] == form
    assert stats["decode_iterations"] >= stats["decode_steps"] > 0
