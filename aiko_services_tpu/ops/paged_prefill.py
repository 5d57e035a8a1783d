"""Ragged paged append-attention: chunked prefill straight against the
block pool, plus the jnp oracle it must match.

Admission is the serving cold path that stalls the hot one: the bucket
admission flow gathers a prompt's cached blocks into a contiguous
bucket (``paged_gather_blocks``), runs contiguous chunked prefill over
it, then scatters the result back into the pool
(``paged_scatter_blocks``) — every prompt KV byte crosses HBM twice
before the first decode step, and a prefix-cache hit still pays the
full gather.  The append kernel here removes both copies:

* a **write kernel** (grid ``(row, chunk-block)``) lands the chunk's
  new K/V rows — quantized by XLA with the cache writer's own
  quantizer for int8 layouts — directly in the row's pool blocks, one
  whole ``(block_size, kv_heads, head_dim)`` block per program: the
  block table rides scalar prefetch, so the output BlockSpec index map
  targets ``tables[row, cached//bs + cb]`` and the flush IS the pool
  write.  Blocks past ``chunk_len`` retarget the allocator's reserved
  scratch block 0 (never attendable, the same contract inactive decode
  lanes rely on).
* an **attention kernel** (grid ``(row, query-tile, band step)``,
  band steps fastest) runs flash-style online softmax for the chunk's
  queries over the tile's LIVE band of table entries — from the block
  the tile's first query's sliding window still reaches to the block
  of its last query — 128 keys a step (``P = 128 / block_size`` pool
  blocks; each pool array is passed ``P`` times, and copy ``i`` of
  step ``j`` is one lookup in a scalar-prefetched plan of the band's
  pool blocks that XLA resolves from the table once a call), laying
  the step's whole blocks side by side in VMEM and splitting heads
  in-kernel (:func:`~.paged_attention.load_head_rows`, one strided
  read of 128 rows a head).  Score, mask, softmax update and
  accumulator rescale happen once per 128 keys on full-lane tiles;
  int8 rows stay bf16-exact and their scales multiply the score and
  weight tiles (:func:`~.paged_attention._contract_pool_rows`).
  Steps past the band's end repeat the last live step's blocks (no
  HBM copy) and skip compute, so a row's HBM traffic is O(its real
  history) and its time is in live steps.
* all ``group`` query heads of a kv head stack into the tile's row
  axis (``(q_tile·group, head_dim)``), so masking is per-row by
  absolute ids and every matmul is MXU-shaped 2D.
* unlike single-token decode, a multi-query tile CAN hold rows with no
  visible key in a live step (a later chunk row's first block, or a
  window that has slid past), so masked positions are explicitly
  zeroed in the probability tile — the decode kernel's "every live
  block has a visible key" invariant does not extend here.

``cached_lens`` must be block-aligned (multiples of ``block_size``):
shared prefixes are whole blocks and chunk widths are powers of two,
so every caller satisfies this by construction.  The sequence-parallel
prefill window (``models/llama_tp._tp_sp_prefill_core``) dispatches
through this same path per sp shard — shard ``j`` appends chunk ``j``
with ``cached_lens = start + j·cap`` (cap is the admission cap, a pow2
multiple of ``block_size``, so alignment holds per shard) and the
window's K/V is all-gathered so every sp pool replica lands identical
bytes.  Layout contract and dispatch rules are documented in
docs/KERNELS.md.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .paged_attention import (MXU_PRECISION, _contract_pool_rows,
                              cached_gqa_attention,
                              blocks_per_group, kernel_mode,
                              kernel_serves, load_head_rows, runs_kernel)

__all__ = ["paged_prefill_attention", "paged_prefill_reference",
           "paged_verify_attention", "block_causal_positions",
           "paged_prefill_call", "prefill_key_blocks",
           "prefill_kernel_mode", "prefill_dispatch",
           "verify_dispatch", "prefill_attention_path"]

#: Largest query tile (tokens) one attention program carries; the tile
#: row axis is ``q_tile * group`` so this also bounds scratch size.
Q_TILE_CAP = 128

#: VMEM budget for one attention program's per-tile state.  Mosaic's
#: default scoped limit is 16 MiB: at Llama-3-8B width a 128-query f32
#: tile needed 19.2 MiB and was refused on the chip (PR 21), the bf16
#: one (10 MiB of state) fits with its block buffers and temporaries.
TILE_STATE_BYTES = 12 * 2**20

#: Rows (queries x group heads) of one kv head in a tile.  The kernel's
#: temporaries are per kv head, ``(rows, head_dim)`` and ``(rows,
#: block)`` in f32, on top of the tile's state: 128 queries of group 4
#: (512 rows) compile; 128 queries of group 16 (2,048 rows, 2 kv heads)
#: asked for 18.5 MiB of the 16 (TPU compiler, PR 26).
TILE_HEAD_ROWS = 512


# ---------------------------------------------------------------------------
# Dispatch policy


def prefill_kernel_mode() -> Tuple[bool, bool]:
    """:func:`~.paged_attention.kernel_mode` of
    ``AIKO_PREFILL_ATTENTION`` — the append-attention twin of the
    decode knob."""
    return kernel_mode("AIKO_PREFILL_ATTENTION")


def prefill_dispatch(head_dim: int, kv_heads: int, pool_dtype,
                     block_size: int, chunk: int) -> Tuple[bool, bool]:
    """``(use_kernel, interpret)`` for one admission geometry: the mode
    from :func:`prefill_kernel_mode`, and the reference for what the
    append kernels cannot serve — a pool
    :func:`~.paged_attention.kernel_serves` refuses, or a ``chunk``
    width that is not whole blocks — so nothing else runs under the
    kernels' name.  The serving path tag is this same answer at the
    server's admission slice."""
    use_kernel, interpret = prefill_kernel_mode()
    return (use_kernel and chunk % block_size == 0
            and kernel_serves(head_dim, kv_heads, pool_dtype,
                              interpret)), interpret


def verify_dispatch(head_dim: int, kv_heads: int, pool_dtype,
                    window_tokens: int) -> Tuple[bool, bool]:
    """:func:`prefill_dispatch` for the speculative verify window: any
    start position, at most :data:`Q_TILE_CAP` tokens."""
    use_kernel, interpret = prefill_kernel_mode()
    return (use_kernel and window_tokens <= Q_TILE_CAP
            and kernel_serves(head_dim, kv_heads, pool_dtype,
                              interpret)), interpret


def prefill_attention_path(head_dim: int, kv_heads: int, pool_dtype,
                           block_size: int, chunk: int) -> str:
    """``"kernel"`` or ``"reference"`` — the serving-counter path tag,
    decided by :func:`prefill_dispatch` at the server's real geometry."""
    use_kernel, _ = prefill_dispatch(head_dim, kv_heads, pool_dtype,
                                     block_size, chunk)
    return "kernel" if use_kernel else "reference"


def _q_tile_size(chunk: int, heads: int, itemsize: int,
                 group: int = 1) -> int:
    """Default query tile: the largest power-of-two divisor of
    ``chunk``, capped at :data:`Q_TILE_CAP`, at what
    :data:`TILE_STATE_BYTES` holds and at :data:`TILE_HEAD_ROWS` rows
    a kv head.  A tile keeps, per query and query head, one lane-padded
    128-wide row of q and of the output (both double-buffered,
    ``itemsize`` bytes) and of the f32 accumulator, running max and
    denominator."""
    per_query = heads * 128 * (4 * itemsize + 3 * 4)
    fits = max(TILE_STATE_BYTES // per_query, 1)
    head_rows = max(TILE_HEAD_ROWS // group, 1)
    return min(chunk & -chunk, Q_TILE_CAP, 1 << (fits.bit_length() - 1),
               1 << (head_rows.bit_length() - 1))


def prefill_key_blocks(start: int, width: int, block_size: int,
                       window: Optional[int], *, heads: int, group: int,
                       itemsize: int) -> int:
    """Key blocks x query tiles the attention of ONE append slice
    ``[start, start + width)`` has to visit in one layer: for each
    query tile (:func:`_q_tile_size`), the pool blocks from the first
    its sliding window still reaches to the one holding its last query
    — what :func:`_live_bands` gives the kernel, counted on the host
    (the serving counter ``prefill_key_blocks``).  With the kernel's
    device time over the same dispatches it gives microseconds per
    ``128 / block_size`` blocks per tile, a step of the sweep."""
    q_tile = _q_tile_size(width, heads, itemsize, group)
    total = 0
    for q_min in range(start, start + width, q_tile):
        first = max(q_min - window + 1, 0) // block_size if window else 0
        total += (q_min + q_tile - 1) // block_size - first + 1
    return total


# ---------------------------------------------------------------------------
# jnp oracle (also the CPU path) — numerics the kernel must match


def _kv_quantize_rows(rows):
    """(…, hd) → (int8 rows, f32 scales (…,)) — symmetric absmax per
    vector, identical numerics to the models-side cache quantizer (one
    scale per token per kv head)."""
    r32 = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(r32), axis=-1)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(r32 / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _pool_rows(pool, k_new, v_new):
    """The chunk's rows as the pool stores them: int8 layouts quantize
    exactly like the cache writer, others cast to the pool dtype."""
    if "ks" in pool:
        kq, ks = _kv_quantize_rows(k_new)
        vq, vs = _kv_quantize_rows(v_new)
        return {"k": kq, "v": vq, "ks": ks, "vs": vs}
    return {"k": k_new.astype(pool["k"].dtype),
            "v": v_new.astype(pool["v"].dtype)}


def _write_rows(pool, k_new, v_new, tables, positions, chunk_lens=None):
    """Scatter the chunk rows into the pool at their absolute positions
    — one ``(kv_heads, head_dim)`` row per token, the pool's own tile.
    Without ``chunk_lens`` every padded row lands (pad keys sit past
    every real query's visibility); with it, rows at or past a row's
    chunk length retarget reserved scratch block 0."""
    block_size = pool["k"].shape[1]
    block_ids = jnp.take_along_axis(tables, positions // block_size,
                                    axis=1)
    if chunk_lens is not None:
        real = (jnp.arange(positions.shape[1], dtype=jnp.int32)[None, :]
                < chunk_lens[:, None])
        block_ids = jnp.where(real, block_ids, 0)
    offsets = positions % block_size
    return {key: pool[key].at[block_ids, offsets].set(src)
            for key, src in _pool_rows(pool, k_new, v_new).items()}


def block_causal_positions(positions, mask_block: Optional[int]):
    """The position whose causal mask IS the block-causal one of
    ``positions``: the last position of each query's block of
    ``mask_block`` positions (key ``j`` is visible to query ``i`` iff
    ``j // mask_block <= i // mask_block``).  ``None``: causal, the
    positions as they are."""
    if mask_block is None:
        return positions
    return (positions // mask_block + 1) * mask_block - 1


def paged_prefill_reference(q, k_new, v_new, pool, tables, cached_lens,
                            chunk_lens, window: Optional[int] = None,
                            mask_block: Optional[int] = None):
    """Write-then-gather-then-attend oracle for the append kernel:
    scatter the chunk's K/V into the pool, view ``pool[tables]`` as
    per-row contiguous caches, and run :func:`cached_gqa_attention`
    with query positions ``cached + [0, T)`` (each moved to the end of
    its block of ``mask_block`` positions under the block-causal
    mask, :func:`block_causal_positions`).

    ``q`` (batch, T, kv, group, hd); ``k_new``/``v_new`` (batch, T, kv,
    hd); ``pool`` the per-layer dict (``k``/``v`` + optional
    ``ks``/``vs``); returns ``(out (batch, T, kv, group, hd),
    new_pool)``.  Query/output rows at or past ``chunk_lens[row]`` are
    padding — attended against garbage, discarded by callers."""
    batch, T = k_new.shape[:2]
    hd = q.shape[-1]
    positions = (cached_lens.astype(jnp.int32)[:, None]
                 + jnp.arange(T, dtype=jnp.int32)[None, :])
    new_pool = _write_rows(pool, k_new, v_new, tables, positions)

    def view(buf):
        gathered = buf[tables]
        n_blocks, bs = gathered.shape[1:3]
        return gathered.reshape((batch, n_blocks * bs)
                                + gathered.shape[3:])

    cache_layer = {key: view(buf) for key, buf in new_pool.items()}
    out = cached_gqa_attention(
        q, cache_layer, block_causal_positions(positions, mask_block),
        hd, window=window)
    return out, new_pool


# ---------------------------------------------------------------------------
# The write kernel: land the chunk's K/V rows in their pool blocks


def _append_kv_kernel(tables_ref, meta_ref,        # scalar prefetch
                      *refs):
    """Grid: (batch, chunk_blocks).  One program moves one row's chunk
    block — every kv head, and the scale planes of an int8 layout —
    from the activation slabs into the pool block the index map
    resolved from the prefetched table: the output flush IS the pool
    write.  Dead steps (block past ``chunk_len``) still flush, but the
    index map retargeted them at reserved scratch block 0, which is
    never attendable.  ``refs`` is (slabs…, aliased pools…, outputs…);
    the aliased pool inputs stay in HBM untouched."""
    n = len(refs) // 3
    for src, dst in zip(refs[:n], refs[2 * n:]):
        dst[...] = src[...]


def _append_kv(k_new, v_new, pool, tables, meta, interpret: bool):
    """Write the (batch, T, kv, hd) chunk slabs into the pool blocks
    named by ``tables`` starting at block ``cached // bs`` — in-kernel,
    via aliased pool outputs whose index maps resolve the target block
    from the scalar-prefetched table.  The tile is one whole pool
    block ``(block_size, kv_heads, head_dim)``: its last two dimensions
    are the array's own, the only cut of this layout Mosaic's tiling
    accepts, and int8 rows arrive already quantized so the body is a
    copy."""
    batch, T = k_new.shape[:2]
    block_size = pool["k"].shape[1]
    max_blocks = tables.shape[1]
    grid = (batch, T // block_size)

    def new_index(b, cb, tables_ref, meta_ref):
        return (b, cb, 0, 0)

    def pool_index(b, cb, tables_ref, meta_ref):
        # Blocks past the row's real chunk length flush garbage — but
        # into reserved scratch block 0, exactly like inactive decode
        # lanes.  The live-block table lookup is clamped so dead steps
        # never read past the row's allocated entries.
        live = cb * block_size < meta_ref[b, 1]
        entry = jnp.minimum(meta_ref[b, 0] // block_size + cb,
                            max_blocks - 1)
        return (jnp.where(live, tables_ref[b, entry], 0), 0, 0, 0)

    def block_spec(index_map, buf):
        # k/v tiles are whole blocks, scale planes the same minus the
        # head_dim axis: one index map, cut to the buffer's rank.
        tile = (1, block_size) + buf.shape[2:]
        return pl.BlockSpec(
            tile, lambda *args: index_map(*args)[:len(tile)])

    rows = _pool_rows(pool, k_new, v_new)
    keys = list(rows)
    slab_specs = [block_spec(new_index, pool[key]) for key in keys]
    pool_specs = [block_spec(pool_index, pool[key]) for key in keys]
    n = len(keys)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=slab_specs + [pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=pool_specs)
    outs = pl.pallas_call(
        _append_kv_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool[key].shape, pool[key].dtype)
                   for key in keys],
        # Operand positions count the scalar-prefetch args: (tables,
        # meta, slabs…, pools…) puts pool i at 2 + n + i.
        input_output_aliases={2 + n + i: i for i in range(n)},
        interpret=interpret,
    )(tables, meta, *(rows[key] for key in keys),
      *(pool[key] for key in keys))
    return dict(zip(keys, outs))


# ---------------------------------------------------------------------------
# The attention kernel: chunk queries over cached prefix + chunk


def _live_bands(cached_lens, n_tiles: int, *, q_tile: int,
                block_size: int, window: Optional[int], kv_blocks: int):
    """``(q_min, first, last)``, each ``(batch, n_tiles)``: per query
    tile of a row holding ``cached`` positions, the tile's first query
    position and the first and last table entries its queries can see
    — from the block the FIRST query's sliding window still reaches to
    the block of the LAST query (never past the ``kv_blocks`` the sweep
    is bounded to)."""
    q_min = (cached_lens[:, None]
             + jnp.arange(n_tiles, dtype=jnp.int32)[None, :] * q_tile)
    last = jnp.minimum((q_min + q_tile - 1) // block_size, kv_blocks - 1)
    first = jnp.zeros_like(last)
    if window is not None:
        first = jnp.minimum(
            jnp.maximum(q_min - window + 1, 0) // block_size, last)
    return q_min, first, last


def _band_steps(q_tile: int, block_size: int, window: Optional[int],
                kv_blocks: int, blocks_per_step: int) -> int:
    """Grid steps that cover the longest live band a tile can have:
    the bounded table, or with a sliding window the blocks that
    ``window + q_tile`` positions can straddle."""
    blocks = kv_blocks
    if window is not None:
        blocks = min(blocks, (q_tile + window - 2) // block_size + 2)
    return -(-blocks // blocks_per_step)


def _sweep_plan(tables, cached_lens, n_tiles: int, steps: int, P: int,
                **geometry):
    """What the sweep's scalar prefetch carries, computed by XLA once
    a call (a gather of a few hundred integers out of the row's table)
    so that an index map is ONE table lookup: ``bands`` ``(batch ·
    n_tiles, 3)`` = :func:`_live_bands`, and ``blocks`` ``(batch ·
    n_tiles, steps · P)``, the pool block behind every (tile, step,
    copy).  A step past the band's end is clamped onto its last live
    step, and an entry past the band's last onto that one: an unchanged
    block index makes Pallas keep the resident VMEM tile instead of
    issuing a fresh HBM copy, and no entry a tile cannot see is ever
    dereferenced."""
    q_min, first, last = _live_bands(cached_lens, n_tiles, **geometry)
    step = jnp.minimum(jnp.arange(steps, dtype=jnp.int32),
                       ((last - first) // P)[..., None])
    entry = jnp.minimum(
        first[..., None, None] + step[..., None] * P
        + jnp.arange(P, dtype=jnp.int32), last[..., None, None])
    batch = tables.shape[0]
    blocks = jnp.take_along_axis(
        tables, entry.reshape(batch, n_tiles * steps * P), axis=1)
    return (jnp.stack([q_min, first, last], axis=-1).reshape(-1, 3),
            blocks.reshape(batch * n_tiles, steps * P))


def _head_scale_rows(planes):
    """Per-head, lane-dense scales ``(kv_heads, keys)`` from a step's
    scale planes ``(keys, kv_heads)`` (keys on sublanes, as the pool
    holds them): a contraction with the identity over the head axis,
    so a selection — exact at :data:`MXU_PRECISION`."""
    kv_heads = planes.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (kv_heads, kv_heads), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (kv_heads, kv_heads), 1)
           ).astype(jnp.float32)
    return jax.lax.dot_general(
        eye, planes, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
        preferred_element_type=jnp.float32)


def _lanes(column, width: int):
    """A per-row statistic kept lane-replicated ``(rows, n)`` (every
    lane of a row the same value) at ``width`` lanes: itself where the
    widths agree (compiled: score tile and head_dim are both 128)."""
    if column.shape[1] == width:
        return column
    return jnp.broadcast_to(column[:, :1], (column.shape[0], width))


def _prefill_attention_kernel(bands_ref, blocks_ref,   # scalar prefetch
                              q_ref, *rest,
                              blocks_per_step: int, group: int,
                              sm_scale: float, window: Optional[int],
                              quantized: bool,
                              mask_block: Optional[int] = None):
    """Grid: (batch, q_tiles, band steps); band steps fastest.

    One program sweeps one (row, query-tile), every kv head, through
    the tile's LIVE band of table entries, ``blocks_per_step`` pool
    blocks (128 keys) a step, carrying online-softmax state in VMEM
    scratch.  Step ``j`` holds entries ``first + j·P … + P - 1`` (each
    pool array arrives ``P`` times, once per entry of the step, at the
    blocks :func:`_sweep_plan` resolved); a step past the band's end
    repeats the last live step's blocks (no copy) and skips compute.
    The tile's row axis interleaves queries and their group heads
    (``row = token·group + head``), so per-row masking by absolute ids
    covers ragged causality, the sliding window AND the entries a short
    last step clamps, in one 2D tile.  ``mask_block`` (a divisor of the
    pool block, so a tile's band ends where it did) makes the mask
    block-causal: a query sees every key of its own block of
    ``mask_block`` positions (:func:`block_causal_positions`).

    Running max and denominator are kept lane-replicated, as wide as
    the score tile: every vector op of the update is a full-lane op on
    ``(rows, 128)`` tiles, once per 128 keys."""
    P = blocks_per_step
    pools, (o_ref, m_scr, l_scr, acc_scr, k_buf, v_buf) = (rest[:-6],
                                                            rest[-6:])
    k_refs, v_refs = pools[:P], pools[P:2 * P]
    j = pl.program_id(2)
    tile = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    q_min, first, last = (bands_ref[tile, 0], bands_ref[tile, 1],
                          bands_ref[tile, 2])
    block_size, kv_heads, head_dim = k_refs[0].shape[1:]
    rows = q_ref.shape[2]
    keys = P * block_size

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    entry = first + j * P               # the step's first table entry

    @pl.when(entry <= last)
    def _compute():
        q_ids = block_causal_positions(
            q_min + jax.lax.broadcasted_iota(
                jnp.int32, (rows, keys), 0) // group, mask_block)
        key_ids = entry * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        # Entries past `last` inside the band's last step were clamped
        # to it by the index maps: their ids lie past every query of
        # the tile (or past the bounded table) and are masked here.
        visible = (key_ids <= q_ids) & (key_ids < (last + 1) * block_size)
        if window is not None:
            visible &= key_ids > q_ids - window
        # Pool rows that are bf16 values (bf16 and widened int8) meet
        # the MXU as bf16: see _contract_pool_rows.
        row_dtype = (jnp.float32 if k_refs[0].dtype == jnp.float32
                     else jnp.bfloat16)
        score_scale = sm_scale
        if quantized:
            # Per-(token, head) scales factor OUT of the q·k
            # contraction and INTO the softmax weights
            # (cached_gqa_attention has the algebra): they multiply the
            # (rows, keys) tiles, and the int8 rows stay bf16-exact.
            ks_refs, vs_refs = pools[2 * P:3 * P], pools[3 * P:]
            k_scales = _head_scale_rows(jnp.concatenate(
                [ref[0] for ref in ks_refs], axis=0)) * sm_scale
            v_scales = _head_scale_rows(jnp.concatenate(
                [ref[0] for ref in vs_refs], axis=0))

        # The step's P blocks side by side in one buffer: a head's 128
        # rows are then ONE strided read and one widening, not P of
        # each (the kernel's Mosaic lowering is priced per read and
        # per conversion, docs/KERNELS.md).
        for i in range(P):
            k_buf[i * block_size:(i + 1) * block_size] = k_refs[i][0]
            v_buf[i * block_size:(i + 1) * block_size] = v_refs[i][0]

        for head in range(kv_heads):
            if quantized:
                score_scale = k_scales[head:head + 1, :]
            s = _contract_pool_rows(
                q_ref[0, head],
                load_head_rows(k_buf, head, row_dtype),
                ((1,), (1,))) * score_scale
            s = jnp.where(visible, s, NEG_INF)       # (rows, keys)

            m_prev = m_scr[head]                     # (rows, keys)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            # A live step can hold rows with NO visible key (later
            # chunk rows, or a window that slid past): their m stays
            # NEG_INF and exp(NEG_INF - NEG_INF) = 1 would be bogus
            # mass — zero masked probabilities explicitly (the
            # single-query decode kernel's
            # every-live-group-has-a-visible-key invariant does not
            # extend to multi-query tiles).
            p = jnp.where(visible, jnp.exp(s - m_new), 0.0)
            correction = jnp.exp(m_prev - m_new)
            l_scr[head] = correction * l_scr[head] + jnp.sum(
                p, axis=-1, keepdims=True)
            if quantized:
                p = p * v_scales[head:head + 1, :]
            acc_scr[head] = (
                acc_scr[head] * _lanes(correction, head_dim)
                + _contract_pool_rows(
                    p, load_head_rows(v_buf, head, row_dtype),
                    ((1,), (0,))))
            m_scr[head] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        for head in range(kv_heads):
            denom = _lanes(l_scr[head], head_dim)
            o_ref[0, head] = (
                acc_scr[head] / jnp.where(denom == 0.0, 1.0, denom)
            ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "sm_scale", "q_tile",
                                    "kv_blocks", "interpret",
                                    "mask_block"))
def paged_prefill_call(q, pool, tables, cached_lens, *,
                       window: Optional[int],
                       sm_scale: float, q_tile: int, kv_blocks: int,
                       interpret: bool,
                       mask_block: Optional[int] = None):
    """The attention sweep over the (already appended) pool, behind a
    jit of its own so that the layers of a program share ONE trace of
    the kernel and ONE Mosaic lowering, and the device trace prints
    one stable name for it (XLA names a Pallas custom call after the
    innermost computation around it).  ``q`` (batch, T, kv, group, hd)
    → out same shape."""
    batch, T, kv_heads, group, head_dim = q.shape
    block_size = pool["k"].shape[1]
    quantized = "ks" in pool
    P = blocks_per_group(block_size)
    # All group heads of a query stack into the tile row axis: 2D tiles
    # everywhere in-kernel, one (q_tile*group, hd) x (hd, keys) matmul
    # per head per step.
    q_r = q.transpose(0, 2, 1, 3, 4).reshape(batch, kv_heads,
                                             T * group, head_dim)
    n_tiles = T // q_tile
    steps = _band_steps(q_tile, block_size, window, kv_blocks, P)
    bands, blocks = _sweep_plan(
        tables, cached_lens, n_tiles, steps, P, q_tile=q_tile,
        block_size=block_size, window=window, kv_blocks=kv_blocks)
    grid = (batch, n_tiles, steps)
    rows = q_tile * group

    def q_index(b, qt, j, bands_ref, blocks_ref):
        return (b, 0, qt, 0)

    def pool_specs(buf):
        # k/v tiles are whole blocks, scale planes the same minus the
        # head_dim axis; copy i of a step is block `blocks[tile, j·P +
        # i]`, the rest of the index zeros to the buffer's rank.
        tile = (1,) + buf.shape[1:]
        rest = (0,) * (len(tile) - 1)
        return [pl.BlockSpec(
            tile, lambda b, qt, j, bands_ref, blocks_ref, i=i: (
                blocks_ref[b * n_tiles + qt, j * P + i],) + rest)
            for i in range(P)]

    q_block = (1, kv_heads, rows, head_dim)
    keys = [key for key in ("k", "v", "ks", "vs") if key in pool]
    in_specs = [pl.BlockSpec(q_block, q_index)]
    operands = [q_r]
    for key in keys:
        in_specs += pool_specs(pool[key])
        operands += [pool[key]] * P

    kernel = functools.partial(
        _prefill_attention_kernel, blocks_per_step=P, group=group,
        sm_scale=sm_scale, window=window, quantized=quantized,
        mask_block=mask_block)
    stat = pltpu.VMEM((kv_heads, rows, P * block_size), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(q_block, q_index),
        scratch_shapes=[
            stat, stat,
            pltpu.VMEM((kv_heads, rows, head_dim), jnp.float32),
            pltpu.VMEM((P * block_size, kv_heads, head_dim),
                       pool["k"].dtype),
            pltpu.VMEM((P * block_size, kv_heads, head_dim),
                       pool["v"].dtype),
        ])
    out_r = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_r.shape, q.dtype),
        interpret=interpret,
    )(bands, blocks, *operands)
    return out_r.reshape(batch, kv_heads, T, group,
                         head_dim).transpose(0, 2, 1, 3, 4)


def paged_prefill_attention(q, k_new, v_new, pool, tables, cached_lens,
                            chunk_lens, window: Optional[int] = None,
                            sm_scale: Optional[float] = None,
                            interpret: bool = False,
                            q_tile: Optional[int] = None,
                            kv_limit: Optional[int] = None,
                            mask_block: Optional[int] = None):
    """Ragged paged append attention: write the chunk's K/V into the
    pool in-kernel, then attend the chunk's queries over cached prefix
    blocks + the causally-visible chunk itself.

    Args:
      q: ``(batch, T, kv_heads, group, head_dim)`` chunk queries (rope
        applied), all query heads of each kv head together.
      k_new / v_new: ``(batch, T, kv_heads, head_dim)`` the chunk's new
        K/V rows (rope applied to K) — written to the pool at absolute
        positions ``cached_lens[row] + [0, T)``.
      pool: per-layer dict ``{"k", "v"[, "ks", "vs"]}`` of
        ``(n_blocks, block_size, kv_heads, head_dim)`` block pools
        (int8 layouts quantize absmax per (token, head)).
      tables: ``(batch, max_blocks)`` int32 block table; entries
        covering ``[0, cached + T)`` must be allocated.
      cached_lens: ``(batch,)`` int32 — tokens already in the pool for
        the row; MUST be a multiple of ``block_size`` (true by
        construction: shared prefixes are whole blocks, chunk widths
        are powers of two ≥ the block size).
      chunk_lens: ``(batch,)`` int32 — real new tokens (≤ T).  Rows at
        or past a row's chunk length are padding: their K/V lands in
        scratch-block garbage territory past every real query's
        visibility, and their output rows are garbage the caller
        discards.
      window: sliding-window size (Mistral semantics).
      sm_scale: score scale (default ``head_dim ** -0.5``).
      interpret: run the Pallas kernels in interpret mode (CPU tests).
      q_tile: queries per attention program (default: largest pow2
        divisor of T, capped at :data:`Q_TILE_CAP`).
      kv_limit: static bound on the kv-block sweep (e.g. the padded
        bucket's block count) — trims dead grid steps when the table
        is much longer than the row can be.
      mask_block: ``None`` for the causal mask; a block length that
        divides the pool block for the block-causal one (generation by
        diffusion over blocks: a query sees its whole block).

    Returns ``(out (batch, T, kv_heads, group, head_dim) in q.dtype,
    new_pool)``.  Off the TPU backend (and not interpreting) this IS
    :func:`paged_prefill_reference`; see
    :func:`~.paged_attention.runs_kernel`.
    """
    batch, T, kv_heads, group, head_dim = q.shape
    block_size = pool["k"].shape[1]
    max_blocks = tables.shape[1]
    if sm_scale is None:
        sm_scale = head_dim ** -0.5

    if mask_block is not None and block_size % mask_block:
        raise ValueError(
            f"mask_block {mask_block} must divide the pool block "
            f"{block_size}: a query tile's band ends with its last "
            "query's pool block")
    if not runs_kernel(interpret):
        return paged_prefill_reference(q, k_new, v_new, pool, tables,
                                       cached_lens, chunk_lens,
                                       window=window,
                                       mask_block=mask_block)
    if T % block_size or not kernel_serves(head_dim, kv_heads,
                                           pool["k"].dtype, interpret):
        raise ValueError(
            f"paged append kernel cannot serve head_dim={head_dim} "
            f"kv_heads={kv_heads} {pool['k'].dtype} pools, chunk width "
            f"{T} over {block_size}-token blocks")

    tables = tables.astype(jnp.int32)
    meta = jnp.stack([cached_lens.astype(jnp.int32),
                      chunk_lens.astype(jnp.int32)], axis=1)
    if q_tile is None:
        q_tile = _q_tile_size(T, kv_heads * group, q.dtype.itemsize,
                              group)
    if T % q_tile:
        raise ValueError(f"q_tile {q_tile} must divide chunk width {T}")
    kv_blocks = max_blocks if kv_limit is None else min(kv_limit,
                                                        max_blocks)

    new_pool = _append_kv(k_new, v_new, pool, tables, meta, interpret)
    out = paged_prefill_call(q, new_pool, tables, meta[:, 0],
                             window=window, sm_scale=sm_scale,
                             q_tile=q_tile,
                             kv_blocks=kv_blocks, interpret=interpret,
                             mask_block=mask_block)
    return out, new_pool


# ---------------------------------------------------------------------------
# Ragged verify: short append chunks at UNALIGNED per-row positions
# (speculative decoding on the paged path — each slot's verify window
# starts mid-block at its own decode position)


def paged_verify_attention(q, k_new, v_new, pool, tables, cached_lens,
                           chunk_lens, window: Optional[int] = None,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False,
                           kv_limit: Optional[int] = None):
    """Ragged paged VERIFY attention: the speculative twin of
    :func:`paged_prefill_attention` for short windows at arbitrary
    (mid-block) per-row start positions.

    Two contract differences from the prefill entry:

    * ``cached_lens`` need NOT be block-aligned — each slot verifies at
      its own decode position.  A window that starts mid-block cannot
      land as whole blocks, so its rows are scattered one
      ``(kv_heads, head_dim)`` tile per token, the way single-token
      decode writes its row (:func:`_write_rows`).
    * ``chunk_lens`` may vary per row (ragged k across the batch); rows
      at or past ``chunk_lens[row]`` — every row of an inactive slot —
      land in reserved scratch block 0.

    ``T`` (the slab width) is padded internally to a power of two ≥ 16
    so the attention tile satisfies the TPU sublane floor; pad rows are
    never written and their output rows are sliced off.  The attention
    sweep is the SAME online-softmax kernel chunked prefill uses
    (absolute-id masking already handles unaligned ``cached``), so a
    verify pass reads each row's real history once — no pool gather.

    Returns ``(out (batch, T, kv_heads, group, head_dim), new_pool)``.
    Off the TPU backend (and not interpreting) this IS
    :func:`paged_prefill_reference` (which supports arbitrary per-row
    positions natively); see :func:`~.paged_attention.runs_kernel`.
    """
    batch, T, kv_heads, group, head_dim = q.shape
    max_blocks = tables.shape[1]
    if sm_scale is None:
        sm_scale = head_dim ** -0.5

    if not runs_kernel(interpret):
        return paged_prefill_reference(q, k_new, v_new, pool, tables,
                                       cached_lens, chunk_lens,
                                       window=window)
    if T > Q_TILE_CAP or not kernel_serves(head_dim, kv_heads,
                                           pool["k"].dtype, interpret):
        raise ValueError(
            f"paged verify kernel cannot serve head_dim={head_dim} "
            f"kv_heads={kv_heads} {pool['k'].dtype} pools, window {T} "
            f"(one query tile holds {Q_TILE_CAP})")

    tables = tables.astype(jnp.int32)
    cached_lens = cached_lens.astype(jnp.int32)
    chunk_lens = chunk_lens.astype(jnp.int32)
    positions = cached_lens[:, None] + jnp.arange(T, dtype=jnp.int32)
    new_pool = _write_rows(pool, k_new, v_new, tables, positions,
                           chunk_lens)

    Tp = max(16, 1 << (T - 1).bit_length())
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, Tp - T)) + ((0, 0),) * (q.ndim - 2))
    kv_blocks = max_blocks if kv_limit is None else min(kv_limit,
                                                        max_blocks)
    out = paged_prefill_call(q, new_pool, tables, cached_lens,
                             window=window, sm_scale=sm_scale, q_tile=Tp,
                             kv_blocks=kv_blocks, interpret=interpret)
    return out[:, :T], new_pool
