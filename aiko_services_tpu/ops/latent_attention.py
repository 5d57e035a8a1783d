"""Paged attention over a LATENT block pool (multi-head latent
attention, DeepSeek-V2's MLA): Pallas TPU kernels and the jnp forms
they must match.

A position's cache row is ``[c_kv | k_r]``: the compressed key/value
vector after its norm (``rank`` values) and the one rotary key every
head shares (``rope`` values) — ``width = rank + rope`` values, no head
axis.  The pool is ``(n_blocks, block_size, width)``, walked through the
same block tables as the K/V pools of :mod:`.paged_attention`.

Both kernels compute the ABSORBED form.  A head's query arrives already
carried into the latent width (``q~_h = q_nope_h W_uk_h^T``, beside its
rotated ``q_rope_h``), so for every head at once

    s = [q~ | q_rope] . [c_kv | k_r]^T * scale,   o = softmax(s) c_kv

and the caller carries ``o`` back out (``o_h W_uv_h``).  All heads of a
token read ONE row a key, so a step is two plain matmuls with the heads
on the row axis — ``(rows, width) x (width, keys)`` and ``(rows, keys)
x (keys, rank)`` — and nothing of the context is ever expanded to
per-head keys or values.

* :func:`latent_decode_attention`: one query token a batch row against
  the row's live blocks (grid ``(batch,)``, a loop over the live table
  entries, double-buffered DMAs, one online-softmax update an
  iteration).
* :func:`latent_prefill_attention`: a slice of ``T`` query tokens of ONE
  row against the ``start`` positions its table already holds (shared
  prefix blocks included) and against the slice's own rows, causally
  (grid ``(T / q_tile,)``; the own rows ride VMEM).

  Both are ONE kernel body on two grids, and the keys an iteration
  covers are a function of a program's query rows
  (:func:`keys_per_iteration`): what an iteration pays whatever its
  width (its serial chain; the lane reductions, the correction and the
  accumulator's rescale of its softmax state) is paid per query row,
  so it covers as many keys as its temporaries leave room for: 1,024
  for a decode program's 32 rows, 512 for a prefill tile's 2,048.
  Only a row's last iteration and the slice's own rows are masked.
* :func:`latent_append`: rows into the pool in place.  A decode step's
  one row a slot is read-modify-write of its 16-key block (a bf16 row
  is half of each 32-bit word of its sublane pair, so a lone row is not
  a DMA); a prefill slice writes whole blocks.

Off the TPU (and not interpreting) each is its jnp form.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF
from .paged_attention import (LANES, _contract_terms, _mxu_terms,
                              decode_kernel_mode, runs_kernel)
from .paged_prefill import prefill_kernel_mode

__all__ = ["latent_decode_attention", "latent_decode_reference",
           "latent_prefill_attention", "latent_prefill_reference",
           "latent_append", "latent_append_reference",
           "latent_attention_paths", "latent_slice_key_blocks",
           "closed_call", "keys_per_iteration", "KEYS_PER_GROUP",
           "PREFILL_Q_TILE"]

#: Keys of one group of block copies: the least an iteration covers,
#: the step its width grows in, and (less one block) the most it copies
#: past a row's last live block.
KEYS_PER_GROUP = 128

#: The most keys one iteration covers (:func:`keys_per_iteration`).
MAX_KEYS_PER_ITERATION = 1024

#: Query tokens of one prefill program.  Its state is per (token, head)
#: row: 64 tokens of 32 heads are 2,048 rows, whose f32 accumulator
#: ``(rows, rank 256)`` is 2 MiB.
PREFILL_Q_TILE = 64

#: Scoped VMEM either attention program may take.  Mosaic's default is
#: 16 MiB of a v5e's 128; a prefill tile of 2,048 rows at 512 keys an
#: iteration holds 10 MiB of state, queries, result and key buffers
#: and asks for 9 to 24 MiB more of temporaries, as much as it is left
#: (TPU compiler, ``tests/test_tpu_compile.py``).
VMEM_LIMIT_BYTES = 48 * 2**20

#: What the temporaries of ONE iteration may take, a quarter of
#: :data:`VMEM_LIMIT_BYTES`, and their bytes a (query row, key): the
#: f32 scores, the f32 weights and the weights as the MXU takes them.
ITERATION_VMEM_BYTES = VMEM_LIMIT_BYTES // 4
ITERATION_BYTES_PER_SCORE = 4 + 4 + 2


def latent_attention_paths() -> Tuple[str, str]:
    """``(decode, prefill)`` serving path tags: ``"kernel"`` where the
    mode variables of :mod:`.paged_attention` / :mod:`.paged_prefill`
    run the Pallas kernels (the TPU, or interpreting), else
    ``"reference"``.  The kernels serve any latent pool whose block
    size divides :data:`KEYS_PER_GROUP`."""
    return tuple("kernel" if mode()[0] else "reference"
                 for mode in (decode_kernel_mode, prefill_kernel_mode))


def latent_slice_key_blocks(start: int, width: int, block_size: int,
                            q_tile: int = PREFILL_Q_TILE) -> int:
    """Pool blocks x query tiles the attention of ONE prefill slice
    ``[start, start + width)`` sweeps in one layer: every tile reads
    the ``start / block_size`` cached blocks, and of the slice's own
    rows the blocks up to its last query (the serving counter
    ``prefill_key_blocks``, counted on the host)."""
    q_tile = min(q_tile, width)
    total = 0
    for first in range(0, width, q_tile):
        total += start // block_size + -(-(first + q_tile) // block_size)
    return total


# --------------------------------------------------------------------------- #
# jnp forms


def _gathered(pool, tables):
    """``(batch, table width * block_size, width)`` rows of each row's
    table, in position order."""
    rows = pool[tables]
    return rows.reshape(tables.shape[0], -1, pool.shape[-1])


def latent_decode_reference(q, pool, tables, positions, *, rank: int,
                            sm_scale: float):
    """``q (batch, heads, width)`` against keys ``0..positions[row]`` of
    each row's table -> ``(batch, heads, rank)`` in ``q.dtype``."""
    rows = _gathered(pool, tables).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    visible = jnp.arange(rows.shape[1])[None] <= positions[:, None]
    s = jnp.where(visible[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, rows[..., :rank],
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


def latent_prefill_reference(q, own, pool, table, start, *, rank: int,
                             sm_scale: float):
    """``q (T, heads, width)`` of positions ``start .. start + T - 1``
    against the ``start`` cached positions of ``table (table width,)``
    and the slice's ``own (T, width)`` rows -> ``(T, heads, rank)``."""
    tokens = q.shape[0]
    cached = _gathered(pool, table[None])[0]
    rows = jnp.concatenate([cached, own.astype(pool.dtype)], axis=0
                           ).astype(jnp.float32)
    s = jnp.einsum("thw,sw->ths", q.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    key = jnp.arange(rows.shape[0])
    n_cached = cached.shape[0]
    visible = jnp.where(key[None] < n_cached, key[None] < start,
                        key[None] - n_cached <= jnp.arange(tokens)[:, None])
    s = jnp.where(visible[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("ths,sr->thr", p, rows[:, :rank],
                      precision=jax.lax.Precision.HIGHEST).astype(q.dtype)


def latent_append_reference(pool, rows, block_ids, offsets=None):
    """``rows (batch, width)`` at ``pool[block_ids, offsets]``, or
    whole blocks ``rows (n, block_size, width)`` at ``pool[block_ids]``
    (``offsets`` None)."""
    rows = rows.astype(pool.dtype)
    if offsets is None:
        return pool.at[block_ids].set(rows)
    return pool.at[block_ids, offsets].set(rows)


# --------------------------------------------------------------------------- #
# The attention kernel: one body, two grids


def _latent_attention_kernel(tables_ref, lengths_ref,   # scalar prefetch
                             q_ref, pool_hbm, *rest, block_size: int,
                             blocks_per_iter: int, group_blocks: int,
                             heads: int, rank: int, sm_scale: float,
                             prefill: bool):
    """One program = one tile of query rows (``token * heads + head``)
    and a loop over the cached blocks its table row holds,
    ``blocks_per_iter`` an iteration, copied into one of two VMEM key
    buffers while the other is attended over: ONE online-softmax update
    (one max, one exp, one rescale of the accumulator) of the ``(rows,
    blocks_per_iter * block_size)`` score tile an iteration.

    Only a row's LAST iteration can hold a key it may not see (its
    ragged end, and entries past its last live block), so only that one
    is masked, by absolute key id; every other iteration attends with
    no iota, compare or select.

    Decode (grid ``(batch,)``): table row ``program_id``, keys
    ``0 .. lengths_ref[row] - 1`` (the step's own row is already in the
    pool).  Its 32 rows leave an iteration to the scalar core's copy
    descriptors, so the loop is unrolled by two, each half naming its
    buffer statically, and the next iteration's copies are issued
    AFTER this one's wait, unrolled, in the block that attends: the
    descriptors (a table entry read, an address and an enqueue a block)
    then share bundles with the vector work.  A row of ONE iteration
    (an idle slot on the scratch block, a short context) copies its
    live groups of ``group_blocks`` blocks only, from a loop.

    Prefill (grid ``(T / q_tile,)``): table row 0, the
    ``lengths_ref[0]`` cached keys all visible, then the slice's own
    rows (``own_ref``, VMEM) up to the tile's last query, causally.
    An iteration of its hundreds of rows is microseconds of vector and
    MXU work, so its copies come from a loop and its buffer is indexed
    by the iteration: one attending block in the loop and one after
    it, where decode's structure has six.  Its running max and sum
    are kept replicated over the lanes (``(rows, 128)``, what a
    ``(rows, 1)`` tile takes of VMEM anyway), so no use of them is a
    lane broadcast of 256 registers.

    An iteration copies all its ``blocks_per_iter`` entries, those past
    the row's last live block clamped to it (no entry the row does not
    own is dereferenced): up to one iteration's blocks less one, once a
    row.  Keys no copy wrote are masked and weigh zero; what they
    multiply has to be finite, so the buffers start as zeros and hold
    pool rows ever after."""
    if prefill:
        own_ref, o_ref, buf, sems, m_scr, l_scr, acc_scr = rest
        tile = pl.program_id(0)
        row, length = 0, lengths_ref[0]
    else:
        o_ref, buf, sems, m_scr, l_scr, acc_scr = rest
        row = pl.program_id(0)
        length = lengths_ref[row]
    rows = q_ref.shape[1]
    keys = blocks_per_iter * block_size
    n_blocks = (length + block_size - 1) // block_size
    last_live = jnp.minimum(n_blocks, tables_ref.shape[1]) - 1
    iterations = (n_blocks + blocks_per_iter - 1) // blocks_per_iter

    def copy(c, slot, at, resolve: bool):
        """The DMA of entry ``at`` of iteration ``c``; a descriptor
        built only to be waited on names block 0."""
        block = 0
        if resolve:
            entry = jnp.minimum(c * blocks_per_iter + at, last_live)
            block = tables_ref[row, entry]
        return pltpu.make_async_copy(
            pool_hbm.at[block],
            buf.at[slot, pl.ds(pl.multiple_of(at * block_size, block_size),
                               block_size)],
            sems.at[slot])

    @pl.when(pl.program_id(0) == 0)
    def _finite_buffers():
        buf[...] = jnp.zeros_like(buf)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    row_dtype = (jnp.float32 if buf.dtype == jnp.float32
                 else jnp.bfloat16)
    q_is_bf16 = q_ref.dtype == jnp.bfloat16
    # The queries' MXU terms are the program's, not an iteration's.
    q_terms = _mxu_terms(q_ref[0], row_dtype)

    def across(stat, n: int):
        """A row statistic against a tile of ``n`` lanes: ``(rows, 1)``
        broadcasts, ``(rows, LANES)`` is repeated and cut to ``n``."""
        if stat.shape[1] == 1:
            return stat
        if n <= LANES:
            return stat[:, :n]
        wide = jnp.tile(stat, (1, -(-n // LANES)))
        return wide if wide.shape[1] == n else wide[:, :n]

    def attend(k, visible):
        """Online-softmax update of every row from ``k (n, width)``;
        ``visible`` None: every row sees every key.  A masked score is
        NEG_INF against a running max that is finite from a row's first
        update on (the first keys a row meets, key 0 of its cache or of
        the slice, are visible to it), so a masked key's weight is
        ``exp`` of -1e30: zero, with no second select."""
        n = k.shape[0]
        k = k.astype(row_dtype)
        s = _contract_terms(q_terms, rows, k, ((1,), (1,))) * sm_scale
        if visible is not None:
            s = jnp.where(visible, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - across(m_new, n))
        correction = jnp.exp(m_prev - m_new)
        l_scr[:] = correction * l_scr[:] + jnp.sum(p, axis=-1,
                                                    keepdims=True)
        if q_is_bf16:
            p = p.astype(jnp.bfloat16)
        acc_scr[:] = acc_scr[:] * across(correction, rank) + _contract_terms(
            _mxu_terms(p, row_dtype), rows, k[:, :rank], ((1,), (0,)))
        m_scr[:] = m_new

    def for_groups(count, act):
        """``act(entry)`` on every entry of an iteration's first
        ``count`` groups, from a loop."""
        def group(g, carry):
            for i in range(group_blocks):
                act(g * group_blocks + i)
            return carry
        jax.lax.fori_loop(0, count, group, 0)

    all_groups = blocks_per_iter // group_blocks

    def start_all(c, slot):
        if prefill:
            for_groups(all_groups, lambda at: copy(c, slot, at, True).start())
            return

        def start(at, carry):
            copy(c, slot, at, True).start()
            return carry
        jax.lax.fori_loop(0, blocks_per_iter, start, 0, unroll=True)

    def step(c, slot, successor: bool, ragged: bool,
             live_groups=all_groups):
        """Iteration ``c``, whose copies fill ``buf[slot]``; ``ragged``:
        it is the row's last, the one that may hold a hidden key."""
        for_groups(live_groups, lambda at: copy(c, slot, at, False).wait())
        if successor:
            start_all(c + 1, 1 - slot)
        visible = None
        if ragged:
            visible = c * keys + jax.lax.broadcasted_iota(
                jnp.int32, (rows, keys), 1) < length
        attend(buf[slot], visible)

    last = iterations - 1
    if prefill:
        @pl.when(iterations > 0)
        def _cached():
            start_all(0, 0)

            def whole(c, carry):
                step(c, jax.lax.rem(c, 2), True, False)
                return carry

            jax.lax.fori_loop(0, last, whole, 0)
            step(last, jax.lax.rem(last, 2), False, True)
    else:
        @pl.when(iterations == 1)
        def _one_iteration():
            live_groups = (n_blocks + group_blocks - 1) // group_blocks
            for_groups(live_groups, lambda at: copy(0, 0, at, True).start())
            step(0, 0, False, True, live_groups)

        @pl.when(iterations > 1)
        def _iterations():
            start_all(0, 0)
            pairs = last // 2

            def pair(j, carry):
                step(2 * j, 0, True, False)
                step(2 * j + 1, 1, True, False)
                return carry

            jax.lax.fori_loop(0, pairs, pair, 0)
            # What the pairs left: the last iteration, or the last two.
            two_left = last == 2 * pairs + 1

            @pl.when(two_left)
            def _last_two():
                step(last - 1, 0, True, False)
                step(last, 1, False, True)

            @pl.when(jnp.logical_not(two_left))
            def _last_one():
                step(last, 0, False, True)

    if prefill:
        tokens = own_ref.shape[0]
        q_tile = rows // heads
        first_query = tile * q_tile
        query = first_query + jax.lax.broadcasted_iota(
            jnp.int32, (rows, min(keys, tokens)), 0) // heads
        for chunk in range(0, tokens, keys):
            size = min(keys, tokens - chunk)

            @pl.when(chunk < first_query + q_tile)
            def _own(chunk=chunk, size=size):
                key = chunk + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, size), 1)
                attend(own_ref[chunk:chunk + size], key <= query[:, :size])
    denom = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
    o_ref[0] = (acc_scr[:] / across(denom, rank)).astype(o_ref.dtype)


def keys_per_iteration(query_rows: int, block_size: int) -> int:
    """Keys one pass through a program's loop covers, from what the
    call can see: the program's query rows and the pool's block size.

    An iteration pays, whatever its width, for a serial chain (wait
    for the copies, QK, lane max, exp, PV, rescale) and for its softmax
    state: two lane reductions, the correction's ``exp`` and the f32
    accumulator's read, rescale and write, all of them per query ROW.
    A decode program of 32 rows took 0.58 us per 128 keys computing at
    128 keys an iteration and 0.18 at 1,024 (TPU v5e, PERF.md section
    6, PR 32); a prefill tile of 2,048 rows 6.2 us per 128 keys at 128
    keys an iteration, 3.4 at 256, 2.3 at 512 and 2.1 at 1,024, whose
    8 MiB score tile then costs a slice over nothing 0.17 ms (PERF.md
    section 6, PR 35).  So an iteration takes as many keys as its
    temporaries (:data:`ITERATION_BYTES_PER_SCORE` a row a key) fit in
    :data:`ITERATION_VMEM_BYTES`, a quarter of the programs' VMEM
    limit, in whole :data:`KEYS_PER_GROUP` groups between one group
    and :data:`MAX_KEYS_PER_ITERATION`: 512 keys for a prefill tile of
    2,048 rows (a 4 MiB score tile), the most for 1,024 rows or fewer,
    a decode program's 32 among them."""
    group = max(KEYS_PER_GROUP, block_size)
    keys = (ITERATION_VMEM_BYTES // (ITERATION_BYTES_PER_SCORE * query_rows)
            // group * group)
    return max(group, min(keys, MAX_KEYS_PER_ITERATION // group * group))


def _attention_call(q_tiles, pool, tables, lengths, own, *, heads: int,
                    rank: int, sm_scale: float, interpret: bool,
                    out_dtype):
    """``q_tiles (programs, rows, width)`` through the kernel;
    ``own`` None for decode."""
    programs, rows, width = q_tiles.shape
    block_size = pool.shape[1]
    group_blocks = max(1, KEYS_PER_GROUP // block_size)
    keys = keys_per_iteration(rows, block_size)
    prefill = own is not None
    stat_lanes = LANES if prefill else 1

    def tile_index(i, tables_ref, lengths_ref):
        return (i, 0, 0)

    in_specs = [pl.BlockSpec((1, rows, width), tile_index),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q_tiles, pool]
    if prefill:
        in_specs.append(pl.BlockSpec(
            own.shape, lambda i, tables_ref, lengths_ref: (0, 0)))
        operands.append(own.astype(pool.dtype))
    kernel = functools.partial(
        _latent_attention_kernel, block_size=block_size,
        blocks_per_iter=keys // block_size, group_blocks=group_blocks,
        heads=heads, rank=rank, sm_scale=sm_scale, prefill=prefill)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(programs,), in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, rank), tile_index),
        scratch_shapes=[
            pltpu.VMEM((2, keys, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, stat_lanes), jnp.float32),
            pltpu.VMEM((rows, stat_lanes), jnp.float32),
            pltpu.VMEM((rows, rank), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((programs, rows, rank), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)


def _kernel_runs(use_kernel, interpret: bool) -> bool:
    """A caller that asked the mode variables passes their answer
    (``use_kernel``); called bare (None), the entry rule of the paged
    kernels: interpreting or on the TPU the kernel runs, anywhere else
    the jnp form is the path."""
    return runs_kernel(interpret) if use_kernel is None else use_kernel


def latent_decode_attention(q, pool, tables, positions, *, rank: int,
                            sm_scale: float, interpret: bool = False,
                            use_kernel=None):
    """Ragged paged latent decode attention: ``q (batch, heads,
    width)``, ONE query token a row whose own cache row is already in
    the pool; ``tables (batch, table width)``; keys
    ``0..positions[row]`` visible.  Returns ``(batch, heads, rank)`` in
    ``q.dtype``: the softmax-weighted ``c_kv`` rows, still latent."""
    if not _kernel_runs(use_kernel, interpret):
        return latent_decode_reference(q, pool, tables, positions,
                                       rank=rank, sm_scale=sm_scale)
    return closed_call(q, pool, tables, positions, rank=rank,
                       sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def closed_call(q, pool, tables, positions, *, rank: int, sm_scale: float,
                interpret: bool):
    """The decode kernel's call behind a jit of its own (one trace and
    one Mosaic lowering a program).  The name is the one
    :func:`.paged_attention.closed_call` carries, for the same reason:
    ``benchmark/layer_metrics/decode_attn_roofline.json`` finds a
    paged decode attention kernel in a device trace as
    ``%closed_call.N`` with a 3-D result and the block tables first,
    and this is the decode attention of the models it serves.

    The queries ride as f32 rows (three bf16 terms in the kernel:
    f32's precision at one pass over the bf16 pool rows, as there)."""
    return _attention_call(q.astype(jnp.float32), pool, tables,
                           positions + 1, None, heads=q.shape[1],
                           rank=rank, sm_scale=sm_scale,
                           interpret=interpret, out_dtype=q.dtype)


def latent_prefill_attention(q, own, pool, table, start, *, rank: int,
                             sm_scale: float, interpret: bool = False,
                             use_kernel=None):
    """A prefill slice's attention: ``q (T, heads, width)`` of
    positions ``start .. start + T - 1`` of ONE row, its ``own (T,
    width)`` cache rows (not yet in the pool), and ``table (table
    width,)`` whose first ``start / block_size`` blocks hold the row's
    earlier positions — written by its earlier slices or shared from
    the prefix cache, read in place either way.  ``start`` is a
    multiple of the block size.  Returns ``(T, heads, rank)``."""
    if not _kernel_runs(use_kernel, interpret):
        return latent_prefill_reference(q, own, pool, table, start,
                                        rank=rank, sm_scale=sm_scale)
    return latent_prefill_call(q, own, pool, table, start, rank=rank,
                               sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def latent_prefill_call(q, own, pool, table, start, *, rank: int,
                        sm_scale: float, interpret: bool):
    """The prefill kernel's call, a jit of its own name."""
    tokens, heads, width = q.shape
    q_tile = min(PREFILL_Q_TILE, tokens)
    if tokens % q_tile:
        raise ValueError(f"a slice of {tokens} tokens is not whole "
                         f"query tiles of {q_tile}")
    out = _attention_call(
        q.reshape(tokens // q_tile, q_tile * heads, width), pool,
        table[None], jnp.reshape(start, (1,)), own, heads=heads,
        rank=rank, sm_scale=sm_scale, interpret=interpret,
        out_dtype=q.dtype)
    return out.reshape(tokens, heads, rank)


# --------------------------------------------------------------------------- #
# The append kernel


def _latent_append_kernel(blocks_ref,                     # scalar prefetch
                          offsets_ref, rows_ref, pool_hbm, pool_out, buf,
                          sems, *,
                          whole_blocks: bool):
    """Grid ``(1,)``.  ``whole_blocks``: ``rows_ref (n, block_size,
    width)`` copied to ``pool[blocks_ref[i]]``.  Else, per batch row
    ``r`` whose block is not the scratch block 0: its block is read,
    row ``offsets_ref[r]`` of it replaced by ``rows_ref[r]`` (a
    selection: the other keys pass through bit for bit) and the block
    written back.  Every row's copy is in flight before the first
    wait: a row costs a DMA's latency, not its bytes."""
    count = rows_ref.shape[0]

    def for_rows(act):
        def body(r, carry):
            @pl.when(jnp.logical_or(whole_blocks, blocks_ref[r] != 0))
            def _live():
                act(r)
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    if whole_blocks:
        def write(r):
            return pltpu.make_async_copy(
                rows_ref.at[r], pool_out.at[blocks_ref[r]], sems.at[0])
        for_rows(lambda r: write(r).start())
        for_rows(lambda r: write(r).wait())
        return

    def read(r):
        return pltpu.make_async_copy(pool_hbm.at[blocks_ref[r]], buf.at[r],
                                     sems.at[0])

    def write(r):
        return pltpu.make_async_copy(buf.at[r], pool_out.at[blocks_ref[r]],
                                     sems.at[1])

    for_rows(lambda r: read(r).start())
    for_rows(lambda r: read(r).wait())
    key = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
    buf[...] = jnp.where(key == offsets_ref[...][:, :, None],
                         rows_ref[...][:, None, :], buf[...])
    for_rows(lambda r: write(r).start())
    for_rows(lambda r: write(r).wait())


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def latent_append(pool, rows, block_ids, offsets=None, *,
                  interpret: bool = False, use_kernel=None):
    """Cache rows into a latent pool, in place (the pool is aliased to
    the result and has to be the caller's to overwrite: a scan's carry
    or a donated argument, as in every serving program).

    ``offsets (batch,)``: ``rows (batch, width)`` land at
    ``pool[block_ids, offsets]`` — a decode step's one row a slot; rows
    whose block is the reserved scratch block 0 (idle slots) are
    skipped, and two live rows never write one block.  ``offsets``
    None: ``rows (n, block_size, width)`` replace the blocks
    ``block_ids`` — a prefill slice.  No pass over the pool either
    way."""
    if not _kernel_runs(use_kernel, interpret):
        return latent_append_reference(pool, rows, block_ids, offsets)
    whole_blocks = offsets is None
    count = rows.shape[0]
    block_size, width = pool.shape[1:]
    rows = rows.astype(pool.dtype)
    block_ids = block_ids.astype(jnp.int32)
    if whole_blocks:
        offsets_2d = jnp.zeros((1, 1), jnp.int32)
    else:
        offsets_2d = offsets.astype(jnp.int32)[:, None]

    def whole(array):
        return pl.BlockSpec(array.shape,
                            lambda i, *prefetch: (0,) * array.ndim)

    in_place = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[whole(offsets_2d), whole(rows), in_place],
        out_specs=in_place,
        scratch_shapes=[
            pltpu.VMEM((1 if whole_blocks else count, block_size, width),
                       pool.dtype),
            pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_latent_append_kernel,
                          whole_blocks=whole_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=2 * count * block_size * width
            * pool.dtype.itemsize),
        interpret=interpret,
    )(block_ids, offsets_2d, rows, pool)
