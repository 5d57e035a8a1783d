"""Ragged paged decode-attention: a Pallas TPU kernel that walks each
row's block table directly in HBM, plus the jnp oracle it must match.

Decode attention is the serving hot path: one query token per row
against that row's whole KV history.  The fallback implementation
(:func:`cached_gqa_attention`, shared with chunked prefill and
speculative verify) masks over the FULL preallocated cache — O(max_seq)
HBM reads per row per step no matter how short the row really is, and
the paged layout must first gather its blocks into a contiguous bucket.
The kernel here reads only the blocks a row actually occupies:

* grid ``(batch-row, block)``; the block axis is fastest-varying, so
  one program instance sweeps one row through its live blocks carrying
  online-softmax state for every head in VMEM scratch (flash-decoding
  style — running max ``m``, denominator ``l``, accumulator ``acc`` in
  f32).
* a program's K/V tile is one WHOLE pool block ``(block_size,
  kv_heads, head_dim)``: the tile's last two dimensions are the
  array's own, which is the only cut of this pool layout Mosaic's
  (sublane, lane) tiling accepts (a one-head tile is refused: 1 is
  neither a multiple of 8 nor ``kv_heads``), and the DMA is one
  contiguous copy.  Heads are split in-kernel
  (:func:`load_head_rows`).
* the block table and per-row positions ride scalar prefetch
  (``PrefetchScalarGridSpec``), so the K/V BlockSpec index maps resolve
  ``tables[row, j]`` into a pool block id BEFORE the body runs — the
  DMA engine streams exactly the row's own blocks, nothing else.
* dead grid steps (``j`` past the row's last live block, or wholly
  below the sliding window) clamp their index map to a resident block
  and skip compute via ``pl.when`` — no HBM traffic, (almost) no work.
* all ``group = n_heads // n_kv_heads`` query heads of a kv head share
  one (group, head_dim) × (head_dim, block_size) matmul per block
  instead of ``group`` skinny dot products.
* int8 KV dequantizes in-kernel: the block's per-(token, head) scale
  plane loads whole, the head's column broadcast-multiplies its rows
  right after the load — the cache is read at 1 byte/element and no
  bf16 copy of it ever exists.

The contiguous ragged cache is the degenerate case: reshape
``(batch, S, kv, hd)`` to ``(batch·S/bs, bs, kv, hd)`` with iota block
tables (a free reshape) and the same kernel serves both layouts.

Layout contract and dispatch rules are documented in docs/KERNELS.md.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF

__all__ = ["paged_decode_attention", "paged_decode_reference",
           "cached_gqa_attention", "kernel_mode", "runs_kernel",
           "decode_kernel_mode",
           "decode_dispatch", "decode_attention_path",
           "contiguous_block_size", "kernel_serves", "load_head_rows"]

#: Maximum pool block size the degenerate contiguous view uses — small
#: enough that short rows skip most of the cache, large enough for the
#: MXU's lane dimension.
CONTIGUOUS_BLOCK_CAP = 128

#: Fallback dequantization span cap (see :func:`_dequant_block`).
DEQUANT_BLOCK_CAP = 512


# ---------------------------------------------------------------------------
# Dispatch policy


def kernel_mode(env_var: str) -> Tuple[bool, bool]:
    """``(use_kernel, interpret)`` from one of the attention mode
    variables (read at TRACE time — set it before the first call of a
    given shape, jit caches traces):

    * ``auto`` (default): kernel on TPU, jnp reference elsewhere.
    * ``kernel``: force the kernel; off-TPU it runs in interpret mode
      (slow — testing only).
    * ``interpret``: kernel in interpret mode everywhere.
    * ``reference`` / ``off`` / ``0``: always the jnp reference.
    """
    mode = os.environ.get(env_var, "auto").lower()
    if mode in ("reference", "fallback", "off", "0"):
        return False, False
    on_tpu = jax.default_backend() == "tpu"
    if mode in ("kernel", "force"):
        return True, not on_tpu
    if mode == "interpret":
        return True, True
    return on_tpu, False


def decode_kernel_mode() -> Tuple[bool, bool]:
    """:func:`kernel_mode` of ``AIKO_DECODE_ATTENTION``."""
    return kernel_mode("AIKO_DECODE_ATTENTION")


def runs_kernel(interpret: bool) -> bool:
    """Entry rule of the public kernels when called directly:
    interpreting, or on the TPU backend, the kernel runs (and refuses,
    loudly, a geometry it cannot serve — callers that want the
    reference there ask the ``*_dispatch`` functions first, which is
    also what the serving path tags report); anywhere else the jnp
    oracle IS the path."""
    return interpret or jax.default_backend() == "tpu"


def kernel_serves(head_dim: int, kv_heads: int, pool_dtype,
                  interpret: bool) -> bool:
    """Can the paged kernels (decode, append, verify) serve a pool of
    this geometry?  ``kv_heads`` is the LOCAL count under tensor
    parallelism.  One head's row rides the lane axis, so ``head_dim``
    may not exceed 128 anywhere.  Compiled, the whole-block tile
    ``(block_size, kv_heads, head_dim)`` must also sit on Mosaic's
    tiling, which refuses everything else (TPU v5e, PR 21):

    * lanes: ``head_dim`` a multiple of 128 — 64 fails with "Slice
      shape along dimension 3 must be aligned to tiling (128)";
    * sublanes: 32-bit words, so ``kv_heads`` a multiple of the rows a
      word packs (1 f32, 2 bf16, 4 int8) — two int8 heads, TP=4 of an
      8-kv-head model with int8 KV, fail with "Slice shape along
      dimension 2 must be aligned to tiling (4), but is 2"."""
    if head_dim > 128:
        return False
    if interpret:
        return True
    packing = 4 // jnp.dtype(pool_dtype).itemsize
    return head_dim == 128 and kv_heads % packing == 0


def decode_dispatch(head_dim: int, kv_heads: int,
                    pool_dtype) -> Tuple[bool, bool]:
    """``(use_kernel, interpret)`` for one pool geometry: the mode from
    :func:`decode_kernel_mode`, and the reference for a geometry the
    kernel cannot serve (:func:`kernel_serves`) — so nothing else runs
    under the kernel's name.  The serving path tag is this same
    answer."""
    use_kernel, interpret = decode_kernel_mode()
    return (use_kernel and kernel_serves(head_dim, kv_heads, pool_dtype,
                                         interpret)), interpret


def decode_attention_path(head_dim: int, kv_heads: int,
                          pool_dtype) -> str:
    """``"kernel"`` or ``"reference"`` — the serving-counter path tag,
    decided by :func:`decode_dispatch` at the server's real geometry."""
    use_kernel, _ = decode_dispatch(head_dim, kv_heads, pool_dtype)
    return "kernel" if use_kernel else "reference"


def contiguous_block_size(max_seq: int) -> int:
    """Block size for viewing a contiguous ``(batch, max_seq, kv, hd)``
    cache as a degenerate block pool, or 0 when no usable size exists
    (→ caller falls back to the jnp reference).  Largest power of two
    dividing ``max_seq``, capped at :data:`CONTIGUOUS_BLOCK_CAP`; at
    least 16 so blocks meet the int8 sublane tile."""
    if max_seq <= 0:
        return 0
    bs = min(max_seq & -max_seq, CONTIGUOUS_BLOCK_CAP)
    return bs if bs >= 16 else 0


# ---------------------------------------------------------------------------
# jnp oracle (also the CPU / chunked-prefill / speculative-verify path)


def _dequant_block(seq: int) -> int:
    """Span the quantized fallback dequantizes at a time: the largest
    power-of-two divisor of ``seq`` capped at
    :data:`DEQUANT_BLOCK_CAP`, halved if it would cover the whole
    cache — so a full-cache bf16 copy is never materialized (the kv8
    regression: reading int8 at 1 byte/elem is the POINT of the
    layout; a wholesale ``astype`` turns that into 5 bytes/elem of
    traffic).  Odd ``seq`` degenerates to the single-span path."""
    if seq <= 1 or seq % 2:
        return seq
    block = min(seq & -seq, DEQUANT_BLOCK_CAP)
    if block == seq:
        block = seq // 2
    return block


def _quantized_scores(q, k_cache, ks, hd):
    """q·k scores against an int8 K cache, dequantizing one
    :func:`_dequant_block` span per loop step — numerically identical
    per element to the single-shot einsum (the hd contraction never
    crosses span boundaries), with peak extra memory O(span) instead
    of O(max_seq).  Returns f32 ``(b, kv, group, Q, S)``."""
    seq = k_cache.shape[1]
    span = _dequant_block(seq)
    scale = hd ** -0.5

    def span_scores(k_blk, ks_blk):
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_blk.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        return s * ks_blk.transpose(0, 2, 1)[:, :, None, None, :]

    if span == seq:
        return span_scores(k_cache, ks)
    batch, Q, kv, group = (q.shape[0], q.shape[1], q.shape[2],
                           q.shape[3])
    init = jnp.zeros((batch, kv, group, Q, seq), jnp.float32)

    def body(i, buf):
        start = i * span
        k_blk = jax.lax.dynamic_slice_in_dim(k_cache, start, span, 1)
        ks_blk = jax.lax.dynamic_slice_in_dim(ks, start, span, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, span_scores(k_blk, ks_blk), start, axis=4)

    return jax.lax.fori_loop(0, seq // span, body, init)


def _quantized_weighted_sum(weights, v_cache, vs, out_dtype):
    """``softmax-weights @ V`` against an int8 V cache, one span at a
    time with f32 accumulation across spans.  ``weights`` f32
    ``(b, kv, group, Q, S)``; returns ``(b, Q, kv, group, hd)``."""
    seq = v_cache.shape[1]
    span = _dequant_block(seq)

    def span_sum(w_blk, v_blk, vs_blk):
        w = w_blk * vs_blk.transpose(0, 2, 1)[:, :, None, None, :]
        return jnp.einsum("bkgqs,bskd->bqkgd", w.astype(out_dtype),
                          v_blk.astype(out_dtype),
                          preferred_element_type=jnp.float32)

    if span == seq:
        return span_sum(weights, v_cache, vs).astype(out_dtype)
    batch, kv, group, Q = weights.shape[:4]
    hd = v_cache.shape[-1]
    init = jnp.zeros((batch, Q, kv, group, hd), jnp.float32)

    def body(i, acc):
        start = i * span
        v_blk = jax.lax.dynamic_slice_in_dim(v_cache, start, span, 1)
        vs_blk = jax.lax.dynamic_slice_in_dim(vs, start, span, 1)
        w_blk = jax.lax.dynamic_slice_in_dim(weights, start, span, 4)
        return acc + span_sum(w_blk, v_blk, vs_blk)

    acc = jax.lax.fori_loop(0, seq // span, body, init)
    return acc.astype(out_dtype)


def cached_gqa_attention(q, cache_layer, query_positions, hd,
                         window: Optional[int] = None):
    """Masked GQA attention over a KV cache — the jnp oracle shared by
    ragged decode (CPU fallback), chunked prefill, and speculative
    verify.  ``q`` (batch, Q, kv, group, hd); ``query_positions``
    (batch, Q) absolute positions; key row ``s`` is attended iff ``s <=
    position`` of the query (and within ``window`` of it, when
    sliding-window attention is on).

    Int8 KV layout: per-(token, head) scales factor OUT of the q·k
    contraction (over hd), so they multiply the score afterwards; on
    the value side they factor INTO the softmax weights (contraction is
    over tokens), so the weights are scaled per key row before the
    weighted sum — both exact dequantizations.  Dequantization runs one
    :func:`_dequant_block` span at a time so the int8 cache is read at
    1 byte/element and no full-cache bf16 copy is ever materialized
    (asserted by tests/test_paged_attention.py on the decode jaxpr)."""
    k_cache, v_cache = cache_layer["k"], cache_layer["v"]
    quantized = "ks" in cache_layer
    if quantized:
        s = _quantized_scores(q, k_cache, cache_layer["ks"], hd)
    else:
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k_cache,
                       preferred_element_type=jnp.float32) * hd ** -0.5
    if "pos" in cache_layer:
        # Rolling layout: each row stores its ABSOLUTE position (-1 =
        # never written); visibility is decided from those, so ring
        # wraparound needs no special casing.
        key_pos = cache_layer["pos"][:, None, :]     # (b, 1, S)
        mask = (key_pos >= 0) & (key_pos
                                 <= query_positions[:, :, None])
    else:
        key_pos = jnp.arange(k_cache.shape[1])[None, None, :]
        mask = key_pos <= query_positions[:, :, None]
    if window is not None:
        mask &= key_pos > query_positions[:, :, None] - window
    s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
    weights = jax.nn.softmax(s, axis=-1)
    if quantized:
        return _quantized_weighted_sum(weights, v_cache,
                                       cache_layer["vs"], q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd",
                      weights.astype(v_cache.dtype), v_cache)


def paged_decode_reference(q, k_pool, v_pool, tables, positions,
                           ks=None, vs=None,
                           window: Optional[int] = None):
    """Gather-then-masked-attend oracle for the kernel: pool[tables] →
    per-row contiguous view, then :func:`cached_gqa_attention`.  ``q``
    (batch, kv, group, hd); pools (n_blocks, bs, kv, hd); returns
    (batch, kv, group, hd)."""
    def view(pool):
        gathered = pool[tables]
        batch, n_blocks, bs = gathered.shape[:3]
        return gathered.reshape((batch, n_blocks * bs)
                                + gathered.shape[3:])

    cache_layer = {"k": view(k_pool), "v": view(v_pool)}
    if ks is not None:
        cache_layer["ks"] = view(ks)
        cache_layer["vs"] = view(vs)
    hd = q.shape[-1]
    out = cached_gqa_attention(q[:, None], cache_layer,
                               positions[:, None], hd, window=window)
    return out[:, 0]


# ---------------------------------------------------------------------------
# The kernel


#: The kernels' matmuls contract at f32 precision.  The MXU's default
#: rounds f32 operands to bf16, which on the chip put the compiled
#: kernels 3e-3..1e-2 from their oracles where the interpreter sits at
#: 1e-6 (PERF.md, PR 21); what precision the serving dtype can afford
#: is a decision for a measurement against a logits tolerance
#: (ROADMAP C3), not a default to inherit unseen.
MXU_PRECISION = jax.lax.Precision.HIGHEST


def load_head_rows(block_ref, head: int):
    """One kv head of a ``(block_size, kv_heads, head_dim)`` pool tile
    as f32 ``(block_size, head_dim)`` rows — shared by the decode and
    append-attention kernels.  The tile's sublane axis is ``kv_heads``,
    so this is a strided read (every ``kv_heads``-th row, sub-word rows
    for bf16 and int8), which Mosaic lowers for f32, bf16 and int8
    tiles (compiled and compared on a v5e, PR 21)."""
    return block_ref[:, head, :].astype(jnp.float32)


def _paged_decode_kernel(tables_ref, positions_ref,   # scalar prefetch
                         q_ref, k_ref, v_ref, *rest,
                         block_size: int, group: int, sm_scale: float,
                         window: Optional[int], quantized: bool):
    """Grid: (batch, blocks); blocks fastest-varying.

    One program = one row × one pool block, every head.  Scratch
    carries the online-softmax state of all ``kv_heads · group`` query
    heads (one scratch row each, kv-head major) across the block
    sweep.  ``tables_ref`` / ``positions_ref`` are the scalar-prefetched
    block table and per-row positions (also consumed by the K/V index
    maps in :func:`paged_decode_attention`)."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    row = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    pos = positions_ref[row]
    kv_heads = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Liveness: a block past the row's length contributes nothing, and
    # with a sliding window neither does a block whose LAST key is
    # already out of the window.  Dead steps also clamp their index map
    # (see kv_index) so they trigger no HBM→VMEM copy.  Every live
    # block provably contains ≥1 visible key, so no bogus softmax mass
    # is ever accumulated (NEG_INF stays finite regardless — see
    # ops/attention.py).
    block_live = j * block_size <= pos
    if window is not None:
        block_live &= (j + 1) * block_size - 1 > pos - window

    @pl.when(block_live)
    def _compute():
        key_ids = jax.lax.broadcasted_iota(
            jnp.int32, (group, block_size), 1) + j * block_size
        visible = key_ids <= pos
        if window is not None:
            visible &= key_ids > pos - window
        if quantized:
            k_scales = ks_ref[0]                       # (bs, kv_heads)
            v_scales = vs_ref[0]
        for head in range(kv_heads):
            rows = slice(head * group, (head + 1) * group)
            q = q_ref[0, rows, :]                      # (group, hd) f32
            k = load_head_rows(k_ref.at[0], head)      # (bs, hd) f32
            v = load_head_rows(v_ref.at[0], head)
            if quantized:
                # The head's scale column broadcasts along hd —
                # dequantization never leaves VMEM.
                k = k * k_scales[:, head:head + 1]
                v = v * v_scales[:, head:head + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=MXU_PRECISION,
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(visible, s, NEG_INF)         # (group, bs)

            m_prev = m_scr[rows, :]                    # (group, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            correction = jnp.exp(m_prev - m_new)
            l_scr[rows, :] = correction * l_scr[rows, :] + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_scr[rows, :] = (
                acc_scr[rows, :] * correction + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    precision=MXU_PRECISION,
                    preferred_element_type=jnp.float32))
            m_scr[rows, :] = m_new

    @pl.when(j == num_j - 1)
    def _finish():
        denom = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, positions,
                           ks=None, vs=None,
                           window: Optional[int] = None,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False):
    """Ragged paged GQA decode attention.

    Args:
      q: ``(batch, kv_heads, group, head_dim)`` — ONE query token per
        row, all query heads of each kv head together.
      k_pool / v_pool: ``(n_blocks, block_size, kv_heads, head_dim)``
        block pools (bf16/f32, or int8 with ``ks``/``vs``).
      tables: ``(batch, max_blocks)`` int32 — pool block id of each
        row's logical block ``j`` (entries past the row's length are
        never read).
      positions: ``(batch,)`` int32 — the query's absolute position;
        keys ``0..positions[row]`` are visible (the current token's K/V
        must already be written to the pool).
      ks / vs: optional ``(n_blocks, block_size, kv_heads)`` f32
        per-(token, head) scales → int8 in-kernel dequantization.
      window: sliding-window size (Mistral semantics, matches
        :func:`cached_gqa_attention`).
      interpret: run the Pallas kernel in interpret mode (CPU testing).

    Returns ``(batch, kv_heads, group, head_dim)`` in ``q.dtype``.
    Off the TPU backend (and not interpreting) this IS
    :func:`paged_decode_reference`, the CPU path; see
    :func:`runs_kernel`.
    """
    batch, kv_heads, group, head_dim = q.shape
    block_size = k_pool.shape[1]
    max_blocks = tables.shape[1]
    quantized = ks is not None
    if sm_scale is None:
        sm_scale = head_dim ** -0.5

    if not runs_kernel(interpret):
        return paged_decode_reference(q, k_pool, v_pool, tables,
                                      positions, ks=ks, vs=vs,
                                      window=window)
    if not kernel_serves(head_dim, kv_heads, k_pool.dtype, interpret):
        raise ValueError(
            f"paged decode kernel cannot serve head_dim={head_dim} "
            f"kv_heads={kv_heads} {k_pool.dtype} pools")

    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    heads = kv_heads * group
    grid = (batch, max_blocks)

    def kv_index(row, j, tables_ref, positions_ref):
        # Clamp dead steps into the live band [first_live, last_live]:
        # an unchanged block index means Pallas reuses the resident
        # VMEM tile instead of issuing a fresh HBM copy, so a row's
        # HBM traffic is O(its actual length), not O(max_seq).
        pos = positions_ref[row]
        j_c = jnp.minimum(j, pos // block_size)
        if window is not None:
            first_live = jnp.maximum(pos - window + 1, 0) // block_size
            j_c = jnp.maximum(j_c, first_live)
        return (tables_ref[row, j_c], 0, 0, 0)

    def scale_index(row, j, tables_ref, positions_ref):
        return kv_index(row, j, tables_ref, positions_ref)[:3]

    def q_index(row, j, tables_ref, positions_ref):
        return (row, 0, 0)

    # Queries ride as f32 rows, kv-head major (row = kv_head·group + g):
    # a few KB that XLA widens once, so a head's rows are a plain
    # sublane slice of an unpacked tile.
    q_rows = q.reshape(batch, heads, head_dim).astype(jnp.float32)
    block = (1, block_size, kv_heads, head_dim)
    in_specs = [
        pl.BlockSpec((1, heads, head_dim), q_index),
        pl.BlockSpec(block, kv_index),
        pl.BlockSpec(block, kv_index),
    ]
    operands = [q_rows, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec(block[:3], scale_index),
                     pl.BlockSpec(block[:3], scale_index)]
        operands += [ks, vs]

    kernel = functools.partial(
        _paged_decode_kernel, block_size=block_size, group=group,
        sm_scale=sm_scale, window=window, quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, head_dim), q_index),
        scratch_shapes=[
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, 1), jnp.float32),
            pltpu.VMEM((heads, head_dim), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q.dtype),
        interpret=interpret,
    )(tables, positions, *operands)
    return out.reshape(q.shape)
