"""Byte-level decoder with EVA attention (``evabyte``): a window of
exact K/V rows and one summary row for every chunk behind it, served
through the same paged engine as :mod:`.llama`.

Every layer is two pre-norm residual blocks; the residual stream is
float32 (``fp32_skip_add``) and an RMSNorm's weight is ``1 + g``
(``norm_add_unit_offset``).  Attention has as many kv heads as query
heads.  With ``W = window_size`` and ``C = chunk_size``, position ``t``
lies in window ``w(t) = t // W`` and chunk ``j = t // C``:

* a chunk's **summary** is one K/V row a head, from the chunk's rotated
  keys and its values: ``a_m = softmax_m(s phi . k_m)`` over the
  chunk's positions, ``k~_j = sum_m a_m k_m + mu`` and
  ``v~_j = sum_m a_m v_m``, with learned per-head ``phi`` and ``mu``
  and ``s = head_dim ** -0.5``;
* query ``t`` sees the positions ``W w(t) .. t`` of its own window
  exactly, and every chunk of every EARLIER window as its summary,
  under one softmax.

So for ``t < W`` the layer is causal softmax attention, and a query
never sees more than ``W + t / C`` rows.  The head gives
``num_pred_heads`` next-byte distributions a position (head ``i`` the
byte ``i + 1`` ahead); the served path picks from head 0.

The cache.  One pool of K/V blocks as :mod:`.llama`'s, the pool block
being one chunk (``block_size == chunk_size``): **one exact block is
one chunk is one summary row**.  A slot's table row, which the engine
fills at admission and never edits (:func:`slot_blocks`), is
``[ring ‖ summaries]``: ``W / C`` exact blocks that every window writes
anew, then ``W / C**2`` summary blocks for each window the request can
reach.  What the K/V kernels are handed is the **composed table**
(:func:`composed_tables`) ``[summary blocks of the windows behind ‖
ring]`` and the **composed position** (:func:`composed_positions`)
``(W / C) w + (t - W w)``, both reckoned on the device from the
absolute position, which RoPE and the window arithmetic keep: the decode
and append kernels then see an ordinary causal row.  A chunk's summary
is written when the chunk ends (a decode step makes it from the chunk's
block in the pool, :mod:`..ops.chunk_summary`; a prefill slice has its
rows in hand) into the summary blocks of the window IN PROGRESS, which the composed table does
not show; it shows them once ``w`` has moved on, inside whatever step
crosses the window's end, so no program depends on where that falls.
A chunk a prompt leaves partial (or a bucket pads) is summarised again
by the decode step that completes it, before anything can read it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.chunk_summary import chunk_summary, chunk_summary_reference
from ..ops.paged_attention import (decode_dispatch, paged_decode_attention,
                                   paged_decode_reference)
from ..ops.paged_prefill import (paged_prefill_attention,
                                 paged_prefill_reference, prefill_dispatch,
                                 prefill_key_blocks)
from ..ops.quant import is_quantized, quantize_named_int8
from .llama import (_embed_lookup, _kv_layer_buffers, _matmul,
                    _paged_write_rows, _quantize_pairs, _rest_scale_planes,
                    _rope_freqs, _scale_planes, _scan_scale_rows,
                    _serve_scan, apply_rope, rms_norm, scatter_state_rows)
from .mistral4 import _program

__all__ = ["EvaByteConfig", "CONFIGS", "COUNTERS", "CACHE_COUNTERS",
           "RECURRENT_STATE", "UNSUPPORTED", "init_params",
           "quantize_params", "forward", "summarise", "init_paged_cache",
           "kv_pool_layers", "kv_geometry", "state_bytes_per_slot",
           "layer_kinds", "slice_key_blocks", "check_layout",
           "table_blocks", "slot_blocks", "block_kinds",
           "composed_positions", "composed_tables", "cache_rows",
           "cache_events", "prefill_append_paged", "serve_chunk_paged",
           "serve_chunk_mixed", "scatter_state_rows"]

RECURRENT_STATE = False
#: Nothing comes back with a serve chunk beside its tokens: what the
#: cache did is reckoned on the host from the positions
#: (:data:`CACHE_COUNTERS`).
COUNTERS = ()
#: The engine's counters of a cache of two kinds of row, in
#: ``server.counters`` (:func:`cache_events` and :func:`cache_rows`
#: give their increments; docs/OBSERVABILITY.md).
CACHE_COUNTERS = ("eva_windows_closed", "eva_chunks_summarised",
                  "eva_blocks_returned", "decode_summary_blocks_read",
                  "eva_rows_held", "eva_positions_held")

UNSUPPORTED = ("a cache of a window's exact rows and the summaries of "
               "the chunks behind it", {
    "prefix_cache": "chains of two block kinds with a window in "
                    "progress in the index (a prefix's summary blocks "
                    "are shareable, its ring is not)",
    "host_tier": "demotion of a chain that is two kinds of block and "
                 "a window in progress",
    "spill": "spilled chains that are two kinds of block and a window "
             "in progress",
    "kv_transfer": "an export of a chain that is two kinds of block "
                   "and a window in progress",
    "migration": "the ring and the window in progress in the "
                 "migration snapshot",
    "speculation": "multibyte drafting from the prediction heads with "
                   "a rollback across a chunk's and a window's end",
    "adapters": "LoRA factors through this module's projections",
    "mesh": "a sharding rule for these programs (this model module "
            "has only the single-chip programs)",
    "replica_mesh": "the composed table under the shard_map engine",
    "contiguous_layout": "contiguous-cache programs in this model "
                         "module (serve it with PagedContinuousServer)",
})


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320             # bytes, and a few specials
    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 4               # as many as query heads
    d_ff: int = 128
    #: Next-byte distributions a position (the head is ``(d, heads x
    #: vocab)``); the served path reads head 0.
    n_pred_heads: int = 8
    window_size: int = 32
    chunk_size: int = 4
    rope_theta: float = 1e5
    rope_scaling: Optional[tuple] = None
    norm_eps: float = 1e-5
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16
    #: The engine's block accounting asks every config: no window of
    #: :mod:`.llama`'s kind (this module's is ``window_size``).
    sliding_window: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def summaries_per_window(self) -> int:
        return self.window_size // self.chunk_size


CONFIGS: Dict[str, EvaByteConfig] = {
    "evabyte_tiny": EvaByteConfig(dtype=jnp.float32),
}


def layer_kinds(config: EvaByteConfig) -> Dict[str, int]:
    return {"attention": config.n_layers, "mlp": config.n_layers}


def kv_geometry(config: EvaByteConfig, quantize_kv: bool):
    """``(head_dim, kv heads, dtype)`` of the block pool."""
    return (config.head_dim, config.n_kv_heads,
            jnp.int8 if quantize_kv else config.dtype)


def state_bytes_per_slot(config: EvaByteConfig) -> int:
    """Bytes a slot holds beside its blocks: none (a chunk's summary is
    made from the pool's own rows)."""
    return 0


# --------------------------------------------------------------------------- #
# The two kinds of block, on the host (plain arithmetic: numpy or jnp)


def check_layout(config: EvaByteConfig, block_size: int,
                 chunk_prefill_tokens: int) -> None:
    """What the composed table rests on, refused at construction."""
    c = config
    if block_size != c.chunk_size:
        raise ValueError(
            f"block_size {block_size} must be chunk_size {c.chunk_size}: "
            "one exact block is one chunk is one summary row")
    if c.window_size % (c.chunk_size * block_size):
        raise ValueError(
            f"window_size {c.window_size} must hold whole blocks of "
            f"summary rows ({c.chunk_size} x {block_size} positions)")
    if not chunk_prefill_tokens or c.window_size % chunk_prefill_tokens:
        raise ValueError(
            f"chunk_prefill_tokens {chunk_prefill_tokens} must divide "
            f"window_size {c.window_size}: a prefill slice never "
            "straddles a window's end")


def _windows(config: EvaByteConfig, rows: int) -> int:
    return -(-int(rows) // config.window_size)


def table_blocks(config: EvaByteConfig, max_seq: int,
                 block_size: int) -> int:
    """Width of a slot's table row: the ring, then the summary blocks
    of every window ``max_seq`` positions can reach."""
    return slot_blocks(config, max_seq, block_size)


def slot_blocks(config: EvaByteConfig, rows: int, block_size: int) -> int:
    """Blocks a slot that will write ``rows`` positions holds from
    admission to release, laid out ``[ring ‖ summaries]``."""
    ring, per_window = block_kinds(config, block_size)
    return ring + per_window * _windows(config, rows)


def block_kinds(config: EvaByteConfig, block_size: int):
    """``(exact blocks of the ring, summary blocks a window)``."""
    return (config.window_size // block_size,
            config.summaries_per_window // block_size)


def composed_positions(config: EvaByteConfig, positions):
    """Where the K/V kernels find absolute ``positions``: past one row
    for every chunk of the windows behind."""
    w = positions // config.window_size
    return positions - w * (config.window_size
                            - config.summaries_per_window)


def cache_rows(config: EvaByteConfig, positions, block_size: int):
    """What live slots whose next row is ``positions`` read and hold:
    the composed position a decode step attends up to, and what a step
    of each adds to the engine's counters — the summary blocks among
    the blocks it reads, the rows held (exact rows of the window,
    summaries of the windows behind and of the window's finished
    chunks) and the ``positions + 1`` positions they stand for."""
    c = config
    w = positions // c.window_size
    inside = positions - w * c.window_size
    return composed_positions(c, positions), dict(
        decode_summary_blocks_read=w * (c.summaries_per_window
                                        // block_size),
        eva_rows_held=w * c.summaries_per_window + inside + 1
        + (inside + 1) // c.chunk_size,
        eva_positions_held=positions + 1)


def cache_events(config: EvaByteConfig, before: int, after: int,
                 block_size: int) -> Dict[str, int]:
    """What happened to a slot's cache as the rows it has written went
    from ``before`` to ``after``: chunks summarised, windows closed and
    the exact blocks those gave back to the ring."""
    c = config
    closed = after // c.window_size - before // c.window_size
    return dict(
        eva_chunks_summarised=after // c.chunk_size
        - before // c.chunk_size,
        eva_windows_closed=closed,
        eva_blocks_returned=closed * (c.window_size // block_size))


def slice_key_blocks(config: EvaByteConfig, start: int, width: int,
                     block_size: int) -> int:
    """Key blocks x query tiles a prefill slice's attention visits in a
    layer: the kernel's own count, at the composed position."""
    return prefill_key_blocks(
        int(composed_positions(config, np.int64(start))), width,
        block_size, None, heads=config.n_heads, group=1,
        itemsize=jnp.dtype(config.dtype).itemsize)


# --------------------------------------------------------------------------- #
# Parameters


def init_params(config: EvaByteConfig, key) -> Dict:
    c, dt = config, config.dtype
    d, f, hd = c.d_model, c.d_ff, c.head_dim
    keys = jax.random.split(key, c.n_layers + 2)

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[0] ** -0.5).astype(dt)

    layers = []
    for lk in keys[:c.n_layers]:
        lk = jax.random.split(lk, 9)
        layers.append({
            # Norm weights are the offsets g of ``1 + g``.
            "attn_norm": jnp.zeros((d,), dt),
            "mlp_norm": jnp.zeros((d,), dt),
            "wq": dense(lk[0], (d, c.n_heads * hd)),
            "wk": dense(lk[1], (d, c.n_kv_heads * hd)),
            "wv": dense(lk[2], (d, c.n_kv_heads * hd)),
            "wo": dense(lk[3], (c.n_heads * hd, d)),
            "phi": jax.random.normal(lk[4], (c.n_kv_heads, hd)
                                     ).astype(dt),
            "mu": (0.1 * jax.random.normal(lk[5], (c.n_kv_heads, hd))
                   ).astype(dt),
            "w_gate": dense(lk[6], (d, f)),
            "w_up": dense(lk[7], (d, f)),
            "w_down": dense(lk[8], (f, d))})
    return {"embed": jax.random.normal(keys[-2], (c.vocab_size, d)
                                       ).astype(dt),
            "layers": layers,
            "final_norm": jnp.zeros((d,), dt),
            "lm_head": dense(keys[-1],
                             (d, c.n_pred_heads * c.vocab_size))}


#: The 2-D matrices served int8 weight-only; norms, ``phi`` and ``mu``
#: stay as they are.
_INT8_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "embed", "lm_head")


def quantize_params(params, bits: int = 8) -> Dict:
    if bits != 8:
        raise NotImplementedError("int8 weight-only is the one "
                                  "quantized layout of this model")
    return quantize_named_int8(params, _INT8_LEAVES)


# --------------------------------------------------------------------------- #
# The layer's pieces


def _norm(x, offset, config: EvaByteConfig):
    """RMSNorm of the float32 stream with weight ``1 + g``, in the
    model's type for the matmuls."""
    return rms_norm(x, 1.0 + offset.astype(jnp.float32),
                    config.norm_eps).astype(config.dtype)


def _qkv(layer, config: EvaByteConfig, normed, positions):
    """``normed (batch, seq, d)`` at absolute ``positions (batch, seq)``
    -> ``q, k, v (batch, seq, H, hd)``, q and k rotated."""
    c = config
    lead = normed.shape[:2]
    cos, sin = _rope_freqs(c, positions)
    q = _matmul(normed, layer["wq"]).reshape(lead + (c.n_heads,
                                                     c.head_dim))
    k = _matmul(normed, layer["wk"]).reshape(lead + (c.n_kv_heads,
                                                     c.head_dim))
    v = _matmul(normed, layer["wv"]).reshape(lead + (c.n_kv_heads,
                                                     c.head_dim))
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def _embed(params, tokens):
    """Rows of the embedding as the float32 residual stream."""
    return _embed_lookup(params, tokens, jnp.float32).astype(jnp.float32)


def _residual(x, out, weight):
    return x + _matmul(out, weight).astype(jnp.float32)


def _mlp(layer, config: EvaByteConfig, x):
    normed = _norm(x, layer["mlp_norm"], config)
    gate = jax.nn.silu(_matmul(normed, layer["w_gate"]
                               ).astype(jnp.float32))
    up = _matmul(normed, layer["w_up"]).astype(jnp.float32)
    return _residual(x, (gate * up).astype(config.dtype),
                     layer["w_down"])


def _head(params, config: EvaByteConfig, x, heads: int = 1):
    """float32 logits (``fp32_logits``) of the first ``heads``
    prediction heads, ``(..., heads x vocab)``."""
    normed = rms_norm(x, 1.0 + params["final_norm"].astype(jnp.float32),
                      config.norm_eps)
    weight = params["lm_head"]
    quantized = is_quantized(weight)
    matrix = (weight["q"] if quantized else weight).astype(jnp.float32)
    logits = jnp.dot(normed, matrix,
                     precision=jax.lax.Precision.HIGHEST)
    if quantized:
        logits = logits * weight["s"]
    return logits[..., :heads * config.vocab_size]


def summarise(layer, config: EvaByteConfig, k, v):
    """Chunks' summary rows: ``k, v (..., C, H, hd)`` (keys rotated) ->
    ``k~, v~ (..., H, hd)`` float32."""
    with jax.named_scope("eva_summarise"):
        return chunk_summary_reference(k, v, layer["phi"], layer["mu"],
                                       config.head_dim ** -0.5)


@functools.partial(jax.jit, static_argnames=("config",))
def forward(params, tokens, config: EvaByteConfig):
    """Whole-sequence forward, no cache: tokens ``(batch, seq)`` ->
    logits ``(batch, seq, n_pred_heads, vocab)`` float32."""
    c = config
    batch, seq = tokens.shape
    C, W = c.chunk_size, c.window_size
    chunks = -(-seq // C)
    index = jnp.arange(seq, dtype=jnp.int32)
    positions = jnp.broadcast_to(index, (batch, seq))
    exact = (index[None, :] <= index[:, None]) & (
        index[None, :] // W == index[:, None] // W)
    behind = ((jnp.arange(chunks)[None, :] + 1) * C
              <= (index[:, None] // W) * W)
    visible = jnp.concatenate([exact, behind], axis=1)
    x = _embed(params, tokens)
    for layer in params["layers"]:
        q, k, v = _qkv(layer, c, _norm(x, layer["attn_norm"], c),
                       positions)
        pad = ((0, 0), (0, chunks * C - seq), (0, 0), (0, 0))
        # A last chunk the sequence leaves partial is behind no query.
        k_sum, v_sum = summarise(
            layer, c,
            jnp.pad(k, pad).reshape(batch, chunks, C, c.n_kv_heads, -1),
            jnp.pad(v, pad).reshape(batch, chunks, C, c.n_kv_heads, -1))
        keys = jnp.concatenate([k.astype(jnp.float32), k_sum], axis=1)
        values = jnp.concatenate([v.astype(jnp.float32), v_sum], axis=1)
        scores = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                            keys) * c.head_dim ** -0.5
        weights = jax.nn.softmax(
            jnp.where(visible, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", weights, values)
        x = _residual(x, out.reshape(batch, seq, -1).astype(c.dtype),
                      layer["wo"])
        x = _mlp(layer, c, x)
    return _head(params, c, x, c.n_pred_heads).reshape(
        batch, seq, c.n_pred_heads, c.vocab_size)


# --------------------------------------------------------------------------- #
# The pool, the composed table, and the prompt's slices


def init_paged_cache(config: EvaByteConfig, n_blocks: int,
                     block_size: int = 16, quantize_kv: bool = False,
                     slots: int = 1) -> list:
    """One K/V block pool a layer, exact and summary rows alike
    (``n_blocks`` INCLUDES scratch block 0)."""
    del slots
    return _kv_layer_buffers(
        config, (n_blocks, block_size, config.n_kv_heads,
                 config.head_dim), quantize_kv)


def kv_pool_layers(pool) -> list:
    """The block pools among what :func:`init_paged_cache` returns:
    all of it."""
    return pool


def composed_tables(config: EvaByteConfig, tables, positions,
                    block_size: int):
    """The table the K/V kernels walk for rows at absolute
    ``positions (S,)``: from a slot's ``[ring ‖ summaries]`` row
    ``tables (S, width)``, the summary blocks of the windows behind and
    then the ring; the scratch block past it."""
    ring, per_window = block_kinds(config, block_size)
    width = tables.shape[1]
    index = jnp.arange(width, dtype=jnp.int32)[None, :]
    behind = per_window * (positions // config.window_size)[:, None]
    source = jnp.where(index < behind, ring + index, index - behind)
    held = (index < behind) | (index - behind < ring)
    return jnp.where(held, jnp.take_along_axis(
        tables, jnp.clip(source, 0, width - 1), axis=1), 0)


def _summary_positions(config: EvaByteConfig, chunks, block_size: int):
    """Where the ``[ring ‖ summaries]`` row holds the summary of chunk
    ``chunks`` (counted from the sequence's first), as a position a
    table lookup takes."""
    ring, _ = block_kinds(config, block_size)
    return ring * block_size + chunks


def _write_summary_rows(pool_layer, k_sum, v_sum, block, row):
    """A slice's summary rows ``k_sum, v_sum (n, H, hd)`` written over
    rows ``row ..`` of pool block ``block`` (scalars): a slice of the
    pool updated in place."""
    rows = _quantize_pairs(pool_layer, k_sum, v_sum)
    zero = jnp.zeros((), jnp.int32)
    return {key: jax.lax.dynamic_update_slice(
                held, rows[key][None].astype(held.dtype),
                (block, row) + (zero,) * (held.ndim - 2))
            for key, held in pool_layer.items()}


def _prefill_core(params, tokens, pool, tables, start_index,
                  config: EvaByteConfig, compute_logits):
    """A ``(1, K)`` prompt slice at absolute ``start_index`` (whole
    chunks, inside one window) appended to the row whose ``[ring ‖
    summaries]`` table is ``tables (1, width)``: its rows land in the
    ring and attend through the composed table, and its chunks'
    summaries land in the window's summary blocks."""
    c = config
    batch, width = tokens.shape
    if batch != 1:
        raise ValueError("a prefill slice is one request's")
    block_size = pool[0]["k"].shape[1]
    C = c.chunk_size
    start = jnp.broadcast_to(jnp.asarray(start_index, jnp.int32),
                             (batch,))
    positions = start[:, None] + jnp.arange(width, dtype=jnp.int32)
    composed = composed_tables(c, tables, start, block_size)
    cached = composed_positions(c, start)
    lens = jnp.full((batch,), width, jnp.int32)
    # The slice's chunks lie in ONE summary block (a slice is a power
    # of two chunks wide and starts at a multiple of its width).
    summary_at = _summary_positions(c, start[0] // C, block_size)
    summary_block = tables[0, summary_at // block_size]
    summary_row = summary_at % block_size
    use_kernel, interpret = prefill_dispatch(
        c.head_dim, c.n_kv_heads, pool[0]["k"].dtype, block_size, width)
    x = _embed(params, tokens)
    pool = list(pool)
    for index, layer in enumerate(params["layers"]):
        q, k, v = _qkv(layer, c, _norm(x, layer["attn_norm"], c),
                       positions)
        # The summaries first, on the pool as the program was handed
        # it: behind the append kernel's output the same update made
        # XLA park that output on chip and copy the whole pool back.
        k_sum, v_sum = summarise(
            layer, c, k.reshape(width // C, C, c.n_kv_heads, -1),
            v.reshape(width // C, C, c.n_kv_heads, -1))
        held = _write_summary_rows(
            pool[index], k_sum.astype(c.dtype), v_sum.astype(c.dtype),
            summary_block, summary_row)
        q = q.reshape(batch, width, c.n_kv_heads, 1, c.head_dim)
        if use_kernel:
            out, pool[index] = paged_prefill_attention(
                q, k, v, held, composed, cached, lens,
                interpret=interpret)
        else:
            out, pool[index] = paged_prefill_reference(
                q, k, v, held, composed, cached, lens)
        x = _residual(x, out.reshape(batch, width, -1), layer["wo"])
        x = _mlp(layer, c, x)
    return (_head(params, c, x) if compute_logits else None), pool


@_program("prefill_append_paged",
          static_argnames=("config", "compute_logits"),
          donate_argnames=("pool",))
def _prefill_program(params, tokens, pool, tables, start_index, config,
                     compute_logits):
    return _prefill_core(params, tokens, pool, tables, start_index,
                         config, compute_logits)


def prefill_append_paged(params, tokens, pool, tables, start_index,
                         config: EvaByteConfig, lora=None, kv_limit=None,
                         compute_logits: bool = True):
    """Admit a ``(1, K)`` prompt slice at ``start_index``; the contract
    of :func:`.llama.prefill_append_paged`.  ``kv_limit`` (a prompt
    bucket's blocks) bounds nothing here, the composed row is never
    wider than the table, so it stays outside the jit: as a static
    argument it would compile one identical program a bucket."""
    del kv_limit
    if lora is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _prefill_program(params, tokens, pool, tables, start_index,
                            config, compute_logits)


# --------------------------------------------------------------------------- #
# A decode step of every slot


def _block_rows(pool_layer, block_ids):
    """Blocks ``block_ids (S,)`` of a pool layer read back as float32
    rows ``k, v (S, block, H, hd)``: the jnp form of what
    :func:`~..ops.chunk_summary.chunk_summary` reads in place."""
    k = pool_layer["k"][block_ids].astype(jnp.float32)
    v = pool_layer["v"][block_ids].astype(jnp.float32)
    if "ks" not in pool_layer:
        return k, v
    planes = _scale_planes(pool_layer)
    return (k * planes["ks"][block_ids][..., None],
            v * planes["vs"][block_ids][..., None])


def _decode_core(params, token, pool, tables, positions, active,
                 config: EvaByteConfig):
    """One token per slot at absolute ``positions`` through every
    layer: its K/V row is appended to the ring, it attends through the
    composed table, and where it ends a chunk the chunk's block is read
    back and its summary appended to the window's summary blocks.
    ``tables`` are the slots' ``[ring ‖ summaries]`` rows; idle rows
    write the scratch block."""
    c = config
    slots = tables.shape[0]
    block_size = pool[0]["k"].shape[1]
    scratch = jnp.arange(slots, dtype=jnp.int32) % block_size
    tables = jnp.where(active[:, None], tables, 0)
    positions = jnp.where(active, positions, scratch)
    composed = composed_tables(c, tables, positions, block_size)
    at = composed_positions(c, positions)
    ends_chunk = active & (positions % c.chunk_size == c.chunk_size - 1)
    written = jnp.take_along_axis(composed, (at // block_size)[:, None],
                                  axis=1)[:, 0]
    summary_tables = jnp.where(ends_chunk[:, None], tables, 0)
    summary_at = jnp.where(
        ends_chunk,
        _summary_positions(c, positions // c.chunk_size, block_size),
        scratch)
    use_kernel, interpret = decode_dispatch(c.head_dim, c.n_kv_heads,
                                            pool[0]["k"].dtype)
    attend = (functools.partial(paged_decode_attention,
                                interpret=interpret)
              if use_kernel else paged_decode_reference)
    x = _embed(params, token)
    pool = list(pool)
    for index, layer in enumerate(params["layers"]):
        q, k, v = _qkv(layer, c, _norm(x, layer["attn_norm"], c),
                       positions[:, None])
        held = _paged_write_rows(pool[index], k, v, composed, at)
        planes = _scale_planes(held)
        q = q.reshape(slots, c.n_kv_heads, 1, c.head_dim)
        out = attend(q, planes["k"], planes["v"], composed, at,
                     ks=planes.get("ks"), vs=planes.get("vs"))
        if use_kernel:
            k_sum, v_sum = chunk_summary(
                held, written, ends_chunk, layer["phi"], layer["mu"],
                sm_scale=c.head_dim ** -0.5, interpret=interpret)
        else:
            k_sum, v_sum = summarise(layer, c,
                                     *_block_rows(held, written))
        pool[index] = _paged_write_rows(
            held, k_sum[:, None].astype(c.dtype),
            v_sum[:, None].astype(c.dtype), summary_tables, summary_at)
        x = _residual(x, out.reshape(slots, 1, -1).astype(c.dtype),
                      layer["wo"])
        x = _mlp(layer, c, x)
    return _head(params, c, x), pool


def _serve(params, state, pool, num_steps, config: EvaByteConfig, eos_id,
           sampled, rng_key):
    tables = state["tables"]

    def step_core(token, pool, positions, active):
        return _decode_core(params, token, pool, tables, positions,
                            active, config)

    *out, pool = _serve_scan(step_core, state,
                             _scan_scale_rows(pool, config), num_steps,
                             eos_id, sampled, rng_key)
    return (*out, _rest_scale_planes(pool))


@functools.partial(jax.jit,
                   static_argnames=("config", "num_steps", "eos_id",
                                    "sampled"),
                   donate_argnames=("pool",))
def serve_chunk_paged(params, state, pool, num_steps,
                      config: EvaByteConfig, eos_id: int = -1,
                      sampled: bool = False, rng_key=None,
                      lora_shared=None):
    """``num_steps`` decode steps of every live slot; the contract of
    :func:`.llama.serve_chunk_paged`.  A window's end is crossed inside
    the scan: the composed table follows the positions."""
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _serve(params, state, pool, num_steps, config, eos_id,
                  sampled, rng_key)


@_program("serve_chunk_mixed",
          static_argnames=("config", "num_steps", "eos_id", "sampled"),
          donate_argnames=("pool",))
def _mixed_program(params, state, pool, prefill_tokens, prefill_row,
                   prefill_start, num_steps, config, eos_id, sampled,
                   rng_key):
    tables_row = jax.lax.dynamic_slice_in_dim(
        state["tables"], jnp.asarray(prefill_row, jnp.int32), 1, axis=0)
    _, pool = _prefill_core(params, prefill_tokens, pool, tables_row,
                            prefill_start, config, False)
    return _serve(params, state, pool, num_steps, config, eos_id,
                  sampled, rng_key)


def serve_chunk_mixed(params, state, pool, prefill_tokens, prefill_row,
                      prefill_start, num_steps, config: EvaByteConfig,
                      eos_id: int = -1, sampled: bool = False,
                      rng_key=None, lora_shared=None,
                      prefill_kv_limit=None):
    """One prefill slice of the slot ``prefill_row`` (idle among the
    decoding rows until its last slice lands), then the steps, as one
    program."""
    del prefill_kv_limit
    if lora_shared is not None:
        raise NotImplementedError("no LoRA path in this model module")
    return _mixed_program(params, state, pool, prefill_tokens,
                          prefill_row, prefill_start, num_steps, config,
                          eos_id, sampled, rng_key)
