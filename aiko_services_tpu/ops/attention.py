"""Attention ops: Pallas flash-attention kernel for TPU with a reference
jnp fallback.

The reference framework has no attention code at all (SURVEY.md §5.7) —
its LLM examples call Ollama over HTTP.  Here attention is a first-class
op: the kernel implements online-softmax flash attention (one pass over
K/V blocks, f32 running max/denominator in VMEM scratch, bf16-friendly
inputs) tiled for the MXU; the fallback is a numerically-identical jnp
implementation used on CPU and for testing (the kernel itself is also
testable on CPU via ``interpret=True``).

Layout: ``(batch, heads, seq, head_dim)``; ``head_dim`` ≤ 128 rides the
lane dimension, query blocks ride sublanes.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_tiles", "attention_reference",
           "NEG_INF"]

NEG_INF = -1e30
# NEG_INF must stay FINITE (never -inf): with sliding-window masking a
# q-row can be fully masked inside the first LIVE k-block, making every
# score NEG_INF → m_new == NEG_INF and p == exp(0) == 1 of bogus mass.
# That mass is cancelled later only because the row's diagonal block is
# guaranteed live and its rescale correction exp(NEG_INF - m_real)
# underflows to exactly 0.0.  With -inf the same update computes
# exp(-inf - (-inf)) = NaN.  (See the online-softmax update in
# _flash_kernel.)
assert NEG_INF < 0 and NEG_INF > float("-inf")

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def attention_reference(q, k, v, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Plain jnp attention (the numerics oracle and CPU path).

    ``window`` (requires ``causal``): each query attends to at most the
    ``window`` most recent positions including itself (Mistral-style
    sliding-window attention)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        q_ids = jnp.arange(q_len)[:, None] + (k_len - q_len)
        k_ids = jnp.arange(k_len)[None, :]
        visible = k_ids <= q_ids
        if window is not None:
            visible &= k_ids > q_ids - window
        logits = jnp.where(visible, logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd",
                      weights.astype(v.dtype), v).astype(q.dtype)


def flash_tiles(q_len: int, k_len: int, causal: bool = True,
                block_q: int = DEFAULT_BLOCK_Q,
                block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Can the flash kernel tile this shape?  Both lengths must be
    whole blocks (a length below the block size is its own block), and
    a causal query may not outrun the keys — rows with no visible key
    make the block-skip index map go negative, and the jnp reference
    defines the semantics there."""
    return (q_len % min(block_q, q_len) == 0
            and k_len % min(block_k, k_len) == 0
            and not (causal and q_len > k_len))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scratch, l_scratch, acc_scratch,
                  *, sm_scale: float, causal: bool,
                  block_q: int, block_k: int, k_len: int, q_len: int,
                  window: Optional[int]):
    """Grid: (batch*heads, q_blocks, k_blocks); k fastest-varying.

    Scratch carries the online-softmax state (running max ``m``, sum
    ``l``, accumulator ``acc``) across the k-block sweep for one q block.
    """
    k_idx = pl.program_id(2)
    num_k = pl.num_programs(2)
    # program_id must be read at kernel top level (not inside pl.when's
    # traced cond body).
    q_block_start = pl.program_id(1) * block_q

    @pl.when(k_idx == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    if causal:
        # Causal block skipping: a k block strictly above the diagonal
        # (its first key id > this q block's last query id) contributes
        # nothing — skip its MXU work entirely.  Paired with the clamped
        # K/V index maps in flash_attention, the skipped steps also
        # trigger no new HBM->VMEM copies, so causal prefill does ~half
        # the work of the full grid sweep.
        q_last = q_block_start + block_q - 1 + (k_len - q_len)
        block_live = k_idx * block_k <= q_last
        if window is not None:
            # Sliding window: a k block entirely BELOW the window of
            # this q block's first query contributes nothing either —
            # long-context prefill cost becomes O(seq * window).
            q_first = q_block_start + (k_len - q_len)
            block_live &= (k_idx + 1) * block_k - 1 > q_first - window
    else:
        block_live = True

    @pl.when(block_live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)          # (block_k, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

        if causal:
            q_ids = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) \
                + q_block_start + (k_len - q_len)
            k_ids = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + k_idx * block_k
            visible = k_ids <= q_ids
            if window is not None:
                visible &= k_ids > q_ids - window
            s = jnp.where(visible, s, NEG_INF)

        m_prev = m_scratch[:]                      # (bq, 1)
        # Fully-masked rows rely on NEG_INF being finite: s == NEG_INF
        # everywhere gives p == 1 (bogus mass), later cancelled by the
        # diagonal block's correction underflowing to exactly 0 — see
        # the NEG_INF module comment before "simplifying" to -inf.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (bq, bk)
        correction = jnp.exp(m_prev - m_new)       # (bq, 1)
        l_new = correction * l_scratch[:] + \
            jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * correction + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(k_idx == num_k - 1)
    def _finish():
        denom = jnp.where(l_scratch[:] == 0.0, 1.0, l_scratch[:])
        o_ref[0] = (acc_scratch[:] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    window: Optional[int] = None):
    """Flash attention; dispatches to the Pallas kernel on TPU (or in
    interpret mode), else the jnp reference.  A shape the kernel
    cannot tile (:func:`flash_tiles`) also takes the reference; on the
    TPU backend that is warned about at trace time, never silent.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    than ``q`` (``heads % kv_heads == 0``) — query-head grid steps index
    the shared K/V head via the BlockSpec index map, so the repeated K/V
    never exists in memory (repeating would multiply HBM traffic by the
    group size)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5

    batch, heads, q_len, head_dim = q.shape
    kv_heads, k_len = k.shape[1], k.shape[2]
    assert heads % kv_heads == 0, (heads, kv_heads)
    group = heads // kv_heads

    def fallback():
        k_full = jnp.repeat(k, group, axis=1) if group > 1 else k
        v_full = jnp.repeat(v, group, axis=1) if group > 1 else v
        return attention_reference(q, k_full, v_full, causal=causal,
                                   sm_scale=sm_scale, window=window)

    if not (jax.default_backend() == "tpu" or interpret):
        return fallback()
    if not flash_tiles(q_len, k_len, causal, block_q, block_k):
        if not interpret:
            warnings.warn(
                f"flash_attention: q_len={q_len} k_len={k_len} does not "
                "tile; running the jnp reference on the TPU",
                stacklevel=2)
        return fallback()
    block_q = min(block_q, q_len)
    block_k = min(block_k, k_len)

    bh = batch * heads
    q3 = q.reshape(bh, q_len, head_dim)
    k3 = k.reshape(batch * kv_heads, k_len, head_dim)
    v3 = v.reshape(batch * kv_heads, k_len, head_dim)

    grid = (bh, q_len // block_q, k_len // block_k)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, k_len=k_len, q_len=q_len,
        window=window)

    if causal:
        # Clamp the k index for blocks outside the live band: the
        # kernel skips their compute (pl.when), and an unchanged block
        # index means Pallas re-uses the already-resident VMEM tile
        # instead of issuing a fresh HBM copy.  With a sliding window
        # the band is two-sided (diagonal above, window edge below).
        def kv_index(b, i, j):
            q_first = i * block_q + (k_len - q_len)
            last_live = (q_first + block_q - 1) // block_k
            j_clamped = jnp.minimum(j, last_live)
            if window is not None:
                first_live = jnp.maximum(
                    q_first - window + 1, 0) // block_k
                j_clamped = jnp.maximum(j_clamped, first_live)
            return (b // group, j_clamped, 0)
    else:
        def kv_index(b, i, j):
            return (b // group, j, 0)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim),
                         lambda b, i, j: (b, i, 0)),
            # Query-head b uses shared K/V head b // group.
            pl.BlockSpec((1, block_k, head_dim), kv_index),
            pl.BlockSpec((1, block_k, head_dim), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, head_dim),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q_len, head_dim), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(batch, heads, q_len, head_dim)
