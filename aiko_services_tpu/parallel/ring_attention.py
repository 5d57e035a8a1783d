"""Ring attention: exact attention over sequences sharded across a mesh
axis (context parallelism).

The reference has no long-context machinery (SURVEY.md §5.7); this is the
TPU-native design: Q/K/V are sharded over the ``sp`` mesh axis on their
sequence dimension; each device computes blockwise attention against the
K/V shard it currently holds while rotating K/V shards around the ring
with ``ppermute`` (ICI neighbor exchange), merging partial results with
the online-softmax recurrence — so memory per device stays O(seq/n) and
the full-sequence result is exact (Liu et al. ring attention, via
blockwise attention numerics).

Causality is handled with *global* position ids so the mask is correct
regardless of which ring step a K/V block arrives on.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import NEG_INF

__all__ = ["ring_attention", "ring_attention_sharded"]


def _block_attend(q, k, v, q_offset, k_offset, sm_scale, causal,
                  m, l, acc, window=None):
    """One blockwise-attention accumulation step (f32 state).

    GQA-native: ``q`` is (batch, kv_heads, group, q_len, head_dim) and
    ``k``/``v`` are (batch, kv_heads, k_len, head_dim) — the rotated
    K/V never materialize the repeated query heads.  ``window``:
    sliding-window masking by GLOBAL position (requires causal)."""
    s = jnp.einsum("bkgqd,bksd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_len, k_len = q.shape[3], k.shape[2]
        q_ids = jnp.arange(q_len)[:, None] + q_offset
        k_ids = jnp.arange(k_len)[None, :] + k_offset
        visible = k_ids <= q_ids
        if window is not None:
            visible &= k_ids > q_ids - window
        s = jnp.where(visible[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m - m_new)
    l_new = correction * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + jnp.einsum(
        "bkgqs,bksd->bkgqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   window: Optional[int] = None):
    """Inside-shard_map body: local q (batch, heads, seq_local, hd) and
    k/v (batch, kv_heads, seq_local, hd) shards — ``kv_heads`` may be
    smaller (GQA; only the kv heads rotate around the ring).  Returns
    the local output shard.  K/V rotate ``axis_size`` steps.

    ``window``: sliding-window (Mistral-class) masking by global
    position — requires ``causal``.  Shards entirely below a device's
    window are skipped like future shards, so long-context windowed
    prefill does O(window/shard + 1) live steps per device instead of
    O(axis_index)."""
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    axis_size = jax.lax.psum(1, axis_name)
    axis_index = jax.lax.axis_index(axis_name)
    seq_local = q.shape[2]
    q_offset = axis_index * seq_local

    batch, heads, _, head_dim = q.shape
    kv_heads = k.shape[1]
    group = heads // kv_heads
    q = q.reshape(batch, kv_heads, group, seq_local, head_dim)
    state_shape = (batch, kv_heads, group, seq_local, 1)
    m = jnp.full(state_shape, NEG_INF, jnp.float32)
    l = jnp.zeros(state_shape, jnp.float32)
    acc = jnp.zeros((batch, kv_heads, group, seq_local, head_dim),
                    jnp.float32)
    # shard_map's varying-axis tracking: the carry becomes 'sp'-varying
    # after the first step, so the init must be marked varying too.
    from .mesh import mark_varying
    m, l, acc = (mark_varying(x, axis_name) for x in (m, l, acc))

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # The block currently held arrived from device (index - i).
        src = (axis_index - i) % axis_size
        k_offset = src * seq_local
        if causal:
            # Causal step skipping: a K/V shard whose keys all come
            # after this device's queries (src > axis_index) is fully
            # masked — skip its attention math (the rotation still
            # happens; later devices need the shard).  Halves causal
            # ring FLOPs on average.  With a sliding window, a shard
            # entirely BELOW the window of this device's first query
            # (max key id <= min query id - window) is fully masked
            # too — windowed long-context prefill then runs
            # O(window/shard + 1) live steps per device.
            live = src <= axis_index
            if window is not None:
                live &= (src + 1) * seq_local - 1 > q_offset - window
            m, l, acc = jax.lax.cond(
                live,
                lambda state: _block_attend(
                    q, k_cur, v_cur, q_offset, k_offset, sm_scale,
                    True, *state, window=window),
                lambda state: state,
                (m, l, acc))
        else:
            m, l, acc = _block_attend(q, k_cur, v_cur, q_offset,
                                      k_offset, sm_scale, False,
                                      m, l, acc)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(
        0, axis_size, step, (k, v, m, l, acc))
    denom = jnp.where(l == 0.0, 1.0, l)
    out = (acc / denom).astype(q.dtype)
    return out.reshape(batch, heads, seq_local, head_dim)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp",
                           causal: bool = True,
                           sm_scale: Optional[float] = None,
                           window: Optional[int] = None):
    """Global entry: q/k/v are full arrays (batch, heads, seq, head_dim);
    shard_map shards the sequence dimension over ``axis`` and runs the
    ring.  Heads are additionally sharded over ``tp`` when present.
    ``window``: sliding-window masking by global position (causal)."""
    head_axis = "tp" if "tp" in mesh.axis_names else None
    spec = P(None, head_axis, axis, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal,
                          sm_scale=sm_scale, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
