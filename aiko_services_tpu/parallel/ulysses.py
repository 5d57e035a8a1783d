"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The reference has no long-context machinery (SURVEY.md §5.7).  Ring
attention (``parallel/ring_attention.py``) is one TPU-native answer;
this module is the other standard design (DeepSpeed-Ulysses): instead of
rotating K/V shards around a ring, two ``all_to_all`` collectives swap
the sharded dimension around the attention op —

* inputs arrive sharded on **sequence** over the ``sp`` axis
  (``batch, heads, seq/n, head_dim``);
* an all-to-all re-shards to **heads** (``batch, heads/n, seq,
  head_dim``), so every device holds the *full* sequence for a subset
  of heads and runs ordinary (flash) attention locally — no online
  merge needed;
* a second all-to-all restores sequence sharding for the rest of the
  network (MLP etc. stay sequence-sharded).

Trade-off vs ring: Ulysses does O(2) collectives of the whole activation
per attention instead of ``n`` neighbor exchanges of K/V, and it needs
``heads % n == 0`` — but the local attention is a single dense block
(better MXU utilisation) and composes directly with the Pallas flash
kernel.  Both are exact.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import attention_reference

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      window: Optional[int] = None):
    """Inside-shard_map body.  ``q/k/v`` are local sequence shards of
    shape ``(batch, heads, seq_local, head_dim)`` with the FULL head
    count; returns the local output shard, same shape.

    ``attn_fn(q, k, v, causal=, sm_scale=, window=)`` runs the
    per-device dense attention; defaults to the jnp reference (swap in
    ``ops.attention.flash_attention`` on real TPU).  ``window``:
    sliding-window masking — after the head-scatter each device holds
    the FULL sequence for its head group, so plain local windowed
    masking is globally correct (no offset bookkeeping).
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if attn_fn is None:
        attn_fn = attention_reference
    n = jax.lax.psum(1, axis_name)
    heads = q.shape[1]
    kv_heads = k.shape[1]
    if heads % n or kv_heads % n:
        raise ValueError(
            f"Ulysses needs q heads ({heads}) and kv heads "
            f"({kv_heads}) divisible by axis size ({n}); repeat kv "
            "heads first when they do not divide")

    # seq-sharded -> head-sharded: split the head dim across devices,
    # concatenate the sequence shards.  all_to_all is the single XLA
    # collective purpose-built for this swap (rides ICI all-to-all
    # links; no host involvement).
    def scatter_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def scatter_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    q_h, k_h, v_h = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    # GQA: the all-to-all moved only the kv heads (group-x fewer bytes
    # over ICI); device i's q-head slice [i*h/n, (i+1)*h/n) maps
    # exactly onto its kv-head slice [i*kv/n, (i+1)*kv/n), so a LOCAL
    # repeat aligns them for the dense attention.
    if q_h.shape[1] != k_h.shape[1]:
        group = q_h.shape[1] // k_h.shape[1]
        k_h = jnp.repeat(k_h, group, axis=1)
        v_h = jnp.repeat(v_h, group, axis=1)
    # Full sequence is now local: plain causal masking is correct with
    # no global-offset bookkeeping (unlike the ring).
    o_h = attn_fn(q_h, k_h, v_h, causal=causal, sm_scale=sm_scale,
                  window=window)
    return scatter_seq(o_h)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, axis: str = "sp",
                              causal: bool = True,
                              sm_scale: Optional[float] = None,
                              attn_fn: Optional[Callable] = None,
                              window: Optional[int] = None):
    """Global entry: q/k/v are full arrays ``(batch, heads, seq,
    head_dim)``; shard_map shards the sequence dim over ``axis`` and
    runs the all-to-all swap around dense local attention.
    ``window``: sliding-window masking (causal)."""
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=axis,
                          causal=causal, sm_scale=sm_scale,
                          attn_fn=attn_fn, window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
