"""Latency-hiding collective matmuls (ICI overlap).

Tensor-parallel layers alternate between an all-gather (activations) and
a matmul against a weight shard; done naively the ICI transfer and the
MXU work serialize.  These "collective matmul" kernels interleave them:
at every ring step the device multiplies the activation shard it already
holds while ``ppermute`` moves the next shard to its neighbor, so the
transfer hides behind the MXU (the classic TPU decomposition from the
scaling playbook; the reference framework has no tensor math at all —
SURVEY.md §2.6).

Two primitives, both written for use inside ``shard_map`` bodies:

- ``allgather_matmul(x_shard, w_shard, axis)``:
  computes ``allgather(x) @ w_shard`` without ever materializing the
  full gathered ``x``.  (Column-parallel layer: x sharded on batch/seq,
  w sharded on columns.)
- ``matmul_reducescatter(x_shard, w_shard, axis)``:
  computes ``reduce_scatter(x_shard @ w_shard)`` accumulating the ring
  partial sums while shards rotate.  (Row-parallel layer.)

Numerics are exact (pure reordering of the same dot products).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["allgather_matmul", "matmul_reducescatter",
           "allgather_matmul_sharded", "matmul_reducescatter_sharded"]


def _ring_perm(axis_name):
    size = jax.lax.axis_size(axis_name)
    return [(i, (i + 1) % size) for i in range(size)]


from .mesh import mark_varying as _mark_varying


def allgather_matmul(x_shard, w_shard, axis_name: str):
    """``allgather(x, axis) @ w_shard`` with the gather hidden behind the
    matmuls.  x_shard ``(m_local, k)``, w_shard ``(k, n_local)`` →
    ``(m_local * axis_size, n_local)``.

    Each step: start moving our current x block to the next neighbor,
    multiply the block we hold, place the product at the owning row
    offset.  After ``axis_size`` steps every device has computed the
    full gathered product against its own weight shard.
    """
    size = jax.lax.axis_size(axis_name)
    index = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_name)
    m_local = x_shard.shape[0]
    n_local = w_shard.shape[1]
    out = _mark_varying(jnp.zeros((m_local * size, n_local),
                                  x_shard.dtype), axis_name)

    def body(step, carry):
        block, out = carry
        # The block we hold at `step` originated on device
        # (index - step) mod size: its rows live at that offset.
        src = (index - step) % size
        prod = jnp.dot(block, w_shard,
                       preferred_element_type=jnp.float32)
        out = jax.lax.dynamic_update_slice(
            out, prod.astype(out.dtype), (src * m_local, 0))
        # Rotate while the NEXT multiply runs (XLA schedules the
        # ppermute concurrently with the dot — that's the overlap).
        block = jax.lax.ppermute(block, axis_name, perm)
        return block, out

    _, out = jax.lax.fori_loop(0, size, body, (x_shard, out),
                               unroll=True)
    return out


def matmul_reducescatter(x_shard, w_shard, axis_name: str):
    """``reduce_scatter(x_shard @ w_shard, axis)`` with the scatter
    hidden behind the matmuls.  x_shard ``(m, k_local)``, w_shard
    ``(k_local, n)`` → ``(m, n / axis_size)``-worth: every device ends
    with the fully-summed slice of columns it owns.

    Walks the ring accumulating: at each step a device multiplies its
    x/w shard against the column slice owned by the device the
    accumulator is travelling toward, adds, and forwards.
    """
    size = jax.lax.axis_size(axis_name)
    index = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_name)
    m = x_shard.shape[0]
    n = w_shard.shape[1]
    assert n % size == 0, "output columns must divide the axis"
    n_local = n // size
    acc = _mark_varying(jnp.zeros((m, n_local), jnp.float32), axis_name)

    def slice_for(owner):
        return jax.lax.dynamic_slice(w_shard, (0, owner * n_local),
                                     (w_shard.shape[0], n_local))

    def body(step, acc):
        # After `step` hops the accumulator we hold is destined for
        # device (index + (size - 1 - step)) mod size.
        owner = (index + (size - 1 - step)) % size
        partial = jnp.dot(x_shard, slice_for(owner),
                          preferred_element_type=jnp.float32)
        acc = acc + partial
        # Forward every step except the last (it has arrived home).
        return jax.lax.cond(
            step < size - 1,
            lambda a: jax.lax.ppermute(a, axis_name, perm),
            lambda a: a, acc)

    acc = jax.lax.fori_loop(0, size, body, acc, unroll=True)
    return acc.astype(x_shard.dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def allgather_matmul_sharded(x, w, mesh: Mesh, axis: str = "tp"):
    """Host-level wrapper: x sharded ``P(axis, None)`` on rows, w sharded
    ``P(None, axis)`` on columns → fully-gathered-x @ w, sharded on
    columns (standard column-parallel layer)."""
    return shard_map(
        functools.partial(allgather_matmul, axis_name=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
    )(x, w)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def matmul_reducescatter_sharded(x, w, mesh: Mesh, axis: str = "tp"):
    """Host-level wrapper: x sharded ``P(None, axis)`` on contraction, w
    sharded ``P(axis, None)`` → x @ w summed over shards, scattered on
    columns (standard row-parallel layer)."""
    return shard_map(
        functools.partial(matmul_reducescatter, axis_name=axis),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, axis),
    )(x, w)
