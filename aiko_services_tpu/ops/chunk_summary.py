"""One summary row a chunk: a Pallas TPU kernel that pools a pool
block's K/V rows under a learned per-head score, for the decode step
that ends the chunk, plus the jnp form it must match.

A model that keeps a window of exact K/V rows and one summary row for
every chunk behind it (:mod:`..models.evabyte`) makes, when a decode
step writes a chunk's last row, ``k~ = sum_m a_m k_m + mu`` and
``v~ = sum_m a_m v_m`` a head, ``a = softmax_m(s phi . k_m)`` over the
chunk's rows, which are one pool block.  In plain XLA that is a gather
of the block out of the pool a slot a layer a step, for every slot
whether or not its step ends a chunk (one in ``chunk`` does), and XLA
parks whole pools in on-chip memory for it (TPU compiler and a traced
run, PR 41).  The kernel here is one call a layer:

* grid ``(slot,)``; the block ids and the flags "this slot's step ends
  a chunk" ride scalar prefetch, so a slot's K and V blocks
  ``(block, kv_heads, head_dim)`` are the only pool bytes that move
  (the pools stay in HBM, one contiguous DMA a block), and a slot whose
  step ends no chunk copies and computes nothing;
* int8 rows are widened in the kernel and their per-(row, head) scales
  factor out of the score and into the weights, as in the decode
  kernel, so no float copy of a block exists outside VMEM; the scales
  arrive already cut to the slots' blocks, ``(slots, kv_heads, block,
  1)``, a few KB the caller gathers from the planes or lane rows;
* a head is a strided read of the block tile
  (:func:`~.paged_attention.load_head_rows`' form) and ``block`` rows of
  ``head_dim`` lanes: a lane reduction for the score, a softmax down the
  rows, two weighted sums.

Behind a jit of its own named ``eva_summarise``: a program traces and
lowers it once, and the custom call takes that name in a device trace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_summary", "chunk_summary_reference"]


def chunk_summary_reference(k, v, phi, mu, sm_scale: float):
    """``k, v (..., C, H, hd)`` -> ``k~, v~ (..., H, hd)`` float32."""
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    score = jnp.einsum("...chd,hd->...ch", k,
                       phi.astype(jnp.float32)) * sm_scale
    weights = jax.nn.softmax(score, axis=-2)
    return (jnp.einsum("...ch,...chd->...hd", weights, k)
            + mu.astype(jnp.float32),
            jnp.einsum("...ch,...chd->...hd", weights, v))


def _kernel(blocks_ref, ends_ref,                      # scalar prefetch
            k_hbm, v_hbm, ks_ref, vs_ref, phi_ref, mu_ref,
            k_out, v_out, k_buf, v_buf, sems, *, heads: int,
            sm_scale: float):
    """Grid: (slot,).  ``k_hbm`` / ``v_hbm``: the pools, where they
    are; ``ks_ref`` / ``vs_ref`` ``(1, heads, block, 1)``; ``phi_ref`` /
    ``mu_ref`` ``(heads, 1, hd)``; outputs ``(1, heads, 1, hd)``
    float32, zero where the slot's step ends no chunk; ``k_buf`` /
    ``v_buf`` one block each."""
    slot = pl.program_id(0)

    @pl.when(ends_ref[slot] == 0)
    def _idle():
        k_out[...] = jnp.zeros_like(k_out)
        v_out[...] = jnp.zeros_like(v_out)

    @pl.when(ends_ref[slot] != 0)
    def _summarise():
        copies = [pltpu.make_async_copy(pool.at[blocks_ref[slot]], buf,
                                        sems.at[index])
                  for index, (pool, buf) in enumerate(
                      ((k_hbm, k_buf), (v_hbm, v_buf)))]
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()
        for head in range(heads):
            rows_k = k_buf[:, head, :].astype(jnp.float32)
            rows_v = v_buf[:, head, :].astype(jnp.float32)
            ks, vs = ks_ref[0, head], vs_ref[0, head]      # (block, 1)
            score = jnp.sum(rows_k * phi_ref[head], axis=-1,
                            keepdims=True) * (ks * sm_scale)
            weights = jnp.exp(score - jnp.max(score, axis=0,
                                              keepdims=True))
            weights = weights / jnp.sum(weights, axis=0, keepdims=True)
            k_out[0, head] = jnp.sum((weights * ks) * rows_k, axis=0,
                                     keepdims=True) + mu_ref[head]
            v_out[0, head] = jnp.sum((weights * vs) * rows_v, axis=0,
                                     keepdims=True)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def eva_summarise(block_ids, ends, k_pool, v_pool, ks, vs, phi, mu, *,
                  sm_scale: float, interpret: bool):
    slots = block_ids.shape[0]
    _, block, heads, head_dim = k_pool.shape

    def per_slot(slot, blocks, ends):
        return (slot, 0, 0, 0)

    def whole(slot, blocks, ends):
        return (0, 0, 0)

    # The pools stay in HBM and are CONSTRAINED to (the call's
    # arguments below): left free, XLA parks a whole 100 MB pool in
    # on-chip memory for a call that reads 64 KB of it (TPU compiler,
    # PR 41; as PR 29 found for the append kernel's outputs).
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    scales = pl.BlockSpec((1, heads, block, 1), per_slot)
    vectors = pl.BlockSpec((heads, 1, head_dim), whole)
    result = pl.BlockSpec((1, heads, 1, head_dim), per_slot)
    shape = jax.ShapeDtypeStruct((slots, heads, 1, head_dim), jnp.float32)
    block_buffer = pltpu.VMEM((block, heads, head_dim), k_pool.dtype)
    k_sum, v_sum = pl.pallas_call(
        functools.partial(_kernel, heads=heads, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(slots,),
            in_specs=[in_hbm] * 2 + [scales] * 2 + [vectors] * 2,
            out_specs=[result] * 2,
            scratch_shapes=[block_buffer, block_buffer,
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[shape, shape],
        interpret=interpret,
    )(block_ids.astype(jnp.int32), ends.astype(jnp.int32),
      *(pool if interpret else
        pltpu.with_memory_space_constraint(pool, pltpu.HBM)
        for pool in (k_pool, v_pool)),
      ks, vs, phi.astype(jnp.float32)[:, None, :],
      mu.astype(jnp.float32)[:, None, :])
    return k_sum[:, :, 0], v_sum[:, :, 0]


def chunk_summary(pool_layer, block_ids, ends, phi, mu, *,
                  sm_scale: float, interpret: bool = False):
    """Summary rows of the pool blocks ``block_ids (S,)`` for the slots
    whose flag ``ends (S,)`` is set (zeros for the others).

    ``pool_layer``: ``k`` / ``v`` ``(n_blocks, block, kv_heads,
    head_dim)`` (int8 with ``ks`` / ``vs`` scales, as planes ``(n_blocks,
    block, kv_heads)`` or as a decode scan's lane rows ``(n_blocks x
    rows, W)``; or a float type with none).  ``phi``, ``mu``
    ``(kv_heads, head_dim)``.  Returns ``k~, v~ (S, kv_heads,
    head_dim)`` float32."""
    n_blocks, block, heads, _ = pool_layer["k"].shape
    slots = block_ids.shape[0]

    def scales(name):
        if name not in pool_layer:
            return jnp.ones((slots, heads, block, 1), jnp.float32)
        held = pool_layer[name]
        if held.ndim == 3:
            cut = held[block_ids]
        else:
            rows = block * heads // held.shape[1]      # lane rows a block
            index = block_ids[:, None] * rows + jnp.arange(rows)[None, :]
            cut = held[index].reshape(slots, block, heads)
        return cut.transpose(0, 2, 1)[..., None]

    return eva_summarise(block_ids, ends, pool_layer["k"], pool_layer["v"],
                         scales("ks"), scales("vs"), phi, mu,
                         sm_scale=sm_scale, interpret=interpret)
