"""Raw-RDMA ring collective matmuls (Pallas kernels).

The shard_map+ppermute collective matmuls
(:mod:`.collective_matmul`) let XLA schedule the overlap; these
kernels own it explicitly with inter-chip RDMA
(``pltpu.make_async_remote_copy``): per ring step, the MXU multiplies
the activation block a device already holds while the DMA engines move
the next block to its right neighbor — transfer strictly behind
compute, no collective op in the XLA graph at all (SURVEY.md §7.2
step 8's "raw RDMA" north star).

Correctness protocol (the part ppermute gave us for free):

- **Double-buffered comm slots** ``(2, m_local, k)``: step ``s``
  computes from ``slot = s % 2`` while the RDMA receives the next
  block into ``1 - slot``.
- **Capacity handshake** (REGULAR semaphore): a sender may overwrite a
  receiver slot only after the receiver signalled it free — without
  it, a fast left neighbor racing one step ahead corrupts the block a
  slow device is still multiplying (a real hazard of raw RDMA; the
  kernel would be wrong on hardware even though interpret mode's
  sequential execution can't exhibit it).
- **Start barrier** (``pltpu.get_barrier_semaphore``): ring neighbors
  must not start signalling before everyone entered the kernel.

Validation: interpret mode on the virtual CPU mesh, exact against the
dense oracle and the ppermute twins (``tests/test_rdma_collective.py``).
Hardware dispatch stays GATED (``interpret=False`` requires a real
multi-chip TPU backend): one chip has no neighbor to DMA to, and the
compiled kernels have not run on hardware yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["rdma_allgather_matmul", "rdma_matmul_reducescatter",
           "rdma_allgather_matmul_sharded",
           "rdma_matmul_reducescatter_sharded"]

#: Distinct collective_ids per kernel family (barrier semaphores are
#: keyed by these; sharing one id across different kernels deadlocks).
_AG_COLLECTIVE_ID = 11
_RS_COLLECTIVE_ID = 12


def _neighbors(axis_name):
    my_id = jax.lax.axis_index(axis_name)
    num = jax.lax.axis_size(axis_name)
    right = jax.lax.rem(my_id + 1, num)
    left = jax.lax.rem(my_id + num - 1, num)
    return my_id, num, right, left


def _ring_barrier(left, right):
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, inc=1, device_id=left,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_signal(barrier, inc=1, device_id=right,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(barrier, 2)


def _ag_kernel(x_ref, w_ref, out_ref, comm_ref, local_sem, send_sem,
               recv_sem, capacity_sem, *, axis_name, m_local,
               interpret):
    # `interpret` gates the REMOTE-SEMAPHORE protocol (start barrier +
    # capacity handshake) at trace time: interpret mode implements
    # remote DMA but not remote signals (NotImplementedError), and its
    # sequential execution cannot exhibit the overwrite race the
    # handshake prevents.  Hardware dispatch traces the full protocol.
    my_id, num, right, left = _neighbors(axis_name)
    if not interpret:
        _ring_barrier(left, right)

    # Stage the local shard into comm slot 0 (plain local DMA).
    staged = pltpu.make_async_copy(x_ref, comm_ref.at[0], local_sem)
    staged.start()
    staged.wait()
    if not interpret:
        # Slot 1 is free for the left neighbor's first incoming block.
        pltpu.semaphore_signal(
            capacity_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def body(step, _):
        slot = jax.lax.rem(step, 2)
        next_slot = jax.lax.rem(step + 1, 2)
        # The block held at `step` originated on (my_id - step) mod
        # num: its product lands at that row offset.
        src = jax.lax.rem(my_id - step + num, num)

        @pl.when(step < num - 1)
        def _send():
            # Right neighbor must have freed the slot we are about to
            # overwrite (capacity handshake).
            if not interpret:
                pltpu.semaphore_wait(capacity_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=comm_ref.at[slot],
                dst_ref=comm_ref.at[next_slot],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[next_slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()

        # MXU work overlaps the in-flight DMAs.
        product = jnp.dot(comm_ref[slot], w_ref[:],
                          preferred_element_type=jnp.float32)
        out_ref[pl.ds(src * m_local, m_local), :] = \
            product.astype(out_ref.dtype)

        @pl.when(step < num - 1)
        def _settle():
            # Send done (slot reusable) + our own receive arrived.
            pltpu.make_async_copy(comm_ref.at[slot], comm_ref.at[slot],
                                  send_sem.at[slot]).wait()
            pltpu.make_async_copy(comm_ref.at[next_slot],
                                  comm_ref.at[next_slot],
                                  recv_sem.at[next_slot]).wait()
            if not interpret:
                # The slot we just computed from is now free for the
                # left neighbor's NEXT write.
                pltpu.semaphore_signal(
                    capacity_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    jax.lax.fori_loop(0, num, body, 0)
    if not interpret:
        # Drain the one unconsumed credit (num signals received,
        # num-1 waited): a REGULAR semaphore must leave the kernel at
        # zero or the next invocation starts with a stale +1 — which
        # would let a fast left neighbor skip one handshake.
        pltpu.semaphore_wait(capacity_sem, 1)


def rdma_allgather_matmul(x_shard, w_shard, axis_name: str,
                          interpret: bool = True):
    """``allgather(x, axis) @ w_shard`` — shard_map-body twin of
    ``collective_matmul.allgather_matmul``, transfer via raw RDMA.
    x_shard ``(m_local, k)``, w_shard ``(k, n_local)`` →
    ``(m_local * axis_size, n_local)``."""
    m_local, k = x_shard.shape
    n_local = w_shard.shape[1]
    size = jax.lax.axis_size(axis_name)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, m_local, k), x_shard.dtype),  # comm slots
            pltpu.SemaphoreType.DMA(()),                 # local stage
            pltpu.SemaphoreType.DMA((2,)),               # send per slot
            pltpu.SemaphoreType.DMA((2,)),               # recv per slot
            pltpu.SemaphoreType.REGULAR,                 # capacity
        ],
    )
    return pl.pallas_call(
        functools.partial(_ag_kernel, axis_name=axis_name,
                          m_local=m_local, interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((m_local * size, n_local),
                                       x_shard.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            collective_id=_AG_COLLECTIVE_ID),
    )(x_shard, w_shard)


def _rs_kernel(x_ref, w_ref, out_ref, acc_ref, partial_ref, local_sem,
               send_sem, recv_sem, capacity_sem, *, axis_name, n_local,
               interpret):
    my_id, num, right, left = _neighbors(axis_name)
    if not interpret:          # see _ag_kernel on the interpret gate
        _ring_barrier(left, right)
        pltpu.semaphore_signal(
            capacity_sem, inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    def partial_for(owner):
        w_slice = w_ref[:, pl.dslice(owner * n_local, n_local)]
        return jnp.dot(x_ref[:], w_slice,
                       preferred_element_type=jnp.float32)

    def body(step, _):
        slot = jax.lax.rem(step, 2)
        next_slot = jax.lax.rem(step + 1, 2)
        # The accumulator we hold is travelling to device
        # (my_id + num-1-step) mod num — add our partial for it.
        owner = jax.lax.rem(my_id + num - 1 - step, num)

        @pl.when(step == 0)
        def _init():
            acc_ref[0] = partial_for(owner).astype(acc_ref.dtype)

        @pl.when(step > 0)
        def _accumulate():
            # The matmul for this step was precomputed while the
            # accumulator was in flight — only a cheap add here.
            acc_ref[slot] = acc_ref[slot] + partial_ref[:]

        @pl.when(step < num - 1)
        def _forward():
            if not interpret:
                pltpu.semaphore_wait(capacity_sem, 1)
            rdma = pltpu.make_async_remote_copy(
                src_ref=acc_ref.at[slot],
                dst_ref=acc_ref.at[next_slot],
                send_sem=send_sem.at[slot],
                recv_sem=recv_sem.at[next_slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
            rdma.start()
            # Overlap: while the accumulator flies, compute the
            # partial the INCOMING accumulator will need (next step's
            # owner = this owner - 1 mod num).  partial_ref and the
            # in-flight acc slots are distinct buffers, so this is
            # race-free.
            next_owner = jax.lax.rem(owner + num - 1, num)
            partial_ref[:] = partial_for(next_owner)
            rdma.wait()
            if not interpret:
                pltpu.semaphore_signal(
                    capacity_sem, inc=1, device_id=left,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)
        return 0

    jax.lax.fori_loop(0, num, body, 0)
    if not interpret:
        pltpu.semaphore_wait(capacity_sem, 1)   # drain (see _ag_kernel)
    final_slot = jax.lax.rem(num - 1, 2)
    out_ref[:] = acc_ref[final_slot].astype(out_ref.dtype)


def rdma_matmul_reducescatter(x_shard, w_shard, axis_name: str,
                              interpret: bool = True):
    """``reduce_scatter(x_shard @ w_shard, axis)`` — twin of
    ``collective_matmul.matmul_reducescatter`` over raw RDMA.
    x_shard ``(m, k_local)``, w_shard ``(k_local, n)`` →
    ``(m, n // axis_size)``."""
    m = x_shard.shape[0]
    n = w_shard.shape[1]
    size = jax.lax.axis_size(axis_name)
    n_local = n // size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, m, n_local), jnp.float32),    # acc slots
            pltpu.VMEM((m, n_local), jnp.float32),       # next partial
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ],
    )
    return pl.pallas_call(
        functools.partial(_rs_kernel, axis_name=axis_name,
                          n_local=n_local, interpret=interpret),
        out_shape=jax.ShapeDtypeStruct((m, n_local), x_shard.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            collective_id=_RS_COLLECTIVE_ID),
    )(x_shard, w_shard)


def _require_multichip_tpu():
    if jax.default_backend() not in ("tpu",) or len(jax.devices()) < 2:
        raise RuntimeError(
            "interpret=False needs a real multi-chip TPU backend; "
            "inter-chip RDMA cannot run on a single chip or CPU "
            "(keep interpret=True there)")


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "interpret"))
def rdma_allgather_matmul_sharded(x, w, mesh: Mesh, axis: str = "tp",
                                  interpret: bool = True):
    """Host-level wrapper matching
    ``collective_matmul.allgather_matmul_sharded``."""
    if not interpret:
        _require_multichip_tpu()
    return shard_map(
        functools.partial(rdma_allgather_matmul, axis_name=axis,
                          interpret=interpret),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "interpret"))
def rdma_matmul_reducescatter_sharded(x, w, mesh: Mesh,
                                      axis: str = "tp",
                                      interpret: bool = True):
    """Host-level wrapper matching
    ``collective_matmul.matmul_reducescatter_sharded``."""
    if not interpret:
        _require_multichip_tpu()
    return shard_map(
        functools.partial(rdma_matmul_reducescatter, axis_name=axis,
                          interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)
