"""Pipeline parallelism (pp): GPipe-style microbatching over a mesh axis.

The reference expresses pipeline stages as remote PipelineElements in
different OS processes with MQTT frame hops (SURVEY.md §2.6 maps that to
PP).  On TPU the same idea lives *inside* one jitted program: layers are
split into ``pp`` stages (one per device along the ``pp`` mesh axis),
microbatches stream through the stages, and activations hop stage→stage
with ``ppermute`` over ICI.  The schedule is the classic GPipe fill/
drain: ``n_micro + pp − 1`` rounds, stage ``s`` working on microbatch
``t − s`` in round ``t``; bubbles compute garbage that is masked out of
the result (branch-free — XLA/SPMD want a uniform program).

``stage_params`` must be a pytree whose leaves are stacked on a leading
stage axis, sharded ``P("pp", …)`` — inside ``shard_map`` every device
then holds exactly its stage's slice.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_apply_sharded", "stack_stages"]


def stack_stages(per_stage_params):
    """Stack a list of per-stage pytrees on a new leading stage axis
    (what ``P("pp", …)`` shards)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *per_stage_params)


from .mesh import mark_varying as _mark_varying


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   axis_name: str):
    """Inside-shard_map body.

    ``stage_params``: this device's stage slice (leading stage axis of
    size 1, squeezed here).  ``microbatches``: ``(n_micro, mb, …)`` —
    replicated; only stage 0 reads it.  Returns ``(n_micro, mb, …)``
    outputs, valid on the LAST stage (zeros elsewhere; the host wrapper
    psum-selects them).
    """
    pp = jax.lax.axis_size(axis_name)
    index = jax.lax.axis_index(axis_name)
    my_params = jax.tree.map(lambda leaf: leaf[0], stage_params)
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    recv = _mark_varying(jnp.zeros_like(microbatches[0]), axis_name)
    outputs = _mark_varying(
        jnp.zeros((n_micro,) + microbatches.shape[1:],
                  microbatches.dtype), axis_name)

    def round_body(carry, t):
        recv, outputs = carry
        # Stage 0 feeds from the microbatch queue; others from the ring.
        feed_index = jnp.clip(t, 0, n_micro - 1)
        feed = jax.lax.dynamic_index_in_dim(microbatches, feed_index,
                                            keepdims=False)
        inp = jnp.where(index == 0, feed, recv)
        out = stage_fn(my_params, inp)
        # Microbatch id this stage just produced; valid in [0, n_micro).
        micro = t - index
        valid = jnp.logical_and(micro >= 0, micro < n_micro)
        is_last = index == pp - 1
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(jnp.logical_and(valid, is_last), out,
                      jax.lax.dynamic_index_in_dim(
                          outputs, jnp.clip(micro, 0, n_micro - 1),
                          keepdims=False)),
            jnp.clip(micro, 0, n_micro - 1), axis=0)
        # Hand this round's activation to the next stage (the wrap-around
        # last→0 edge carries garbage; stage 0 never reads recv).
        recv = jax.lax.ppermute(out, axis_name, perm)
        return (recv, outputs), None

    # scan (not fori_loop) so reverse-mode AD works: this makes the
    # whole schedule differentiable and enables pipeline-parallel
    # TRAINING (grad of ppermute = ppermute with the inverse ring).
    (_, outputs), _ = jax.lax.scan(
        round_body, (recv, outputs), jnp.arange(n_micro + pp - 1))
    # Only the last stage holds real outputs; make them uniform so the
    # host wrapper can return replicated results.
    return jax.lax.psum(
        jnp.where(index == pp - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


@functools.partial(jax.jit,
                   static_argnames=("stage_fn", "mesh", "axis",
                                    "n_microbatches"))
def pipeline_apply_sharded(stage_fn: Callable, stage_params, x,
                           mesh: Mesh, axis: str = "pp",
                           n_microbatches: int = 4):
    """Host-level wrapper: ``x (batch, …)`` is split into
    ``n_microbatches`` along batch, streamed through the stages, and
    reassembled.  ``stage_params`` leaves are stacked ``(pp, …)`` and
    get sharded over ``axis``."""
    batch = x.shape[0]
    assert batch % n_microbatches == 0, (batch, n_microbatches)
    micro = x.reshape((n_microbatches, batch // n_microbatches)
                      + x.shape[1:])
    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    out = shard_map(
        functools.partial(pipeline_apply, stage_fn,
                          axis_name=axis),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )(stage_params, micro)
    return out.reshape((batch,) + out.shape[2:])
