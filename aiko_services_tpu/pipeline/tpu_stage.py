"""TPU execution layer: jittable elements and stage fusion.

The core TPU-first idea (SURVEY.md §7.1): the pipeline *graph* stays a
host-side dataflow engine, but contiguous runs of TPU-capable elements
are fused into a **single jitted XLA program**.  Between fused elements
no host transfer, no serialization, no per-element dispatch — array swag
values are device buffers end to end, and XLA fuses elementwise chains
into the surrounding matmuls (MXU) instead of bouncing through HBM.

* :class:`TpuElement` — subclasses declare ``compute(params, inputs) ->
  outputs`` as a pure jittable function over arrays plus optional
  ``init_params(key)``.  Standalone, each TpuElement still runs jitted.
* :func:`build_fused_stages` — walks an execution path and groups maximal
  contiguous TpuElement runs; each group traces one composed function
  (per-element input renames resolved at trace time) compiled once and
  cached per input-shape signature.
* A ``runtime: "tpu"`` pipeline definition turns fusion on; the hot loop
  executes a fused stage as one step and skips its member nodes.

Sharded execution: a TpuElement may declare ``mesh_spec`` /
``param_partition_specs`` so its parameters live sharded over the process
mesh; the fused program then runs SPMD with XLA-inserted collectives.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .definition import apply_output_renames
from .element import PipelineElement
from .stream import StreamEvent

__all__ = ["TpuElement", "FusedStage", "build_fused_stages", "is_array"]


def is_array(value: Any) -> bool:
    return isinstance(value, (jax.Array, jnp.ndarray)) or \
        hasattr(value, "__array__")


class TpuElement(PipelineElement):
    """A PipelineElement whose computation is a pure JAX function."""

    def __init__(self, context, process=None):
        super().__init__(context, process)
        seed, _ = self.get_parameter("seed", 0)
        self.params = self.init_params(jax.random.PRNGKey(int(seed)))
        self._jitted: Optional[Callable] = None

    # -- subclass API -------------------------------------------------------- #

    def init_params(self, key) -> Any:
        """Return this element's parameter pytree (weights)."""
        return {}

    def compute(self, params, inputs: Dict[str, jax.Array]) \
            -> Dict[str, jax.Array]:
        """Pure jittable array function: swag-name → array in/out."""
        raise NotImplementedError

    # -- standalone execution (not fused) ------------------------------------- #

    def process_frame(self, stream, **inputs):
        if self._jitted is None:
            self._jitted = jax.jit(self.compute)
        arrays = {k: jnp.asarray(v) for k, v in inputs.items()}
        return StreamEvent.OKAY, self._jitted(self.params, arrays)


class FusedStage:
    """A maximal contiguous run of TpuElements compiled as one program."""

    def __init__(self, nodes: Sequence, elements: List[TpuElement],
                 input_sources: Dict[str, Dict[str, str]],
                 output_renames: Dict[str, Dict[str, List[str]]]):
        self.node_names = [node.name for node in nodes]
        self.elements = elements
        # node name -> {input: swag key} / {output: [namespaced keys]}
        # (the pipeline's map_in/map_out edge semantics, resolved at
        # trace time so fused numerics match the unfused hot loop).
        self.input_sources = input_sources
        self.output_renames = output_renames
        self.name = "+".join(self.node_names)
        params = tuple(element.params for element in self.elements)
        self._params = params
        self._compiled = jax.jit(self._trace)
        # Swag keys the member elements consume (post-mapping): these are
        # coerced to arrays (lists/scalars included) so fusion accepts
        # exactly what the standalone TpuElement path accepts.
        self._consumed = set()
        for element in self.elements:
            mapping = self.input_sources.get(element.name, {})
            names = (element.definition.input_names()
                     if element.definition else [])
            for input_name in names:
                self._consumed.add(mapping.get(input_name, input_name))

    def _trace(self, params: Tuple, swag_arrays: Dict[str, jax.Array]):
        """Composed compute across member elements; runs under jit."""
        pool = dict(swag_arrays)
        for element, element_params in zip(self.elements, params):
            mapping = self.input_sources.get(element.name, {})
            names = (element.definition.input_names()
                     if element.definition else list(pool))
            inputs = {}
            for input_name in names:
                source = mapping.get(input_name, input_name)
                if source in pool:
                    inputs[input_name] = pool[source]
            outputs = apply_output_renames(
                self.output_renames.get(element.name),
                dict(element.compute(element_params, inputs)))
            pool.update(outputs)
        return pool

    def __call__(self, swag: Dict[str, Any]) -> Dict[str, Any]:
        """Run the fused program over the array-valued swag entries;
        non-array entries pass through untouched.  Computed outputs take
        precedence over passthrough values of the same name (matching the
        non-fused ``frame.swag.update(outputs)`` semantics)."""
        arrays = {}
        passthrough = {}
        for key, value in swag.items():
            if is_array(value):
                arrays[key] = jnp.asarray(value)
            elif key in self._consumed:
                try:   # lists / scalars an element declared as input
                    arrays[key] = jnp.asarray(value)
                except (TypeError, ValueError):
                    passthrough[key] = value
            else:
                passthrough[key] = value
        # TraceAnnotation: free with no profiler attached; names this
        # stage's device ops in jax.profiler / XLA traces (SURVEY §5.1's
        # TPU equivalent of the reference's per-element wall stamps).
        with jax.profiler.TraceAnnotation(f"stage:{self.name}"):
            result = self._compiled(self._params, arrays)
        return {**passthrough, **result}

    def sync_outputs(self, swag: Dict[str, Any]) -> None:
        """Block until this stage's device work is COMPLETE, via a
        1-element host readback of one output (the per-device queue is
        FIFO, so one output syncs the whole program; the readback
        waits like block_until_ready and costs one extra element's
        copy).  Used for sampled device-true frame metrics."""
        import numpy as np
        for value in swag.values():
            if isinstance(value, jax.Array):
                np.asarray(value.ravel()[0:1])
                return


def build_fused_stages(path_nodes: Sequence, elements: Dict[str, Any],
                       input_sources: Dict[str, Dict[str, str]],
                       output_renames: Dict[str, Dict[str, List[str]]]) \
        -> Dict[str, FusedStage]:
    """Group maximal contiguous runs of TpuElements along an execution
    path.  Returns {first-node-name: FusedStage} for runs of length ≥ 2
    (a single TpuElement already runs jitted on its own)."""
    stages: Dict[str, FusedStage] = {}
    run: List = []

    def flush():
        nonlocal run
        if len(run) >= 2:
            stage = FusedStage(
                run, [elements[n.name] for n in run],
                {n.name: input_sources.get(n.name, {}) for n in run},
                {n.name: output_renames.get(n.name, {}) for n in run})
            stages[run[0].name] = stage
        run = []

    for node in path_nodes:
        element = elements.get(node.name)
        if isinstance(element, TpuElement):
            run.append(node)
        else:
            flush()
    flush()
    return stages
