"""Device meshes and sharding policy.

The TPU build's answer to the reference's process-fleet scaling
(SURVEY.md §2.6): instead of NCCL/MPI-style point-to-point plumbing, a
``jax.sharding.Mesh`` over the chip topology with named axes, and
``NamedSharding`` annotations that let XLA insert ICI collectives.

Axis conventions (the "How to Scale Your Model" recipe):

* ``dp``    — data parallel (batch dimension)
* ``tp``    — tensor parallel (hidden / heads dimension)
* ``sp``    — sequence/context parallel (ring attention over this axis)
* ``pp``    — pipeline-parallel stage axis (inter-stage hand-off)

``make_mesh`` builds a mesh from whatever devices exist (real TPU chips,
or the 8 virtual CPU devices used in tests via
``--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "MeshSpec", "make_mesh", "named_sharding", "shard_batch_spec",
    "logical_axis_rules", "filter_specs_for_mesh", "DEFAULT_AXES",
    "ReplicaMesh",
]

DEFAULT_AXES = ("dp", "tp")

P = PartitionSpec


class MeshSpec:
    """Declarative mesh shape: ``MeshSpec(dp=2, tp=4)``.

    ``-1`` for one axis means "all remaining devices".
    """

    def __init__(self, **axes: int):
        if not axes:
            axes = {"dp": -1}
        self.axes: Dict[str, int] = dict(axes)

    def resolve(self, device_count: int) -> Dict[str, int]:
        sizes = dict(self.axes)
        wildcard = [k for k, v in sizes.items() if v == -1]
        if len(wildcard) > 1:
            raise ValueError("Only one mesh axis may be -1")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wildcard:
            if device_count % fixed:
                raise ValueError(
                    f"{device_count} devices not divisible by {fixed}")
            sizes[wildcard[0]] = device_count // fixed
        elif fixed != device_count:
            raise ValueError(
                f"Mesh {sizes} needs {fixed} devices, have {device_count}")
        return sizes

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        devices = list(devices if devices is not None else jax.devices())
        sizes = self.resolve(len(devices))
        shape = tuple(sizes.values())
        array = np.asarray(devices).reshape(shape)
        return Mesh(array, tuple(sizes.keys()))


def make_mesh(devices: Optional[Sequence] = None, **axes: int) -> Mesh:
    return MeshSpec(**axes).build(devices)


@dataclasses.dataclass(frozen=True)
class ReplicaMesh:
    """One serving replica's device mesh: ``tp`` chips on the tensor
    axis, optionally × a SECOND axis (``sp`` sequence-parallel OR
    ``ep`` expert-parallel).  The serving tier's unit of capacity
    changes from "one chip" to "one mesh" — the paged KV pool shards
    along the kv-head dimension over ``axis`` (and REPLICATES over the
    second axis), model weights shard on their output feature axis,
    and the per-slot decode state stays replicated so the host-side
    admission/commit protocol is mesh-agnostic.

    Second-axis roles:

    * ``sp`` — sequence-parallel chunked prefill: one admission
      dispatch carries ``sp`` prompt chunks, each shard prefills its
      own chunk and all-gathers the window's K/V so every pool copy
      stays identical.  Decode runs replicated over ``sp`` (prefill
      TTFT is what the axis buys).
    * ``ep`` — expert-parallel MoE: the 3-D expert weights shard
      ``P(ep, None, tp)`` and every collective stays an all-gather, so
      MoE serving is exact (the old blanket MoE rejection is gone).

    A speculative DRAFT model rides the same mesh fully REPLICATED
    (params + its contiguous cache): draft passes run collective-free
    on every chip, identical by construction, and only the target's
    verify/decode programs shard — so TP spec serving stays bitwise
    equal to single-chip (ARCHITECTURE invariants 9 + 11 + 19).

    ``tp=1`` (and ``sp=ep=1``) degenerates to the single-chip layout.
    ``overlap=True`` opts the MLP down-projection into the
    :mod:`..parallel.collective_matmul` reduce-scatter layout — a
    LOSSY-layout bandwidth trade (partial-sum float order differs from
    single-chip), off by default; no cell or server turns it on.
    """

    tp: int = 1
    axis: str = "tp"
    sp: int = 1
    ep: int = 1
    sp_axis: str = "sp"
    ep_axis: str = "ep"
    overlap: bool = False

    @property
    def size(self) -> int:
        return self.tp * self.sp * self.ep

    @property
    def second_axis(self) -> Optional[str]:
        """Name of the active second axis, or None for a 1-D mesh."""
        if self.sp > 1:
            return self.sp_axis
        if self.ep > 1:
            return self.ep_axis
        return None

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        devices = list(devices if devices is not None
                       else jax.devices())
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.sp < 1 or self.ep < 1:
            raise ValueError(
                f"sp/ep must be >= 1, got sp={self.sp} ep={self.ep}")
        if self.sp > 1 and self.ep > 1:
            raise ValueError(
                "ReplicaMesh is at most 2-D: pick ONE second axis "
                f"(got sp={self.sp} AND ep={self.ep})")
        need = self.size
        if len(devices) < need:
            raise ValueError(
                f"ReplicaMesh(tp={self.tp}, sp={self.sp}, "
                f"ep={self.ep}) needs {need} devices, "
                f"have {len(devices)} (tests: set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)")
        second = self.second_axis
        if second is None:
            return Mesh(np.asarray(devices[: self.tp]), (self.axis,))
        n2 = self.sp if self.sp > 1 else self.ep
        array = np.asarray(devices[:need]).reshape(self.tp, n2)
        return Mesh(array, (self.axis, second))

    def validate(self, config) -> None:
        """Fail fast on layouts the TP engine cannot shard exactly.

        Every tensor-sharded dimension must divide by ``tp``: kv heads
        (the paged pool + attention grid), query heads (contiguous
        q-head ranges must cover whole kv-head groups), d_model / d_ff
        / vocab (output-axis weight sharding).  MoE configs shard
        their expert weights over the second (``ep``) axis — so
        ``n_experts`` must divide by ``ep`` — and their per-expert
        feature dims fall under the same ``tp`` rule."""
        if self.sp > 1 and self.ep > 1:
            raise ValueError(
                "ReplicaMesh is at most 2-D: pick ONE second axis "
                f"(got sp={self.sp} AND ep={self.ep})")
        n_experts = getattr(config, "n_experts", 0)
        if n_experts and n_experts % self.ep:
            raise ValueError(
                f"ReplicaMesh(ep={self.ep}): config.n_experts="
                f"{n_experts} is not divisible by the 'ep' axis size "
                f"{self.ep} (MoE expert weights shard over the "
                "second, expert-parallel mesh axis)")
        if self.ep > 1 and not n_experts:
            raise ValueError(
                f"ReplicaMesh(ep={self.ep}): the 'ep' axis shards MoE "
                "expert weights, but config.n_experts=0 (dense "
                "config) — use sp for a dense second axis")
        for name in ("n_kv_heads", "n_heads", "d_model", "d_ff",
                     "vocab_size"):
            value = getattr(config, name)
            if value % self.tp:
                raise ValueError(
                    f"ReplicaMesh(tp={self.tp}): config.{name}="
                    f"{value} is not divisible by the 'tp' axis size "
                    f"{self.tp}")


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def shard_batch_spec(mesh: Mesh) -> PartitionSpec:
    """Batch sharded over dp (and sp if present merges into batch rows)."""
    return P("dp") if "dp" in mesh.axis_names else P()


#: Logical-axis → mesh-axis rules for model parameter shardings
#: (flax-linen style but framework-agnostic).
def logical_axis_rules(mesh: Mesh) -> Dict[str, Optional[str]]:
    names = mesh.axis_names
    return {
        "batch": "dp" if "dp" in names else None,
        "seq": "sp" if "sp" in names else None,
        "heads": "tp" if "tp" in names else None,
        "kv_heads": "tp" if "tp" in names else None,
        "embed": None,
        "mlp": "tp" if "tp" in names else None,
        "vocab": "tp" if "tp" in names else None,
        "stage": "pp" if "pp" in names else None,
    }


def filter_specs_for_mesh(specs, mesh: Mesh):
    """Drop spec axes the mesh does not have (e.g. megatron "tp" specs
    on a dp-only mesh become replicated on that dim) — the same param
    layout tree then serves every topology."""
    names = set(mesh.axis_names)

    def fix(spec):
        if not isinstance(spec, PartitionSpec):
            return spec
        return PartitionSpec(*(axis if axis in names else None
                               for axis in spec))

    return jax.tree.map(
        fix, specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def mark_varying(x, axis_name):
    """shard_map varying-axis tracking: loop carries that pass through
    ``ppermute`` become axis-varying, so zero-inits must be marked
    varying too."""
    import jax
    return jax.lax.pcast(x, axis_name, to="varying")
