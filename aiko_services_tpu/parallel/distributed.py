"""Multi-host bootstrap: the DCN story.

The reference scales by spawning OS processes glued with MQTT
(``main/process_manager.py:48-110``, ``main/lifecycle.py:98-288``); its
"comms backend" is the broker.  The TPU equivalent splits the two
planes: the *control* plane stays on the framework's broker transports,
while the *data* plane is ``jax.distributed`` — one JAX process per
host, a global device set, and XLA collectives riding ICI within a
slice and DCN across slices.

Three pieces:

* :func:`initialize_multihost` — guarded, idempotent wrapper around
  ``jax.distributed.initialize``; reads standard env vars, supports
  UDP coordinator discovery (same idiom as the reference's ``boot?``
  broadcast, ``utilities/configuration.py:160-187``), and picks the
  gloo CPU collectives automatically so the SAME code path runs real
  multi-process tests on CPU hosts.
* :class:`CoordinatorAnnouncer` / :func:`discover_coordinator` — the
  process hosting the coordinator answers ``coord?`` broadcasts with
  ``coord {address} {num_processes}`` so workers need no static config.
* :func:`hybrid_mesh` — a ``Mesh`` whose leading axes span slices (DCN)
  and trailing axes span chips within a slice (ICI), grouped by the
  devices' slice/process attributes.  Shardings then place the
  bandwidth-hungry collectives (tp/sp) on ICI and the amortized ones
  (dp gradient reduction) on DCN.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence

from ..utils.config import UdpResponder, udp_request

__all__ = [
    "MultiHostConfig", "initialize_multihost", "hybrid_mesh",
    "CoordinatorAnnouncer", "discover_coordinator", "worker_env",
    "COORDINATOR_DISCOVERY_PORT",
]

#: One above the reference's broker-bootstrap port (4149): same idiom,
#: different plane.
COORDINATOR_DISCOVERY_PORT = 4150
_DISCOVERY_REQUEST = b"coord?"


@dataclasses.dataclass(frozen=True)
class MultiHostConfig:
    coordinator_address: str
    num_processes: int
    process_id: int

    @classmethod
    def from_env(cls) -> Optional["MultiHostConfig"]:
        """Standard jax.distributed env triplet; None when absent (the
        single-host case — callers then skip initialization)."""
        address = os.environ.get("JAX_COORDINATOR_ADDRESS")
        num = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        if not (address and num and pid):
            return None
        return cls(address, int(num), int(pid))


def worker_env(process_id: int, num_processes: int,
               coordinator_address: str,
               local_device_count: Optional[int] = None) -> Dict[str, str]:
    """Environment for a ProcessManager-spawned multi-host worker: the
    orchestration layer (reference semantics: LifeCycleManager fleets)
    starts one OS process per host with exactly this env and the child
    calls :func:`initialize_multihost()` with no arguments."""
    env = {
        "JAX_COORDINATOR_ADDRESS": coordinator_address,
        "JAX_NUM_PROCESSES": str(num_processes),
        "JAX_PROCESS_ID": str(process_id),
    }
    if local_device_count is not None:
        # Append to (not clobber) any operator-supplied tuning flags.
        existing = os.environ.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{existing} --xla_force_host_platform_device_count="
            f"{local_device_count}").strip()
    return env


def initialize_multihost(config: Optional[MultiHostConfig] = None,
                         discover: bool = False,
                         discovery_port: int = COORDINATOR_DISCOVERY_PORT,
                         discovery_timeout: float = 5.0,
                         discovery_address: str = "255.255.255.255",
                         process_id: Optional[int] = None,
                         _initialize=None) -> Dict:
    """Bring this process into the global JAX world.

    Resolution order: explicit ``config`` → env triplet
    (:meth:`MultiHostConfig.from_env`) → UDP discovery (when
    ``discover=True``; the coordinator host runs a
    :class:`CoordinatorAnnouncer` and workers learn the address +
    world size, supplying only their ``process_id``).  Idempotent: a
    second call returns the current world without re-initializing.

    Returns ``{"initialized", "process_id", "num_processes",
    "coordinator_address"}``.  ``_initialize`` is injectable for tests.
    """
    import jax

    try:  # private API, guarded: absence just disables the fast no-op
        state = jax._src.distributed.global_state
        already = getattr(state, "client", None) is not None
    except Exception:  # noqa: BLE001
        state, already = None, False
    if already:
        return {"initialized": False,
                "process_id": jax.process_index(),
                "num_processes": jax.process_count(),
                "coordinator_address": getattr(
                    state, "coordinator_address", None)}

    if config is None:
        config = MultiHostConfig.from_env()
    if config is None and discover:
        found = discover_coordinator(port=discovery_port,
                                     timeout=discovery_timeout,
                                     address=discovery_address)
        if found is None:
            raise RuntimeError(
                "coordinator discovery timed out: no CoordinatorAnnouncer "
                f"answered on UDP port {discovery_port}")
        address, num_processes = found
        if process_id is None:
            raise ValueError(
                "discovery provides the coordinator, not your rank: pass "
                "process_id=")
        config = MultiHostConfig(address, num_processes, process_id)
    if config is None:
        raise RuntimeError(
            "no multi-host config: pass MultiHostConfig, set "
            "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/JAX_PROCESS_ID, "
            "or use discover=True")

    # CPU fleets/tests need gloo collectives to cross process
    # boundaries the way ICI/DCN do on pods.  Inspect the CONFIG, not
    # jax.default_backend(): touching the backend before
    # jax.distributed.initialize would pin a single-process world.
    platforms = (jax.config.jax_platforms or
                 os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in (platforms or ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    initialize = _initialize or jax.distributed.initialize
    try:
        initialize(coordinator_address=config.coordinator_address,
                   num_processes=config.num_processes,
                   process_id=config.process_id)
    except RuntimeError as error:
        # Idempotence backstop should the private-state probe above
        # ever stop working across a jax upgrade.
        if "already" in str(error).lower():
            return {"initialized": False,
                    "process_id": jax.process_index(),
                    "num_processes": jax.process_count(),
                    "coordinator_address": config.coordinator_address}
        raise
    return {"initialized": True,
            "process_id": config.process_id,
            "num_processes": config.num_processes,
            "coordinator_address": config.coordinator_address}


# --------------------------------------------------------------------------- #
# Coordinator discovery (UDP broadcast, reference boot? idiom)

class CoordinatorAnnouncer(UdpResponder):
    """Answer ``coord?`` broadcasts with ``coord {address} {n}`` — the
    reference's broker-bootstrap idiom applied to the data plane.  Run
    on the host that will be process 0; ``port=0`` binds an ephemeral
    port (tests)."""

    def __init__(self, coordinator_address: str, num_processes: int,
                 port: int = COORDINATOR_DISCOVERY_PORT,
                 bind_address: str = ""):
        super().__init__(
            _DISCOVERY_REQUEST,
            f"coord {coordinator_address} {num_processes}".encode(),
            port, bind_address, thread_name="coordinator_announcer")


def discover_coordinator(port: int = COORDINATOR_DISCOVERY_PORT,
                         timeout: float = 5.0,
                         address: str = "255.255.255.255"):
    """Broadcast ``coord?``; returns (coordinator_address, num_processes)
    or None on timeout."""
    def parse(fields):
        if len(fields) == 3 and fields[0] == "coord":
            return fields[1], int(fields[2])
        return None
    return udp_request(_DISCOVERY_REQUEST, parse, port, timeout, address)


# --------------------------------------------------------------------------- #
# Hybrid DCN x ICI meshes

def _group_keys(devices):
    """Slice keys for DCN grouping.  TPU multislice: ``slice_index``
    differs per slice.  When every device reports the same slice (CPU
    fleets, single-slice pods driven as a process fleet), the owning
    process stands in — the process boundary IS the DCN there."""
    slice_keys = [getattr(d, "slice_index", None) for d in devices]
    if None not in slice_keys and len(set(slice_keys)) > 1:
        return [int(k) for k in slice_keys]
    return [int(getattr(d, "process_index", 0)) for d in devices]


def hybrid_mesh(dcn: Dict[str, int], ici: Dict[str, int],
                devices: Optional[Sequence] = None):
    """Mesh with leading DCN axes (across slices) and trailing ICI axes
    (within a slice): ``hybrid_mesh({"dp": 2}, {"tp": 4})`` on 2 slices
    x 4 chips.  Data-parallel gradient reductions then cross DCN once
    per step while tensor/sequence-parallel collectives stay on ICI —
    the standard placement, because tp/sp traffic is per-layer and
    bandwidth-hungry.

    Device order within each group follows ``id`` (jax's enumeration
    order, which matches the physical ICI order for TPU backends).
    ``-1`` works as in :class:`MeshSpec` within each of dcn/ici.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from .mesh import MeshSpec

    devices = list(devices if devices is not None else jax.devices())
    groups: Dict[int, list] = {}
    for device, key in zip(devices, _group_keys(devices)):
        groups.setdefault(key, []).append(device)
    n_slices = len(groups)
    per_slice = {key: len(group) for key, group in groups.items()}
    if len(set(per_slice.values())) != 1:
        raise ValueError(f"uneven slices: {per_slice}")
    slice_size = next(iter(per_slice.values()))

    dcn_sizes = MeshSpec(**dcn).resolve(n_slices)
    ici_sizes = MeshSpec(**ici).resolve(slice_size)
    overlap = set(dcn_sizes) & set(ici_sizes)
    if overlap:
        raise ValueError(f"axis named in both dcn and ici: {overlap}")

    ordered = []
    for key in sorted(groups):
        ordered.extend(sorted(groups[key], key=lambda d: d.id))
    shape = tuple(dcn_sizes.values()) + tuple(ici_sizes.values())
    array = np.asarray(ordered, dtype=object).reshape(shape)
    return Mesh(array, tuple(dcn_sizes.keys()) + tuple(ici_sizes.keys()))
