"""Device mesh construction for keyed-state sharding.

The key-group axis (SURVEY.md §2.9 "keyed parallelism") is THE parallel axis
of a streaming dataflow: state, timers and window merges are all partitioned
by key group (reference: KeyGroupRangeAssignment.java). On TPU this axis maps
onto a 1-D ``jax.sharding.Mesh``; cross-shard exchange ("the shuffle",
reference: flink-runtime/.../io/network/) becomes host-side bucketing into a
[shards, ...] leading axis + ``shard_map`` collectives over ICI.

Pod scale (ROADMAP item 2): the same key-group axis can SPAN PROCESSES —
``make_mesh(span="process")`` builds the mesh over ``jax.devices()``
(global, process-major order), and a :class:`HostTopology` records the
``(hosts, local)`` factorization the two-level ICI/DCN exchange
(``parallel/exchange2.py``) programs against. On CPU the same shape runs
as N processes x M virtual devices (``jax.distributed.initialize`` + the
gloo cross-process collectives — :func:`initialize_distributed`), which
is how the two-process tests and chaos scenarios exercise the pod data
plane without a pod.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

KEY_AXIS = "keygroups"
#: axis names of the 2-D (hosts, local) view the two-level exchange uses —
#: flattened host-major, the 2-D view IS the key-group axis (sharding
#: equivalence holds because the device order is identical)
HOST_AXIS = "hosts"
LOCAL_AXIS = "local"

shard_map = jax.shard_map


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """The ``(hosts, local)`` factorization of the key-group axis.

    ``num_hosts`` is the DCN dimension (one entry per process / TPU
    host), ``local_devices`` the ICI dimension (devices per host). The
    flat shard index is host-major: shard ``p`` lives on host
    ``p // local_devices`` at local index ``p % local_devices`` — the
    same order ``jax.devices()`` enumerates a multi-process mesh, so
    the 2-D ``(hosts, local)`` mesh view and the flat key-group mesh
    address the same device the same way. A single-process test mesh
    can declare a VIRTUAL topology (e.g. 2x4 over 8 virtual CPU
    devices); the exchange programs only see the factorization.
    """

    num_hosts: int
    local_devices: int

    def __post_init__(self):
        if self.num_hosts < 1 or self.local_devices < 1:
            raise ValueError(
                f"topology must be positive, got "
                f"{self.num_hosts}x{self.local_devices}")

    @property
    def num_shards(self) -> int:
        return self.num_hosts * self.local_devices

    def host_of_shard(self, shard: int) -> int:
        return int(shard) // self.local_devices

    def shards_of_host(self, host: int) -> range:
        h = int(host)
        if not (0 <= h < self.num_hosts):
            raise ValueError(
                f"no host {h} in a {self.num_hosts}-host topology")
        return range(h * self.local_devices,
                     (h + 1) * self.local_devices)

    def check_covers(self, num_shards: int) -> None:
        """Raise unless this factorization describes exactly
        ``num_shards`` shards (the one validation every consumer —
        engines, watchdog, pod plane — applies)."""
        if self.num_shards != int(num_shards):
            raise ValueError(
                f"host topology {self.num_hosts}x"
                f"{self.local_devices} does not cover a "
                f"{int(num_shards)}-shard mesh")


def make_mesh(num_devices: Optional[int] = None, devices=None,
              span: str = "local") -> Mesh:
    """A 1-D mesh over the key-group axis.

    ``span="local"`` (the default) builds over this process's view —
    identical to the historical behavior on a single process.
    ``span="process"`` builds over ALL processes' devices
    (``jax.devices()`` is global once ``jax.distributed.initialize``
    ran), process-major — the pod mesh the two-level exchange spans.
    """
    if span not in ("local", "process"):
        raise ValueError(
            f"span must be 'local' or 'process', got {span!r}")
    if devices is None:
        if span == "process":
            devices = _global_devices_process_major()
        else:
            devices = jax.devices()
        if num_devices is not None:
            if num_devices > len(devices):
                raise ValueError(
                    f"requested a {num_devices}-device mesh but only "
                    f"{len(devices)} device(s) are available "
                    f"(span={span!r}) — a silently smaller mesh would "
                    "re-route key groups; shrink the request or add "
                    "devices")
            devices = devices[:num_devices]
    return Mesh(np.array(devices), (KEY_AXIS,))


def _global_devices_process_major() -> List:
    """``jax.devices()`` ordered (process, local) — the host-major flat
    order :class:`HostTopology` assumes. jax already enumerates by
    process; the explicit sort pins the contract."""
    return sorted(jax.devices(),
                  key=lambda d: (d.process_index, d.id))


def process_topology() -> HostTopology:
    """The REAL process topology: one "host" per jax process, uniform
    local device count (jax requires it for collectives)."""
    return HostTopology(jax.process_count(), jax.local_device_count())


def pod_mesh_view(mesh: Mesh, topology: HostTopology) -> Mesh:
    """The 2-D ``(hosts, local)`` view of a flat key-group mesh: SAME
    devices, same order, reshaped — ``NamedSharding(view, P((HOST_AXIS,
    LOCAL_AXIS)))`` is equivalent to the flat ``P(KEY_AXIS)`` sharding,
    so arrays flow between flat and two-level programs without a copy."""
    devs = list(mesh.devices.flat)
    if topology.num_shards != len(devs):
        raise ValueError(
            f"topology {topology.num_hosts}x{topology.local_devices} "
            f"does not cover a {len(devs)}-device mesh")
    return Mesh(
        np.array(devs).reshape(topology.num_hosts,
                               topology.local_devices),
        (HOST_AXIS, LOCAL_AXIS))


def initialize_distributed(coordinator_address: str,
                           num_processes: int,
                           process_id: int) -> None:
    """Bring up the multi-process runtime for a CPU pod-shape run:
    enables the gloo cross-process CPU collectives (without which the
    CPU backend raises "Multiprocess computations aren't implemented")
    and calls ``jax.distributed.initialize``. Must run before the first
    backend touch; real TPU pods skip the gloo step (ICI/DCN collectives
    are native) but the call is harmless there."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def shard_leading(mesh: Mesh) -> NamedSharding:
    """Sharding that splits the leading axis across the key-group axis."""
    return NamedSharding(mesh, P(KEY_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
