"""State backend SPI — where keyed state lives.

reference: StateBackend SPI (flink-runtime/.../state/StateBackend.java)
with HashMapStateBackend (JVM heap) and EmbeddedRocksDBStateBackend
(native, beyond-memory) selected by ``state.backend``.

Re-design: in this architecture every backend runs the SAME batched
kernels — what a backend actually decides is *placement*: which device
holds the accumulator arrays. XLA computation follows data placement, so
committing the state to a device is the whole backend:

- ``tpu-slot-table`` (default): accumulators live on the accelerator
  (HBM); scatters/fires are device kernels; the spill tier extends
  beyond HBM (state.slot-table.max-device-slots).
- ``host-heap``: accumulators committed to the host CPU device —
  NOTHING crosses the accelerator link. The HashMapStateBackend role:
  right for small-state jobs where the accelerator's per-dispatch
  latency exceeds the compute (control-plane-heavy pipelines, tests).

Third-party backends register a placement factory under a name
(``register_state_backend``) — e.g. a second accelerator, or a specific
device of a multi-chip host.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

#: name -> () -> Optional[jax.Device] (None = default device)
_BACKENDS: Dict[str, Callable] = {}


def register_state_backend(name: str, placement_factory: Callable) -> None:
    """Register a backend: ``placement_factory() -> jax.Device | None``."""
    _BACKENDS[name] = placement_factory


def _default_placement():
    return None  # the platform's default device (accelerator when present)


def _host_placement():
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None  # no CPU backend registered: fall back to default


register_state_backend("tpu-slot-table", _default_placement)
register_state_backend("host-heap", _host_placement)


def resolve_placement(backend: str):
    """The device keyed-state accumulators commit to (None = default)."""
    try:
        factory = _BACKENDS[backend]
    except KeyError:
        raise ValueError(
            f"unknown state.backend {backend!r}; registered: "
            f"{sorted(_BACKENDS)}") from None
    return factory()
