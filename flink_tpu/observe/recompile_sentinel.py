"""Zero-recompile sentinel: assert the steady state stays compiled.

The framework's throughput rests on every per-batch step being a cache
hit on an already-compiled XLA program. A regression that varies a jit
cache key per step (a fresh lambda identity, an unpadded shape, a
cache key missing a device id) does not fail any correctness test — it
just recompiles every batch and quietly erases the pipelining wins.
This module counts *actual backend compiles* via :mod:`jax.monitoring`
(the ``/jax/core/compile/backend_compile_duration`` event fires once
per real XLA compilation, cache hits do not emit it) and
*device->host materializations* (every ``ArrayImpl.__array__``
invocation — the choke point ``jax.device_get`` and friends funnel
through), and exposes a context manager that raises when a guarded
region exceeds its budget::

    with RecompileSentinel(max_compiles=0, label="steady state") as s:
        for batch in stream:           # post-warmup reps
            engine.process_batch(batch)
    print(s.compiles, s.transfers)

Counting is process-global and installed once (jax.monitoring has no
listener deregistration); the sentinel reads deltas. The transfer
count is a *lower bound* on host reads: on the CPU backend NumPy can
consume jax arrays zero-copy through the buffer protocol without
calling ``__array__`` — on a real TPU every host materialization goes
through it. Budgets on transfers are therefore best-effort bounds,
while the compile count is exact on every backend.
"""

from __future__ import annotations

from typing import Callable, List, Optional

#: the monitoring event emitted once per real XLA backend compilation
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_probe_counts = {"compiles": 0, "transfers": 0}
_installed = False
#: subscriber fan-out: the hooks are installed ONCE process-wide
#: (jax.monitoring has no listener deregistration), so other consumers
#: of the same signals — the flight recorder correlates compiles and
#: D2H materializations into its span timeline — subscribe here
#: instead of double-wrapping __array__
_compile_listeners: List[Callable[[float], None]] = []
_transfer_listeners: List[Callable[[], None]] = []


class SteadyStateViolation(AssertionError):
    """A guarded region compiled or transferred past its budget."""


def add_compile_listener(cb: Callable[[float], None]) -> None:
    """Subscribe ``cb(duration_secs)`` to real XLA backend compiles
    (idempotent per callback)."""
    if cb not in _compile_listeners:
        _compile_listeners.append(cb)


def add_transfer_listener(cb: Callable[[], None]) -> None:
    """Subscribe ``cb()`` to device->host materializations (idempotent
    per callback; best-effort, see module docstring)."""
    if cb not in _transfer_listeners:
        _transfer_listeners.append(cb)


def _on_duration_event(name: str, secs: float, **kwargs) -> None:
    if name == _COMPILE_EVENT:
        _probe_counts["compiles"] += 1
        for cb in _compile_listeners:
            cb(secs)


def install() -> None:
    """Idempotent one-time hook installation (listener + __array__
    wrapper). Deferred so importing flink_tpu never forces jax init."""
    global _installed
    if _installed:
        return
    import jax

    # the installed jaxlib's concrete array type; an installation this
    # cannot hook raises here rather than counting 0 transfers for ever
    from jax._src.array import ArrayImpl

    orig_array = ArrayImpl.__array__

    def _counting_array(self, *args, **kwargs):
        _probe_counts["transfers"] += 1
        for cb in _transfer_listeners:
            cb()
        return orig_array(self, *args, **kwargs)

    ArrayImpl.__array__ = _counting_array
    jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
    _installed = True


#: original (pre-rename) spelling, kept for existing callers
_install = install


def compile_count() -> int:
    """Process-lifetime XLA backend compiles observed so far (0 until
    the first sentinel installs the hooks)."""
    return _probe_counts["compiles"]


def transfer_count() -> int:
    """Process-lifetime device->host materializations observed so far
    (lower bound; see module docstring)."""
    return _probe_counts["transfers"]


class RecompileSentinel:
    """Context manager asserting compile/transfer budgets over a region.

    ``max_compiles`` — hard budget of XLA backend compiles inside the
    region (0 = the steady-state contract); ``None`` disarms the check
    (observe-only). ``max_transfers`` — optional budget of D2H
    materializations. On exit past a budget the sentinel raises
    :class:`SteadyStateViolation` (unless the region is already
    unwinding another exception). Nesting is fine — each sentinel reads
    its own deltas of the shared process counters.
    """

    def __init__(self, max_compiles: Optional[int] = 0,
                 max_transfers: Optional[int] = None,
                 label: str = "") -> None:
        self.max_compiles = max_compiles
        self.max_transfers = max_transfers
        self.label = label
        self.compiles = 0
        self.transfers = 0
        self._c0 = 0
        self._t0 = 0

    def __enter__(self) -> "RecompileSentinel":
        _install()
        self._c0 = _probe_counts["compiles"]
        self._t0 = _probe_counts["transfers"]
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.compiles = _probe_counts["compiles"] - self._c0
        self.transfers = _probe_counts["transfers"] - self._t0
        if exc_type is not None:
            return False  # never mask the region's own failure
        tag = f" [{self.label}]" if self.label else ""
        if self.max_compiles is not None \
                and self.compiles > self.max_compiles:
            raise SteadyStateViolation(
                f"recompile sentinel{tag}: {self.compiles} XLA "
                f"compilation(s) in a region budgeted for "
                f"{self.max_compiles} — a jit identity or shape is "
                "varying per step (new lambda per call, unpadded "
                "bucket, cache key missing a device id?)")
        if self.max_transfers is not None \
                and self.transfers > self.max_transfers:
            raise SteadyStateViolation(
                f"recompile sentinel{tag}: {self.transfers} device->"
                f"host transfer(s) exceed the budget of "
                f"{self.max_transfers} — an unbatched host read crept "
                "onto the guarded path")
        return False
