"""Asynchronous window-fire results.

A synchronous ``np.asarray(device_array)`` stalls the host loop until the
fire kernel — and every scatter queued ahead of it — has run and its
result has crossed to the host. The reference overlaps operator output
with network/state I/O threads (reference:
runtime/asyncprocessing/AsyncExecutionController.java:57,364-369 — in-flight
record contexts drain asynchronously while the mailbox keeps processing).

Re-design for the XLA dispatch model: a window fire is *dispatched* (kernel
enqueued, ``copy_to_host_async`` started on every output buffer) and
*harvested* later, when the DMA has already landed — the executor keeps
ingesting source batches in between, so the device queue and the D2H copy
are hidden behind useful work instead of stalling the pipeline. (What a
blocking read costs with the process beside the chip has not been
measured; ROADMAP queue 1 item 2.) Event-time correctness is
preserved by watermark holdback: the executor does not forward a watermark
past an operator with pending fires until those fires' results have been
emitted downstream (see LocalExecutor._drain_pending).
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Optional, Sequence

import numpy as np


class PendingFire:
    """A dispatched-but-unharvested fire: device output buffers (async host
    copies already in flight) plus a host-side finisher that assembles the
    final result batch once the bytes land. It takes with it the flight
    recorder's ``(watermark, origin)`` of the thread that dispatched it —
    the watermark advance that fired the window and the hand-over time of
    the batch that caused it — so the harvest, turns later, attributes
    itself and what it forwards to this window and not to whatever the
    loop processed last."""

    __slots__ = ("arrays", "build", "dispatched_at", "watchdog",
                 "watermark", "origin", "unready_at")

    def __init__(self, arrays: Sequence,
                 build: Callable[[List[np.ndarray]], object],
                 watchdog=None):
        from flink_tpu.observe import flight_recorder as flight

        self.arrays = list(arrays)
        self.build = build
        #: optional DeviceWatchdog: the harvest is a deadline-tracked
        #: section (a fire whose D2H never lands is a dead device)
        self.watchdog = watchdog
        self.watermark, self.origin = flight.fire_context()
        #: the last time a poll found this fire (or one dispatched before
        #: it) not ready; 0.0 until a poll has
        self.unready_at = 0.0
        self.dispatched_at = time.perf_counter()
        for a in self.arrays:
            copy = getattr(a, "copy_to_host_async", None)
            if copy is not None:
                copy()

    def ready(self) -> bool:
        """True when every output buffer's computation has finished (the
        async host copy then only waits for its DMA). A host array is
        ready as it stands."""
        for a in self.arrays:
            is_ready = getattr(a, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def wait_ready(self) -> None:
        """Block until ``ready()`` would say yes: what the blocking
        harvests (a drain, a checkpoint cut, the bound on pending fires)
        wait for before they start — ahead of the harvest, so that the
        wait is timed as waiting (``fire.in_flight`` / ``fire.poll_gap``)
        and not as the harvest's work — under the harvest's own
        deadline."""
        section = contextlib.nullcontext() if self.watchdog is None \
            else self.watchdog.section("pending_harvest")
        with section:
            for a in self.arrays:
                block = getattr(a, "block_until_ready", None)
                if block is not None:
                    block()

    def harvest(self) -> Optional[object]:
        """Materialize host values and build the result (blocks only on
        buffers whose async copy has not yet landed).

        All buffers are fetched in ONE ``jax.device_get`` call: it starts
        every buffer's copy before waiting on any, so a fire with k
        output columns waits for the slowest copy, not for k in turn."""
        import jax

        from flink_tpu.chaos import injection as chaos
        from flink_tpu.observe import flight_recorder as flight

        # chaos: a harvest failure — the fire was dispatched but its
        # D2H results never land (link loss mid-coalesced-harvest)
        chaos.fault_point("harvest.pending_fire",
                          arrays=len(self.arrays))
        with flight.span("fire.harvest", watermark=self.watermark) as span:
            if self.watchdog is not None:
                with self.watchdog.section("pending_harvest"):
                    host = jax.device_get(self.arrays)
            else:
                host = jax.device_get(self.arrays)
            host = [np.asarray(a) for a in host]
            span.work = sum(a.nbytes for a in host)
            return self.build(host)
