"""Two-input operator wrappers for the device-native join engines.

These plug the :mod:`flink_tpu.joins.engine` mesh engines into the
DataStream/job-graph runtime exactly like ``WindowAggOperator`` plugs
the mesh window engines in: the operator opens its engine over the
task's mesh (parallelism-clamped to the device count), rides the
configured keyBy data plane (``shuffle.mode``), attaches the job
watchdog, and speaks the checkpoint protocol
(``snapshot_state``/``restore_state(key_group_filter=...)``).

Selected by ``join.mode=device`` (``DeploymentOptions.JOIN_MODE``, the
default); the host operators (``runtime/join_operators.py``) are
``join.mode=host``, the fallback for a shape the device cannot run
(counted: :func:`fallbacks`) and the semantics oracle.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from flink_tpu.core.records import RecordBatch
from flink_tpu.joins.engine import (
    MeshIntervalJoinEngine,
    MeshRegularJoinEngine,
    MeshTemporalJoinEngine,
    MeshWindowJoinEngine,
    UnsupportedJoinColumn,
)
from flink_tpu.runtime.operators import AsyncFireOperator, Operator

#: window and regular joins that asked for the device and run on the
#: host operator
_fallbacks = 0


def fallbacks() -> int:
    """Window and regular joins of this process that fell back to the
    host operator: a shape the device engine cannot run."""
    return _fallbacks


def fall_back(reason: str, what: str = "window join") -> None:
    """Say loudly that a join leaves the device, and count it."""
    import warnings

    global _fallbacks
    _fallbacks += 1
    warnings.warn(f"{what} on the host operator: {reason}",
                  RuntimeWarning, stacklevel=3)


def device_window_join_refusal(assigner, max_device_slots: int
                               ) -> Optional[str]:
    """Why the device window join cannot run this shape, or None: it
    takes the event-time windows the slice bookkeeping knows (tumbling,
    sliding, cumulative), and no spill budget."""
    from flink_tpu.windowing.assigners import WindowAssigner

    if not isinstance(assigner, WindowAssigner) \
            or getattr(assigner, "is_merging", False):
        return f"{type(assigner).__name__} is not a sliced window"
    if getattr(assigner, "is_processing_time", False):
        return f"{type(assigner).__name__} is a processing-time window"
    if max_device_slots:
        return "state.spill.max-device-slots is set: the join has no spill"
    return None


def _engine_kwargs(ctx, capacity: int, max_device_slots: int,
                   spill_dir: Optional[str],
                   spill_host_max_bytes: int = 0):
    from flink_tpu.parallel.mesh import make_mesh

    # make_mesh refuses a request larger than the devices that exist
    effective = max(getattr(ctx, "parallelism", 1), 1)
    mesh = getattr(ctx, "mesh", None) or make_mesh(effective)
    return dict(
        mesh=mesh,
        capacity_per_shard=capacity,
        max_parallelism=getattr(ctx, "max_parallelism", 128),
        max_device_slots=max_device_slots,
        spill_dir=spill_dir,
        spill_host_max_bytes=spill_host_max_bytes,
        key_group_range=getattr(ctx, "key_group_range", None),
        backend="device",
        shuffle_mode=getattr(ctx, "shuffle_mode", "device"),
    )


class DeviceIntervalJoinOperator(Operator):
    """Keyed interval join on the device state plane (INNER).

    Same stream contract as ``IntervalJoinOperator``: matches emit when
    the second side arrives; watermark advances prune both buffers."""

    name = "device_interval_join"

    def __init__(self, lower: int, upper: int,
                 suffixes: Tuple[str, str] = ("_l", "_r"),
                 capacity: int = 1 << 16,
                 max_device_slots: int = 0,
                 spill_dir: Optional[str] = None,
                 spill_host_max_bytes: int = 0) -> None:
        if lower > upper:
            raise ValueError(f"lower {lower} > upper {upper}")
        self.lower = int(lower)
        self.upper = int(upper)
        self.suffixes = tuple(suffixes)
        self._capacity = int(capacity)
        self._max_device_slots = int(max_device_slots)
        self._spill_dir = spill_dir
        self._spill_host_max_bytes = int(spill_host_max_bytes)
        self.engine: Optional[MeshIntervalJoinEngine] = None

    def open(self, ctx) -> None:
        self.engine = MeshIntervalJoinEngine(
            self.lower, self.upper, suffixes=self.suffixes,
            **_engine_kwargs(ctx, self._capacity,
                             self._max_device_slots, self._spill_dir,
                             self._spill_host_max_bytes))
        wd = getattr(ctx, "watchdog", None)
        if wd is not None:
            self.engine.attach_watchdog(wd)

    def process_batch(self, batch, input_index=0) -> List[RecordBatch]:
        return self.engine.process_batch(batch, input_index)

    def process_watermark(self, watermark, input_index=0
                          ) -> List[RecordBatch]:
        return self.engine.on_watermark(int(watermark))

    def close(self) -> List[RecordBatch]:
        from flink_tpu.runtime.elements import MAX_WATERMARK

        return self.engine.on_watermark(MAX_WATERMARK)

    def snapshot_state(self):
        return self.engine.snapshot()

    def restore_state(self, state, key_group_filter=None):
        self.engine.restore(state, key_group_filter=key_group_filter)

    def supports_live_rescale(self) -> bool:
        return True

    def reshard(self, new_shards: int):
        return self.engine.reshard(new_shards)

    def spill_counters(self):
        return self.engine.spill_counters()


class DeviceTemporalJoinOperator(Operator):
    """Event-time temporal join against the versioned device plane."""

    name = "device_temporal_join"

    def __init__(self, suffixes: Tuple[str, str] = ("_l", "_r"),
                 capacity: int = 1 << 16,
                 max_device_slots: int = 0,
                 spill_dir: Optional[str] = None,
                 spill_host_max_bytes: int = 0) -> None:
        self.suffixes = tuple(suffixes)
        self._capacity = int(capacity)
        self._max_device_slots = int(max_device_slots)
        self._spill_dir = spill_dir
        self._spill_host_max_bytes = int(spill_host_max_bytes)
        self.engine: Optional[MeshTemporalJoinEngine] = None

    def open(self, ctx) -> None:
        self.engine = MeshTemporalJoinEngine(
            suffixes=self.suffixes,
            **_engine_kwargs(ctx, self._capacity,
                             self._max_device_slots, self._spill_dir,
                             self._spill_host_max_bytes))
        wd = getattr(ctx, "watchdog", None)
        if wd is not None:
            self.engine.attach_watchdog(wd)

    def process_batch(self, batch, input_index=0) -> List[RecordBatch]:
        return self.engine.process_batch(batch, input_index)

    def process_watermark(self, watermark, input_index=0
                          ) -> List[RecordBatch]:
        return self.engine.on_watermark(int(watermark))

    @property
    def late_left_dropped(self) -> int:
        return self.engine.late_left_dropped if self.engine else 0

    def close(self) -> List[RecordBatch]:
        from flink_tpu.runtime.elements import MAX_WATERMARK

        return self.engine.on_watermark(MAX_WATERMARK)

    def snapshot_state(self):
        return self.engine.snapshot()

    def restore_state(self, state, key_group_filter=None):
        self.engine.restore(state, key_group_filter=key_group_filter)

    def supports_live_rescale(self) -> bool:
        return True

    def reshard(self, new_shards: int):
        return self.engine.reshard(new_shards)

    def spill_counters(self):
        return self.engine.spill_counters()


class DeviceWindowJoinOperator(AsyncFireOperator):
    """INNER window join on the device (:class:`~flink_tpu.joins.engine.
    MeshWindowJoinEngine`), with the stream contract of the host
    ``WindowJoinOperator``. Like ``WindowAggOperator``, ``windower`` is
    the engine, ``process_batch`` / ``on_watermark`` are what it is
    driven by, fires come back as ``PendingFire`` results where the
    executor harvests asynchronously, and the snapshot is the host
    operator's, so either restores the other's. A column the device
    cannot carry (strings) moves the join to the host operator, state
    and all, at the batch that brings it (counted: :func:`fallbacks`)."""

    name = "device_window_join"

    def __init__(self, assigner, suffixes=("_l", "_r"),
                 key_fields=(None, None), capacity: int = 1 << 16) -> None:
        super().__init__()
        self.assigner = assigner
        self.suffixes = tuple(suffixes)
        self.key_fields = tuple(key_fields)
        self._capacity = int(capacity)
        self.windower: Optional[MeshWindowJoinEngine] = None
        #: the host operator, once the join has left the device
        self.host = None
        self._ctx = None

    def open(self, ctx) -> None:
        self._ctx = ctx
        self.windower = MeshWindowJoinEngine(
            self.assigner, self.key_fields, self.suffixes,
            **_engine_kwargs(ctx, self._capacity, 0, None))
        self._async_fires = bool(getattr(ctx, "async_fires", False))
        self._max_dispatch_ahead = int(
            getattr(ctx, "max_dispatch_ahead", self._max_dispatch_ahead))
        wd = getattr(ctx, "watchdog", None)
        if wd is not None:
            self.windower.attach_watchdog(wd)

    @property
    def late_records_dropped(self) -> int:
        return (self.host or self.windower).book.late_records_dropped

    def process_batch(self, batch, input_index=0) -> List[RecordBatch]:
        if self.host is not None:
            return self.host.process_batch(batch, input_index)
        self.windower.input_side = input_index
        try:
            dispatched = self.windower.process_batch(batch)
        except UnsupportedJoinColumn as e:
            self._to_host(str(e))
            return self.host.process_batch(batch, input_index)
        if dispatched and self._async_fires:
            self._push_fence(self.windower.make_fence())
        return []

    def poll_pending_output(self, wait: bool = False):
        """The base class's harvests, after the rows the engine staged
        while its match ran go up: they run on the device while the host
        builds the fire's columns. One fence stands behind them."""
        w = self.windower
        if w is not None and w.staged and (wait or not w.match_running()):
            self._flush()
        return super().poll_pending_output(wait)

    def _flush(self) -> None:
        if self.windower.flush() and self._async_fires:
            self._push_fence(self.windower.make_fence())

    def process_watermark(self, watermark, input_index=0
                          ) -> List[RecordBatch]:
        if self.host is not None:
            return self.host.process_watermark(watermark, input_index)
        from flink_tpu.runtime.pending import PendingFire

        outs = []
        for f in self.windower.on_watermark(int(watermark),
                                            async_ok=self._async_fires):
            if isinstance(f, PendingFire):
                self._pending.append(f)
            else:
                outs.append(f)
        return outs

    def _to_host(self, reason: str) -> None:
        """The join leaves the device: its state, exported, restores a
        host operator that takes over from here."""
        from flink_tpu.runtime.join_operators import WindowJoinOperator

        self._check_no_pending()
        host = WindowJoinOperator(self.assigner, self.suffixes,
                                  key_fields=self.key_fields)
        host.open(self._ctx)
        host.restore_state(self.windower.snapshot())
        self.host, self.windower = host, None
        self._fences.clear()
        fall_back(reason)

    def snapshot_state(self):
        if self.host is not None:
            return self.host.snapshot_state()
        self._check_no_pending()
        return self.windower.snapshot()

    def restore_state(self, state, key_group_filter=None):
        if self.host is not None:
            return self.host.restore_state(state, key_group_filter)
        return self.windower.restore(state,
                                     key_group_filter=key_group_filter)

    def close(self) -> List[RecordBatch]:
        if self.windower is not None:
            self._flush()
        return []

    def dispose(self) -> None:
        self._pending.clear()
        self._fences.clear()


class DeviceRegularJoinOperator(Operator):
    """SQL's regular INNER join on the device
    (:class:`~flink_tpu.joins.engine.MeshRegularJoinEngine`), with the
    stream contract of the host ``IntervalJoinOperator`` without bounds
    plus its residual filter: a pair is found when its second row
    arrives, every row is held for the job's life. ``windower`` is the
    engine; once both inputs have brought a batch since the last time, at
    a watermark and at the end, the operator takes the engine's probes
    (``on_watermark``) and harvests them: the pairs of a source batch
    that feeds both sides leave together, before the next batch. A shape the engine cannot run — a mesh wider than one device, a
    column it cannot carry, a residual it cannot compare — moves the join
    to ``host_factory()``'s operator, state and all, whose pairs
    ``residual_filter`` (a batch -> row mask) then filters (counted:
    :func:`fallbacks`)."""

    name = "device_regular_join"

    def __init__(self, suffixes=("_l", "_r"), residual=(),
                 keep=(None, None), ts_fields=(None, None),
                 capacity: int = 1 << 16, host_factory=None,
                 residual_filter=None) -> None:
        self.suffixes = tuple(suffixes)
        self.residual = tuple(residual)
        self.keep = tuple(keep)
        self.ts_fields = tuple(ts_fields)
        self._capacity = int(capacity)
        self._host_factory = host_factory
        self._residual_filter = residual_filter
        self.windower: Optional[MeshRegularJoinEngine] = None
        #: the host operator, once the join has left the device
        self.host = None
        self._ctx = None
        self._watermark = 0
        #: the inputs that brought a batch since the last emission
        self._brought = set()

    def open(self, ctx) -> None:
        self._ctx = ctx
        if max(getattr(ctx, "parallelism", 1), 1) > 1:
            self._to_host("the regular join runs on one device")
            return
        from flink_tpu.parallel.mesh import make_mesh

        self.windower = MeshRegularJoinEngine(
            self.suffixes, residual=self.residual,
            keep=self.keep, ts_fields=self.ts_fields,
            mesh=getattr(ctx, "mesh", None) or make_mesh(1),
            capacity_per_shard=self._capacity,
            max_parallelism=getattr(ctx, "max_parallelism", 128))
        wd = getattr(ctx, "watchdog", None)
        if wd is not None:
            self.windower.attach_watchdog(wd)

    def process_batch(self, batch, input_index=0) -> List[RecordBatch]:
        if self.host is None:
            self.windower.input_side = input_index
            try:
                self.windower.process_batch(batch)
                self._brought.add(input_index)
                return self._emit() if len(self._brought) == 2 else []
            except UnsupportedJoinColumn as e:
                self._to_host(str(e))
        return self._filtered(self.host.process_batch(batch, input_index))

    def _emit(self) -> List[RecordBatch]:
        self._brought.clear()
        out = []
        for fire in self.windower.on_watermark(self._watermark):
            rows = fire.harvest()
            if rows is not None and len(rows):
                out.append(rows)
        return out

    def _filtered(self, outs) -> List[RecordBatch]:
        if self._residual_filter is None:
            return outs
        out = []
        for b in outs:
            b = b.filter(np.asarray(self._residual_filter(b), dtype=bool))
            if len(b):
                out.append(b)
        return out

    def process_watermark(self, watermark, input_index=0
                          ) -> List[RecordBatch]:
        if self.host is not None:
            return self._filtered(
                self.host.process_watermark(watermark, input_index))
        self._watermark = int(watermark)
        return self._emit()

    def _to_host(self, reason: str) -> None:
        """The join leaves the device: its held rows restore the host
        operator, which takes over from here."""
        host = self._host_factory()
        host.open(self._ctx)
        if self.windower is not None:
            host.restore_state(self.windower.snapshot())
        self.host, self.windower = host, None
        fall_back(reason, "regular join")

    def snapshot_state(self):
        if self.host is not None:
            return self.host.snapshot_state()
        return self.windower.snapshot()

    def restore_state(self, state, key_group_filter=None):
        if self.host is not None:
            return self.host.restore_state(state, key_group_filter)
        return self.windower.restore(state,
                                     key_group_filter=key_group_filter)

    def close(self) -> List[RecordBatch]:
        if self.host is not None:
            return self._filtered(self.host.close())
        return self._emit()
