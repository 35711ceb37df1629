"""Two-input operator wrappers for the device-native join engines.

These plug the :mod:`flink_tpu.joins.engine` mesh engines into the
DataStream/job-graph runtime exactly like ``WindowAggOperator`` plugs
the mesh window engines in: the operator opens its engine over the
task's mesh (parallelism-clamped to the device count), rides the
configured keyBy data plane (``shuffle.mode``), attaches the job
watchdog, and speaks the checkpoint protocol
(``snapshot_state``/``restore_state(key_group_filter=...)``).

Selected by ``join.mode=device`` (``DeploymentOptions.JOIN_MODE``);
the default host
operators (``runtime/join_operators.py``) remain both the fallback and
the semantics oracle.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from flink_tpu.core.records import RecordBatch
from flink_tpu.joins.engine import (
    MeshIntervalJoinEngine,
    MeshTemporalJoinEngine,
)
from flink_tpu.runtime.operators import Operator


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
