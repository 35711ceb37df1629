"""Operator wrapper for the device-native CEP engine.

Plugs :class:`flink_tpu.cep.mesh_engine.MeshCepEngine` into the
DataStream/job-graph runtime the way ``DeviceIntervalJoinOperator``
plugs the join engines in: the operator opens its engine over the
task's mesh (parallelism-clamped to the device count), rides the
configured keyBy data plane (``shuffle.mode``), attaches the job
watchdog, and speaks the checkpoint protocol
(``snapshot_state``/``restore_state(key_group_filter=...)``).

Selected by ``cep.mode=device`` (``DeploymentOptions.CEP_MODE``). A
pattern outside the bounded-partial device class does NOT fail the
job: :class:`UnsupportedCepPattern` at open() routes the operator to
the host :class:`CepOperator` oracle — counted and logged
(``record_host_fallback``), never silent.
"""

from __future__ import annotations

from typing import List, Optional

from flink_tpu.cep.kernels import UnsupportedCepPattern
from flink_tpu.cep.mesh_engine import (
    MeshCepEngine,
    record_host_fallback,
)
from flink_tpu.cep.pattern import Pattern
from flink_tpu.core.records import RecordBatch
from flink_tpu.runtime.operators import Operator


class MeshCepOperator(Operator):
    """Keyed CEP on the device state plane, host-oracle fallback."""

    name = "device_cep"

    def __init__(self, pattern: Pattern,
                 key_field: Optional[str] = None,
                 select=None,
                 capacity: int = 1 << 16,
                 match_capacity: int = 1 << 10,
                 spill_dir: Optional[str] = None,
                 spill_host_max_bytes: int = 0) -> None:
        self.pattern = pattern
        self.key_field = key_field
        self.select = select
        self._capacity = int(capacity)
        self._match_capacity = int(match_capacity)
        self._spill_dir = spill_dir
        self._spill_host_max_bytes = int(spill_host_max_bytes)
        self.engine: Optional[MeshCepEngine] = None

    def open(self, ctx) -> None:
        from flink_tpu.parallel.mesh import make_mesh

        # make_mesh refuses a request larger than the devices that exist
        effective = max(getattr(ctx, "parallelism", 1), 1)

        kwargs = dict(
            key_field=self.key_field,
            select=self.select,
            capacity_per_shard=self._capacity,
            max_parallelism=getattr(ctx, "max_parallelism", 128),
            match_capacity=self._match_capacity,
            spill_dir=self._spill_dir,
            spill_host_max_bytes=self._spill_host_max_bytes,
            key_group_range=getattr(ctx, "key_group_range", None),
        )
        try:
            mesh = getattr(ctx, "mesh", None) or make_mesh(effective)
            self.engine = MeshCepEngine(
                self.pattern, mesh=mesh, backend="device",
                shuffle_mode=getattr(ctx, "shuffle_mode", "device"),
                **kwargs)
        except UnsupportedCepPattern as e:
            record_host_fallback(str(e))
            self.engine = MeshCepEngine(
                self.pattern, num_shards=1, backend="host",
                shuffle_mode="host", **kwargs)
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
        return self.engine is not None \
            and self.engine.backend == "device"

    def reshard(self, new_shards: int):
        return self.engine.reshard(new_shards)

    def spill_counters(self):
        return self.engine.spill_counters()

    def register_metrics(self, group) -> None:
        self.engine.register_metrics(group)
