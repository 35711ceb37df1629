"""StreamExecutionEnvironment — the API entry point.

reference: streaming/api/environment/StreamExecutionEnvironment.java
(execute :1823, getStreamGraph :2020). Re-design: the environment collects
sink transformations, builds a StreamGraph and hands it to an executor
(local single-process by default — the MiniCluster analog; see
flink_tpu.cluster). Executors are pluggable like the reference's
PipelineExecutor SPI (flink-core/.../core/execution/PipelineExecutor.java).
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Sequence

from flink_tpu.core.config import (
    BatchOptions,
    CheckpointOptions,
    DeploymentOptions,
    Configuration,
    CoreOptions,
    StateOptions,
)
from flink_tpu.core.records import RecordBatch
from flink_tpu.graph.transformations import StreamGraph, Transformation
from flink_tpu.runtime.watermarks import WatermarkStrategy


from flink_tpu.core.annotations import public

@public
class StreamExecutionEnvironment:
    def __init__(self, config: Optional[Configuration] = None):
        self.config = config or Configuration()
        self._sinks: List[Transformation] = []

    def _effective_config(self) -> Configuration:
        """CLI `-D` dynamic properties override programmatic config —
        applied at execute() time so they win over any mutator the script
        called after constructing the environment (reference: CliFrontend
        dynamic properties > user Configuration)."""
        import json
        import os

        raw = os.environ.get("FLINK_TPU_DYNAMIC_PROPS")
        if not raw:
            return self.config
        try:
            props = json.loads(raw)
        except ValueError:
            return self.config
        return Configuration(props).with_fallback(self.config)

    @staticmethod
    def get_execution_environment(
        config: Optional[Configuration] = None,
    ) -> "StreamExecutionEnvironment":
        return StreamExecutionEnvironment(config)

    # ------------------------------------------------------------- settings

    @property
    def parallelism(self) -> int:
        return self._effective_config().get(CoreOptions.DEFAULT_PARALLELISM)

    def set_parallelism(self, p: int) -> "StreamExecutionEnvironment":
        self.config.set(CoreOptions.DEFAULT_PARALLELISM, p)
        return self

    @property
    def max_parallelism(self) -> int:
        return self.config.get(CoreOptions.MAX_PARALLELISM)

    @property
    def batch_size(self) -> int:
        return self._effective_config().get(BatchOptions.BATCH_SIZE)

    @property
    def state_slot_capacity(self) -> int:
        return self.config.get(StateOptions.SLOT_CAPACITY)

    @property
    def state_spill_options(self) -> dict:
        """Beyond-HBM spill knobs handed to keyed-state operators."""
        return {
            "max_device_slots": self.config.get(
                StateOptions.MAX_DEVICE_SLOTS),
            "spill_dir": self.config.get(StateOptions.SPILL_DIR),
            "spill_host_max_bytes": self.config.get(
                StateOptions.SPILL_HOST_MAX_BYTES),
        }

    @property
    def window_layout(self) -> str:
        """state.window-layout: 'slots' | 'panes' | 'auto'."""
        return self.config.get(StateOptions.WINDOW_LAYOUT)

    @property
    def shuffle_mode(self) -> str:
        """shuffle.mode: 'device' (in-program keyBy exchange, default)
        | 'host' (explicit [shards, B] bucketing fallback)."""
        return self.config.get(DeploymentOptions.SHUFFLE_MODE)

    @property
    def state_backend(self) -> str:
        """state.backend: keyed-state placement (flink_tpu.state.backends)."""
        return self.config.get(StateOptions.BACKEND)

    def enable_checkpointing(self, interval_ms: int) -> "StreamExecutionEnvironment":
        self.config.set(CheckpointOptions.INTERVAL_MS, interval_ms)
        return self

    # -------------------------------------------------------------- sources

    def add_source(self, source, watermark_strategy: Optional[WatermarkStrategy]
                   = None, name: Optional[str] = None):
        from flink_tpu.datastream.stream import DataStream

        t = Transformation(
            name=name or type(source).__name__, kind="source",
            source=source,
            watermark_strategy=watermark_strategy
            or WatermarkStrategy.for_monotonous_timestamps())
        return DataStream(self, t)

    def from_source(self, source, watermark_strategy=None, name=None):
        return self.add_source(source, watermark_strategy, name)

    def from_collection(self, rows: Iterable[dict],
                        timestamp_field: Optional[str] = None,
                        watermark_strategy: Optional[WatermarkStrategy] = None):
        from flink_tpu.connectors.sources import CollectionSource

        src = CollectionSource.of_rows(rows, batch_size=self.batch_size)
        ws = watermark_strategy or WatermarkStrategy.for_monotonous_timestamps()
        if timestamp_field is not None:
            ws = ws.with_timestamp_field(timestamp_field)
        return self.add_source(src, ws, name="collection")

    def from_batches(self, batches: Sequence[RecordBatch],
                     watermark_strategy: Optional[WatermarkStrategy] = None):
        from flink_tpu.connectors.sources import CollectionSource

        return self.add_source(CollectionSource(list(batches)),
                               watermark_strategy, name="batches")

    # ------------------------------------------------------------ execution

    def get_stream_graph(self) -> StreamGraph:
        if not self._sinks:
            raise RuntimeError("no sinks defined — nothing to execute")
        return StreamGraph(self._sinks)

    def execute(self, job_name: str = "job",
                restore_from: Optional[str] = None,
                restore_mode: str = "no-claim") -> "JobExecutionResult":
        """Run the pipeline. ``restore_from`` points at a checkpoint root
        directory (latest completed checkpoint wins) or directly at a
        savepoint / single checkpoint directory. ``restore_mode`` is
        "no-claim" (default: the artifact stays user-owned and untouched) or
        "claim" (the job owns it and deletes it once subsumed) —
        reference: savepoint/restore CLI flow + claim modes."""
        import os

        if restore_from is None:  # CLI `run --restore` injects via env
            restore_from = os.environ.get("FLINK_TPU_RESTORE_FROM") or None
            restore_mode = os.environ.get("FLINK_TPU_RESTORE_MODE",
                                          restore_mode)
        graph = self.get_stream_graph()
        # the job runs on the devices JAX gives it and fails when JAX
        # fails — no probe, no fallback
        from flink_tpu.platform import enable_compilation_cache

        enable_compilation_cache()
        config = self._effective_config()
        # subtask-expansion mode (execution.stage-parallelism > 0) expands
        # the pipeline into source + keyed subtasks wired by the shuffle
        # SPI; unsupported shapes fall back to single-slot with a warning
        # (reference: ExecutionGraph parallel expansion / Execution.deploy)
        from flink_tpu.cluster.stage_executor import make_executor

        executor = make_executor(config, graph)
        result = executor.run(graph, job_name=job_name,
                              restore_from=restore_from,
                              restore_mode=restore_mode)
        self._sinks = []
        return result


@public
class JobExecutionResult:
    def __init__(self, job_name: str, metrics: dict):
        self.job_name = job_name
        self.metrics = metrics
        #: MetricRegistry with the job's operator-scoped metrics
        self.registry = None
        #: TraceCollector with checkpoint/recovery spans
        self.traces = None

    def __repr__(self):  # pragma: no cover - cosmetic
        return f"JobExecutionResult({self.job_name}, {self.metrics})"
