"""The DataStream fluent API.

reference: streaming/api/datastream/DataStream.java, KeyedStream.java,
WindowedStream.java (e.g. WindowedStream.aggregate at
streaming/api/datastream/WindowedStream.java:310). The fluent surface is kept;
the semantics of each method build ``Transformation`` nodes that the executor
turns into batched operators.

User functions are vectorized (RecordBatch -> RecordBatch / mask); see
flink_tpu.runtime.operators.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Callable, List, Optional, Sequence

import numpy as np

from flink_tpu.core.records import RecordBatch
from flink_tpu.graph.transformations import Transformation
from flink_tpu.runtime.operators import (
    FilterOperator,
    FlatMapOperator,
    KeyByOperator,
    MapOperator,
    SinkOperator,
    UnionOperator,
    WindowAggOperator,
)
from flink_tpu.windowing.aggregates import (
    AggregateFunction,
    AvgAggregate,
    CountAggregate,
    MaxAggregate,
    MinAggregate,
    MultiAggregate,
    SumAggregate,
)
from flink_tpu.windowing.assigners import WindowAssigner

if TYPE_CHECKING:
    from flink_tpu.connectors.sinks import Sink
    from flink_tpu.datastream.environment import StreamExecutionEnvironment


from flink_tpu.core.annotations import public, public_evolving

@public
class DataStream:
    def __init__(self, env: "StreamExecutionEnvironment",
                 transformation: Transformation):
        self.env = env
        self.transformation = transformation

    def set_parallelism(self, parallelism: int) -> "DataStream":
        """Parallelism of this operator (reference:
        SingleOutputStreamOperator.setParallelism). For keyed window
        operators, parallelism N > 1 executes on an N-device mesh with
        state sharded over the key-group axis (MeshWindowEngine); the
        config default is ``parallelism.default``."""
        if parallelism < 1:
            raise ValueError(f"parallelism must be >= 1, got {parallelism}")
        self.transformation.parallelism = parallelism
        return self

    # ------------------------------------------------------------ stateless

    def map(self, fn: Callable[[RecordBatch], RecordBatch],
            name: str = "map") -> "DataStream":
        t = Transformation(name=name, kind="one_input",
                           operator_factory=lambda: MapOperator(fn),
                           inputs=[self.transformation])
        return DataStream(self.env, t)

    def ml_predict(self, model, input_fields=None, output_prefix: str = "",
                   asynchronous: bool = False, capacity: int = 4,
                   name: str = "ml_predict") -> "DataStream":
        """Batched model inference appending the model's output columns
        (reference: SQL ML_PREDICT / MLPredictRunner; flink-models). With
        ``asynchronous=True``, inference overlaps upstream work under a
        bounded in-flight budget (AsyncMLPredictRunner)."""
        from flink_tpu.ml.operators import (
            AsyncMLPredictOperator,
            MLPredictOperator,
        )

        if asynchronous:
            factory = lambda: AsyncMLPredictOperator(  # noqa: E731
                model, input_fields, output_prefix, capacity=capacity)
        else:
            factory = lambda: MLPredictOperator(  # noqa: E731
                model, input_fields, output_prefix)
        t = Transformation(name=name, kind="one_input",
                           operator_factory=factory,
                           inputs=[self.transformation])
        return DataStream(self.env, t)

    def filter(self, predicate: Callable[[RecordBatch], np.ndarray],
               name: str = "filter") -> "DataStream":
        t = Transformation(name=name, kind="one_input",
                           operator_factory=lambda: FilterOperator(predicate),
                           inputs=[self.transformation])
        return DataStream(self.env, t)

    def flat_map(self, fn: Callable[[RecordBatch], List[RecordBatch]],
                 name: str = "flat_map") -> "DataStream":
        t = Transformation(name=name, kind="one_input",
                           operator_factory=lambda: FlatMapOperator(fn),
                           inputs=[self.transformation])
        return DataStream(self.env, t)

    def union(self, *others: "DataStream",
              _require_consistent_time: bool = False) -> "DataStream":
        """Merge streams. The DataStream API permits mixing timed and
        untimed inputs (valid when nothing downstream uses event time);
        SQL UNION ALL passes the strict flag because its result feeds
        relational operators that do."""
        t = Transformation(
            name="union", kind="union",
            operator_factory=lambda: UnionOperator(
                require_consistent_time=_require_consistent_time),
            inputs=[self.transformation] + [o.transformation for o in others])
        return DataStream(self.env, t)

    # ----------------------------------------------------- process functions

    def process(self, fn, name: str = "process") -> "DataStream":
        """Low-level processing with timers and side outputs
        (reference: DataStream.process ->
        streaming/api/operators/ProcessOperator.java)."""
        from flink_tpu.runtime.process import ProcessOperator

        t = Transformation(name=name, kind="one_input",
                           operator_factory=lambda: ProcessOperator(fn),
                           inputs=[self.transformation])
        return DataStream(self.env, t)

    def slot_sharing_group(self, name: str) -> "DataStream":
        """Put this transformation (and, by inheritance, its downstream
        chain) into slot sharing group ``name`` — subtasks of the SAME
        group share a slot, a distinct group forces additional slots
        (reference: DataStream.slotSharingGroup / SlotSharingGroup)."""
        self.transformation.slot_group = name
        return self

    def get_side_output(self, tag) -> "DataStream":
        """reference: SingleOutputStreamOperator.getSideOutput(OutputTag)."""
        from flink_tpu.runtime.process import OutputTag, SideOutputSelectOperator

        if isinstance(tag, str):
            tag = OutputTag(tag)
        t = Transformation(
            name=f"side_output({tag.name})", kind="one_input",
            operator_factory=lambda: SideOutputSelectOperator(tag),
            inputs=[self.transformation], side_tag=tag.name)
        return DataStream(self.env, t)

    def connect(self, other: "DataStream") -> "ConnectedStreams":
        """reference: DataStream.connect -> ConnectedStreams (co-process) or
        BroadcastConnectedStream when ``other`` is ``.broadcast()``."""
        if isinstance(other, BroadcastStream):
            return BroadcastConnectedStream(self, other)
        return ConnectedStreams(self, other)

    def broadcast(self) -> "BroadcastStream":
        """reference: DataStream.broadcast(MapStateDescriptor...)."""
        return BroadcastStream(self)

    # ---------------------------------------------------------------- joins

    def join(self, other: "DataStream") -> "JoinedStreams":
        """Window equi-join builder (reference:
        streaming/api/datastream/JoinedStreams.java):
        ``a.join(b).where(f).equal_to(g).window(assigner).apply()``."""
        return JoinedStreams(self, other)

    # --------------------------------------------------------------- keying

    def key_by(self, key_field: str) -> "KeyedStream":
        t = Transformation(
            name=f"key_by({key_field})", kind="one_input",
            operator_factory=lambda: KeyByOperator(key_field),
            inputs=[self.transformation], keyed=True, key_field=key_field)
        return KeyedStream(self.env, t, key_field)

    # ---------------------------------------------------------------- sinks

    def sink_to(self, sink: "Sink", name: str = "sink") -> "DataStreamSink":
        from flink_tpu.connectors.two_phase import (
            TwoPhaseCommitSink,
            TwoPhaseSinkOperator,
        )

        if isinstance(sink, TwoPhaseCommitSink):
            factory = lambda: TwoPhaseSinkOperator(sink)  # noqa: E731
        else:
            factory = lambda: SinkOperator(sink)  # noqa: E731
        t = Transformation(name=name, kind="sink",
                           operator_factory=factory,
                           inputs=[self.transformation])
        self.env._sinks.append(t)
        return DataStreamSink(self.env, t, sink)

    def print(self, label: str = "") -> "DataStreamSink":
        from flink_tpu.connectors.sinks import PrintSink

        return self.sink_to(PrintSink(label), name="print")

    def execute_and_collect(self) -> RecordBatch:
        """Convenience: attach a collect sink, run, return the result batch."""
        from flink_tpu.connectors.sinks import CollectSink

        sink = CollectSink()
        self.sink_to(sink, name="collect")
        self.env.execute()
        return sink.result()


class DataStreamSink:
    def __init__(self, env, transformation, sink):
        self.env = env
        self.transformation = transformation
        self.sink = sink


class JoinedStreams:
    def __init__(self, left: DataStream, right: DataStream):
        self.left = left
        self.right = right
        self.left_key: Optional[str] = None
        self.right_key: Optional[str] = None

    def where(self, left_key: str) -> "JoinedStreams":
        self.left_key = left_key
        return self

    def equal_to(self, right_key: str) -> "JoinedStreams":
        self.right_key = right_key
        return self

    def window(self, assigner: WindowAssigner) -> "WindowedJoin":
        if self.left_key is None or self.right_key is None:
            raise ValueError(
                "call .where(left_key).equal_to(right_key) before .window()")
        return WindowedJoin(self, assigner)


class WindowedJoin:
    def __init__(self, joined: JoinedStreams, assigner: WindowAssigner):
        self.joined = joined
        self.assigner = assigner

    def apply(self, suffixes=("_l", "_r"), name: str = "window_join"
              ) -> DataStream:
        """The INNER join's pairs per window. ``join.mode=device`` (the
        default) runs it on the device window-join engine
        (flink_tpu/joins/); ``host``, and a shape the engine cannot run
        (counted, with a warning), on the host operator."""
        from flink_tpu.core.config import DeploymentOptions
        from flink_tpu.joins.operators import (
            DeviceWindowJoinOperator,
            device_window_join_refusal,
            fall_back,
        )
        from flink_tpu.runtime.join_operators import WindowJoinOperator

        j = self.joined
        env = j.left.env
        left_keyed = j.left.key_by(j.left_key).transformation
        right_keyed = j.right.key_by(j.right_key).transformation
        assigner = self.assigner
        key_fields = (j.left_key, j.right_key)
        device = env.config.get(DeploymentOptions.JOIN_MODE) == "device"
        if device:
            refusal = device_window_join_refusal(
                assigner, env.state_spill_options["max_device_slots"])
            if refusal is not None:
                fall_back(refusal)
                device = False
        if device:
            capacity = env.state_slot_capacity
            factory = lambda: DeviceWindowJoinOperator(  # noqa: E731
                assigner, suffixes, key_fields, capacity=capacity)
        else:
            factory = lambda: WindowJoinOperator(  # noqa: E731
                assigner, suffixes, key_fields=key_fields)
        t = Transformation(
            name=name, kind="two_input", operator_factory=factory,
            inputs=[left_keyed, right_keyed], keyed=True)
        return DataStream(env, t)


class ConnectedStreams:
    """reference: streaming/api/datastream/ConnectedStreams.java."""

    def __init__(self, first: DataStream, second: DataStream):
        self.first = first
        self.second = second
        self._keyed = False

    def key_by(self, first_key: str, second_key: str) -> "ConnectedStreams":
        c = ConnectedStreams(self.first.key_by(first_key),
                             self.second.key_by(second_key))
        c._keyed = True
        return c

    def process(self, fn, name: str = "co_process") -> DataStream:
        from flink_tpu.runtime.process import CoProcessOperator

        keyed = self._keyed
        t = Transformation(
            name=name, kind="two_input",
            operator_factory=lambda: CoProcessOperator(fn, keyed=keyed),
            inputs=[self.first.transformation, self.second.transformation],
            keyed=keyed)
        return DataStream(self.first.env, t)

    def map(self, fn1, fn2, name: str = "co_map") -> DataStream:
        """CoMap: fn1 on the first input's batches, fn2 on the second's."""
        from flink_tpu.runtime.process import CoProcessFunction

        class _CoMap(CoProcessFunction):
            def process_batch1(self, batch, ctx):
                ctx.collect(fn1(batch))

            def process_batch2(self, batch, ctx):
                ctx.collect(fn2(batch))

        return self.process(_CoMap(), name=name)


class BroadcastStream:
    """Marker wrapper produced by DataStream.broadcast()."""

    def __init__(self, stream: DataStream):
        self.stream = stream


class BroadcastConnectedStream:
    """reference: streaming/api/datastream/BroadcastConnectedStream.java."""

    def __init__(self, data: DataStream, broadcast: BroadcastStream):
        self.data = data
        self.broadcast = broadcast

    def process(self, fn, name: str = "broadcast_process") -> DataStream:
        from flink_tpu.runtime.process import BroadcastProcessOperator

        keyed = isinstance(self.data, KeyedStream)
        bt = Transformation(
            name="broadcast", kind="one_input",
            operator_factory=lambda: UnionOperator(),
            inputs=[self.broadcast.stream.transformation], broadcast=True)
        t = Transformation(
            name=name, kind="two_input",
            operator_factory=lambda: BroadcastProcessOperator(fn, keyed=keyed),
            inputs=[self.data.transformation, bt], keyed=keyed)
        return DataStream(self.data.env, t)


@public_evolving
class AsyncDataStream:
    """reference: streaming/api/datastream/AsyncDataStream.java."""

    @staticmethod
    def _wait(stream: DataStream, fn, ordered: bool, timeout_ms, capacity,
              name: str) -> DataStream:
        from flink_tpu.runtime.async_operator import AsyncWaitOperator

        t = Transformation(
            name=name, kind="one_input",
            operator_factory=lambda: AsyncWaitOperator(
                fn, ordered=ordered, capacity=capacity,
                timeout_ms=timeout_ms),
            inputs=[stream.transformation])
        return DataStream(stream.env, t)

    @staticmethod
    def ordered_wait(stream: DataStream, fn, timeout_ms: int = None,
                     capacity: int = 8) -> DataStream:
        return AsyncDataStream._wait(stream, fn, True, timeout_ms, capacity,
                                     "async_wait_ordered")

    @staticmethod
    def unordered_wait(stream: DataStream, fn, timeout_ms: int = None,
                       capacity: int = 8) -> DataStream:
        return AsyncDataStream._wait(stream, fn, False, timeout_ms, capacity,
                                     "async_wait_unordered")


@public
class KeyedStream(DataStream):
    def __init__(self, env, transformation, key_field: str):
        super().__init__(env, transformation)
        self.key_field = key_field

    def window(self, assigner: WindowAssigner) -> "WindowedStream":
        return WindowedStream(self, assigner)

    def interval_join(self, other: "KeyedStream") -> "IntervalJoinBuilder":
        """reference: KeyedStream.intervalJoin / IntervalJoinOperator."""
        return IntervalJoinBuilder(self, other)

    def process(self, fn, name: str = "keyed_process") -> DataStream:
        """reference: KeyedStream.process ->
        streaming/api/operators/KeyedProcessOperator.java (state + timers)."""
        from flink_tpu.runtime.process import ProcessOperator

        capacity = self.env.state_slot_capacity
        t = Transformation(
            name=name, kind="one_input",
            operator_factory=lambda: ProcessOperator(
                fn, keyed=True, state_capacity=capacity),
            inputs=[self.transformation], keyed=True,
            key_field=self.key_field)
        return DataStream(self.env, t)

    # -- running (unwindowed) keyed aggregates -------------------------------
    # reference: KeyedStream.sum/min/max/reduce — continuous per-key
    # aggregation with upsert emission, executed by the same slot-table
    # GroupAggOperator the SQL layer uses.

    def reduce(self, agg: AggregateFunction, name: str = None) -> DataStream:
        from flink_tpu.runtime.group_agg import GroupAggOperator

        capacity = self.env.state_slot_capacity
        key_field = self.key_field
        t = Transformation(
            name=name or f"keyed_reduce({type(agg).__name__})",
            kind="one_input",
            operator_factory=lambda: GroupAggOperator(
                agg, key_field, capacity=capacity),
            inputs=[self.transformation], keyed=True, key_field=key_field)
        return DataStream(self.env, t)

    def sum(self, field: str) -> DataStream:
        return self.reduce(SumAggregate(field))

    def max(self, field: str) -> DataStream:
        return self.reduce(MaxAggregate(field))

    def min(self, field: str) -> DataStream:
        return self.reduce(MinAggregate(field))


class IntervalJoinBuilder:
    def __init__(self, left: "KeyedStream", right: "KeyedStream"):
        self.left = left
        self.right = right

    def between(self, lower_ms: int, upper_ms: int,
                suffixes=("_l", "_r")) -> DataStream:
        from flink_tpu.core.config import DeploymentOptions
        from flink_tpu.runtime.join_operators import IntervalJoinOperator

        env = self.left.env
        if env.config.get(DeploymentOptions.JOIN_MODE) == "device":
            # the device-native path: dual keyed slot tables on the
            # mesh, banded segment-intersection kernel per batch
            # (flink_tpu/joins/) — the host operator stays the
            # semantics oracle and the join.mode=host fallback
            from flink_tpu.joins.operators import (
                DeviceIntervalJoinOperator,
            )

            capacity = env.state_slot_capacity
            spill = env.state_spill_options
            factory = lambda: DeviceIntervalJoinOperator(  # noqa: E731
                lower_ms, upper_ms, suffixes, capacity=capacity,
                max_device_slots=spill["max_device_slots"],
                spill_dir=spill["spill_dir"],
                spill_host_max_bytes=spill["spill_host_max_bytes"])
        else:
            factory = lambda: IntervalJoinOperator(  # noqa: E731
                lower_ms, upper_ms, suffixes)
        t = Transformation(
            name="interval_join", kind="two_input",
            operator_factory=factory,
            inputs=[self.left.transformation, self.right.transformation],
            keyed=True)
        return DataStream(self.left.env, t)

    # keyed running aggregates without windows (KeyedStream.sum/reduce in the
    # reference) can be expressed as a GlobalWindow; deferred to the table
    # runtime's GroupAggOperator equivalent.


@public
class WindowedStream:
    """reference: streaming/api/datastream/WindowedStream.java."""

    def __init__(self, keyed: KeyedStream, assigner: WindowAssigner):
        self.keyed = keyed
        self.assigner = assigner
        self._allowed_lateness = 0

    def allowed_lateness(self, ms: int) -> "WindowedStream":
        self._allowed_lateness = ms
        return self

    def aggregate(self, agg: AggregateFunction,
                  name: Optional[str] = None,
                  fire_projector=None) -> DataStream:
        """``fire_projector`` (flink_tpu.windowing.fire_projectors) reduces
        each fired window's rows ON DEVICE before host transfer — the fused
        form of a Top-N/arg-max consumer directly downstream."""
        env = self.keyed.env
        capacity = env.state_slot_capacity
        key_field = self.keyed.key_field
        assigner = self.assigner
        lateness = self._allowed_lateness
        if getattr(assigner, "is_merging", False):
            from flink_tpu.runtime.operators import SessionWindowAggOperator

            if fire_projector is not None:
                raise ValueError(
                    "fire_projector is not supported for merging (session) "
                    "windows yet — a session fire emits one row per "
                    "(key, merged window), not one batch per aligned window")
            gap = assigner.gap
            spill = env.state_spill_options
            backend = env.state_backend
            factory = lambda: SessionWindowAggOperator(  # noqa: E731
                gap, agg, key_field, capacity=capacity,
                allowed_lateness=lateness, spill=spill,
                state_backend=backend)
        else:
            spill = env.state_spill_options
            layout = env.window_layout
            backend = env.state_backend
            factory = lambda: WindowAggOperator(  # noqa: E731
                assigner, agg, key_field, capacity=capacity,
                allowed_lateness=lateness, spill=spill,
                fire_projector=fire_projector, window_layout=layout,
                state_backend=backend)
        t = Transformation(
            name=name or f"window_agg({type(agg).__name__})",
            kind="one_input",
            operator_factory=factory,
            inputs=[self.keyed.transformation],
            keyed=True, key_field=key_field)
        return DataStream(env, t)

    # SQL-ish shorthands
    def sum(self, field: str) -> DataStream:
        return self.aggregate(SumAggregate(field))

    def count(self) -> DataStream:
        return self.aggregate(CountAggregate())

    def max(self, field: str) -> DataStream:
        return self.aggregate(MaxAggregate(field))

    def min(self, field: str) -> DataStream:
        return self.aggregate(MinAggregate(field))

    def avg(self, field: str) -> DataStream:
        return self.aggregate(AvgAggregate(field))

    def aggregate_all(self, aggs: Sequence[AggregateFunction]) -> DataStream:
        return self.aggregate(MultiAggregate(aggs))
