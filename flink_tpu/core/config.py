"""Typed, layered configuration.

Semantic equivalent of the reference's ``ConfigOption``/``Configuration``
(reference: flink-core/src/main/java/org/apache/flink/configuration/ConfigOption.java:41,
Configuration.java): typed keys with defaults, deprecated-key fallbacks and
layered override (cluster config < per-job config < dynamic overrides).

Idiomatic-Python re-design: a ``ConfigOption`` is a small frozen descriptor;
``Configuration`` is a dict-backed store with typed access and layering via
``with_fallback``. No reflection, no YAML coupling (a YAML front-end can load
into a plain dict).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Generic, Iterator, List, Optional, Sequence, TypeVar

T = TypeVar("T")


from flink_tpu.core.annotations import public

@public
@dataclasses.dataclass(frozen=True)
class ConfigOption(Generic[T]):
    """A typed configuration key with a default.

    Mirrors the builder contract of the reference ConfigOption (key, type,
    default, description, deprecated/fallback keys) without the builder
    ceremony.
    """

    key: str
    default: Optional[T] = None
    type: type = str
    description: str = ""
    fallback_keys: Sequence[str] = ()

    def with_default(self, default: T) -> "ConfigOption[T]":
        return dataclasses.replace(self, default=default)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"ConfigOption({self.key!r}, default={self.default!r})"


def _coerce(value: Any, typ: type) -> Any:
    if value is None or typ is None:
        return value
    if isinstance(value, typ):
        return value
    if typ is bool:
        if isinstance(value, str):
            return value.strip().lower() in ("true", "1", "yes", "on")
        return bool(value)
    if typ in (int, float, str):
        return typ(value)
    if typ is list and isinstance(value, str):
        return [v.strip() for v in value.split(";") if v.strip()]
    return value


@public
class Configuration:
    """Layered key/value store with typed access through ConfigOptions."""

    def __init__(self, data: Optional[Dict[str, Any]] = None) -> None:
        self._data: Dict[str, Any] = dict(data or {})
        self._fallback: Optional[Configuration] = None

    # -- typed access -------------------------------------------------------

    def get(self, option: ConfigOption[T]) -> Optional[T]:
        for key in (option.key, *option.fallback_keys):
            found, value = self._lookup(key)
            if found:
                return _coerce(value, option.type)
        return option.default

    def set(self, option: "ConfigOption[T] | str", value: T) -> "Configuration":
        key = option.key if isinstance(option, ConfigOption) else option
        self._data[key] = value
        return self

    def contains(self, option: "ConfigOption | str") -> bool:
        key = option.key if isinstance(option, ConfigOption) else option
        return self._lookup(key)[0]

    # -- raw access ---------------------------------------------------------

    def get_raw(self, key: str, default: Any = None) -> Any:
        found, value = self._lookup(key)
        return value if found else default

    def _lookup(self, key: str):
        if key in self._data:
            return True, self._data[key]
        if self._fallback is not None:
            return self._fallback._lookup(key)
        return False, None

    # -- layering -----------------------------------------------------------

    def with_fallback(self, other: "Configuration") -> "Configuration":
        """Return a new Configuration: self's entries override ``other``'s."""
        merged = Configuration(self._data)
        merged._fallback = other
        return merged

    def to_dict(self) -> Dict[str, Any]:
        base = self._fallback.to_dict() if self._fallback else {}
        base.update(self._data)
        return base

    def copy(self) -> "Configuration":
        c = Configuration(dict(self._data))
        c._fallback = self._fallback
        return c

    def keys(self) -> List[str]:
        """Every key visible through this configuration: its own layer
        plus the fallback chain."""
        return list(self.to_dict().keys())

    def __iter__(self) -> Iterator[str]:
        return iter(self.to_dict())

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Configuration({self.to_dict()!r})"


# ---------------------------------------------------------------------------
# Core options (colocated here; subsystem options live with their subsystem,
# mirroring the reference's option placement convention).
# ---------------------------------------------------------------------------

class CoreOptions:
    DEFAULT_PARALLELISM = ConfigOption(
        "parallelism.default", default=1, type=int,
        description="Default operator parallelism (number of key-group shards "
        "processed concurrently; on TPU this is the mesh size of the keyed axis).")
    MAX_PARALLELISM = ConfigOption(
        "pipeline.max-parallelism", default=128, type=int,
        description="Number of key groups (rescale granularity). Mirrors the "
        "reference default lower bound of 1<<7 "
        "(reference: KeyGroupRangeAssignment.java:32).")
    AUTO_WATERMARK_INTERVAL = ConfigOption(
        "pipeline.auto-watermark-interval-ms", default=200, type=int,
        description="Periodic watermark emission interval.")
    OBJECT_REUSE = ConfigOption(
        "pipeline.object-reuse", default=True, type=bool,
        description="Batches are immutable columnar arrays; reuse is always safe.")


class BatchOptions:
    """Micro-batching knobs — the analog of the reference's async state
    batching (reference: runtime/asyncprocessing/AsyncExecutionController.java:67
    batchSize / bufferTimeout)."""

    BATCH_SIZE = ConfigOption(
        "execution.micro-batch.size", default=8192, type=int,
        description="Max records per micro-batch handed to the device.")
    BATCH_TIMEOUT_MS = ConfigOption(
        "execution.micro-batch.timeout-ms", default=10, type=int,
        description="Max time to wait filling a micro-batch before flushing.")
    LATENCY_TARGET_MS = ConfigOption(
        "execution.micro-batch.latency-target-ms", default=0, type=int,
        description="Adaptive batch sizing: hold the per-batch processing "
        "time to a fraction of this latency budget by resizing the "
        "micro-batch online from an EMA of observed throughput "
        "(reference: BufferDebloater). 0 = fixed batch size.")
    MIN_BATCH_SIZE = ConfigOption(
        "execution.micro-batch.min-size", default=256, type=int,
        description="Lower bound for adaptive batch sizing.")
    MAX_DISPATCH_AHEAD = ConfigOption(
        "execution.pipeline.max-dispatch-batches", default=4, type=int,
        description="How many batches of device work the task loop may "
        "dispatch ahead of completion (per-batch fences). Smaller = "
        "tighter fire latency (a fire kernel queues behind at most this "
        "many batches); larger = more overlap headroom on "
        "high-latency device links.")
    ASYNC_FIRES = ConfigOption(
        "execution.window.async-fires", default=True, type=bool,
        description="Dispatch window fires asynchronously: the fire kernel "
        "and its device->host copies run while the loop keeps ingesting; "
        "the executor forwards results (and the covering watermark) once "
        "they land. Hides the device-link round-trip latency behind "
        "useful work (reference: AsyncExecutionController overlap).")
    IN_FLIGHT_BATCHES = ConfigOption(
        "execution.pipeline.in-flight-batches", default=2, type=int,
        description="Bounded prefetch depth per source: a pump thread "
        "polls/timestamps the next batches while the task loop drives the "
        "device (credit-style backpressure — the pump blocks when the loop "
        "falls behind; reference: RemoteInputChannel credit flow control). "
        "0 = poll sources inline on the task loop.")


class LatencyOptions:
    """The fire-latency tier: a watermark fire must cost a bounded delta,
    not a full-window harvest, and must never queue behind a
    multi-hundred-ms ingest dispatch (the Drizzle/Spark-Streaming
    micro-batch latency/throughput trade, applied to the device state
    plane — see README "Latency tier")."""

    FIRE_DEADLINE_MS = ConfigOption(
        "latency.fire-deadline-ms", default=0, type=int,
        description="Fire-latency budget in wall-clock ms. When > 0 the "
        "task loop splits each ingest micro-batch against this budget "
        "using the measured per-record step rate, harvesting landed "
        "async fires between the splits — a due fire is never stuck "
        "behind a full batch dispatch. Also the deadline the autoscale "
        "fire-latency signal judges p99 against. 0 (default) = whole "
        "batches, fires harvested at batch boundaries only.")
    PANE_PREAGG = ConfigOption(
        "latency.pane-preagg", default=True, type=bool,
        description="Incremental pane pre-aggregation for the panes "
        "window layout (state.window-layout=panes): maintain per-window "
        "running partials combined AT ABSORB, so a watermark fire "
        "gathers ONE partial ring row (the pane that closes) instead of "
        "merging the window's k slice rows (the full-window harvest). "
        "The full-harvest path remains as the fallback for windows "
        "without a maintained partial (and for this option = false). "
        "Float sums fold in record order rather than per-slice order, "
        "so f32 results can differ from the full harvest in the last "
        "ulp (exact for count/min/max and integer-valued sums).")


class ServingOptions:
    """The queryable-state read path (tenancy serving plane). The read
    replica decouples lookups from ingest: engines publish a bounded
    delta at fire/watermark boundaries into a double-buffered
    device-resident replica, serving workers resolve misses against
    the SEALED generation off the task loop, and the host hot-row
    cache (generation-invalidated) absorbs repeat traffic without
    touching the device at all. See README "Multi-tenant serving"."""

    REPLICA = ConfigOption(
        "serving.replica", default=True, type=bool,
        description="Arm the read-replica serving plane for jobs "
        "submitted to a tenancy session cluster: mesh engines publish "
        "a boundary delta per watermark (one device-to-device copy "
        "program, no D2H) and lookups resolve against the sealed "
        "generation — snapshot isolation, zero contention with "
        "ingest. false = every lookup takes the legacy control-queue "
        "path, serialized behind the owning job's batch boundaries "
        "(the pre-replica behavior). Plain LocalExecutor runs never arm a "
        "replica regardless — publishing costs a per-boundary "
        "metadata diff that only pays off when something reads it.")
    PUBLISH_INTERVAL_MS = ConfigOption(
        "serving.replica.publish-interval-ms", default=0, type=int,
        description="Minimum milliseconds between replica publishes. "
        "0 (default) publishes at every fire/watermark boundary — the "
        "tightest staleness. > 0 batches boundaries under one publish: "
        "the per-boundary metadata diff is paid once per interval and "
        "the hot-row cache invalidates at a bounded rate (lookup "
        "staleness stays <= the interval + one boundary). Per-boundary "
        "costs only matter when boundaries are much more frequent than "
        "readers need.")
    # NOTE: the worker-pool size and hot-row cache capacity are
    # CLUSTER-scoped (one serving plane serves every tenant), so they
    # are constructor parameters of ServingPlane / SessionCluster, not
    # per-job config options.


class ExecutionModeOptions:
    """Bounded/batch execution (reference: RuntimeExecutionMode.BATCH,
    the adaptive batch scheduler deciding parallelism from data volume —
    scheduler/adaptivebatch/AdaptiveBatchScheduler.java — and bulk batch
    shuffle — SortMergeResultPartition.java)."""

    RUNTIME_MODE = ConfigOption(
        "execution.runtime-mode", default="streaming", type=str,
        description="'streaming' (default) or 'batch'. Batch mode requires "
        "bounded sources, suppresses intermediate watermarks (every "
        "window/aggregate fires exactly once at end-of-input), and ships "
        "coalesced bulk blocks through the shuffle instead of "
        "latency-sized micro-batches.")
    TARGET_RECORDS_PER_SUBTASK = ConfigOption(
        "execution.batch.target-records-per-subtask", default=1_000_000,
        type=int,
        description="Adaptive batch parallelism: with "
        "execution.stage-parallelism=-1 in batch mode, the keyed stage "
        "parallelism is ceil(estimated source records / this target), "
        "like the reference's adaptive batch scheduler deciding "
        "parallelism from produced data volume.")


class DeploymentOptions:
    """Subtask-expansion execution (reference: ExecutionGraph parallel
    expansion — DefaultExecutionGraph / Execution.deploy — where every
    JobVertex runs `parallelism` subtasks connected by the shuffle)."""

    STAGE_PARALLELISM = ConfigOption(
        "execution.stage-parallelism", default=0, type=int,
        description="Subtask count for the keyed stage. 0 (default) runs "
        "the whole pipeline in one task; N > 0 expands the job into "
        "source subtasks + N keyed subtasks connected through the shuffle "
        "service with key-group routing and aligned checkpoint barriers "
        "(reference: ExecutionJobVertex parallel expansion + "
        "KeyGroupStreamPartitioner).")
    STAGE_FALLBACK = ConfigOption(
        "execution.stage-fallback", default=False, type=bool,
        description="When execution.stage-parallelism is set but the "
        "graph shape is not stage-expandable, fall back to single-slot "
        "execution instead of failing the submission. Off by default: a "
        "user who asked for parallelism N should not silently get 1.")
    SOURCE_PARALLELISM = ConfigOption(
        "execution.source-parallelism", default=1, type=int,
        description="Subtask count for the source stage in multi-slot "
        "mode. Each source subtask receives open(subtask_index, "
        "parallelism) and must split its input accordingly.")
    STAGE_MESH_DEVICES = ConfigOption(
        "execution.stage-mesh-devices", default=0, type=int,
        description="Mesh x stage composition: devices each KEYED subtask "
        "opens its window engine over (a private sub-mesh sharded within "
        "the subtask's key-group range). 0 (default) = one device per "
        "subtask. Subtask expansion distributes across slots/hosts (the "
        "reference's distribution model); the sub-mesh distributes across "
        "chips within one subtask's jitted program (the SPMD model).")
    SHUFFLE_MODE = ConfigOption(
        "shuffle.mode", default="device", type=str,
        description="keyBy data plane for the mesh engines: 'device' "
        "(default) computes shard routing, segment sort and the record "
        "exchange INSIDE the compiled program (one flat device_put + "
        "all_to_all over the mesh axis, fused with the aggregate "
        "scatter — keyBy -> window -> aggregate is one XLA program); "
        "'host' keeps the explicit fallback: [shards, B] bucketing in "
        "host numpy + a sharded device_put per block. See "
        "flink_tpu/parallel/shuffle.py.")
    SHUFFLE_HOSTS = ConfigOption(
        "shuffle.hosts", default=0, type=int,
        description="Number of HOSTS the key-group mesh spans (the "
        "(hosts, local) factorization of the device axis). 0/1 (the "
        "default) keeps the flat single-axis exchange; >1 routes "
        "device-mode keyBy through the two-level ICI/DCN exchange "
        "(parallel/exchange2.py): stage 1 all_to_all over the "
        "intra-host axis, stage 2 batches only the cross-host residue "
        "over the hosts axis — on a multi-process pod mesh the hosts "
        "axis IS the process boundary; on one process it is a virtual "
        "factorization (tests/CI). Engines whose mesh size the count "
        "does not divide keep the flat exchange.")
    JOIN_MODE = ConfigOption(
        "join.mode", default="device", type=str,
        description="Execution plane for the two DataStream joins (INNER): "
        "the window join (DataStream.join().where().equal_to()"
        ".window().apply()) and the interval join (KeyedStream."
        "interval_join().between()). 'device' (default) runs them on the "
        "device engines of flink_tpu/joins/: the window join keeps each "
        "side's rows as int32 words in pages per window slice and finds "
        "a window's pairs with one sort-and-expand program when the "
        "watermark passes its end; the interval join keeps dual keyed "
        "row tables and probes each batch with a banded "
        "segment-intersection program. 'host' buffers both sides as "
        "columnar batches in host numpy (runtime/join_operators.py — "
        "also the semantics oracle). A window join the device engine "
        "cannot run — a session or processing-time window, a spill "
        "budget, a string column — runs on the host operator, with a "
        "warning, and is counted (joins.operators.fallbacks()). Outer "
        "joins and the SQL planner's join operators stay on the host "
        "path regardless of this option.")
    CEP_MODE = ConfigOption(
        "cep.mode", default="host", type=str,
        description="Execution plane for CEP pattern matching "
        "(CEP.pattern() and SQL MATCH_RECOGNIZE): 'host' (default) "
        "threads each key's NFA through the Python per-event loop "
        "(cep/operator.py — also the semantics oracle); 'device' keeps "
        "per-key computation states as [P, capacity] bitmask columns "
        "on the key-group mesh and advances ALL keys' NFAs with one "
        "compiled gather/scan/scatter program per fire "
        "(flink_tpu/cep/mesh_engine.py), with completed matches "
        "queryable through the replica plane. Only bounded-partial "
        "patterns (fixed-length sequences, consecutive times(), "
        "SKIP_PAST_LAST_EVENT or NO_SKIP) compile to the device; "
        "anything else falls back LOUDLY to the host operator "
        "(cep.host_fallbacks metric).")
    SHUFFLE_SERVICE = ConfigOption(
        "shuffle.service", default="local", type=str,
        description="Registered ShuffleService transport connecting "
        "subtasks: 'local' (in-process bounded queues, credit-based) or "
        "'grpc' (cross-process batches over gRPC). Reference: "
        "ShuffleServiceFactory SPI.")
    SHUFFLE_CREDITS = ConfigOption(
        "shuffle.credits-per-channel", default=2, type=int,
        description="In-flight batches allowed per (producer, consumer) "
        "channel before the producer blocks — the credit-based flow "
        "control bound (reference: RemoteInputChannel.unannouncedCredit).")
    LOCAL_AGG = ConfigOption(
        "execution.local-agg", default=True, type=bool,
        description="Two-phase aggregation: pre-aggregate window "
        "contributions on the source stage before the keyed shuffle "
        "(at most one row per (key, slice) per batch), shrinking shuffle "
        "volume and defusing key skew (reference: "
        "MiniBatchLocalGroupAggFunction / agg-phase-strategy TWO_PHASE). "
        "Applies when the keyed stage is an aligned window aggregation.")


class StateOptions:
    TABLE_EXEC_OVER_ENGINE = ConfigOption(
        "table.exec.over.engine", default="auto", type=str,
        description="Compute engine for OVER windowed aggregations: "
        "'device' = one fused jitted kernel computes every frame of "
        "every key per fire (segmented scans + monotonicized "
        "searchsorted, runtime/over_device.py); 'host' = per-key-segment "
        "NumPy prefix scans (runtime/over_agg.py); 'auto' (default) = "
        "device when the frame family supports it (bounded RANGE "
        "MIN/MAX stays host). Reference operators: "
        "flink-table-runtime/.../over/RowTimeRowsBoundedPrecedingFunction.java:1.")
    TABLE_EXEC_STATE_TTL = ConfigOption(
        "table.exec.state.ttl", default=0, type=int,
        description="Idle-state retention for SQL operators, in ms: a "
        "GROUP BY accumulator or upsert-materializer key untouched this "
        "long is dropped (slot freed, snapshots shrink); a later arrival "
        "re-INSERTs. 0 (default) = keep state forever. The reference's "
        "table.exec.state.ttl / StateTtlConfig semantics (reference: "
        "flink-core/.../api/common/state/StateTtlConfig.java:1, "
        "flink-runtime/.../runtime/state/ttl/TtlStateFactory.java:1).")
    DEVICE_MEMORY_BUDGET = ConfigOption(
        "memory.device.size", default=0, type=int,
        description="Managed device (HBM) memory budget in BYTES shared "
        "by every stateful operator of a job — the "
        "taskmanager.memory.managed.size role (reference: "
        "MemoryManager.java). Slot tables and pane rings reserve their "
        "accumulator footprint from this pool at creation and each "
        "growth; an over-budget reservation fails with a per-operator "
        "breakdown instead of an opaque device OOM. 0 (default) = "
        "unlimited.")
    BACKEND = ConfigOption(
        "state.backend", default="tpu-slot-table", type=str,
        description="Keyed-state backend (flink_tpu.state.backends SPI): "
        "'tpu-slot-table' commits accumulators to the accelerator (HBM, "
        "with the spill tier beyond it); 'host-heap' commits them to the "
        "host CPU device — no accelerator traffic at all, the "
        "HashMapStateBackend role for small-state jobs. Third-party "
        "placements register via register_state_backend().")
    SLOT_CAPACITY = ConfigOption(
        "state.slot-table.capacity", default=1 << 20, type=int,
        description="Fixed slot capacity per keyed window state (XLA static shape).")
    CHECKPOINT_DIR = ConfigOption(
        "state.checkpoints.dir", default=None, type=str,
        description="Directory for checkpoint snapshots.")
    NUM_RETAINED = ConfigOption(
        "state.checkpoints.num-retained", default=3, type=int,
        description="Completed checkpoints to keep on disk (reference: "
        "state.checkpoints.num-retained). GC anchors on the newest "
        "checkpoints that PASS CRC verification: a torn/corrupt newest "
        "can never strand the job by deleting its fallback chain. "
        "Overrides execution.checkpointing.retained when both are set.")
    MAX_DEVICE_SLOTS = ConfigOption(
        "state.slot-table.max-device-slots", default=0, type=int,
        description="Device-resident slot budget per keyed state (HBM "
        "bound). 0 = unbounded (grow by doubling). When the budget is "
        "reached, cold namespaces spill to host memory and reload "
        "transparently on access (the RocksDB/ForSt beyond-memory role). "
        "At parallelism > 1 the budget applies PER DEVICE (each mesh "
        "shard owns one device's HBM), so total capacity scales with the "
        "mesh while each chip stays bounded.")
    WINDOW_LAYOUT = ConfigOption(
        "state.window-layout", default="auto", type=str,
        description="Keyed window state layout: 'slots' ((key, slice) "
        "slot table — the general engine: sessions, spill, mesh), "
        "'panes' (ring-of-slices x key-rows — fires are pure device "
        "reductions with no per-fire host->device transfer; aligned "
        "windows on one device only; has not run on the chip), or "
        "'auto' (the slot layout; ROADMAP.md queue 3 item 7's A/B "
        "decides whether panes stay).")
    SPILL_DIR = ConfigOption(
        "state.spill.dir", default=None, type=str,
        description="Filesystem tier for spilled state (any core.fs "
        "scheme). None = spill stays in host memory.")
    SPILL_HOST_MAX_BYTES = ConfigOption(
        "state.spill.host-max-bytes", default=0, type=int,
        description="Host-memory budget for spilled namespaces before they "
        "overflow to state.spill.dir. 0 = unbounded host tier.")


class AutoscaleOptions:
    """Elastic rescaling of the keyed mesh (flink_tpu/autoscale/): a
    DS2-style policy reads the job metric tree and live-migrates key
    groups between mesh shards (reference: the reactive/adaptive
    scheduler pair + the k8s autoscaler's ScalingMetricEvaluator)."""

    ENABLED = ConfigOption(
        "autoscale.enabled", default=False, type=bool,
        description="Tick a scaling policy inside the task loop and "
        "LIVE-rescale mesh-sharded keyed operators (no stop-redeploy). "
        "Requires an operator running a mesh engine (parallelism > 1).")
    INTERVAL_MS = ConfigOption(
        "autoscale.interval-ms", default=1000, type=int,
        description="Policy sampling/decision interval.")
    UTILIZATION_TARGET = ConfigOption(
        "autoscale.utilization-target", default=0.7, type=float,
        description="Size the operator so busy fraction lands here; the "
        "headroom absorbs bursts without rescaling (DS2 utilization).")
    MIN_SHARDS = ConfigOption(
        "autoscale.min-shards", default=1, type=int,
        description="Lower bound on the mesh size.")
    MAX_SHARDS = ConfigOption(
        "autoscale.max-shards", default=0, type=int,
        description="Upper bound on the mesh size; 0 = the number of "
        "visible devices.")
    COOLDOWN_MS = ConfigOption(
        "autoscale.cooldown-ms", default=30_000, type=int,
        description="Minimum time between rescales.")
    HYSTERESIS = ConfigOption(
        "autoscale.hysteresis", default=0.25, type=float,
        description="Relative dead band: targets within this fraction of "
        "the current size are noise and ignored.")
    IMBALANCE_LIMIT = ConfigOption(
        "autoscale.imbalance-limit", default=2.0, type=float,
        description="Refuse to scale DOWN while max/mean resident rows "
        "per shard exceeds this — a hot shard under key skew is not "
        "spare capacity.")
    FIRE_BREACH_TICKS = ConfigOption(
        "autoscale.fire-breach-ticks", default=3, type=int,
        description="Consecutive policy ticks the fire-latency p99 must "
        "exceed latency.fire-deadline-ms before the fire-latency signal "
        "triggers a scale-up — a single slow harvest is noise, a "
        "sustained deadline miss is a capacity problem even when "
        "throughput keeps up.")


class CheckpointOptions:
    INTERVAL_MS = ConfigOption(
        "execution.checkpointing.interval-ms", default=0, type=int,
        description="Checkpoint interval; 0 disables periodic checkpoints.")
    EVERY_N_BATCHES = ConfigOption(
        "execution.checkpointing.every-n-source-batches", default=0, type=int,
        description="Deterministic trigger: checkpoint every N source "
        "batches (tests/benchmarks; 0 = use the time interval).")
    RETAINED = ConfigOption(
        "execution.checkpointing.retained", default=3, type=int,
        description="How many completed checkpoints to keep.")
    COMPRESSION = ConfigOption(
        "execution.checkpointing.compression", default=True, type=bool,
        description="Compress snapshot arrays (zlib inside .npz; the "
        "reference uses lz4/snappy for state artifacts).")
    INCREMENTAL = ConfigOption(
        "execution.checkpointing.incremental", default=False, type=bool,
        description="Write delta checkpoints (dirty rows + tombstones) "
        "between periodic full snapshots.")
    FULL_EVERY = ConfigOption(
        "execution.checkpointing.incremental.full-every", default=10,
        type=int,
        description="Consolidate: every Nth checkpoint is a full snapshot, "
        "bounding restore-chain length.")
    MODE = ConfigOption(
        "execution.checkpointing.mode", default="exactly-once", type=str)
    UNALIGNED = ConfigOption(
        "execution.checkpointing.unaligned", default=False, type=bool,
        description="Barriers overtake in-flight data; overtaken batches "
        "are persisted as channel state so a checkpoint completes in "
        "bounded time under backpressure (reference: "
        "ExecutionCheckpointingOptions.ENABLE_UNALIGNED). Savepoints "
        "remain aligned. Stage-parallel executor only.")


def retained_checkpoints(config) -> int:
    """Checkpoints to keep on disk: ``state.checkpoints.num-retained``
    (the reference's key) wins when explicitly set; the legacy
    ``execution.checkpointing.retained`` remains honored. The ONE copy
    of the precedence rule, shared by both executors."""
    if config.contains(StateOptions.NUM_RETAINED) or \
            not config.contains(CheckpointOptions.RETAINED):
        return config.get(StateOptions.NUM_RETAINED)
    return config.get(CheckpointOptions.RETAINED)


class WatchdogOptions:
    """Device watchdog (flink_tpu/runtime/watchdog.py): deadline-tracked
    device interactions on the mesh engines + shard quarantine — the
    detection half of shard-granular partial failover (the reference's
    HeartbeatManager role, scoped to one device/shard)."""

    ENABLED = ConfigOption(
        "watchdog.enabled", default=False, type=bool,
        description="Wrap mesh-engine device interactions (dispatch "
        "fences, fire harvests, device_get batches, serving lookups) in "
        "deadline-tracked watchdog sections; a shard past its miss "
        "budget is declared dead at the next batch boundary "
        "(ShardFailedError -> failover).")
    DEADLINE_MS = ConfigOption(
        "watchdog.deadline-ms", default=0, type=int,
        description="A device interaction slower than this records a "
        "deadline MISS against its shard(s); 0 tracks heartbeats only.")
    MAX_MISSES = ConfigOption(
        "watchdog.max-misses", default=3, type=int,
        description="Consecutive deadline misses a shard survives "
        "before being declared dead (timeout -> retry -> declare-dead "
        "escalation).")


class RestartOptions:
    """reference: RestartStrategyOptions (restart-strategy.* keys)."""

    STRATEGY = ConfigOption(
        "restart-strategy.type", default="fixed-delay", type=str,
        description="none | fixed-delay | exponential-delay | failure-rate.")
    MAX_ATTEMPTS = ConfigOption(
        "restart-strategy.max-attempts", default=3, type=int)
    DELAY_MS = ConfigOption(
        "restart-strategy.delay-ms", default=100, type=int)
    MAX_BACKOFF_MS = ConfigOption(
        "restart-strategy.exponential-delay.max-backoff-ms",
        default=60_000, type=int,
        description="Backoff ceiling for exponential-delay.")
    BACKOFF_MULTIPLIER = ConfigOption(
        "restart-strategy.exponential-delay.backoff-multiplier",
        default=2.0, type=float)
    JITTER_FACTOR = ConfigOption(
        "restart-strategy.exponential-delay.jitter-factor",
        default=0.0, type=float,
        description="Spread each backoff by +/- this fraction "
        "(thundering-herd protection across concurrent restarts).")
    RESET_BACKOFF_THRESHOLD_MS = ConfigOption(
        "restart-strategy.exponential-delay.reset-backoff-threshold-ms",
        default=0, type=int,
        description="After this long without failures the backoff and "
        "attempt budget reset to initial (0 = never reset; reference: "
        "ExponentialDelayRestartBackoffTimeStrategy).")
    FAILURE_RATE_INTERVAL_MS = ConfigOption(
        "restart-strategy.failure-rate.failure-rate-interval-ms",
        default=60_000, type=int,
        description="Sliding window for failure-rate counting.")


class ClusterOptions:
    NUM_TASK_EXECUTORS = ConfigOption(
        "cluster.task-executors", default=1, type=int)
    SLOTS_PER_EXECUTOR = ConfigOption(
        "taskmanager.numberOfTaskSlots", default=1, type=int)
    HEARTBEAT_INTERVAL_MS = ConfigOption(
        "heartbeat.interval-ms", default=500, type=int)
    HEARTBEAT_TIMEOUT_MS = ConfigOption(
        "heartbeat.timeout-ms", default=5000, type=int)
    REST_PORT = ConfigOption(
        "rest.port", default=0, type=int,
        description="REST status endpoint port; 0 = ephemeral, -1 = off.")
    RPC_PORT = ConfigOption(
        "rpc.port", default=0, type=int,
        description="Control-plane gRPC port (0 = ephemeral). Standalone "
        "deployments pin it so TaskExecutor processes can join "
        "(reference: jobmanager.rpc.port).")
    RPC_BIND_ADDRESS = ConfigOption(
        "rpc.bind-address", default="127.0.0.1", type=str,
        description="Address the control-plane gRPC server binds; use "
        "0.0.0.0 for cross-host standalone clusters (reference: "
        "jobmanager.rpc.address/bind-host).")
    RPC_ADVERTISED_ADDRESS = ConfigOption(
        "rpc.advertised-address", default="", type=str,
        description="Address peers use to CONNECT to this process "
        "(registered with the ResourceManager, returned in slot offers). "
        "Empty = the bind address, or the host's resolved IP when binding "
        "0.0.0.0 (reference: taskmanager.host).")


class SchedulerOptions:
    """reference: JobManagerOptions.SCHEDULER + adaptive scheduler knobs."""

    MODE = ConfigOption(
        "jobmanager.scheduler", default="default", type=str,
        description="'default' (fail fast when no resources) or 'adaptive' "
        "(wait for resources, rescale reactively on resource change — "
        "reference: scheduler/adaptive/AdaptiveScheduler.java).")
    RESOURCE_WAIT_TIMEOUT_MS = ConfigOption(
        "jobmanager.adaptive-scheduler.resource-wait-timeout-ms",
        default=30_000, type=int,
        description="How long WaitingForResources waits for a slot before "
        "the job fails.")
    RESOURCE_STABILIZATION_MS = ConfigOption(
        "jobmanager.adaptive-scheduler.resource-stabilization-timeout-ms",
        default=100, type=int,
        description="Settle time after a resource change before (re)acting "
        "on it.")


class HighAvailabilityOptions:
    """reference: HighAvailabilityOptions (high-availability.* keys)."""

    MODE = ConfigOption(
        "high-availability.type", default="none", type=str,
        description="'none' or 'filesystem' (file-lock leader election + "
        "persisted job graph store; the role ZooKeeper/K8s drivers play in "
        "the reference).")
    STORAGE_DIR = ConfigOption(
        "high-availability.storageDir", default=None, type=str,
        description="Directory for leader locks, job graph store and blobs.")
    LEASE_TIMEOUT_MS = ConfigOption(
        "high-availability.lease-timeout-ms", default=3000, type=int,
        description="Leader lease considered stale after this long without "
        "renewal.")
