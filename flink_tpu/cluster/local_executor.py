"""Local single-process executor — the MiniCluster analog.

reference: runtime/minicluster/MiniCluster.java runs the whole control plane
in one JVM for tests; the per-task engine is the mailbox loop
(streaming/runtime/tasks/StreamTask.java:916 + MailboxProcessor.java:214).

Re-design: one Python thread owns the whole dataflow (single-owner discipline
— the mailbox model without the mailbox). Sources are polled round-robin into
micro-batches; each batch is pushed depth-first through the operator DAG;
watermarks are merged per multi-input operator via WatermarkValve. Operator
"chaining" is implicit (direct method calls); the heavy per-batch math inside
WindowAggOperator is the jitted device code. Checkpoint barriers are batch
boundaries: the executor simply snapshots all operators between pushes
(alignment is structural — SURVEY.md §7 step 6).
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Dict, List, Optional

from flink_tpu.core.config import (
    BatchOptions,
    CheckpointOptions,
    Configuration,
    CoreOptions,
    DeploymentOptions,
    LatencyOptions,
    StateOptions,
)
from flink_tpu.chaos import injection as chaos
from flink_tpu.core.records import RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.graph.transformations import StreamGraph, Transformation
from flink_tpu.runtime.elements import MAX_WATERMARK, Watermark
from flink_tpu.runtime.operators import Operator, OperatorContext
from flink_tpu.runtime.process import TaggedBatch
from flink_tpu.runtime.watermarks import WatermarkValve


from flink_tpu.core.annotations import internal

class _Node:
    __slots__ = ("transformation", "operator", "valve", "children",
                 "child_input_idx", "records_in", "records_out", "held_wm",
                 "busy_s", "marker_hist")

    def __init__(self, transformation: Transformation,
                 operator: Optional[Operator]):
        self.transformation = transformation
        self.operator = operator
        self.valve = WatermarkValve(max(len(transformation.inputs), 1))
        self.children: List[_Node] = []
        self.child_input_idx: List[int] = []
        self.records_in = 0
        self.records_out = 0
        #: wall time spent inside THIS operator's batch/watermark hooks
        #: (excludes downstream forwarding) — the DS2 busy-fraction
        #: numerator the autoscale policy differentiates
        self.busy_s = 0.0
        #: watermark held back while the operator has in-flight async
        #: fires — forwarded downstream only after their results are
        #: (see _drain_pending; reference: watermark must not overtake
        #: the records it covers)
        self.held_wm: Optional[int] = None
        #: per-operator LatencyMarker histogram (observe.export) — the
        #: executor stamps each source batch and records marker->here
        self.marker_hist = None


class JobCancelledError(RuntimeError):
    """Raised inside the task loop when the job is cancelled externally."""


class _ControlRequest:
    """Completion plumbing shared by all task-loop control requests: the
    loop completes them via ``finish(result, error)``, the client blocks in
    ``wait`` — one contract, relied on by _fail_pending_controls."""

    timeout_message = "control request not served"

    def __init__(self):
        import threading

        self.result = None
        self.error: Optional[BaseException] = None
        self._done = threading.Event()

    def finish(self, result, error=None) -> None:
        self.result = result
        self.error = error
        self._done.set()

    def wait(self, timeout: Optional[float] = None):
        if not self._done.wait(timeout):
            raise TimeoutError(self.timeout_message)
        if self.error is not None:
            raise self.error
        return self.result


class StateQueryRequest(_ControlRequest):
    """Queryable-state point lookup served at a batch boundary — the
    single-owner loop means reads never race task-thread mutations
    (reference: flink-queryable-state KvStateServer, but without the
    concurrent-read hazards of its direct backend access)."""

    timeout_message = "state query not served"

    def __init__(self, operator_name: str, key, namespace=None):
        super().__init__()
        self.operator_name = operator_name
        self.key = key
        self.namespace = namespace


class StateQueryBatchRequest(_ControlRequest):
    """Batched queryable-state lookup: ALL keys served in one pass —
    one gather program + ONE device read for the whole batch (the
    serving-plane contract; the one-RTT-per-key path is gone). The
    single-key StateQueryRequest is now a thin wrapper over this."""

    timeout_message = "state query batch not served"

    def __init__(self, operator_name: str, keys, namespace=None):
        super().__init__()
        self.operator_name = operator_name
        self.keys = list(keys)
        self.namespace = namespace


class RescaleRequest(_ControlRequest):
    """Cross-job shard arbitration lands here: the tenancy arbiter posts
    its per-job allocation, the task loop serves it at a batch boundary
    (pending fires drained first — their buffers reference the
    pre-reshard plane) and drives the operator's LIVE ``reshard``."""

    timeout_message = "rescale not served"

    def __init__(self, new_shards: int):
        super().__init__()
        self.new_shards = int(new_shards)


class SavepointRequest(_ControlRequest):
    """A user-triggered savepoint (optionally stop-with-savepoint).

    reference: CheckpointCoordinator.triggerSavepoint + the
    stop-with-savepoint flow (runtime/scheduler/stopwithsavepoint/*).
    Served by the task loop at a batch boundary — the structurally aligned
    barrier point of the micro-batch engine.
    """

    def __init__(self, path: str, stop: bool = False, drain: bool = False):
        super().__init__()
        self.path = path
        self.stop = stop
        self.drain = drain
        self.timeout_message = f"savepoint {path!r} not completed"

    @property
    def result_path(self) -> Optional[str]:
        return self.result


class _SourcePump:
    """Bounded-prefetch source reader: a thread that polls one source,
    assigns timestamps and watermarks, and hands (batch, watermark,
    position, stamp) entries to the task loop through a bounded queue.

    The queue bound IS the backpressure (credit-based flow control,
    reference: RemoteInputChannel.java:114 unannouncedCredit — here a
    credit is a queue slot). Each entry carries the source position taken
    AFTER that batch, so a checkpoint cut at batch boundary N snapshots
    exactly the consumed prefix — prefetched-but-unprocessed batches are
    re-read after restore (reference: source offsets ride the same barrier
    as operator state).

    The hand-over is where either side waits for the other, and each
    says so in the flight recorder on its own thread: the pump at a full
    queue (``source.wait_loop``), the loop at an empty one
    (``loop.wait_source``), and per batch the time between
    ``poll_batch``'s return — the entry's stamp — and the loop taking it
    (``source.queue_wait``). The stamp then is the origin of everything
    that batch and its watermark cause (``flight.set_origin``).

    The pump owns the source object while running (single-owner
    discipline); the task loop touches the source only after ``stop()``.
    """

    _EOS = object()

    def __init__(self, transformation, batch_size: int, in_flight: int):
        import queue as _q
        import threading

        self.t = transformation
        self.batch_size = batch_size
        self.queue: "_q.Queue" = _q.Queue(maxsize=max(in_flight, 1))
        self.wm_gen = transformation.watermark_strategy.create()
        self._stop = threading.Event()    # stop reading new batches
        self._abort = threading.Event()   # discard mode: puts give up
        self.error: Optional[BaseException] = None
        #: batches the task loop has taken (a batch's sequence)
        self.taken = 0
        self._thread = threading.Thread(
            target=self._run, name=f"source-pump-{transformation.name}",
            daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _put(self, item) -> bool:
        # an already-polled batch advanced the source position, so it must
        # reach the consumer unless the job is abandoning data outright
        # (_abort); a mere stop_filling keeps trying while the drain path
        # consumes
        import queue as _q

        if self._abort.is_set():
            return False
        try:
            self.queue.put_nowait(item)
            return True
        except _q.Full:
            pass
        # the loop has not taken what it was handed: back-pressured
        with flight.span("source.wait_loop") as span:
            span.work = 1
            while not self._abort.is_set():
                try:
                    self.queue.put(item, timeout=0.05)
                    return True
                except _q.Full:
                    continue
        return False

    def _run(self) -> None:
        src = self.t.source
        strategy = self.t.watermark_strategy
        try:
            while not self._stop.is_set():
                # batch_size is re-read each poll: the adaptive controller
                # on the task loop may resize it (benign cross-thread read)
                batch = src.poll_batch(self.batch_size)
                stamp = time.perf_counter()
                if batch is None:
                    self._put((self._EOS, None, src.snapshot_position(),
                               stamp))
                    return
                if len(batch) == 0:
                    continue
                batch = strategy.assign_timestamps(batch)
                wm = self.wm_gen.on_batch(batch)
                pos = src.snapshot_position()
                if not self._put((batch, wm, pos, stamp)):
                    return
        except BaseException as e:  # noqa: BLE001 - surfaced to task loop
            self.error = e
            self._put((self._EOS, None, None, time.perf_counter()))

    def _take(self, entry):
        """The task loop takes ``entry``: how long it lay since the source
        handed it over, and its stamp as the origin of what follows."""
        batch, wm, _, stamp = entry
        if batch is not self._EOS:
            self.taken += 1
            now = time.perf_counter()
            # under the watermark the batch brought: the one that fires
            # what this batch closes
            flight.instant("source.queue_wait", batch=self.taken,
                           watermark=flight.WM_NONE if wm is None
                           else int(wm),
                           t0=now, duration_s=now - stamp,
                           work=self.queue.qsize())
        flight.set_origin(stamp)
        return entry

    def poll(self, timeout: float = 0.0):
        """One queue entry or None. Raises the pump's error, if any."""
        import queue as _q

        try:
            entry = self.queue.get_nowait()
        except _q.Empty:
            if not timeout:
                return None
            # idle for want of input; a batch found waiting says nothing
            with flight.span("loop.wait_source"):
                try:
                    entry = self.queue.get(timeout=timeout)
                except _q.Empty:
                    return None
        if entry[0] is self._EOS and self.error is not None:
            raise self.error
        return self._take(entry)

    def stop_filling(self) -> None:
        """Stop reading new batches; already-queued entries stay consumable
        (the drain path processes them before the final snapshot)."""
        self._stop.set()

    def consume_remaining(self):
        """Yield the queued entries after ``stop_filling`` until the pump
        thread has exited and the queue is empty."""
        import queue as _q

        while self._thread.is_alive() or not self.queue.empty():
            try:
                yield self._take(self.queue.get(timeout=0.05))
            except _q.Empty:
                continue

    def stop(self) -> None:
        """Hard stop: discard prefetched entries (no-drain paths — the
        consumed-prefix position makes dropped entries re-readable)."""
        self._stop.set()
        self._abort.set()
        import queue as _q

        try:
            while True:
                self.queue.get_nowait()
        except _q.Empty:
            pass
        self._thread.join(timeout=5)


class _CheckpointWriter:
    """The asynchronous part of a checkpoint (reference:
    ``SubtaskCheckpointCoordinatorImpl`` hands the barrier's snapshot to an
    ``AsyncCheckpointRunnable`` and the task goes on processing records).

    The task loop takes the cut — the drain, the snapshot, its state made
    the writer's own (``split_states``: the snapshot's arrays, its metadata
    pickled) — and hands it to :meth:`submit`. ONE writer thread, started
    at the first hand-off, persists it (``checkpoint.persist``: the
    ``.npz`` files with zlib, the CRCs, the manifest, the atomic rename,
    then ``retain``) while the loop takes batches. At most one persist is
    in flight. The loop's :meth:`complete` ends it on the loop's own
    thread — at the top of the turn after it finished, or waiting for it
    where it must be on disk first (a due cut, a savepoint, the end of
    input, a failure) — and only then is the checkpoint complete: the
    next delta's base, the point the interval counts from,
    ``notify_checkpoint_complete`` delivered (two-phase sinks commit what
    a durable checkpoint sealed). A persist that raised raises there, as
    a failed synchronous write did."""

    def __init__(self, storage, job_name: str, retained: int,
                 base_id: Optional[int], nodes, claimed, traces):
        self.storage = storage
        self.job_name = job_name
        self.retained = retained
        self.nodes = nodes
        #: the restored artifact a CLAIM restore deletes once subsumed
        self.claimed = claimed
        self.traces = traces
        #: the newest completed checkpoint, which a delta may name as base
        self.base_id = base_id
        #: completed checkpoints since, and with, the last full one
        self.since_full = 0
        #: wall ms at which the last checkpoint completed
        self.completed_ms = time.time() * 1000
        #: (checkpoint id, delta?, cut wall ms, future) of the persist in
        #: flight
        self.pending = None
        self._pool = None

    def submit(self, checkpoint_id: int, states, extra, delta: bool,
               cut_ms: float) -> None:
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                1, thread_name_prefix="checkpoint-writer")
        self.pending = (checkpoint_id, delta, cut_ms, self._pool.submit(
            self._persist, checkpoint_id, states, extra, cut_ms))

    def _persist(self, checkpoint_id: int, states, extra, cut_ms: float):
        flight.set_job(self.job_name)
        with flight.span("checkpoint.persist") as persist:
            # stamped with the cut's time: what the sinks held when it
            # was taken was written before it
            new_dir = self.storage.write_checkpoint(
                checkpoint_id, self.job_name, states, extra=extra,
                timestamp_ms=math.ceil(cut_ms))
            size = sum(e.stat().st_size for e in os.scandir(new_dir)
                       if e.is_file())
            persist.work = size
            self.storage.retain(self.retained)
        return new_dir, size

    def done(self) -> bool:
        return self.pending is not None and self.pending[3].done()

    def settle(self) -> None:
        """Complete the persist in flight, if any (waiting for it)."""
        if self.pending is not None:
            self.complete()

    def complete(self) -> None:
        """Complete the persist in flight on the task loop's thread,
        waiting for it first if it has not finished."""
        from flink_tpu.metrics.traces import Span

        checkpoint_id, delta, cut_ms, future = self.pending
        self.pending = None
        name = f"checkpoint-{checkpoint_id}"
        with flight.span("checkpoint.write") as write:
            if not future.done():
                flight.instant("checkpoint.persist_wait", work=1)
            try:
                new_dir, size = future.result()
            except BaseException as e:
                self.traces.add(Span("checkpoint", name, cut_ms,
                                     time.time() * 1000, {"error": repr(e)}))
                raise
            write.work = size
            self.base_id = checkpoint_id
            self.since_full = self.since_full + 1 if delta else 1
            if self.claimed is not None:
                self.claimed.on_checkpoint_complete(new_dir)
            # checkpoint durable -> two-phase sinks publish (reference:
            # notifyCheckpointComplete -> commit)
            for node in self.nodes.values():
                op = node.operator
                if op is not None and hasattr(
                        op, "notify_checkpoint_complete"):
                    op.notify_checkpoint_complete(checkpoint_id)
        self.completed_ms = time.time() * 1000
        self.traces.add(Span("checkpoint", name, cut_ms, self.completed_ms,
                             {"checkpointId": checkpoint_id,
                              "incremental": delta, "stateSizeBytes": size}))

    def close(self) -> None:
        """Join the writer thread (a job that took no checkpoint has
        none)."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


class JobHandle:
    """Setup artifacts of one stepwise job run — the first value yielded
    by :meth:`LocalExecutor.run_stepwise`. The tenancy session cluster
    uses it to bind per-job quotas to the stateful operators, register
    the job's row in the ``tenancy`` metric group, and read the
    fairness/arbitration signals (busy time, backlog, resident rows)."""

    def __init__(self, job_name, graph, nodes, registry, traces,
                 job_group, pumps, sources, watchdog=None):
        self.job_name = job_name
        self.graph = graph
        self.nodes = nodes
        self.registry = registry
        self.traces = traces
        self.job_group = job_group
        self.pumps = pumps
        self.sources = sources
        #: the job's DeviceWatchdog when watchdog.enabled (None
        #: otherwise) — the tenancy arbiter reads its quarantine count
        #: to shrink the cross-job shard budget
        self.watchdog = watchdog

    def stateful_operators(self):
        """Operators owning keyed device state (spill_counters is the
        capability marker the metric tree already keys on)."""
        return [n.operator for n in self.nodes.values()
                if n.operator is not None
                and hasattr(n.operator, "spill_counters")]

    def busy_ms(self) -> float:
        """Wall time spent inside this job's operator hooks — the per-job
        ``busyTimeMsTotal`` the deficit-round-robin scheduler reports."""
        return sum(n.busy_s for n in self.nodes.values()) * 1000.0

    def backlog_records(self) -> int:
        """Prefetched-but-unprocessed records in the job's pump queues
        (the arbitration demand signal)."""
        return sum(p.queue.qsize() * p.batch_size
                   for p in self.pumps.values())

    def resident_rows(self) -> int:
        """Device-resident state rows across the job's engines."""
        return sum(sum(op.shard_resident_rows())
                   for op in self.stateful_operators()
                   if hasattr(op, "shard_resident_rows"))


@internal
class LocalExecutor:
    def __init__(self, config: Optional[Configuration] = None):
        self.config = config or Configuration()

    def run(self, graph: StreamGraph, job_name: str = "job",
            restore_from: Optional[str] = None, cancel_event=None,
            restore_mode="no-claim", control_queue=None):
        """Execute the graph to completion.

        Checkpointing: between two source polls the whole dataflow is
        quiescent (single-owner loop), so a snapshot taken there is a
        perfectly aligned barrier (reference: CheckpointBarrierHandler
        alignment, made structural by the micro-batch design). Sources
        snapshot their positions in the same cut, giving exactly-once state
        on restore.
        """
        gen = self.run_stepwise(graph, job_name, restore_from,
                                cancel_event, restore_mode, control_queue)
        try:
            while True:
                next(gen)
        except StopIteration as done:
            return done.value

    def run_stepwise(self, graph: StreamGraph, job_name: str = "job",
                     restore_from: Optional[str] = None, cancel_event=None,
                     restore_mode="no-claim", control_queue=None,
                     cooperative: bool = False):
        """Generator form of :meth:`run` — the multi-tenant scheduling
        surface. First yields a :class:`JobHandle` (setup artifacts: the
        tenancy session cluster binds quotas and metric gauges through
        it), then yields the number of source records processed per loop
        iteration (the deficit-round-robin accounting unit); the
        StopIteration value is the JobExecutionResult.

        ``cooperative=True`` skips the idle 1 ms sleep — the hosting
        scheduler owns pacing, and one starved job must not stall its
        siblings' quanta. Closing/throwing into the generator runs the
        same resource-release path an in-loop failure does."""
        from flink_tpu.datastream.environment import JobExecutionResult

        #: chaos context: fault plans on a multi-job cluster can target
        #: ONE tenant (where={"job": ...}) — the executor is per-job
        self._chaos_job = job_name

        from flink_tpu.core.config import ExecutionModeOptions

        batch_size = self.config.get(BatchOptions.BATCH_SIZE)
        max_parallelism = self.config.get(CoreOptions.MAX_PARALLELISM)
        # stateplane.backend.<family>=pallas|xla: applied (and validated
        # LOUDLY — unknown family/backend fails at submit, not mid-run)
        # before any engine builds a program; backend selection is
        # process-global, like the program cache the keys live in
        from flink_tpu.stateplane import configure_backends

        configure_backends(self.config)
        ckpt_interval = self.config.get(CheckpointOptions.INTERVAL_MS)
        ckpt_every_n = self.config.get(CheckpointOptions.EVERY_N_BATCHES)
        ckpt_dir = self.config.get(StateOptions.CHECKPOINT_DIR)
        # bounded/batch mode: no intermediate watermarks — every window
        # and aggregate fires exactly once at end-of-input (reference:
        # RuntimeExecutionMode.BATCH; the MAX watermark at source
        # exhaustion is the single "end of time" event)
        batch_mode = self.config.get(
            ExecutionModeOptions.RUNTIME_MODE) == "batch"
        if batch_mode:
            for t in graph.sources:
                if not getattr(t.source, "bounded", True):
                    raise RuntimeError(
                        "execution.runtime-mode=batch requires bounded "
                        f"sources; {t.name!r} is unbounded (reference: "
                        "batch mode rejects unbounded sources)")
        storage = None
        if ckpt_dir and (ckpt_interval or ckpt_every_n):
            from flink_tpu.checkpoint.storage import (
                CheckpointStorage,
                split_states,
            )

            storage = CheckpointStorage(
                ckpt_dir,
                compress=self.config.get(CheckpointOptions.COMPRESSION))

        # metrics + traces (reference: MetricRegistryImpl + Span reporting;
        # standard task I/O metric names follow the reference's
        # numRecordsIn/Out, currentInputWatermark conventions)
        from flink_tpu.metrics import MetricRegistry, TraceCollector

        registry = MetricRegistry()
        traces = TraceCollector()
        job_group = registry.root_group("job", job_name)
        # chaos counters ride the job's metric tree when a fault plan is
        # armed (job.<name>.chaos.faults_injected / retries / recoveries)
        chaos.register_chaos_metrics(job_group)
        # flight recorder: name the job for every span the task loop
        # (and the engines it drives) records, wire the jax-level probes
        # (XLA backend compiles, D2H materializations) into the same
        # timeline, and surface per-span-kind duration aggregates on
        # the job metric tree
        from flink_tpu.observe import install_probes
        from flink_tpu.observe.export import (
            LatencyMarkerPlane,
            register_flight_metrics,
        )

        install_probes()
        flight.set_job(job_name)
        # the flight aggregates are PROCESS-global (the recorder is
        # shared by every job on the mesh), so they register at the
        # registry root, not under this job's scope — a per-job scope
        # would claim other tenants' spans as this job's
        register_flight_metrics(registry.root_group())
        # event-time latency markers: each source batch is the marker;
        # per-operator marker histograms + watermark-lag gauges land
        # under job.<name>.<op>.latency
        lat_plane = self._lat_plane = LatencyMarkerPlane()
        # device watchdog (watchdog.enabled): one per job, attached to
        # every mesh engine through the operator context; heartbeat
        # gauges under job.<name>.watchdog. A ShardFailedError it raises
        # surfaces through the normal failure path (restart strategy ->
        # restore) — the SHARD-granular recovery protocol itself is the
        # chaos harness's run_shard_loss_verify (see README "Failure
        # domains").
        from flink_tpu.runtime.watchdog import watchdog_from_config

        watchdog = watchdog_from_config(
            self.config, self.config.get(CoreOptions.DEFAULT_PARALLELISM))
        if watchdog is not None:
            watchdog.register_metrics(job_group)

        # build nodes
        nodes: Dict[int, _Node] = {}
        default_par = self.config.get(CoreOptions.DEFAULT_PARALLELISM)
        memory_manager = None
        device_budget = self.config.get(StateOptions.DEVICE_MEMORY_BUDGET)
        if device_budget:
            from flink_tpu.core.memory import MemoryManager

            # ONE managed pool for the whole job: every stateful
            # operator's device footprint reserves from it (reference:
            # MemoryManager.java per-slot managed memory)
            memory_manager = MemoryManager(device_budget)
        for t in graph.nodes:
            op = t.operator_factory() if t.operator_factory else None
            node = _Node(t, op)
            if op is not None:
                # explicit set_parallelism wins; otherwise keyed operators
                # pick up parallelism.default (the mesh size of the
                # key-group axis — reference: env default parallelism
                # applied at StreamGraph generation)
                par = t.parallelism if t.parallelism else (
                    default_par if t.keyed else 1)
                ctx = OperatorContext(operator_index=0, parallelism=par,
                                      max_parallelism=max_parallelism,
                                      async_fires=self.config.get(
                                          BatchOptions.ASYNC_FIRES),
                                      max_dispatch_ahead=self.config.get(
                                          BatchOptions.MAX_DISPATCH_AHEAD),
                                      memory_manager=memory_manager,
                                      shuffle_mode=self.config.get(
                                          DeploymentOptions.SHUFFLE_MODE),
                                      host_topology=(self.config.get(
                                          DeploymentOptions.SHUFFLE_HOSTS)
                                          or None),
                                      watchdog=watchdog,
                                      pane_preagg=self.config.get(
                                          LatencyOptions.PANE_PREAGG),
                                      incremental_checkpoints=self.config.get(
                                          CheckpointOptions.INCREMENTAL))
                op.open(ctx)
            nodes[t.uid] = node
            g = job_group.add_group(f"{t.name}#{t.uid}")
            g.gauge("numRecordsIn", lambda n=node: n.records_in)
            g.gauge("numRecordsOut", lambda n=node: n.records_out)
            g.gauge("currentInputWatermark",
                    lambda n=node: n.valve.combined)
            g.gauge("busyTimeMsTotal", lambda n=node: n.busy_s * 1000.0)
            if op is not None:
                # LatencyMarker surface: marker histogram + watermark
                # lag vs the sources' frontier, under <op>.latency
                node.marker_hist = lat_plane.operator_group(
                    g, f"{t.name}#{t.uid}",
                    lambda n=node: n.valve.combined)
            if op is not None and hasattr(op, "spill_counters"):
                # the `state` group: the same numbers spill_counters()
                # reports, on the metric tree the autoscaler reads
                counters = op.spill_counters()
                if counters is not None:
                    sg = g.add_group("state")
                    for cname in counters:
                        sg.gauge(cname,
                                 lambda o=op, c=cname:
                                 (o.spill_counters() or {}).get(c, 0))
                    sg.gauge("resident_rows_per_shard",
                             lambda o=op: list(o.shard_resident_rows()))
                    sg.gauge("resident_rows",
                             lambda o=op: sum(o.shard_resident_rows()))
                    sg.gauge("key_imbalance",
                             lambda o=op: o.key_imbalance())
            if op is not None and hasattr(op, "fire_latencies_ms"):
                from flink_tpu.metrics.core import quantile_sorted

                # the `window` group: live fire-latency percentiles per
                # stateful operator, fed from the operator's bounded
                # reservoir (the autoscaler's fire_p99 reads it too) —
                # the latency tier's observable surface
                # (KNOWN_METRIC_GROUPS discipline;
                # supersedes the old top-level windowFireLatencyP99Ms
                # gauge, which had no consumers)
                wg = g.add_group("window")
                wg.gauge("fireLatencyP50Ms",
                         lambda o=op: quantile_sorted(
                             sorted(o.fire_latencies_ms), 0.5))
                wg.gauge("fireLatencyP99Ms",
                         lambda o=op: quantile_sorted(
                             sorted(o.fire_latencies_ms), 0.99))
                wg.gauge("fireCount",
                         lambda o=op: getattr(
                             o, "fires_total",
                             len(o.fire_latencies_ms)))
            if op is not None and hasattr(op, "late_records_dropped"):
                g.gauge("numLateRecordsDropped",
                        lambda o=op: o.late_records_dropped)
        for t in graph.nodes:
            n = nodes[t.uid]
            for child_t in graph.children(t):
                n.children.append(nodes[child_t.uid])
                n.child_input_idx.append(
                    graph.input_index(t, child_t))

        sources = [(t, nodes[t.uid]) for t in graph.sources]
        generators = {}
        for t, _ in sources:
            t.source.open(0, 1)
            generators[t.uid] = t.watermark_strategy.create()
        in_flight = self.config.get(BatchOptions.IN_FLIGHT_BATCHES)
        latency_target = self.config.get(BatchOptions.LATENCY_TARGET_MS)
        #: fire-deadline-aware micro-batching (latency.fire-deadline-ms):
        #: ingest batches split against the budget using the measured
        #: per-record step rate, with landed fires harvested between the
        #: splits — a due fire never waits out a full batch dispatch
        self._fire_deadline_ms = self.config.get(
            LatencyOptions.FIRE_DEADLINE_MS)
        self._deadline_rate = 0.0  # EMA of records/s through the dataflow
        debloater = None
        if latency_target > 0:
            from flink_tpu.runtime.debloater import BatchSizeController

            debloater = BatchSizeController(
                initial=batch_size,
                min_size=self.config.get(BatchOptions.MIN_BATCH_SIZE),
                max_size=batch_size,
                target_latency_ms=latency_target)
            batch_size = debloater.size

        checkpoint_count = 0
        claimed = None
        if restore_from is not None:
            from flink_tpu.checkpoint.savepoint import prepare_restore
            from flink_tpu.checkpoint.storage import (
                read_checkpoint_chain,
                read_manifest,
            )

            with flight.span("checkpoint.restore"), \
                    traces.span("recovery", "restore") as rsp:
                snap_dir, claimed = prepare_restore(
                    restore_from, restore_mode,
                    own_checkpoint_root=ckpt_dir)
                states = read_checkpoint_chain(snap_dir)
                self._restore_all(graph, nodes, states)
                rsp.set_attribute("snapshot", snap_dir)
                rsp.set_attribute("operators", len(states))
            checkpoint_count = int(read_manifest(snap_dir)["checkpoint_id"])
            restored_id = checkpoint_count
            # a valid delta base is the job's OWN chk-<id> directory — a
            # savepoint that merely lives inside the root is NOT one (its
            # id would alias an unrelated sibling checkpoint)
            restored_in_root = bool(ckpt_dir) and (
                os.path.dirname(os.path.abspath(snap_dir))
                == os.path.abspath(ckpt_dir)) and (
                os.path.basename(snap_dir) == f"chk-{restored_id}")
            if storage is not None:
                # the checkpoint root may hold higher-numbered checkpoints
                # from an abandoned timeline (restore from an older
                # savepoint): keep ids monotonic so new checkpoints
                # supersede the stale ones instead of being retain()-ed away
                checkpoint_count = max(
                    checkpoint_count, storage.latest_checkpoint_id() or 0)

        t0 = time.perf_counter()
        total_records = 0
        batches_since_ckpt = 0
        incremental = self.config.get(CheckpointOptions.INCREMENTAL)
        full_every = max(self.config.get(CheckpointOptions.FULL_EVERY), 1)
        writer = None
        if storage is not None:
            # deltas may build on a restored checkpoint only when it lives
            # in the job's own checkpoint root (its chain stays intact
            # under retain()); savepoints / foreign artifacts are not
            # valid bases
            writer = _CheckpointWriter(
                storage, job_name, self._retained(),
                (restored_id if restore_from is not None
                 and restored_in_root else None), nodes, claimed, traces)

        active = {t.uid for t, _ in sources}
        # host/device overlap: pump threads poll + timestamp the NEXT
        # batches while this loop drives slot lookups and (async-dispatched)
        # device kernels for the current one; the bounded queue is the
        # backpressure (reference: AsyncExecutionController.java:57 overlap,
        # RemoteInputChannel credit flow). Positions consumed so far are
        # tracked per source so checkpoint cuts stay exactly aligned.
        pumps: Dict[int, _SourcePump] = {}
        source_positions: Dict[int, Any] = {
            t.uid: t.source.snapshot_position() for t, _ in sources}
        if in_flight > 0:
            for t, _ in sources:
                pumps[t.uid] = _SourcePump(t, batch_size, in_flight)
            for p in pumps.values():
                p.start()
        # backlog signal: records prefetched-but-unprocessed in the pump
        # queues (the credit-based flow-control depth, estimated from
        # queued batches x current batch size) — feeds the autoscaler
        job_group.gauge(
            "sourceBacklogRecordsEstimate",
            lambda: sum(p.queue.qsize() * p.batch_size
                        for p in pumps.values()))
        autoscale = self._setup_autoscale(nodes, job_group, pumps,
                                          watchdog=watchdog)
        # wall-clock tick targets (processing-time windows/timers)
        pt_nodes = [n for n in nodes.values()
                    if n.operator is not None
                    and getattr(n.operator, "uses_processing_time", False)]
        try:
            yield JobHandle(job_name=job_name, graph=graph, nodes=nodes,
                            registry=registry, traces=traces,
                            job_group=job_group, pumps=pumps,
                            sources=sources, watchdog=watchdog)
            while active:
                step_records = 0
                if cancel_event is not None and cancel_event.is_set():
                    raise JobCancelledError(job_name)
                # harvest landed async fires + release held watermarks
                # (cheap is_ready() polls when nothing is pending)
                self._drain_pending(nodes)
                if writer is not None and writer.done():
                    writer.complete()
                if autoscale is not None:
                    autoscale.tick()
                if pt_nodes:
                    now_ms = int(time.time() * 1000)
                    for n in pt_nodes:
                        for out in n.operator.on_processing_time(now_ms):
                            self._forward(n, out)
                progressed = False
                for t, node in sources:
                    if t.uid not in active:
                        continue
                    if pumps:
                        entry = pumps[t.uid].poll(
                            timeout=0.002 if not progressed else 0.0)
                        if entry is None:
                            continue
                        batch, wm, pos, _ = entry
                        if batch is _SourcePump._EOS:
                            active.discard(t.uid)
                            if pos is not None:
                                source_positions[t.uid] = pos
                            self._emit_watermark(node, MAX_WATERMARK)
                            t.source.close()
                            continue
                    else:
                        batch = t.source.poll_batch(batch_size)
                        # no pump, no queue: the batch (or the end of
                        # input) is taken as the source hands it over
                        flight.set_origin(time.perf_counter())
                        if batch is None:
                            active.discard(t.uid)
                            self._emit_watermark(node, MAX_WATERMARK)
                            t.source.close()
                            continue
                        if len(batch) == 0:
                            continue
                        batch = t.watermark_strategy.assign_timestamps(batch)
                        wm = generators[t.uid].on_batch(batch)
                        pos = t.source.snapshot_position()
                    progressed = True
                    batches_since_ckpt += 1
                    total_records += len(batch)
                    step_records += len(batch)
                    source_positions[t.uid] = pos
                    tb = time.perf_counter() if debloater else 0.0
                    # this batch IS the latency marker: stamp its ingest
                    # wall time; operators record marker->here as the
                    # depth-first push reaches them, and the marker dies
                    # with the push — later drains/flushes are not this
                    # batch's latency
                    lat_plane.stamp_source()
                    if wm is not None and not batch_mode:
                        lat_plane.note_source_watermark(int(wm),
                                                        source=t.uid)
                    try:
                        if self._fire_deadline_ms > 0 and not batch_mode:
                            self._emit_deadline_split(node, batch,
                                                      nodes, wm)
                        else:
                            self._emit_batch(node, batch)
                            if wm is not None and not batch_mode:
                                self._emit_watermark(node, wm)
                    finally:
                        lat_plane.end_marker()
                    if debloater is not None:
                        new_size = debloater.observe(
                            len(batch), time.perf_counter() - tb)
                        if new_size != batch_size:
                            batch_size = new_size
                            for p in pumps.values():
                                p.batch_size = new_size
                if storage is not None:
                    # the interval counts from the END of the last
                    # checkpoint: its completion, after its persist
                    due = (ckpt_every_n
                           and batches_since_ckpt >= ckpt_every_n) or (
                        not ckpt_every_n and ckpt_interval
                        and writer.pending is None
                        and time.time() * 1000 - writer.completed_ms
                        >= ckpt_interval)
                    if due:
                        # at most one persist in flight
                        writer.settle()
                        checkpoint_count += 1
                        use_delta = (incremental
                                     and writer.base_id is not None
                                     and writer.since_full < full_every)
                        # in-flight fire results must reach their sinks
                        # before the cut — the bookkeeper already marked
                        # those windows fired, so a snapshot without them
                        # would lose results on restore
                        # the stall at the cut, in its two parts: the loop
                        # takes no batch and launches no fire until the
                        # snapshot is handed to the writer
                        with flight.span("checkpoint.drain") as drain:
                            drain.work = sum(
                                n.operator.pending_output_count()
                                for n in nodes.values()
                                if n.operator is not None)
                            self._drain_pending(nodes, wait=True)
                        cut_ms = time.time() * 1000
                        # the tables state the bytes they fetch as this
                        # span's work (flight.add_work)
                        with flight.span("checkpoint.snapshot"):
                            snap = self.snapshot_all(graph, nodes,
                                                     source_positions,
                                                     delta=use_delta)
                            # the writer's own from here: the tables'
                            # arrays are copies, and the metadata that
                            # still refers to live objects is pickled
                            states = split_states(snap)
                            flight.instant("checkpoint.offloop", work=1)
                        writer.submit(
                            checkpoint_count, states,
                            {"incremental": True, "base": writer.base_id}
                            if use_delta else None, use_delta, cut_ms)
                        batches_since_ckpt = 0
                        # the manifest's stamp is the cut's, rounded up: no
                        # row reaches a sink before the clock has passed it
                        time.sleep(max(math.ceil(cut_ms)
                                       - time.time() * 1000, 0) / 1000)
                if control_queue is not None:
                    stopped = self._serve_control(
                        control_queue, graph, nodes, sources, active,
                        job_name, checkpoint_count, traces,
                        source_positions, pumps,
                        settle=None if writer is None else writer.settle)
                    if stopped is not None:
                        suppress_final_drain = not stopped.drain
                        savepoint_path = stopped.result_path
                        break
                if not progressed and active and not pumps \
                        and not cooperative:
                    with flight.span("loop.wait_source"):
                        time.sleep(0.001)
                yield step_records
            else:
                suppress_final_drain = False
                savepoint_path = None
            if writer is not None:
                # every checkpoint on disk before the job ends
                writer.settle()
                writer.close()

            # drain/close in topological order (skipped for
            # stop-with-savepoint without --drain: state was saved, in-flight
            # windows intentionally not fired — they resume from the
            # savepoint)
            self._drain_pending(nodes, wait=True)
            if not suppress_final_drain:
                for t in graph.nodes:
                    node = nodes[t.uid]
                    if node.operator is not None:
                        for out in node.operator.close():
                            self._forward(node, out)
            else:
                # no-drain stop still releases resources and flushes sinks —
                # dispose() never emits (reference: Task releaseResources)
                for node in nodes.values():
                    if node.operator is not None:
                        try:
                            node.operator.dispose()
                        except Exception:
                            pass
            self._fail_pending_controls(
                control_queue, f"job {job_name!r} already terminated")
        except BaseException:
            # failure/cancel path: release resources without emitting
            # (reference: Task.doRun finally -> cancel + releaseResources)
            if writer is not None:
                # no writer outlives the job: a cut taken before the
                # failure completes as it would have, the failure wins
                try:
                    writer.settle()
                except Exception:
                    pass
                writer.close()
            for p in pumps.values():
                try:
                    p.stop()
                except Exception:
                    pass
            for t, _ in sources:
                try:
                    t.source.close()
                except Exception:
                    pass
            for node in nodes.values():
                if node.operator is not None:
                    try:
                        node.operator.dispose()
                    except Exception:
                        pass
            self._fail_pending_controls(
                control_queue, f"job {job_name!r} terminated abnormally")
            raise

        elapsed = time.perf_counter() - t0
        metrics = {
            "records_emitted_by_sources": total_records,
            "runtime_s": elapsed,
            **({"effective_batch_size": batch_size}
               if debloater is not None else {}),
            "records_per_s": total_records / elapsed if elapsed > 0 else 0.0,
            "checkpoints": checkpoint_count,
            **({"savepoint": savepoint_path} if savepoint_path else {}),
            "per_operator": {
                f"{n.transformation.name}#{uid}": {
                    "records_in": n.records_in, "records_out": n.records_out}
                for uid, n in nodes.items()
            },
        }
        if getattr(self, "fallback_reason", None):
            # surfaced in REST job status: the user asked for stage
            # parallelism but opted into single-slot fallback
            metrics["stage_fallback"] = self.fallback_reason
        if autoscale is not None and autoscale.events:
            metrics["autoscale"] = {
                "rescales": len(autoscale.events),
                "live_handoffs": autoscale.live_handoffs,
                "path": [(e.source, e.target) for e in autoscale.events],
                "handoff_ms": [round(e.handoff_s * 1e3, 3)
                               for e in autoscale.events
                               if e.mode == "live"],
            }
        result = JobExecutionResult(job_name, metrics)
        result.registry = registry
        result.traces = traces
        return result

    def _retained(self) -> int:
        from flink_tpu.core.config import retained_checkpoints

        return retained_checkpoints(self.config)

    # ------------------------------------------------------------ autoscale

    def _setup_autoscale(self, nodes, job_group, pumps, watchdog=None):
        """Build the in-loop autoscale controller for the first keyed
        operator that supports LIVE reshard (mesh engine), when
        autoscale.enabled. The controller ticks at batch boundaries on
        the task loop — the single-owner point where migrating device
        state is race-free. A watchdog-quarantined (dead) shard shrinks
        the device budget: the policy must not scale onto a device that
        no longer answers."""
        from flink_tpu.core.config import AutoscaleOptions

        if not self.config.get(AutoscaleOptions.ENABLED):
            return None
        target = None
        for node in nodes.values():
            op = node.operator
            if op is not None and getattr(op, "supports_live_rescale",
                                          False):
                target = node
                break
        if target is None:
            return None
        import jax

        from flink_tpu.autoscale.controller import (
            AutoscaleController,
            SignalSample,
        )
        from flink_tpu.autoscale.policy import ScalingPolicy

        engine = target.operator.windower
        # clamp the configured bounds to what reshard() can actually do
        # (devices, the key-group space, the engine's owned range) — a
        # policy allowed to target beyond them would turn a load spike
        # into a ValueError on the task loop, i.e. a job crash
        max_shards = self.config.get(AutoscaleOptions.MAX_SHARDS) \
            or len(jax.devices())
        max_shards = min(max_shards, len(jax.devices()),
                         int(engine.max_parallelism))
        kgr = getattr(engine, "key_group_range", None)
        if kgr is not None:
            max_shards = min(max_shards, int(kgr[1]) - int(kgr[0]) + 1)
        min_shards = min(self.config.get(AutoscaleOptions.MIN_SHARDS),
                         max_shards)
        policy = ScalingPolicy(
            utilization_target=self.config.get(
                AutoscaleOptions.UTILIZATION_TARGET),
            hysteresis=self.config.get(AutoscaleOptions.HYSTERESIS),
            cooldown_s=self.config.get(
                AutoscaleOptions.COOLDOWN_MS) / 1000.0,
            min_shards=min_shards,
            max_shards=max_shards,
            imbalance_limit=self.config.get(
                AutoscaleOptions.IMBALANCE_LIMIT),
            # the fire-latency signal (second input next to backlog):
            # sustained p99 over the fire deadline scales UP and vetoes
            # scale-down, even when the rate signal reads steady
            fire_deadline_ms=self.config.get(
                LatencyOptions.FIRE_DEADLINE_MS),
            fire_breach_ticks=self.config.get(
                AutoscaleOptions.FIRE_BREACH_TICKS))

        _fire_seen = [0]  # fires_total at the previous sample

        def fire_p99(node=target):
            from flink_tpu.metrics.core import quantile_sorted

            op = node.operator
            lat = getattr(op, "fire_latencies_ms", None)
            if not lat:
                return 0.0
            # staleness guard: no NEW fires since the last sample means
            # no deadline misses NOW — a burst of old slow samples must
            # not keep the breach streak alive (and re-trigger a
            # scale-up after every cooldown) once fires stop or recover
            total = getattr(op, "fires_total", len(lat))
            if total == _fire_seen[0]:
                return 0.0
            _fire_seen[0] = total
            # recent window of the bounded reservoir: the signal must
            # track NOW, not the job's whole history
            return quantile_sorted(sorted(list(lat)[-256:]), 0.99)

        def sample(node=target):
            return SignalSample(
                records_total=node.records_in,
                busy_ms_total=node.busy_s * 1000.0,
                backlog=sum(p.queue.qsize() * p.batch_size
                            for p in pumps.values()),
                shard_resident_rows=node.operator.shard_resident_rows(),
                fire_latency_p99_ms=fire_p99())

        def apply(new_shards, node=target):
            # in-flight fires reference the pre-reshard device arrays —
            # the drain boundary is the same one checkpoints use
            self._drain_pending(nodes, wait=True)
            if watchdog is not None:
                # a dead shard changes the budget: never scale onto a
                # quarantined device
                new_shards = min(
                    new_shards, watchdog.available(len(jax.devices())))
            return node.operator.reshard(new_shards)

        return AutoscaleController(
            policy, sample_fn=sample, apply_fn=apply,
            current_shards_fn=lambda: int(target.operator.windower.P),
            interval_s=self.config.get(
                AutoscaleOptions.INTERVAL_MS) / 1000.0,
            metrics_group=job_group)

    # -------------------------------------------------------------- control

    def _serve_control(self, control_queue, graph, nodes, sources, active,
                       job_name: str, checkpoint_id: int, traces,
                       source_positions, pumps, settle=None):
        """Serve pending SavepointRequests at a batch boundary. Returns the
        request if it asked the job to stop, else None. ``settle`` completes
        the checkpoint persist in flight, if any, before a savepoint: its
        failure fails the job, not the request."""
        import queue as _queue

        from flink_tpu.checkpoint.savepoint import write_savepoint

        from flink_tpu.checkpoint.savepoint import check_savepoint_target

        def stop_sources():
            # pumps own the sources while running: stop them first, then
            # close (single-owner hand-back)
            for t, node in sources:
                if t.uid in active:
                    p = pumps.get(t.uid)
                    if p is not None:
                        p.stop()
                    t.source.close()
            active.clear()

        # serve at most the requests ALREADY QUEUED at this boundary:
        # under sustained lookup load, clients re-submit while a served
        # request's device read releases the GIL — an unbounded drain
        # would keep serving forever and starve the data path (observed
        # as a livelock in the serving smoke's batched mode)
        budget = max(control_queue.qsize(), 1)
        while budget > 0:
            budget -= 1
            try:
                req = control_queue.get_nowait()
            except _queue.Empty:
                return None
            if isinstance(req, (StateQueryRequest, StateQueryBatchRequest)):
                try:
                    req.finish(self._serve_query(graph, nodes, req))
                except BaseException as e:  # noqa: BLE001
                    req.finish(None, e)
                continue
            if isinstance(req, RescaleRequest):
                # the arbiter's per-job allocation: drain in-flight fires
                # (their buffers reference the pre-reshard plane), then
                # live-migrate — the same boundary checkpoints use
                try:
                    self._drain_pending(nodes, wait=True)
                    target = None
                    for node in nodes.values():
                        op = node.operator
                        if op is not None and getattr(
                                op, "supports_live_rescale", False):
                            target = op
                            break
                    if target is None:
                        raise RuntimeError(
                            f"job {job_name!r} has no live-rescalable "
                            "operator (mesh engine required)")
                    req.finish(target.reshard(req.new_shards))
                except BaseException as e:  # noqa: BLE001
                    req.finish(None, e)
                continue
            if settle is not None:
                settle()
            try:
                # fail fast on a bad target BEFORE any irreversible action
                # (closing sources / draining): a savepoint that cannot be
                # written must leave the job running (reference semantics)
                check_savepoint_target(req.path)
                if req.stop and req.drain:
                    # --drain: process the pumps' prefetched batches (their
                    # positions are already consumed-from-source), then
                    # flush every window/timer downstream before the
                    # snapshot so results are final (reference:
                    # stop-with-savepoint advanceToEndOfEventTime)
                    for t, node in sources:
                        if t.uid not in active:
                            continue
                        p = pumps.get(t.uid)
                        if p is not None:
                            p.stop_filling()
                            for batch, wm, pos, _ in p.consume_remaining():
                                if pos is not None:
                                    source_positions[t.uid] = pos
                                if batch is _SourcePump._EOS:
                                    continue
                                self._emit_batch(node, batch)
                            if p.error is not None:
                                # a failed source must not masquerade as a
                                # clean end-of-stream in a FINAL savepoint
                                raise p.error
                        self._emit_watermark(node, MAX_WATERMARK)
                    stop_sources()
                self._drain_pending(nodes, wait=True)
                with traces.span("savepoint", req.path):
                    snap = self.snapshot_all(graph, nodes, source_positions,
                                             savepoint=True)
                    path = write_savepoint(req.path, job_name, snap,
                                           checkpoint_id=checkpoint_id)
                if req.stop and not req.drain:
                    stop_sources()
                req.finish(path)
            except BaseException as e:  # noqa: BLE001 - reported to caller
                req.finish(None, e)
                continue
            if req.stop:
                return req

    def _serve_query(self, graph, nodes, req):
        """Serve a single-key or batched state lookup. ALL reads route
        through the batched path: one gather program + ONE device read
        per request batch (a single key is a batch of one) — the old
        one-RTT-per-key loop is gone. Injected ``serving.lookup`` faults
        retry in place: lookups are read-only, so a retry cannot corrupt
        engine state (regression-pinned in tests/test_tenancy.py)."""
        keys = req.keys if isinstance(req, StateQueryBatchRequest) \
            else [req.key]
        for uid, node in nodes.items():
            t = node.transformation
            if req.operator_name in (t.name, graph.stable_id(t)):
                op = node.operator
                if op is None or not (hasattr(op, "query_state_batch")
                                      or hasattr(op, "query_state")):
                    raise RuntimeError(
                        f"operator {req.operator_name!r} has no queryable "
                        "state")

                def _lookup(op=op):
                    chaos.fault_point("serving.lookup",
                                      operator=req.operator_name,
                                      keys=len(keys),
                                      job=getattr(self, "_chaos_job",
                                                  None))
                    if hasattr(op, "query_state_batch"):
                        return op.query_state_batch(keys, req.namespace)
                    return [op.query_state(k, req.namespace)
                            for k in keys]

                out = chaos.run_recoverable("serving.lookup", _lookup)
                return out if isinstance(req, StateQueryBatchRequest) \
                    else out[0]
        raise KeyError(f"no operator named {req.operator_name!r}; "
                       f"available: "
                       f"{sorted(n.transformation.name for n in nodes.values())}")

    @staticmethod
    def _fail_pending_controls(control_queue, reason: str) -> None:
        """Complete any still-queued control requests so clients don't block
        on a job that already terminated."""
        if control_queue is None:
            return
        import queue as _queue

        while True:
            try:
                req = control_queue.get_nowait()
            except _queue.Empty:
                return
            req.finish(None, RuntimeError(reason))

    # --------------------------------------------- fire-deadline splitting

    def _deadline_observe(self, n: int, dt: float) -> None:
        """Fold one emitted chunk into the per-record rate EMA the
        splitter sizes chunks by."""
        if dt <= 1e-6 or n <= 0:
            return
        inst = n / dt
        self._deadline_rate = inst if self._deadline_rate <= 0 else (
            0.7 * self._deadline_rate + 0.3 * inst)

    def _emit_deadline_split(self, node: _Node, batch, nodes,
                             wm: Optional[int]) -> None:
        """Fire-deadline-aware micro-batching: split one source batch so
        each dispatch fits the latency.fire-deadline-ms budget at the
        MEASURED per-record step rate, advancing the watermark between
        splits and harvesting landed async fires — a due fire costs a
        bounded delta instead of waiting out a multi-hundred-ms batch.

        Intermediate watermarks are output-identical to the unsplit run:
        after chunk i the emitted watermark is
        ``min(final_wm, min timestamp of the REMAINING records - 1)``,
        so no remaining record of this batch can be late against it and
        no window fires before its last contributor arrived (the suffix
        minimum handles out-of-order timestamps within the batch)."""
        import numpy as np

        n = len(batch)
        rate = self._deadline_rate
        chunk = n if rate <= 0 else max(
            int(rate * self._fire_deadline_ms / 1000.0), 256)
        if chunk >= n:
            t0 = time.perf_counter()
            self._emit_batch(node, batch)
            self._deadline_observe(n, time.perf_counter() - t0)
            if wm is not None:
                self._emit_watermark(node, wm)
            return
        suffix_min = None
        if wm is not None and batch.has_timestamps:
            ts = np.asarray(batch.timestamps)
            suffix_min = np.minimum.accumulate(ts[::-1])[::-1]
        for a in range(0, n, chunk):
            b = min(a + chunk, n)
            t0 = time.perf_counter()
            self._emit_batch(node, batch.slice(a, b))
            self._deadline_observe(b - a, time.perf_counter() - t0)
            if b < n:
                if suffix_min is not None:
                    self._emit_watermark(
                        node, min(int(wm), int(suffix_min[b]) - 1))
                # harvest whatever landed; release held watermarks
                self._drain_pending(nodes)
        if wm is not None:
            self._emit_watermark(node, wm)

    # ------------------------------------------------------------- plumbing

    def _emit_batch(self, node: _Node, batch) -> None:
        """Route an output to children. Side outputs (TaggedBatch) go only to
        matching side-output edges; main outputs skip side-output edges
        (reference: OutputTag routing in OperatorChain)."""
        tag = batch.tag.name if isinstance(batch, TaggedBatch) else None
        payload = batch.batch if tag is not None else batch
        for child, idx in zip(node.children, node.child_input_idx):
            if child.transformation.side_tag == tag:
                self._process(child, payload, idx)

    def _emit_watermark(self, node: _Node, wm: int) -> None:
        for child, idx in zip(node.children, node.child_input_idx):
            self._process_watermark(child, wm, idx)

    def _process(self, node: _Node, batch: RecordBatch, input_idx: int) -> None:
        # chaos: a task crash mid-batch — surfaces through the normal
        # failure path (job fails, RestartStrategy decides, restore from
        # the latest checkpoint), exactly like a real UDF/executor death
        chaos.fault_point("task.batch", op=node.transformation.name,
                          job=getattr(self, "_chaos_job", None))
        node.records_in += len(batch)
        with flight.span("op.process", timed=True) as span:
            outs = node.operator.process_batch(batch, input_idx)
        node.busy_s += span.duration_s
        if node.marker_hist is not None:
            self._lat_plane.observe(node.marker_hist)
        for out in outs:
            self._forward(node, out)

    def _process_watermark(self, node: _Node, wm: int, input_idx: int) -> None:
        advanced = node.valve.advance(input_idx, wm)
        if advanced is None:
            return
        with flight.span("op.watermark", watermark=int(advanced),
                         timed=True) as span:
            outs = node.operator.process_watermark(advanced)
        node.busy_s += span.duration_s
        for out in outs:
            # a synchronous fire: its rows leave with the batch whose
            # watermark released them
            self._forward(node, out)
            flight.window_emit(int(advanced))
        if node.operator.has_pending_output():
            # async fires in flight: the watermark must not overtake the
            # results it covers — hold it here; _drain_pending releases it
            # once the fires land (a later watermark simply supersedes)
            node.held_wm = advanced
            return
        node.held_wm = None
        self._emit_watermark(node, advanced)

    def _drain_pending(self, nodes: Dict[int, "_Node"],
                       wait: bool = False) -> None:
        """Forward any landed async-fire results; release held watermarks
        whose fires have all been emitted. With ``wait``, block until every
        pending output is drained (checkpoint / drain / close boundaries —
        a snapshot taken with undelivered results would lose them)."""
        while True:
            for node in nodes.values():
                op = node.operator
                if op is None:
                    continue
                if op.has_pending_output():
                    # each harvest put its fire's (watermark, origin)
                    # back into the ambient context before yielding:
                    # the forward and the emission carry that window's
                    for out in op.poll_pending_output(wait=wait):
                        self._forward(node, out)
                        flight.window_emit()
                if node.held_wm is not None and not op.has_pending_output():
                    wm = node.held_wm
                    node.held_wm = None
                    self._emit_watermark(node, wm)
            if not wait:
                return
            # a released watermark can cascade new fires in a downstream
            # window operator — iterate to the fixpoint before returning
            if not any(
                    n.operator is not None
                    and (n.operator.has_pending_output()
                         or n.held_wm is not None)
                    for n in nodes.values()):
                return

    def _forward(self, node: _Node, batch) -> None:
        n = len(batch.batch) if isinstance(batch, TaggedBatch) else len(batch)
        node.records_out += n
        # an INSTANT, not a span: _emit_batch recurses synchronously
        # into the whole downstream subtree, and a duration here would
        # multiply-count each level's op.process time in the per-kind
        # aggregates — the timeline marks WHEN each output left, the
        # durations belong to the operators
        flight.instant("emit")
        self._emit_batch(node, batch)

    # ----------------------------------------------------------- checkpoint

    @staticmethod
    def snapshot_all(graph: StreamGraph, nodes: Dict[int, _Node],
                     source_positions: Optional[Dict[int, Any]] = None,
                     delta: bool = False,
                     savepoint: bool = False) -> Dict[str, Any]:
        snap: Dict[str, Any] = {}
        for uid, node in nodes.items():
            t = node.transformation
            op = node.operator
            if op is None:
                # positions of the CONSUMED prefix, not the pump's
                # prefetched one — the checkpoint cut is the batch boundary
                if source_positions is not None and uid in source_positions:
                    state = {"source": source_positions[uid]}
                else:
                    state = {"source": t.source.snapshot_position()}
            elif delta and hasattr(op, "snapshot_state_delta"):
                state = op.snapshot_state_delta()
            elif savepoint and hasattr(op, "snapshot_state_savepoint"):
                # full, but preserving incremental dirty tracking — a
                # savepoint must not shrink the next delta checkpoint
                state = op.snapshot_state_savepoint()
            else:
                state = op.snapshot_state()
            if state:
                snap[graph.stable_id(t)] = state
        return snap

    @staticmethod
    def _restore_all(graph: StreamGraph, nodes: Dict[int, _Node],
                     states: Dict[str, Any]) -> None:
        consumed = set()
        for uid, node in nodes.items():
            t = node.transformation
            sid = graph.stable_id(t)
            state = states.get(sid)
            if state is None:
                continue
            consumed.add(sid)
            if node.operator is None:
                t.source.restore_position(state["source"])
            else:
                node.operator.restore_state(state)
        leftover = set(states) - consumed
        if leftover:
            # the reference fails on non-restored state by default
            # (allowNonRestoredState opt-in); silently dropping state here
            # would silently undercount aggregates after a graph edit
            raise RuntimeError(
                "checkpoint contains state for operators not present in the "
                f"graph (graph changed since snapshot?): {sorted(leftover)}")
