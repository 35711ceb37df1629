"""Stream operators — batched re-design of the reference's operator model.

The reference's ``StreamOperator`` processes one element at a time
(reference: streaming/api/operators/AbstractStreamOperator.java,
OneInputStreamOperator.processElement). Here an operator processes a
``RecordBatch`` per call and reacts to watermark advances. All operators are
single-owner (called from one task loop), mirroring the mailbox threading
discipline (reference: tasks/mailbox/MailboxProcessor.java:214).

User functions are *vectorized*: a map function takes and returns a
RecordBatch (columnar), not a single element. A row-at-a-time adapter exists
for convenience (``RowMapFunction``) but the batch form is the idiomatic one —
it is what keeps the TPU path wide.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.runtime.elements import Watermark
from flink_tpu.runtime.watermarks import WatermarkValve
from flink_tpu.state.keygroups import hash_keys_to_i64
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.windowing.assigners import WindowAssigner
from flink_tpu.windowing.windower import SliceSharedWindower


class Operator:
    """Base operator. Subclasses override the hooks they need."""

    name: str = "operator"

    def open(self, ctx: "OperatorContext") -> None:
        pass

    def process_batch(self, batch: RecordBatch, input_index: int = 0
                      ) -> List[RecordBatch]:
        raise NotImplementedError

    def process_watermark(self, watermark: int, input_index: int = 0
                          ) -> List[RecordBatch]:
        return []

    #: operators that react to wall-clock ticks (processing-time windows /
    #: timers) set this so the executor loop knows to tick them
    uses_processing_time: bool = False

    def on_processing_time(self, now_ms: int) -> List[RecordBatch]:
        """Wall-clock tick (reference: WindowOperator.onProcessingTime:497 /
        InternalTimerService processing-time timers)."""
        return []

    def close(self) -> List[RecordBatch]:
        return []

    def dispose(self) -> None:
        """Release resources without emitting (failure/cancel path; the
        reference's StreamOperator.close vs dispose split)."""

    # asynchronous outputs (deferred window fires — see
    # flink_tpu.runtime.pending). The executor holds back this operator's
    # output watermark while pending outputs exist and polls them each
    # loop iteration (reference: AsyncExecutionController in-flight drain).
    def has_pending_output(self) -> bool:
        return False

    def poll_pending_output(self, wait: bool = False) -> List[RecordBatch]:
        return []

    def pending_output_count(self) -> int:
        """Fires dispatched and not yet harvested."""
        return 0

    # checkpointing
    def snapshot_state(self) -> Optional[Dict[str, Any]]:
        return None

    def restore_state(self, state: Dict[str, Any]) -> None:
        pass


def _ctx_topology(ctx, mesh):
    """Resolve the context's host-topology declaration against the
    engine's actual mesh: an int (``shuffle.hosts``) factors the mesh
    size; a :class:`~flink_tpu.parallel.mesh.HostTopology` is used when
    it covers. A declaration that cannot factor THIS mesh (e.g. a
    stage sub-mesh of a different size) falls back to the flat
    exchange rather than failing the job."""
    decl = getattr(ctx, "host_topology", None)
    if decl is None:
        return None
    size = int(mesh.devices.size)
    if isinstance(decl, int):
        if decl > 1 and size % decl == 0:
            from flink_tpu.parallel.mesh import HostTopology

            return HostTopology(decl, size // decl)
        return None
    return decl if decl.num_shards == size else None


class OperatorContext:
    """Per-operator runtime context (task info, metrics hook)."""

    def __init__(self, operator_index: int = 0, parallelism: int = 1,
                 max_parallelism: int = 128, metrics=None,
                 async_fires: bool = False, max_dispatch_ahead: int = 4,
                 mesh=None, key_group_range=None, memory_manager=None,
                 shuffle_mode: str = "device", watchdog=None,
                 pane_preagg: bool = True, host_topology=None,
                 incremental_checkpoints: bool = True):
        self.operator_index = operator_index
        self.parallelism = parallelism
        self.max_parallelism = max_parallelism
        self.metrics = metrics
        #: managed device-memory pool shared by the job's stateful
        #: operators (flink_tpu/core/memory.py; None = unlimited)
        self.memory_manager = memory_manager
        #: explicit device mesh for the keyed engine (mesh x stage: a
        #: keyed subtask opens its engine over a private sub-mesh)
        self.mesh = mesh
        #: (first, last) key groups this task owns — the mesh engine
        #: shards WITHIN this range when set (None: the full key space)
        self.key_group_range = key_group_range
        #: the hosting executor supports deferred fire harvesting +
        #: watermark holdback (LocalExecutor's loop); executors that
        #: forward watermarks eagerly must leave this off
        self.async_fires = async_fires
        #: per-batch fence depth (execution.pipeline.max-dispatch-batches)
        self.max_dispatch_ahead = max_dispatch_ahead
        #: keyBy data plane for mesh engines (shuffle.mode):
        #: "device" = in-program exchange, "host" = explicit fallback
        self.shuffle_mode = shuffle_mode
        #: (hosts, local) factorization of the mesh (shuffle.hosts) —
        #: an int host count or a HostTopology; mesh engines then run
        #: the two-level ICI/DCN exchange (parallel/exchange2.py)
        self.host_topology = host_topology
        #: DeviceWatchdog (runtime/watchdog.py) the mesh engines attach
        #: when watchdog.enabled — deadline-tracked device sections +
        #: batch-boundary shard-health probes; None = disabled
        self.watchdog = watchdog
        #: incremental pane pre-aggregation for the panes window layout
        #: (latency.pane-preagg): per-window running partials combined
        #: at absorb, so a fire gathers one closing pane. The other
        #: latency-tier knob (latency.fire-deadline-ms) lives on the
        #: EXECUTOR, which owns the batch loop and the autoscale policy.
        self.pane_preagg = pane_preagg
        #: whether a delta snapshot can be asked of this job's operators
        #: (execution.checkpointing.incremental): a keyed table keeps
        #: the tombstones of what it frees only while one can. On by
        #: default: a host that does not say gets the tombstones
        self.incremental_checkpoints = incremental_checkpoints


class MapOperator(Operator):
    name = "map"

    def __init__(self, fn: Callable[[RecordBatch], RecordBatch]):
        self.fn = fn

    def process_batch(self, batch, input_index=0):
        out = self.fn(batch)
        return [out] if out is not None and len(out) else []


class FilterOperator(Operator):
    name = "filter"

    def __init__(self, predicate: Callable[[RecordBatch], np.ndarray]):
        self.predicate = predicate

    def process_batch(self, batch, input_index=0):
        mask = np.asarray(self.predicate(batch), dtype=bool)
        out = batch.filter(mask)
        return [out] if len(out) else []


class FlatMapOperator(Operator):
    name = "flat_map"

    def __init__(self, fn: Callable[[RecordBatch], List[RecordBatch]]):
        self.fn = fn

    def process_batch(self, batch, input_index=0):
        return [b for b in self.fn(batch) if b is not None and len(b)]


class KeyByOperator(Operator):
    """Attaches the int64 key identity column (``__key_id__``).

    The actual routing (key group -> shard) happens at the exchange edge /
    device sharding, mirroring the split between KeyedStream (API) and
    KeyGroupStreamPartitioner (runtime) in the reference
    (reference: streaming/runtime/partitioner/KeyGroupStreamPartitioner.java:55).
    """

    name = "key_by"

    def __init__(self, key_field: str):
        self.key_field = key_field

    def process_batch(self, batch, input_index=0):
        key_ids = hash_keys_to_i64(batch[self.key_field])
        return [batch.with_column(KEY_ID_FIELD, key_ids)]


class AsyncFireOperator(Operator):
    """An operator whose engine dispatches window fires as
    :class:`~flink_tpu.runtime.pending.PendingFire` results: the queue of
    fires in flight, the per-batch dispatch fences that bound how far the
    host runs ahead of the device, and the one harvest every such fire
    leaves through (``_harvest_one``). The window aggregates and the
    device window join share it."""

    def __init__(self):
        #: wall-clock ms from watermark advance to fired results on host
        #: (the p99 window-fire latency metric; reference measures this at
        #: WindowOperator.emitWindowContents). Bounded reservoir — a
        #: long-running job must not leak host memory.
        from collections import deque

        self.fire_latencies_ms = deque(maxlen=8192)
        #: monotonic fire-sample count — the reservoir above is BOUNDED
        #: (its len saturates at maxlen), so counters and "any new
        #: fires since last tick?" checks read this instead
        self.fires_total = 0
        #: dispatched-but-unharvested fires (FIFO; see poll_pending_output)
        self._pending = deque()
        self._async_fires = False
        #: bound on in-flight fires: past it the next poll harvests the
        #: oldest without waiting for them to land (backpressure — pending
        #: results are small, but a catch-up burst firing hundreds of
        #: windows must not hoard buffers)
        self._max_pending = 32
        #: per-batch dispatch fences bounding how far the host runs ahead
        #: of the device queue — keeps fire kernels (and their latency)
        #: from queueing behind an unbounded scatter backlog
        self._fences = deque()
        self._max_dispatch_ahead = 4  # overridden from ctx in open()

    def _push_fence(self, fence) -> None:
        """Keep one batch's fence; past the dispatch-ahead bound wait for
        the oldest."""
        if fence is not None:
            self._fences.append(fence)
            if len(self._fences) > self._max_dispatch_ahead:
                self._await_fences()

    def _await_fences(self) -> None:
        """The task loop's dispatch-ahead backpressure point: the host
        waits for the device here, and only past the bound."""
        with flight.span("device.fence_wait"):
            while len(self._fences) > self._max_dispatch_ahead:
                # flint: disable=TRC01 -- the depth-bounded fence drain
                # IS the backpressure point (blocks only past the bound)
                self._fences.popleft().block_until_ready()

    def has_pending_output(self) -> bool:
        return bool(self._pending)

    def pending_output_count(self) -> int:
        return len(self._pending)

    def poll_pending_output(self, wait: bool = False):
        """Harvest the fires that have landed, oldest first, yielding each
        one's result with that fire's ``(watermark, origin)`` in the
        flight recorder's ambient context: the caller forwards it before
        it asks for the next. With ``wait``, and past the bound on
        pending fires, the harvest waits for its fire."""
        import time as _time

        while self._pending:
            block = wait or len(self._pending) > self._max_pending
            if not block and not self._pending[0].ready():
                # the device runs its programs in order: what was
                # dispatched behind a fire that has not landed has not
                now = _time.perf_counter()
                for pf in self._pending:
                    pf.unready_at = now
                return
            yield from self._harvest_one(block)

    def _harvest_one(self, block: bool = False) -> List[RecordBatch]:
        """Pop and harvest the oldest pending fire — the one place a
        ``PendingFire`` of any engine leaves the queue, and so the one
        place the wait between its dispatch and its harvest is recorded:
        ``fire.in_flight`` (dispatch -> here) and, of it, ``fire.poll_gap``
        (the last look that found it not ready -> here)."""
        import time as _time

        pf = self._pending.popleft()
        waited_from = pf.unready_at
        if block:
            if not waited_from:
                waited_from = _time.perf_counter()
            pf.wait_ready()
        flight.set_fire_context(pf.watermark, pf.origin)
        t_start = _time.perf_counter()
        flight.instant("fire.in_flight", t0=t_start,
                       duration_s=t_start - pf.dispatched_at)
        flight.instant("fire.poll_gap", t0=t_start, timed=True,
                       duration_s=t_start - waited_from
                       if waited_from else 0.0)
        batch = pf.harvest()
        # fire latency = watermark advance (dispatch) -> results on host,
        # the same span the synchronous path measures: fire.in_flight
        # plus the harvest, off the same dispatch stamp
        self.fire_latencies_ms.append(
            (_time.perf_counter() - pf.dispatched_at) * 1e3)
        self.fires_total += 1
        if batch is None or len(batch) == 0:
            return []
        return [self._emit_harvested(batch)]

    def _emit_harvested(self, batch: RecordBatch) -> RecordBatch:
        """What a harvested fire's batch becomes on its way out."""
        return batch

    def _check_no_pending(self) -> None:
        # the hosting executor must drain (and forward) in-flight fires
        # before a snapshot — silently dropping them here would lose fired
        # windows that the bookkeeper already marked fired
        if self._pending:
            raise RuntimeError(
                "snapshot with in-flight async fires; the executor must "
                "drain pending outputs (poll_pending_output(wait=True)) "
                "before snapshotting")


class WindowAggOperator(AsyncFireOperator):
    """keyBy -> window -> aggregate on the TPU slot table.

    reference semantics: WindowOperator.java / WindowAggOperator.java (see
    flink_tpu.windowing.windower docstring for the mapping).
    """

    name = "window_agg"

    def __init__(self, assigner: WindowAssigner, agg: AggregateFunction,
                 key_field: str, capacity: int = 1 << 16,
                 allowed_lateness: int = 0, spill: dict = None,
                 fire_projector=None, window_layout: str = "auto",
                 state_backend: str = "tpu-slot-table"):
        super().__init__()
        self.window_layout = window_layout
        self.state_backend = state_backend
        self.assigner = assigner
        self.agg = agg
        self.key_field = key_field
        self.capacity = capacity
        self.allowed_lateness = allowed_lateness
        self.spill = spill
        self.fire_projector = fire_projector
        #: processing-time assigner: records are stamped with wall-clock
        #: arrival time; fires come from on_processing_time ticks
        #: (reference: WindowOperator.onProcessingTime:497)
        self.uses_processing_time = bool(
            getattr(assigner, "is_processing_time", False))
        self.windower: Optional[SliceSharedWindower] = None
        self._key_values: Dict[int, Any] = {}  # key_id -> original key value
        #: sorted-array mirror of _key_values for vectorized lookups on the
        #: fire path (np.searchsorted instead of a per-key Python loop);
        #: rebuilt lazily whenever the dict has grown
        self._kv_ids: np.ndarray = np.empty(0, np.int64)
        self._kv_vals: np.ndarray = np.empty(0, object)
        self._keys_hashed = False

    def open(self, ctx):
        if ctx.parallelism > 1:
            # parallelism > 1 selects the mesh-sharded engine: state lives
            # in [P, capacity] device arrays sharded over the key-group
            # mesh axis, records are routed by the reference's key-group
            # formula (reference: Execution.java:572 deploy() expands a
            # vertex into parallel subtasks; KeyGroupStreamPartitioner.java:55
            # routes by key group — here the "subtasks" are mesh shards of
            # one jitted program)
            from flink_tpu.parallel.mesh import make_mesh
            from flink_tpu.parallel.sharded_windower import MeshWindowEngine

            self._reject_backend_on_mesh()
            # make_mesh refuses a request larger than the devices that
            # exist, naming both counts — never a silently smaller mesh
            mesh = getattr(ctx, "mesh", None) or make_mesh(ctx.parallelism)
            spill = dict(self.spill or {})
            self.windower = MeshWindowEngine(
                self.assigner, self.agg, mesh,
                capacity_per_shard=self.capacity,
                max_parallelism=ctx.max_parallelism,
                allowed_lateness=self.allowed_lateness,
                fire_projector=self.fire_projector,
                # the budget is per device: every mesh shard owns one
                # chip's HBM (state capacity ⟂ parallelism, the RocksDB
                # contract)
                max_device_slots=spill.get("max_device_slots", 0),
                spill_dir=spill.get("spill_dir"),
                spill_host_max_bytes=spill.get("spill_host_max_bytes", 0),
                key_group_range=getattr(ctx, "key_group_range", None),
                memory=self._managed_memory(ctx),
                # engine-level dispatch-ahead follows the task's
                # pipeline depth (execution.pipeline.max-dispatch-batches)
                max_dispatch_ahead=getattr(ctx, "max_dispatch_ahead", 2),
                # keyBy data plane (shuffle.mode): in-program device
                # exchange by default, host bucketing as the fallback
                shuffle_mode=getattr(ctx, "shuffle_mode", "device"),
                # (hosts, local) factorization (shuffle.hosts): the
                # two-level ICI/DCN exchange on a pod-spanning mesh
                host_topology=_ctx_topology(ctx, mesh))
        else:
            table_kwargs, placement = self._table_kwargs()
            if self._managed_memory(ctx) is not None:
                table_kwargs["memory"] = self._managed_memory(ctx)
            has_spill = bool(self.spill and any(self.spill.values()))
            # 'auto' is the slot layout: every benchmark cell runs it.
            # The pane layout has not run on the chip; whether it stays
            # is decided by the A/B in ROADMAP.md queue 3 item 7. An
            # explicit 'panes' is honored for aligned windows without
            # spill; its footprint is DENSE ([ring_rows, key_capacity]
            # per leaf), so high-ratio sliding windows multiply HBM by
            # the slice count.
            use_panes = self.window_layout == "panes"
            if use_panes and has_spill:
                raise ValueError(
                    "state.window-layout=panes has no spill tier — use "
                    "'slots' (or 'auto') with state.spill.* options")
            if use_panes and placement is not None:
                raise ValueError(
                    "state.window-layout=panes supports only the default "
                    "placement; state.backend placements (host-heap) use "
                    "the slot layout")
            if use_panes:
                # pane/ring layout: fires are pure device reductions with
                # no per-fire host->device transfer (state/pane_table.py)
                from flink_tpu.windowing.windower import PaneWindower

                self.windower = PaneWindower(
                    self.assigner, self.agg, capacity=self.capacity,
                    max_parallelism=ctx.max_parallelism,
                    allowed_lateness=self.allowed_lateness,
                    fire_projector=self.fire_projector,
                    memory=self._managed_memory(ctx),
                    # latency tier: per-window partials combined at
                    # absorb, fires gather one closing pane
                    preagg=getattr(ctx, "pane_preagg", True))
            else:
                self.windower = SliceSharedWindower(
                    self.assigner, self.agg, capacity=self.capacity,
                    max_parallelism=ctx.max_parallelism,
                    allowed_lateness=self.allowed_lateness,
                    spill=table_kwargs,
                    fire_projector=self.fire_projector)
        self._resolve_async_fires(ctx)
        self._resolve_tombstones(ctx)

    def _managed_memory(self, ctx):
        """(MemoryManager, unique owner) for device-state accounting, or
        None when no budget is configured (flink_tpu/core/memory.py)."""
        mm = getattr(ctx, "memory_manager", None)
        if mm is None:
            return None
        return (mm, f"{self.name}#{id(self):x}")

    def _reject_backend_on_mesh(self) -> None:
        if self.state_backend not in ("tpu-slot-table",):
            # fail loudly, never degrade silently (same contract as
            # execution.stage-fallback): the mesh engine shards state
            # over the device mesh — a placement backend cannot apply
            raise ValueError(
                f"state.backend={self.state_backend!r} is not supported "
                "at operator parallelism > 1: mesh-sharded state is "
                "placed by the device mesh itself. Use the default "
                "'tpu-slot-table' backend, or run placement-backed "
                "state at parallelism 1 / stage-parallel subtasks "
                "(execution.stage-parallelism), where each subtask owns "
                "a single-device engine that honors the placement")

    def _table_kwargs(self):
        """(SlotTable kwargs incl. backend placement, placement) — the
        spill options plus the state backend's device commitment (one
        implementation for aligned and session windows)."""
        from flink_tpu.state.backends import resolve_placement

        placement = resolve_placement(self.state_backend)
        kwargs = dict(self.spill or {})
        if placement is not None:
            kwargs["device"] = placement
        return kwargs, placement

    def _resolve_tombstones(self, ctx) -> None:
        """A job that takes no delta checkpoint
        (execution.checkpointing.incremental off) keeps no tombstones of
        what its keyed table frees: nothing would ever read or clear
        them."""
        table = getattr(self.windower, "table", None)
        if hasattr(table, "keep_tombstones"):
            table.keep_tombstones(
                getattr(ctx, "incremental_checkpoints", True))

    def _resolve_async_fires(self, ctx) -> None:
        """Deferred fire harvesting needs both an engine that can dispatch
        async (single-device slot/pane/session engines declare
        supports_async_fires) and an executor that holds back watermarks
        while fires are in flight (ctx.async_fires)."""
        self._async_fires = bool(
            getattr(ctx, "async_fires", False)
            and getattr(self.windower, "supports_async_fires", False))
        self._max_dispatch_ahead = int(
            getattr(ctx, "max_dispatch_ahead", self._max_dispatch_ahead))
        # device watchdog (watchdog.enabled): deadline-tracked device
        # interactions + shard quarantine on the mesh engines
        wd = getattr(ctx, "watchdog", None)
        if wd is not None and hasattr(self.windower, "attach_watchdog"):
            self.windower.attach_watchdog(wd)

    def process_batch(self, batch, input_index=0):
        if self.key_field in batch.columns:
            keys = batch[self.key_field]
            if keys.dtype.kind not in "iu":
                # remember original key values for emission (dict check is
                # O(uniques) and does NOT touch the sorted fire-path
                # mirror — rebuilding that here would cost O(K log K) per
                # batch while the key space is still growing)
                self._keys_hashed = True
                kid = batch.key_ids
                uniq, first = np.unique(kid, return_index=True)
                kv = self._key_values
                for i, j in zip(uniq.tolist(), first.tolist()):
                    if i not in kv:
                        kv[i] = keys[j]
        if self.uses_processing_time:
            import time as _time

            # arrival time IS the record time in the processing-time
            # domain — a whole micro-batch arrives at one instant
            now = int(_time.time() * 1000)
            batch = batch.with_timestamps(
                np.full(len(batch), now, dtype=np.int64))
        elif not batch.has_timestamps:
            # validate where timestamps are REQUIRED (covers every
            # untimed source: raw collections, mixed unions, ...) — the
            # alternative is a bare KeyError inside the windower
            raise RuntimeError(
                f"event-time window {self.name!r} received records "
                "without timestamps — assign a WatermarkStrategy / "
                "timestamp_field on every input (or use a "
                "processing-time window)")
        self.windower.process_batch(batch)
        if self._async_fires:
            # the mesh engines fence on the engine itself (their state
            # is the sharded [P, cap] arrays, not a .table); the
            # single-device engines fence on their slot/pane table
            fence_src = getattr(self.windower, "make_fence", None)
            if fence_src is None:
                table = getattr(self.windower, "table", None)
                fence_src = getattr(table, "make_fence", None) \
                    if table is not None else None
            self._push_fence(fence_src() if fence_src is not None
                             else None)
        return []

    def process_watermark(self, watermark, input_index=0):
        from flink_tpu.runtime.elements import MAX_WATERMARK

        if self.uses_processing_time and watermark < MAX_WATERMARK:
            # event-time watermarks don't drive processing-time windows;
            # only the end-of-input MAX flushes what remains (reference:
            # processing-time windows fire on close at endOfInput)
            return []
        import time as _time

        from flink_tpu.runtime.pending import PendingFire

        t0 = _time.perf_counter()
        fired = self.windower.on_watermark(
            watermark, async_ok=self._async_fires) \
            if self._async_fires else self.windower.on_watermark(watermark)
        outs = []
        fired_sync = False
        for b in fired:
            if isinstance(b, PendingFire):
                self._pending.append(b)
            else:
                fired_sync = True
                outs.append(self._reattach_keys(b))
        if fired_sync:
            # one sample per watermark advance, like the async path's one
            # sample per fire-to-harvest span
            self.fire_latencies_ms.append((_time.perf_counter() - t0) * 1e3)
            self.fires_total += 1
        return outs

    def on_processing_time(self, now_ms: int):
        if not self.uses_processing_time:
            return []
        # window [start, end) is complete once the wall clock passes end
        fired = self.windower.on_watermark(now_ms - 1)
        return [self._reattach_keys(b) for b in fired]

    def _kv_sync(self) -> None:
        """Rebuild the sorted lookup arrays iff _key_values grew (restore,
        new keys). O(K log K) per rebuild, amortized to nothing once the
        key set stabilizes."""
        if len(self._kv_ids) != len(self._key_values):
            ids = np.fromiter(self._key_values.keys(), np.int64,
                              len(self._key_values))
            order = np.argsort(ids, kind="stable")
            self._kv_ids = ids[order]
            vals = np.empty(len(ids), object)
            vals[:] = list(self._key_values.values())
            self._kv_vals = vals[order]

    def _emit_harvested(self, batch: RecordBatch) -> RecordBatch:
        return self._reattach_keys(batch)

    def _reattach_keys(self, batch: RecordBatch) -> RecordBatch:
        kid = batch.key_ids
        if self._keys_hashed:
            # vectorized id -> value: searchsorted on the sorted mirror (no
            # per-key Python loop on the hot fire path)
            self._kv_sync()
            kidv = np.ascontiguousarray(kid, dtype=np.int64)
            if len(self._kv_ids):
                pos = np.minimum(np.searchsorted(self._kv_ids, kidv),
                                 len(self._kv_ids) - 1)
                vals = self._kv_vals[pos]
                miss = self._kv_ids[pos] != kidv
                if miss.any():
                    vals[miss] = None
            else:
                vals = np.full(len(kidv), None, object)
        else:
            vals = kid
        return batch.with_column(self.key_field, vals)

    def close(self):
        return []

    def dispose(self):
        self._pending.clear()
        self._fences.clear()
        release = getattr(self.windower, "release_memory", None)
        if release is None:
            table = getattr(self.windower, "table", None)
            release = getattr(table, "release_memory", None)
        if release is not None:
            release()

    def snapshot_state(self):
        self._check_no_pending()
        return {
            "windower": self.windower.snapshot(),
            "key_values": dict(self._key_values),
            "keys_hashed": self._keys_hashed,
        }

    def snapshot_state_delta(self):
        """Incremental variant: the keyed table ships only dirty rows +
        tombstones; host metadata (bookkeeping, key values) is small and
        written full (reference: incremental checkpoints still write fresh
        metadata, only SSTs are shared)."""
        self._check_no_pending()
        return {
            "windower": self.windower.snapshot(mode="delta"),
            "key_values": dict(self._key_values),
            "keys_hashed": self._keys_hashed,
        }

    def snapshot_state_savepoint(self):
        """Savepoint variant: full state, but keeps incremental dirty
        tracking intact — a savepoint is a side artifact and must not
        change what the next delta checkpoint contains."""
        self._check_no_pending()
        return {
            "windower": self.windower.snapshot(mode="savepoint"),
            "key_values": dict(self._key_values),
            "keys_hashed": self._keys_hashed,
        }

    def query_state(self, key_value, namespace=None):
        """Queryable-state point lookup: {window_end -> result columns} for
        one key — a batch of one (thin wrapper; every read routes through
        :meth:`query_state_batch`, so a single lookup costs the same one
        gather + one device read a batch does, never one RTT per key)."""
        return self.query_state_batch([key_value], namespace)[0]

    def query_state_batch(self, key_values, namespace=None):
        """Batched queryable-state lookup: one {window_end -> result
        columns} dict per requested key, request order — window values
        composed from per-slice partial accumulators, so sliding/
        cumulative windows return true window results, not slice
        fragments (reference: queryable state KvState lookup). The whole
        batch is served by ONE gather program + ONE device read (the
        serving-plane contract). Served on the task loop at a batch
        boundary, so reads are race-free (single-owner discipline, like
        the reference's mailbox). ``namespace`` restricts every key to
        one window end."""
        from flink_tpu.state.keygroups import hash_keys_to_i64

        key_ids = hash_keys_to_i64(np.asarray(key_values))
        w = self.windower
        if hasattr(w, "query_batch"):            # mesh engines
            outs = w.query_batch(key_ids)
        elif hasattr(w, "query_windows_batch"):  # slot-table windower
            outs = w.query_windows_batch(key_ids)
        else:                                    # pane layout: per key
            outs = [w.query_windows(int(k)) for k in key_ids]
        if namespace is not None:
            ns = int(namespace)
            outs = [({ns: out[ns]} if ns in out else {}) for out in outs]
        return outs

    def restore_state(self, state, key_group_filter=None):
        if key_group_filter is not None:
            # subtask-expansion restore: keep only this instance's key
            # groups from the (merged, logical) snapshot (reference:
            # key-group-range filtered restore on rescale)
            self.windower.restore(state["windower"],
                                  key_group_filter=key_group_filter)
        else:
            self.windower.restore(state["windower"])
        # empty sub-dicts are pruned by the checkpoint codec
        self._key_values = dict(state.get("key_values", {}))
        self._kv_ids = np.empty(0, np.int64)  # lookup mirror: force rebuild
        self._kv_vals = np.empty(0, object)
        self._keys_hashed = state.get("keys_hashed", False)

    # ------------------------------------------------------ elastic rescale

    @property
    def supports_live_rescale(self) -> bool:
        """True when the hosting engine can migrate key groups in place
        (mesh engines); False means the cold path — checkpoint-restore
        at the new parallelism (restore_state(key_group_filter=...))."""
        return hasattr(self.windower, "reshard")

    def reshard(self, new_shards: int) -> Dict[str, Any]:
        """Live rescale of the mesh engine between mesh shard counts —
        drain in-flight async fires FIRST (their device buffers
        reference the pre-reshard arrays); the hosting executor's
        _drain_pending(wait=True) boundary does exactly that."""
        if not self.supports_live_rescale:
            raise RuntimeError(
                f"operator {self.name!r} runs a single-device engine — "
                "live reshard needs the mesh engine (parallelism > 1); "
                "rescale it cold via checkpoint-restore-at-new-"
                "parallelism")
        if self._pending:
            raise RuntimeError(
                "reshard with in-flight async fires; the executor must "
                "drain pending outputs (poll_pending_output(wait=True)) "
                "before rescaling")
        # operator-held fences reference the old plane; the engine
        # drains its own dispatch fences (a superset) inside reshard
        self._fences.clear()
        return self.windower.reshard(new_shards)

    # ------------------------------------------------------ replica serving

    def arm_serving_replica(self, publish_interval_ms: float = 0.0):
        """Arm the engine's read replica (tenancy/replica.py) and return
        its serving adapter, or None when the engine cannot host one
        (single-device layouts serve through the legacy control-queue
        path). Must run on the task thread before/between batches — the
        session cluster calls it at submit/restart."""
        w = self.windower
        if not hasattr(w, "arm_replica"):
            return None
        from flink_tpu.tenancy.replica import WindowReplicaAdapter

        plane = w.arm_replica()
        plane.min_interval_s = float(publish_interval_ms) / 1e3
        return WindowReplicaAdapter(plane, w.agg, w.assigner)

    # ----------------------------------------------------- state observability

    def spill_counters(self) -> Optional[Dict[str, int]]:
        """The engine's spill traffic counters (None when the engine has
        none) — surfaced as the job metric tree's ``state`` group."""
        eng = self.windower
        fn = getattr(eng, "spill_counters", None)
        if fn is None:
            table = getattr(eng, "table", None)
            fn = getattr(table, "spill_counters", None)
        return fn() if fn is not None else None

    def shard_resident_rows(self) -> List[int]:
        """Resident rows per shard (one entry for single-device engines)."""
        eng = self.windower
        fn = getattr(eng, "shard_resident_rows", None)
        if fn is not None:
            return fn()
        table = getattr(eng, "table", None)
        index = getattr(table, "index", None)
        if index is not None:
            return [int(index.slot_used.sum())]
        return []

    def key_imbalance(self) -> float:
        """max/mean resident rows per shard (1.0 for single-device)."""
        eng = self.windower
        fn = getattr(eng, "key_imbalance", None)
        return float(fn()) if fn is not None else 1.0


class SessionWindowAggOperator(WindowAggOperator):
    """Merging session windows (reference: WindowOperator + MergingWindowSet;
    see flink_tpu.windowing.sessions for the host/device split). Shares the
    key-reattachment / latency / snapshot plumbing with WindowAggOperator;
    only the windower implementation differs."""

    name = "session_window_agg"

    def __init__(self, gap: int, agg: AggregateFunction, key_field: str,
                 capacity: int = 1 << 16, allowed_lateness: int = 0,
                 spill: dict = None, state_backend: str = "tpu-slot-table"):
        super().__init__(assigner=None, agg=agg, key_field=key_field,
                         capacity=capacity, allowed_lateness=allowed_lateness,
                         spill=spill, state_backend=state_backend)
        self.gap = gap

    def open(self, ctx):
        from flink_tpu.windowing.sessions import SessionWindower

        if ctx.parallelism > 1:
            # parallelism > 1 selects the mesh-sharded session engine —
            # session merges are shard-local (keys own their sessions), so
            # the metadata stays global and only state shards (reference:
            # keyed state locality of MergingWindowSet state)
            from flink_tpu.parallel.mesh import make_mesh
            from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

            self._reject_backend_on_mesh()
            mesh = getattr(ctx, "mesh", None) or make_mesh(ctx.parallelism)
            spill = dict(self.spill or {})
            self.windower = MeshSessionEngine(
                self.gap, self.agg, mesh,
                capacity_per_shard=self.capacity,
                max_parallelism=ctx.max_parallelism,
                allowed_lateness=self.allowed_lateness,
                # per-device budget, same contract as the window engine
                max_device_slots=spill.get("max_device_slots", 0),
                spill_dir=spill.get("spill_dir"),
                spill_host_max_bytes=spill.get("spill_host_max_bytes", 0),
                key_group_range=getattr(ctx, "key_group_range", None),
                memory=self._managed_memory(ctx),
                # sessions default to the paged (cohort) spill layout,
                # same as the single-device engine
                spill_layout=spill.get("spill_layout", "pages"),
                # engine-level dispatch-ahead follows the task's
                # pipeline depth (execution.pipeline.max-dispatch-batches)
                max_dispatch_ahead=getattr(ctx, "max_dispatch_ahead", 2),
                # keyBy data plane (shuffle.mode)
                shuffle_mode=getattr(ctx, "shuffle_mode", "device"),
                host_topology=_ctx_topology(ctx, mesh))
        else:
            table_kwargs, _ = self._table_kwargs()
            if self._managed_memory(ctx) is not None:
                table_kwargs["memory"] = self._managed_memory(ctx)
            self.windower = SessionWindower(
                self.gap, self.agg, capacity=self.capacity,
                max_parallelism=ctx.max_parallelism,
                allowed_lateness=self.allowed_lateness,
                spill=table_kwargs)
        self._resolve_async_fires(ctx)
        self._resolve_tombstones(ctx)

    def arm_serving_replica(self, publish_interval_ms: float = 0.0):
        """Session form: the adapter composes {session_end -> columns}
        from the published (key, sid) rows' END payloads."""
        w = self.windower
        if not hasattr(w, "arm_replica"):
            return None
        from flink_tpu.tenancy.replica import SessionReplicaAdapter

        plane = w.arm_replica()
        plane.min_interval_s = float(publish_interval_ms) / 1e3
        return SessionReplicaAdapter(plane, w.agg)

    def query_state_batch(self, key_values, namespace=None):
        """Session variant: the keys' live sessions are host metadata
        ({key -> [(start, end, sid)]}); their accumulators are read
        through ONE gather + ONE device read for the whole batch. One
        {session_end -> result columns} dict per key, request order."""
        from flink_tpu.state.keygroups import hash_keys_to_i64

        key_ids = hash_keys_to_i64(np.asarray(key_values))
        w = self.windower
        if hasattr(w, "query_batch"):              # mesh engine
            outs = w.query_batch(key_ids)
        else:                                      # single-device engine
            outs = w.query_sessions_batch(key_ids)
        if namespace is not None:
            ns = int(namespace)
            outs = [({ns: out[ns]} if ns in out else {}) for out in outs]
        return outs


class UnionOperator(Operator):
    """Pass-through merge of multiple inputs; watermark = min over inputs
    (valve handled by the task wiring)."""

    name = "union"

    def __init__(self, require_consistent_time: bool = False):
        #: SQL UNION ALL sets this: its output feeds relational operators
        #: that assume event-time consistency, so a timed/untimed mix
        #: must fail HERE with the cause, not inside a window kernel.
        #: The DataStream API leaves it off — mixing is valid when
        #: nothing downstream uses event time.
        self._require_consistent_time = require_consistent_time
        self._timed: Optional[bool] = None

    def process_batch(self, batch, input_index=0):
        if self._require_consistent_time:
            timed = batch.has_timestamps
            if self._timed is None:
                self._timed = timed
            elif timed != self._timed:
                raise RuntimeError(
                    "union inputs disagree on event time: some carry "
                    "timestamps and some do not — assign timestamps on "
                    "every branch (or none)")
        return [batch]


class SinkOperator(Operator):
    """Owns the sink lifecycle: open on task start, close on drain
    (reference: Sink V2 writer lifecycle)."""

    name = "sink"

    def __init__(self, sink):
        self.sink = sink

    def open(self, ctx):
        self.sink.open(ctx.operator_index)

    def process_batch(self, batch, input_index=0):
        with flight.span("sink.write") as span:
            self.sink.write(batch)
            span.work = len(batch)
        return []

    def snapshot_state(self):
        # sinks with writer state (e.g. KafkaSink's round-robin cursor)
        # participate in checkpoints (reference: SinkWriter state)
        snap = getattr(self.sink, "snapshot_state", None)
        return snap() if snap else None

    def restore_state(self, state, key_group_filter=None):
        restore = getattr(self.sink, "restore_state", None)
        if restore:
            restore(state)

    def close(self):
        self.sink.close()
        return []

    def dispose(self):
        self.sink.close()
