"""Slice-shared window engine (host-side control, device-side math).

Combines a ``WindowAssigner`` (timestamps -> slices, windows -> slices) with a
``SlotTable`` (keyed per-slice accumulators on device). This is the semantic
core of the reference's WindowOperator + WindowAggOperator
(reference: streaming/runtime/operators/windowing/WindowOperator.java:293,450,575;
flink-table-runtime/.../window/tvf/common/WindowAggOperator.java:216,232):

- ``process_batch``: vectorized slice assignment, late-record drop, slot
  lookup, one scatter per accumulator leaf.
- ``on_watermark``: fire every pending window with end-1 <= watermark —
  build the [windows*keys, slices_per_window] slot matrix on host, one
  gather+merge+finish kernel on device, then free exhausted slices
  (the reference frees per-window state in clearAllState; here a slice is
  freed after its last participating window fires).

Window lifecycle metadata lives in ``SliceBookkeeper`` (shared with the
mesh-sharded engine). Timers for aligned windows are implicit — window ends
are known at slice creation, replacing the reference's per-(key, window)
timer registrations (reference: InternalTimerServiceImpl.java:314).
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.runtime.local_agg import is_partial_batch, partial_leaf_values
from flink_tpu.state.slot_table import SlotTable
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.windowing.assigners import WindowAssigner
from flink_tpu.windowing.bookkeeping import SliceBookkeeper

WINDOW_START_FIELD = "window_start"
WINDOW_END_FIELD = "window_end"


def compose_windows(assigner, agg, slice_vals: Dict[int, tuple]
                    ) -> Dict[int, Dict[str, float]]:
    """Slice sharing, host side: one key's ``{slice_end -> per-leaf
    1-element raw accumulator arrays}`` composed into ``{window_end ->
    finished result columns}`` (a sliding window's value = merge of its
    k slices). The ONE copy of the serving-path compose loop —
    ``SliceSharedWindower.query_windows_batch`` and
    ``MeshWindowEngine.query_batch`` read through it, so window/slice
    mapping semantics cannot drift between layouts."""
    from flink_tpu.ops.segment_ops import HOST_COMBINE

    leaves = agg.leaves
    windows = sorted({
        int(w) for se in slice_vals
        for w in assigner.window_ends_for_slice(se)})
    out: Dict[int, Dict[str, float]] = {}
    for w in windows:
        acc = [np.full(1, l.identity, dtype=l.dtype) for l in leaves]
        for se in assigner.slice_ends_for_window(w):
            v = slice_vals.get(int(se))
            if v is None:
                continue
            acc = [HOST_COMBINE[l.reduce](a, x)
                   for a, x, l in zip(acc, v, leaves)]
        finished = agg.finish(tuple(acc))
        out[w] = {name: np.asarray(col).item()
                  for name, col in finished.items()}
    return out


class SliceSharedWindower:
    """Windowed keyed aggregation over one key-group range / device shard."""

    #: on_watermark(async_ok=True) may return PendingFire handles (the
    #: hosting operator/executor owns harvest + watermark holdback)
    supports_async_fires = True
    #: per-engine batch sequence: the flight recorder's batch_id (the
    #: windower numbers its batches as the mesh engines do)
    _flight_batch = 0

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: AggregateFunction,
        capacity: int = 1 << 16,
        max_parallelism: int = 128,
        allowed_lateness: int = 0,
        spill: dict = None,
        fire_projector=None,
    ) -> None:
        self.assigner = assigner
        self.agg = agg
        self.table = SlotTable(agg, capacity=capacity,
                               max_parallelism=max_parallelism,
                               **(spill or {}))
        self.book = SliceBookkeeper(assigner, allowed_lateness)
        #: optional device-side reduction of each fired window's rows
        #: before host transfer (flink_tpu.windowing.fire_projectors)
        self.fire_projector = fire_projector

    @property
    def late_records_dropped(self) -> int:
        return self.book.late_records_dropped

    # --------------------------------------------------------------- ingest

    def process_batch(self, batch: RecordBatch) -> None:
        n = len(batch)
        if n == 0:
            return
        self._flight_batch += 1
        with flight.ingest_span(self._flight_batch) as ingest:
            ingest.work = n
            self._ingest(batch)

    def _values_of(self, batch: RecordBatch):
        """``(values, valued)`` of a batch for the scatter: explicit
        per-leaf partials for locally pre-aggregated rows (two-phase
        agg), else the aggregate's mapped raw inputs."""
        with flight.span("prep.stage"):
            if is_partial_batch(batch):
                return partial_leaf_values(batch, self.agg), True
            return self.agg.map_input(batch), False

    def _ingest(self, batch: RecordBatch) -> None:
        fused = getattr(self.table, "ingest_indices", None)
        if fused is not None:
            with flight.span("prep.resolve"):
                out = fused(batch.key_ids, batch.timestamps,
                            self.assigner.offset, self.assigner.slice_width)
                if out is not None:
                    flat, uniq, sinv = out
                    self._register_fused(uniq, sinv)
            if out is not None:
                values, valued = self._values_of(batch)
                self.table.scatter_flat(flat, values, valued=valued)
                return
        sweep = getattr(self.table, "resolve_slices", None)
        if sweep is not None:
            # the table's one native sweep over keys and timestamps,
            # where the table and the batch allow it (no late record
            # among them); else the path below, with the same results
            with flight.span("prep.resolve") as resolve:
                swept = sweep(batch.key_ids, batch.timestamps,
                              self.assigner.offset,
                              self.assigner.slice_width,
                              self.book.oldest_live_slice_end())
                if swept is not None:
                    slots, uniq, resolve.work = swept
                    self._register(uniq, batch.timestamps)
                    flight.instant("resolve.sweep", work=len(batch))
            if swept is not None:
                values, valued = self._values_of(batch)
                if valued:
                    self.table.scatter_valued(slots, values)
                else:
                    self.table.scatter(slots, values)
                return
        with flight.span("prep.resolve"):
            slice_ends = self.assigner.assign_slice_ends(batch.timestamps)
            live = self.book.live_mask(slice_ends)
            if live is not None:
                slice_ends = slice_ends[live]
                batch = batch.filter(live)
                if len(batch) == 0:
                    return
            # one O(n) pass finds the distinct slice ends + inverse;
            # shared by the bookkeeper AND the state table so neither
            # re-sorts the batch
            plan = self.assigner.slice_plan(slice_ends)
            self._register(plan[0], batch.timestamps)
        accepts_plan = getattr(self.table, "accepts_slice_plan", False)
        kw = {"slice_plan": plan} if accepts_plan else {}
        values, valued = self._values_of(batch)
        if valued:
            self.table.upsert_valued(batch.key_ids, slice_ends, values, **kw)
        else:
            self.table.upsert(batch.key_ids, slice_ends, values, **kw)

    def _register(self, uniq: np.ndarray, timestamps: np.ndarray) -> None:
        """The batch's distinct slice ends to the bookkeeper and, where
        one of them scheduled a window that has fired, the batch's
        records behind the newest fired window's end as a
        ``late.records`` instant: a window's end is its last slice's, so
        those are the records in slices that hold a fired window. One
        pass over the timestamps, taken by a batch with a late record
        and by no other."""
        if self.book.register_slices(uniq, uniq=uniq):
            flight.instant("late.records", work=int(np.count_nonzero(
                timestamps < self.book.max_fired_end)))

    def _register_fused(self, uniq: np.ndarray, sinv: np.ndarray) -> None:
        """Bookkeeping for the fused ingest path. Late records are NOT
        filtered out of the scatter (unlike the numpy path): they land in
        slices whose every window is already past retention, so those
        rows are never gathered by a fire and the cleanup heap frees them
        on the next watermark — observable behavior (results + the
        late-drop metric) matches the filtering path without a second
        pass over the batch."""
        book = self.book
        if book.watermark > -(1 << 61):
            last = self.assigner.last_window_ends(uniq)
            late = last - 1 + book.allowed_lateness <= book.watermark
            if late.any():
                book.late_records_dropped += int(
                    np.bincount(sinv, minlength=len(uniq))[late].sum())
        book.register_slices(uniq, uniq=uniq)

    # ----------------------------------------------------------------- fire

    def on_watermark(self, watermark: int,
                     async_ok: bool = False) -> List[RecordBatch]:
        """Fire all windows with end - 1 <= watermark. Returns result
        batches — or, with ``async_ok``, PendingFire handles whose harvest
        yields the batch (the caller owns watermark holdback; see
        flink_tpu.runtime.pending). Slice frees dispatched after the fires
        are device-queue-ordered behind them, so deferring the host read
        never races the reset."""
        out: List[RecordBatch] = []
        with flight.fire_span(watermark) as fire:
            staged = self.table.fire_matrix_bytes
            while True:
                w_end = self.book.next_window(watermark)
                if w_end is None:
                    break
                if w_end <= self.book.max_fired_end:
                    flight.instant("fire.late", work=1)
                batch = self._fire_window(w_end, async_ok=async_ok)
                if batch is not None and (not hasattr(batch, "__len__")
                                          or len(batch) > 0):
                    out.append(batch)
                self.book.mark_fired(w_end)
            expired = self.book.expired_slices(watermark)
            if expired:
                with flight.span("slice.retire", faults=True) as retire:
                    retire.work = self.table.free_namespaces(expired)
            fire.work = self.table.fire_matrix_bytes - staged
        return out

    def _wrap_pending(self, pending, window_end: int):
        """Compose the table-level PendingFire (keys, result cols) with the
        window-metadata column assembly."""
        if pending is None:
            return None
        inner = pending.build
        w_start = self.assigner.window_start(window_end)

        def build(host):
            keys, results = inner(host)
            m = len(keys)
            if m == 0:
                return None
            cols = {
                KEY_ID_FIELD: keys,
                WINDOW_START_FIELD: np.full(m, w_start, dtype=np.int64),
                WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
                TIMESTAMP_FIELD: np.full(m, window_end - 1, dtype=np.int64),
            }
            cols.update(results)
            return RecordBatch(cols)

        pending.build = build
        return pending

    def _fire_window(self, window_end: int,
                     async_ok: bool = False) -> Optional[RecordBatch]:
        slice_ends = self.assigner.slice_ends_for_window(window_end)
        if any(int(se) in self.table.spill for se in slice_ends):
            # hybrid fire: resident slices merge on device, spilled slices
            # merge on host — no residency requirement, so the device
            # budget is independent of the window's slice count
            keys, results = self.table.fire_hybrid(
                [int(se) for se in slice_ends])
            if len(keys) == 0:
                return None
            if self.fire_projector is not None:
                keys, results = self.fire_projector.project_host(
                    keys, results)
            m = len(keys)
            cols = {
                KEY_ID_FIELD: keys,
                WINDOW_START_FIELD: np.full(
                    m, self.assigner.window_start(window_end),
                    dtype=np.int64),
                WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
                TIMESTAMP_FIELD: np.full(m, window_end - 1, dtype=np.int64),
            }
            cols.update(results)
            return RecordBatch(cols)
        k = len(slice_ends)
        # shard 0: a single device is a mesh of one (the mesh engines
        # record one fire.shard per shard's resolve)
        with flight.span("fire.shard", shard=0) as resolve:
            if k == 1:
                # single-slice (tumbling) fast path: no cross-slice unique
                slots = self.table.slots_for_namespace(slice_ends[0])
                if len(slots) == 0:
                    return None
                keys = self.table.keys_of_slots(slots)
                matrix = slots[:, None].astype(np.int32)
                resolve.work = len(slots)
            else:
                # work: the cells this call resolved — the slice that
                # entered where the matrix was carried from the last
                # window, every live cell of the window where it was not
                keys, matrix, resolve.work = self.table.build_slice_matrix(
                    [int(se) for se in slice_ends])
                if keys is None:
                    return None
        if self.fire_projector is not None:
            if async_ok:
                return self._wrap_pending(
                    self.table.fire_projected_async(
                        matrix, keys, self.fire_projector), window_end)
            keys, results = self.table.fire_projected(
                matrix, keys, self.fire_projector)
        else:
            if async_ok:
                return self._wrap_pending(
                    self.table.fire_async(matrix, keys), window_end)
            results = self.table.fire(matrix)
        m = len(keys)
        cols = {
            KEY_ID_FIELD: keys,
            WINDOW_START_FIELD: np.full(
                m, self.assigner.window_start(window_end), dtype=np.int64),
            WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
            TIMESTAMP_FIELD: np.full(m, window_end - 1, dtype=np.int64),
        }
        cols.update(results)
        return RecordBatch(cols)

    # ---------------------------------------------------------- point query

    def query_windows(self, key_id: int) -> Dict[int, Dict[str, float]]:
        """Queryable-state point lookup: {window_end -> result columns} —
        same contract as MeshWindowEngine.query_windows."""
        return self.table.query_windows(key_id, self.assigner)

    def query_windows_batch(self, key_ids) -> List[Dict[int, Dict[str, float]]]:
        """Batched point lookup: one result dict per requested key, the
        whole batch served by ONE gather kernel + ONE device read
        (``SlotTable.query_batch_pairs`` over keys x live slices) —
        the serving plane's per-request-batch cost model."""
        key_ids = np.asarray(key_ids, dtype=np.int64)
        n = len(key_ids)
        if n == 0:
            return []
        if not hasattr(self.table, "query_batch_pairs"):
            # pane/ring layout: no pair-gather primitive — per key
            return [self.query_windows(int(k)) for k in key_ids]
        live_ns = np.asarray([int(x) for x in self.table.namespaces],
                             dtype=np.int64)
        if len(live_ns) == 0:
            return [{} for _ in range(n)]
        pair_keys = np.repeat(key_ids, len(live_ns))
        pair_ns = np.tile(live_ns, n)
        found, leaves = self.table.query_batch_pairs(pair_keys, pair_ns)
        agg = self.agg
        results: List[Dict[int, Dict[str, float]]] = []
        k = len(live_ns)
        for r in range(n):
            base = r * k
            sv = {int(pair_ns[base + j]):
                  tuple(l[base + j:base + j + 1] for l in leaves)
                  for j in range(k) if found[base + j]}
            results.append(compose_windows(self.assigner, agg, sv)
                           if sv else {})
        return results

    # ------------------------------------------------------------- snapshot

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        """mode: "full" (new incremental base), "delta" (dirty rows only),
        "savepoint" (full, but preserves dirty tracking — a side artifact
        must not change what the next delta checkpoint contains)."""
        if mode == "delta":
            table = self.table.snapshot_delta()
        else:
            table = self.table.snapshot(reset_dirty=(mode != "savepoint"))
        return {
            "table": table,
            **self.book.snapshot(),
        }

    def restore(self, snap: Dict[str, object], key_group_filter=None) -> None:
        self.table.restore(snap["table"], key_group_filter=key_group_filter)
        self.book.restore(snap)


class PaneWindower(SliceSharedWindower):
    """SliceSharedWindower over the pane/ring layout (state/pane_table.py):
    same external contract, but fires are pure device reductions over ring
    rows — no host-built slot matrix, no per-fire host->device transfer —
    and freeing an expired slice is one index-free row reset.

    With ``preagg`` (latency.fire-deadline tier, default on), the layout
    additionally maintains a RUNNING PARTIAL ring row per pending window,
    combined at absorb: each record scatters into its pane AND into every
    pending window containing that pane, in the same single flat-index
    dispatch. A watermark fire then gathers exactly ONE ring row — the
    pane that closes — instead of merging the window's k slice rows (the
    full-window harvest, which remains the fallback for windows without a
    maintained partial and for ``preagg=False``). Partials are DERIVED
    state: snapshots carry only the panes, restore/compaction refold the
    pending windows' rows from them, and a late re-registration under
    allowed lateness refolds too. Float sums fold in record order rather
    than per-slice order, so f32 results can differ from the full harvest
    in the last ulp (count/min/max and integer-valued sums are exact).

    Opt-in via state.window-layout=panes for aligned (non-merging)
    assigners without a spill tier at parallelism 1 ('auto' resolves to
    the slot layout until hardware measurements land); the slot layout
    stays the engine for sessions, spill, and the mesh.
    """

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: AggregateFunction,
        capacity: int = 1 << 16,
        max_parallelism: int = 128,
        allowed_lateness: int = 0,
        fire_projector=None,
        memory=None,
        preagg: bool = True,
    ) -> None:
        from flink_tpu.state.pane_table import PaneTable

        self.assigner = assigner
        self.agg = agg
        # pre-aggregation only pays when windows SHARE panes: for
        # single-slice (tumbling) windows the partial would be an exact
        # duplicate of the pane — double the scatter volume and ring
        # rows for a fire that already gathers one row (k == 1)
        self._preagg = bool(preagg) and int(
            getattr(assigner, "slices_per_window", 1)) > 1
        self.table = PaneTable(agg, capacity=capacity,
                               max_parallelism=max_parallelism,
                               fire_projector=fire_projector,
                               memory=memory,
                               slices_for_window=(
                                   assigner.slice_ends_for_window
                                   if self._preagg else None))
        self.book = SliceBookkeeper(assigner, allowed_lateness)
        self.fire_projector = fire_projector

    # --------------------------------------------------------------- ingest

    def _ingest(self, batch: RecordBatch) -> None:
        if not self._preagg:
            return super()._ingest(batch)
        table = self.table
        with flight.span("prep.resolve"):
            flat = uniq = sinv = None
            fused = getattr(table, "ingest_indices", None)
            if fused is not None:
                out = fused(batch.key_ids, batch.timestamps,
                            self.assigner.offset, self.assigner.slice_width)
                if out is not None:
                    flat, uniq, sinv = out
                    self._register_fused(uniq, sinv)
            if flat is None:
                slice_ends = self.assigner.assign_slice_ends(
                    batch.timestamps)
                live = self.book.live_mask(slice_ends)
                if live is not None:
                    slice_ends = slice_ends[live]
                    batch = batch.filter(live)
                    if len(batch) == 0:
                        return
                plan = self.assigner.slice_plan(slice_ends)
                self.book.register_slices(slice_ends, uniq=plan[0])
                uniq, sinv = plan
                flat = table._flat_indices(batch.key_ids, slice_ends, plan)
            # combine-on-absorb: fold each record into its pending
            # windows' partial rows in the SAME scatter. Only windows
            # that already have a row get direct folds — everything else
            # (new windows, late re-registrations, restored/compacted
            # state) is refolded from the authoritative panes right after.
            pending = self.book.pending_windows()
            wins = [[w for w in self.assigner.window_ends_for_slice(int(se))
                     if w in pending and table.has_window_partial(w)]
                    for se in uniq.tolist()]
            win = table.window_flat(flat % np.int32(table.capacity), sinv,
                                    wins)
        values, valued = self._values_of(batch)
        table.scatter_combined(flat, win, values, valued=valued)
        table.rebuild_window_partials(pending)

    # ----------------------------------------------------------------- fire

    def _fire_window(self, window_end: int,
                     async_ok: bool = False) -> Optional[RecordBatch]:
        if self._preagg and self.table.has_window_partial(window_end):
            # delta harvest: ONE ring row — the pane that closes
            if async_ok:
                return self._wrap_pending(
                    self.table.fire_partial_async(window_end), window_end)
            keys, results = self.table.fire_partial(window_end)
            return self._assemble(window_end, keys, results)
        # full-window harvest (fallback: preagg off, or no partial row)
        slice_ends = [int(se)
                      for se in self.assigner.slice_ends_for_window(
                          window_end)]
        if async_ok:
            return self._wrap_pending(
                self.table.fire_window_async(slice_ends), window_end)
        keys, results = self.table.fire_window(slice_ends)
        return self._assemble(window_end, keys, results)

    def _assemble(self, window_end: int, keys,
                  results) -> Optional[RecordBatch]:
        if len(keys) == 0:
            return None
        m = len(keys)
        cols = {
            KEY_ID_FIELD: keys,
            WINDOW_START_FIELD: np.full(
                m, self.assigner.window_start(window_end), dtype=np.int64),
            WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
            TIMESTAMP_FIELD: np.full(m, window_end - 1, dtype=np.int64),
        }
        cols.update(results)
        return RecordBatch(cols)

    # ------------------------------------------------------------- snapshot

    def restore(self, snap, key_group_filter=None) -> None:
        if self._preagg:
            # partial rows are derived: drop any stale ones, land the
            # panes, then refold the pending windows' partials
            self.table.clear_window_rows()
        super().restore(snap, key_group_filter=key_group_filter)
        if self._preagg:
            self.table.rebuild_window_partials(
                self.book.pending_windows())
