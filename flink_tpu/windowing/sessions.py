"""Session windows: merging windows with device accumulators.

reference semantics: EventTimeSessionWindows + MergingWindowSet
(streaming/runtime/operators/windowing/WindowOperator.java:159-162 splits
merge *metadata* from merged *state*; MergingWindowSet tracks interval merges,
windowMergingState merges namespaces). The TPU re-design keeps exactly that
split:

- **Host**: per-key sorted interval lists ``key -> [(start, end, sid)]``
  (tiny per key), a lazy fire heap, and a session-id allocator — factored
  into :class:`flink_tpu.windowing.session_meta.SessionIntervalSet`, shared
  with the mesh-sharded engine.
- **Device**: one accumulator slot per live session. Batch-local
  sessionization is one sweep (grouped by key in a hash pass, or sorted
  and gap-scanned where a key's timestamps step backwards); record
  values scatter straight into their final session slot; merging two
  sessions is a batched
  ``acc.at[dst].op(acc[src])`` scatter (duplicate dst allowed — scatter
  reduces), then the absorbed slots reset to identity.

A session [start, end) fires when watermark >= end - 1 where
end = last_event_ts + gap. Extensions/merges invalidate heap entries lazily
(entries carry their sid+end; stale ones are skipped on pop).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.ops.segment_ops import pad_bucket_size, pad_i32
from flink_tpu.state.slot_table import SlotTable
from flink_tpu.stateplane import flat_merge_pairs
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.windowing.session_meta import MergeGroup, make_session_meta
from flink_tpu.windowing.windower import WINDOW_END_FIELD, WINDOW_START_FIELD


def _merge_jit(agg: AggregateFunction):
    """acc[dst] op= acc[src] for arrays of (dst, src), then reset src slots."""
    return flat_merge_pairs(agg.leaves)


class SessionWindower:
    """Keyed session windows over one shard (single device)."""

    def __init__(
        self,
        gap: int,
        agg: AggregateFunction,
        capacity: int = 1 << 16,
        max_parallelism: int = 128,
        allowed_lateness: int = 0,
        spill: dict = None,
    ) -> None:
        self.gap = int(gap)
        self.agg = agg
        # Late records within the allowance start a NEW session (emitted as an
        # additional partial result) since fired sessions are freed eagerly;
        # records beyond the allowance are dropped.
        self.allowed_lateness = int(allowed_lateness)
        spill_kwargs = dict(spill or {})
        if spill_kwargs.get("max_device_slots"):
            # sessions are one row per namespace (sid) — the paged spill
            # layout moves eviction cohorts instead of per-session
            # entries (reference: RocksDB block granularity;
            # slot_table.py spill_layout="pages")
            spill_kwargs.setdefault("spill_layout", "pages")
        if spill_kwargs.get("spill_layout", "pages") == "pages":
            # this windower frees by SLOT (free_rows /
            # free_index_only_slots) — skip the per-namespace registry,
            # which costs O(sessions) Python per batch at one row per
            # sid. An explicit spill_layout="namespaces" keeps the
            # registry: its eviction path walks it.
            spill_kwargs.setdefault("track_namespaces", False)
        self.table = SlotTable(agg, capacity=capacity,
                               max_parallelism=max_parallelism,
                               **spill_kwargs)
        #: session-interval metadata: the native C sweep when compiled,
        #: else the pure-Python plane (bit-identical fires/snapshots)
        self.meta = make_session_meta(self.gap, self.allowed_lateness)
        #: batches ingested: the flight recorder's batch id
        self._flight_batch = 0

    @property
    def late_records_dropped(self) -> int:
        return self.meta.late_records_dropped

    @property
    def max_fired_watermark(self) -> int:
        return self.meta.max_fired_watermark

    @property
    def sessions(self):
        return self.meta.sessions

    def spill_counters(self):
        """Paged spill traffic (pages/rows evicted+reloaded, rows split
        on reload); zeros when the table is unbounded."""
        return self.table.spill_counters()

    # ---------------------------------------------------------- point query

    def query_sessions_batch(self, key_ids):
        """Batched point lookup: {session_end -> result columns} per
        requested key. The keys' live sessions come from host metadata;
        their accumulators are read through ONE gather kernel + ONE
        device read for the whole batch (SlotTable.query_batch_pairs) —
        spilled sessions answer from the page tier, read-only."""
        key_ids = np.asarray(key_ids, dtype=np.int64)
        n = len(key_ids)
        results = [dict() for _ in range(n)]
        rows: List[Tuple[int, int, int]] = []  # (request row, sid, end)
        for r in range(n):
            for _start, end, sid in self.meta.sessions.get(
                    int(key_ids[r]), []):
                rows.append((r, int(sid), int(end)))
        if not rows:
            return results
        rr = np.asarray([t[0] for t in rows], dtype=np.int64)
        sids = np.asarray([t[1] for t in rows], dtype=np.int64)
        found, leaves = self.table.query_batch_pairs(key_ids[rr], sids)
        finished = self.agg.finish(tuple(leaves))
        cols = {name: np.asarray(col) for name, col in finished.items()}
        for j, (r, _sid, end) in enumerate(rows):
            if found[j]:
                results[r][end] = {name: col[j].item()
                                   for name, col in cols.items()}
        return results

    def query_sessions(self, key_id: int):
        """Single-key form — a batch of one (same contract as
        MeshSessionEngine.query_sessions)."""
        return self.query_sessions_batch(
            np.asarray([key_id], dtype=np.int64))[0]

    # ---------------------------------------------------------------- ingest

    def process_batch(self, batch: RecordBatch) -> None:
        n = len(batch)
        if n == 0:
            return
        self._flight_batch += 1
        with flight.ingest_span(self._flight_batch) as ingest:
            ingest.work = n
            self._ingest(batch)

    def _ingest(self, batch: RecordBatch) -> None:
        ts = np.asarray(batch.timestamps, dtype=np.int64)
        keys = np.asarray(batch.key_ids, dtype=np.int64)

        with flight.span("prep.meta_sweep") as sweep:
            opened = self.meta.sid_watermark
            res = self.meta.absorb_batch_ex(keys, ts, want_fresh=False)
            sweep.work = self.meta.sid_watermark - opened
        sess_key, sess_sid = res.sess_key, res.sess_sid
        rec_sess = res.rec_sess
        with flight.span("session.merge") as merge:
            for g in res.groups:
                self._run_merge_group(g)
                merge.work += len(g.absorbed_sids)

        live = None
        if res.n_stale:
            # stale-on-arrival sessions: their records go to slot 0
            live = sess_sid >= 0
            sess_counts = np.bincount(rec_sess, minlength=len(sess_key))
            self.meta.late_records_dropped += int(
                sess_counts[~live].sum())
        # ONE vectorized lookup for all session slots, then one gather
        # per record; the native metadata plane's folded slots skip the
        # state-table hash probe for sessions whose fold is still valid
        with flight.span("prep.resolve") as resolve:
            inserted = self.table.index.pairs_inserted
            if live is None:
                slot_of_sess = self._resolve_sessions(
                    sess_key, sess_sid, res.slot_hint, res.meta_row)
            else:
                slot_of_sess = np.zeros(len(sess_key), dtype=np.int32)
                if live.any():
                    slot_of_sess[live] = self._resolve_sessions(
                        sess_key[live], sess_sid[live],
                        None if res.slot_hint is None
                        else res.slot_hint[live],
                        None if res.meta_row is None
                        else res.meta_row[live])
            resolve.work = self.table.index.pairs_inserted - inserted
            rec_slots = slot_of_sess.take(rec_sess)
        with flight.span("prep.stage"):
            values = self.agg.map_input(batch)
        self.table.scatter(rec_slots, values)

    def _resolve_sessions(self, keys, sids, hints, rows) -> np.ndarray:
        """Slots of live sessions, folded back into their metadata rows
        for the next batch's resolve."""
        slots = self.table.lookup_or_insert(keys, sids, hints=hints)
        self.meta.note_slots(keys, sids, slots, rows=rows)
        return slots

    def _run_merge_group(self, g: MergeGroup) -> None:
        """Resolve a chain-free merge group's slots and move accumulators
        in one kernel, then free the absorbed host slots (their device
        slots were reset by the kernel)."""
        dk = np.asarray(g.keys_dst, dtype=np.int64)
        ds = np.asarray(g.sids_dst, dtype=np.int64)
        sk = np.asarray(g.keys_src, dtype=np.int64)
        ss = np.asarray(g.sids_src, dtype=np.int64)
        # ONE combined lookup: with a spill tier, a second lookup could
        # evict slots the first just resolved — dst and src must be
        # resident simultaneously for the merge kernel
        m = len(dk)
        both = self.table.lookup_or_insert(
            np.concatenate([dk, sk]), np.concatenate([ds, ss]))
        dst_slots, src_slots = both[:m], both[m:]
        size = pad_bucket_size(len(dst_slots))
        self.table.mark_dirty(dst_slots)
        self.table.mark_dirty(src_slots)
        self.table.accs = _merge_jit(self.agg)(
            self.table.accs,
            pad_i32(dst_slots, size, fill=0),
            pad_i32(src_slots, size, fill=0))
        # absorbed host slots are only reusable once their values have
        # moved (the merge kernel already reset the device slots); the
        # slots are in hand, so the free needs no registry walk
        self.table.free_index_only_slots(src_slots, g.absorbed_sids)

    # ------------------------------------------------------------------ fire

    #: fires may be dispatched async (see on_watermark(async_ok=True))
    supports_async_fires = True

    def on_watermark(self, watermark: int,
                     async_ok: bool = False) -> List[RecordBatch]:
        with flight.fire_span(watermark) as fire:
            staged = self.table.fire_matrix_bytes
            out = self._fire_closed(watermark, async_ok)
            fire.work = self.table.fire_matrix_bytes - staged
        return out

    def _fire_closed(self, watermark: int, async_ok: bool) -> List:
        """Fire and free every session the watermark closed."""
        # shard 0: a single device is a mesh of one
        with flight.span("fire.shard", shard=0):
            pop = self.meta.pop_fired_ex(watermark)
        fired_keys, fired_starts = pop.keys, pop.starts
        fired_ends, fired_sids = pop.ends, pop.sids
        if not len(fired_keys):
            return []
        total = len(fired_keys)
        # with a bounded device table, a mass fire (e.g. end of stream) can
        # exceed what fits resident at once — fire in budget-sized chunks,
        # freeing each chunk's sessions before resolving the next
        chunk = total
        if self.table.max_device_slots:
            chunk = max(self.table.max_device_slots // 2, 1024)
        out: List[RecordBatch] = []
        for a in range(0, total, chunk):
            b = min(a + chunk, total)
            with flight.span("fire.shard", shard=0) as resolve:
                fired_slots = self.table.lookup_or_insert(
                    np.asarray(fired_keys[a:b], dtype=np.int64),
                    np.asarray(fired_sids[a:b], dtype=np.int64),
                    hints=(None if pop.slot_hint is None
                           else pop.slot_hint[a:b]))
                resolve.work = b - a
            matrix = np.asarray(fired_slots, dtype=np.int32)[:, None]
            cols = {
                KEY_ID_FIELD: np.asarray(fired_keys[a:b], dtype=np.int64),
                WINDOW_START_FIELD: np.asarray(fired_starts[a:b],
                                               dtype=np.int64),
                WINDOW_END_FIELD: np.asarray(fired_ends[a:b],
                                             dtype=np.int64),
                TIMESTAMP_FIELD: np.asarray(fired_ends[a:b],
                                            dtype=np.int64) - 1,
            }
            if async_ok:
                # dispatch the fire and free the sessions immediately —
                # the reset is device-queue-ordered BEHIND the fire
                # kernel, so the deferred host read never races it
                pending = self.table.fire_async(matrix, None)
                self._free_fired(fired_slots, fired_sids[a:b])
                if pending is None:
                    continue
                inner = pending.build

                def build(host, inner=inner, cols=cols):
                    _, results = inner(host)
                    full = dict(cols)
                    full.update(results)
                    return RecordBatch(full)

                pending.build = build
                out.append(pending)
                continue
            results = self.table.fire(matrix)
            self._free_fired(fired_slots, fired_sids[a:b])
            cols.update(results)
            out.append(RecordBatch(cols))
        return out

    def _free_fired(self, slots, sids) -> None:
        with flight.span("slice.retire") as retire:
            self.table.free_rows(slots, sids)
            retire.work = len(slots)

    # -------------------------------------------------------------- snapshot

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        if mode == "delta":
            table = self.table.snapshot_delta()
        else:
            table = self.table.snapshot(reset_dirty=(mode != "savepoint"))
        return {"table": table, **self.meta.snapshot()}

    def restore(self, snap: Dict[str, object], key_group_filter=None) -> None:
        if "table" in snap:
            self.table.restore(snap["table"], key_group_filter=key_group_filter)
        self.meta.restore(snap, key_group_filter=key_group_filter,
                          max_parallelism=self.table.max_parallelism)
