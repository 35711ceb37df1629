"""Unwindowed GROUP BY — running keyed aggregation with changelog emission.

reference: flink-table-runtime/.../aggregate/GroupAggFunction.java:85
(processElement reads accState.value(), folds one record, writes back, and
emits retract+insert pairs downstream; `firstRow` decides INSERT vs
UPDATE_BEFORE/UPDATE_AFTER, and a row-count accumulator decides DELETE) and
its MiniBatch variant (MiniBatchGroupAggFunction.java:163 finishBundle).

Re-design: the per-key accumulators live in the device SlotTable under a
single namespace (namespace 0 — there is no window dimension); a micro-batch
folds in with ONE scatter kernel per accumulator leaf. Emission is a
changelog (RowKind column, flink_tpu.core.records.ROWKIND_FIELD):

- first value of a key             -> INSERT
- updated value                    -> UPDATE_BEFORE(prev) + UPDATE_AFTER(new)
- row count falls to zero          -> DELETE(prev)

The UPDATE_BEFORE image is the value at the key's previous *emission*
(tracked in host arrays), so no extra device read is needed — exactly the
reference's contract, where the retraction carries the previously emitted
row. Retraction INPUT (a second-level aggregate over an updating stream) is
consumed by folding each row's contribution with its changelog sign in one
signed scatter; this requires every accumulator leaf to be additive
(``AggregateFunction.retractable`` — COUNT/SUM/AVG yes, MAX/MIN no, like
the reference's retractable agg function family). As the reference's
GroupAggFunction does without state retention, a key whose new value
equals the one it last emitted emits nothing.

An aggregate whose accumulator takes its input's type (SQL's ``MAX(x)``,
``AVG(x)``: ``dtype=None``) is bound to the first batch's column types,
and the table is made then. An exact 64-bit one
(:class:`~flink_tpu.windowing.aggregates.Int64Aggregate`: BIGINT MAX /
MIN / AVG) folds per-slot partials the host sums, maxes or mins in int64
through one device program (``stateplane.families.wide_fold``), which
returns the parts' new words: the host finishes them (``finish_host``).
A narrow leaf the device would carry at half its width is refused.

A batch's rows are first grouped by key id in one hash pass
(``slot_table.group_rows``, no sort): the slot lookup and the check of
the kept key columns run once per distinct key, and the partials are
taken per key through each row's group number.

Flight recorder: ``agg.fold`` (the batch into the table and the touched
keys' new values back; work: rows), ``agg.groups`` (the distinct keys
taken to the table; work: keys), ``agg.retract`` (retraction rows it
folded; work: rows), ``agg.emit`` (the changelog built on the host) and
``agg.changelog`` (rows emitted; work: rows).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from flink_tpu.core.records import (
    KEY_ID_FIELD,
    ROWKIND_DELETE,
    ROWKIND_FIELD,
    ROWKIND_INSERT,
    ROWKIND_UPDATE_AFTER,
    ROWKIND_UPDATE_BEFORE,
    TIMESTAMP_FIELD,
    RecordBatch,
    rowkind_signs,
)
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.runtime.operators import Operator
from flink_tpu.state.slot_table import SlotTable, group_rows
from flink_tpu.windowing.aggregates import (
    AggregateFunction,
    device_truncates,
)

_GLOBAL_NS = 0


class GroupAggOperator(Operator):
    name = "group_agg"

    def __init__(self, agg: AggregateFunction, key_field: str,
                 capacity: int = 1 << 16,
                 emit_on_watermark_only: bool = False,
                 generate_update_before: bool = True,
                 ttl_ms: Optional[int] = None, clock=None,
                 key_columns=()):
        self.agg = agg
        self.key_field = key_field
        #: the columns a composite key was combined from (``key_field``
        #: then holds their hash): each key's values are kept per slot,
        #: checked against every row that brings the key, and emitted
        self.key_columns = tuple(key_columns)
        self._key_cols: Dict[str, np.ndarray] = {}
        self.capacity = capacity
        #: True = suppress per-batch emission, emit one deduped changelog
        #: per watermark advance (MiniBatch-style emission)
        self.emit_on_watermark_only = emit_on_watermark_only
        #: False = upsert mode: UPDATE_AFTER only (no retraction images),
        #: DELETEs still emitted — for upsert-keyed sinks
        self.generate_update_before = generate_update_before
        #: idle-state retention: accumulators untouched for ttl_ms are
        #: dropped (slot freed, snapshot shrinks); a key arriving after
        #: expiry re-INSERTs — the reference's documented
        #: `table.exec.state.ttl` semantics (reference: StateTtlConfig +
        #: GroupAggFunction's stateRetentionTime cleanup timer). Silent
        #: drop, no DELETE emission, like the reference.
        self.ttl_ms = ttl_ms
        from flink_tpu.state.ttl import SweepGate, default_clock

        self._clock = clock or default_clock
        self._sweep_gate = SweepGate(ttl_ms) if ttl_ms else None
        self.table: Optional[SlotTable] = None
        self._ctx = None
        #: the input columns' types the aggregate was bound to
        self._input_dtypes: Dict[str, str] = {}
        self._fold_bucket = 0
        self._key_values: Dict[int, Any] = {}
        self._keys_hashed = False
        self._dirty: set = set()
        self._max_ts = 0
        # per-slot changelog bookkeeping (host; grown with the table)
        self._row_counts = np.zeros(0, dtype=np.int64)
        self._emitted_mask = np.zeros(0, dtype=bool)
        self._last_emitted: Dict[str, np.ndarray] = {}
        #: per-slot last-update processing-time stamp (-1 = free)
        self._last_update = np.zeros(0, dtype=np.int64)

    def open(self, ctx):
        self._ctx = ctx
        if self.agg.leaves is not None:
            self._make_table()

    def _bind(self, dtypes: Dict[str, np.dtype]) -> None:
        """The aggregate bound to the input's column types, and its
        table (once, at the first batch or restore)."""
        self._input_dtypes = {n: np.dtype(d).str for n, d in dtypes.items()}
        self.agg = self.agg.bind(dtypes)
        self._make_table()

    def _make_table(self) -> None:
        if not self.agg.wide:
            for leaf in self.agg.leaves:
                if device_truncates(leaf.dtype):
                    raise TypeError(
                        f"{type(self.agg).__name__} leaf {leaf.name!r} is "
                        f"{leaf.dtype}: the device (JAX without x64) "
                        "would carry it at half its width; an exact "
                        "64-bit aggregate is Int64Aggregate")
        mm = getattr(self._ctx, "memory_manager", None)
        self.table = SlotTable(
            self.agg, capacity=self.capacity,
            max_parallelism=getattr(self._ctx, "max_parallelism", 128),
            memory=(mm, f"{self.name}#{id(self):x}") if mm else None)

    def dispose(self):
        if self.table is not None:
            self.table.release_memory()

    # ------------------------------------------------------------- host state

    def _ensure_host_capacity(self, n: int) -> None:
        if n <= len(self._row_counts):
            return
        size = max(n, 2 * len(self._row_counts), 1024)
        grown = np.zeros(size, dtype=np.int64)
        grown[: len(self._row_counts)] = self._row_counts
        self._row_counts = grown
        mask = np.zeros(size, dtype=bool)
        mask[: len(self._emitted_mask)] = self._emitted_mask
        self._emitted_mask = mask
        stamps = np.full(size, -1, dtype=np.int64)
        stamps[: len(self._last_update)] = self._last_update
        self._last_update = stamps
        for store in (self._last_emitted, self._key_cols):
            for name, arr in store.items():
                g = np.zeros(size, dtype=arr.dtype)
                g[: len(arr)] = arr
                store[name] = g

    def _carry_keys(self, slots: np.ndarray, group: np.ndarray,
                    first: np.ndarray, batch: RecordBatch) -> None:
        """Each new key's column values kept at its slot; every row's
        checked against its group's first row, and that row against its
        key's kept values (two keys whose hash collides would share a
        slot: that is refused, never folded together). ``slots``: the
        batch's distinct keys' slots, ``group`` each row's key among
        them, ``first`` each key's first row."""
        new = self._row_counts[slots] == 0
        new &= ~self._emitted_mask[slots]
        for name in self.key_columns:
            v = np.asarray(batch[name])
            head = v[first]
            store = self._key_cols.get(name)
            if store is None:
                store = self._key_cols[name] = np.zeros(
                    len(self._row_counts), dtype=v.dtype)
            store[slots[new]] = head[new]
            if not (np.array_equal(head[group], v)
                    and np.array_equal(store[slots], head)):
                raise RuntimeError(
                    f"two GROUP BY keys of ({', '.join(self.key_columns)}) "
                    "hash to one key id: their groups would fold together")

    # --------------------------------------------------------------- TTL

    def _maybe_sweep_ttl(self) -> None:
        """Vectorized idle-state expiry: one masked scan per sweep
        interval (ttl/4, floor 1 ms) instead of the reference's
        per-key cleanup timers."""
        if not self.ttl_ms:
            return
        now = self._clock()
        if not self._sweep_gate.should_sweep(now):
            return
        n = len(self._last_update)
        if n == 0:
            return
        stamps = self._last_update
        expired = np.nonzero((stamps != -1)
                             & (now - stamps > self.ttl_ms))[0]
        if not len(expired):
            return
        if self._keys_hashed:
            for kid in self.table.keys_of_slots(expired).tolist():
                self._key_values.pop(int(kid), None)
        self.table.free_slots(expired)
        self._row_counts[expired] = 0
        self._emitted_mask[expired] = False
        stamps[expired] = -1
        if self._dirty:
            self._dirty.difference_update(expired.tolist())

    # ----------------------------------------------------------------- ingest

    def process_batch(self, batch: RecordBatch, input_index: int = 0
                      ) -> List[RecordBatch]:
        if len(batch) == 0:
            return []
        if self.table is None:
            self._bind({n: np.asarray(batch[n]).dtype
                        for n in batch.names()})
        if batch.has_timestamps:
            self._max_ts = max(self._max_ts, int(batch.timestamps.max()))
        with flight.span("agg.fold") as fold:
            # the batch's rows grouped by key: the table, the key check
            # and the partials see each key once
            group, first = group_rows(batch.key_ids)
            key_ids = np.asarray(batch.key_ids)[first]
            if self.key_field in batch.columns:
                keys = batch[self.key_field]
                if keys.dtype.kind not in "iu":
                    self._keys_hashed = True
                    for i, j in zip(key_ids.tolist(), first.tolist()):
                        self._key_values.setdefault(i, keys[j])
            slots = self.table.lookup_or_insert(
                key_ids, np.full(len(first), _GLOBAL_NS, dtype=np.int64))
            flight.instant("agg.groups", work=len(first))
            self._ensure_host_capacity(int(slots.max()) + 1)
            if self.key_columns:
                self._carry_keys(slots, group, first, batch)
            kinds = batch.row_kinds
            signs = None if kinds is None else rowkind_signs(
                np.asarray(kinds))
            if signs is not None and not (signs < 0).any():
                # an all-INSERT changelog folds as append-only input
                signs = None
            if signs is not None:
                if not self.agg.retractable:
                    raise ValueError(
                        "aggregate over an updating (retraction) input "
                        "requires retractable accumulators (COUNT/SUM/AVG); "
                        f"{type(self.agg).__name__} has MAX/MIN-style "
                        "leaves (reference: GroupAggFunction requires "
                        "retract() for update streams)")
                flight.instant("agg.retract", work=int((signs < 0).sum()))
            emit = not self.emit_on_watermark_only
            if self.agg.wide:
                uniq, results = self._fold_wide(slots, group, first, batch,
                                                signs)
            else:
                row_slots = slots[group]
                if signs is None:
                    # append-only input: the plain scatter path works for
                    # every aggregate, incl. MAX/MIN
                    self.table.scatter(row_slots, self.agg.map_input(batch))
                else:
                    self.table.scatter_signed(
                        row_slots, self.agg.map_input_signed(batch, signs))
                uniq = np.sort(slots)
                results = self._values(uniq) if emit else None
            counts = np.bincount(group, minlength=len(first))
            if signs is not None:
                counts -= 2 * np.bincount(group[signs < 0],
                                          minlength=len(first))
            self._row_counts[slots] += counts
            fold.work = len(batch)
        if self.ttl_ms:
            self._last_update[slots] = self._clock()
            self._maybe_sweep_ttl()
        if not emit:
            self._dirty.update(uniq.tolist())
            return []
        out = self._emit_slots(uniq, results)
        return [out] if out is not None else []

    def _fold_wide(self, slots: np.ndarray, group: np.ndarray,
                   first: np.ndarray, batch: RecordBatch, signs) -> tuple:
        """The exact 64-bit fold: per distinct key (``slots``; ``group``
        each row's key among them, ``first`` each key's first row), each
        part's partial summed, maxed or mined on the host in int64,
        combined with the table's words by one device program, whose new
        words come back (one harvest) and are finished on the host.
        Returns the distinct slots, ascending, and their result
        columns."""
        order = np.argsort(slots)
        parts = []
        for (_, r, dt), v in zip(self.agg.parts, self.agg.part_values(batch)):
            dt = np.dtype(dt)
            v = np.asarray(v, dtype=np.int64 if dt.kind in "iu"
                           else np.float64)
            if signs is not None:
                v = v * signs.astype(v.dtype)
            if r == "sum":
                part = np.zeros(len(first), dtype=v.dtype)
                np.add.at(part, group, v)
            else:
                part = v[first]
                {"max": np.maximum, "min": np.minimum}[r].at(part, group, v)
            parts.append(part[order])
        return self._fold_partials(slots[order], parts)

    def _fold_partials(self, uniq: np.ndarray, parts: List[np.ndarray]
                       ) -> tuple:
        """Each part's partial of the distinct slots ``uniq`` (ascending)
        combined with the table's words by ``wide_fold``; the new words'
        harvest finished on the host. Returns ``uniq`` and the result
        columns."""
        from flink_tpu.ops.segment_ops import sticky_bucket
        from flink_tpu.runtime.pending import PendingFire
        from flink_tpu.stateplane.families import wide_fold

        layout = tuple((r, dt) for _, r, dt in self.agg.parts)
        size = sticky_bucket(len(uniq), self._fold_bucket)
        self._fold_bucket = size
        n = len(uniq)
        pad_slots = np.full(size, self.table.capacity, dtype=np.int32)
        pad_slots[:n] = uniq
        fresh = np.zeros(size, dtype=bool)
        fresh[:n] = self._row_counts[uniq] == 0
        words = []
        for (r, dt), p in zip(layout, parts):
            dt = np.dtype(dt)
            wide = dt.kind in "iu" and dt.itemsize == 8
            part = np.zeros(size, dtype=np.int64 if wide else dt)
            part[:n] = p
            if wide:
                w = part.view(np.int32).reshape(-1, 2)
                words += [np.ascontiguousarray(w[:, 0]),
                          np.ascontiguousarray(w[:, 1])]
            else:
                words.append(part)
        with flight.span("device.dispatch"):
            self.table.accs, out = wide_fold(layout)(
                self.table.accs, pad_slots, fresh, tuple(words))
        self.table.mark_dirty(uniq)
        fire = PendingFire(list(out), lambda host: self.agg.finish_host(
            self._parts_of_words(host, n)))
        return uniq, fire.harvest()

    def _parts_of_words(self, host, n: int) -> List[np.ndarray]:
        """Each part's values from its words: a 64-bit part's low and
        high int32 words joined into int64."""
        parts, at = [], 0
        for _, _, dt in self.agg.parts:
            if np.dtype(dt).itemsize == 8:
                both = np.empty((n, 2), dtype=np.int32)
                both[:, 0] = np.asarray(host[at])[:n]
                both[:, 1] = np.asarray(host[at + 1])[:n]
                parts.append(both.view(np.int64).reshape(-1))
                at += 2
            else:
                parts.append(np.asarray(host[at])[:n])
                at += 1
        return parts

    def _values(self, slots: np.ndarray) -> Dict[str, np.ndarray]:
        """The finished result columns of ``slots``, read from the
        device."""
        if not self.agg.wide:
            return self.table.fire(slots[:, None].astype(np.int32))
        import jax

        from flink_tpu.ops.segment_ops import sticky_bucket

        size = sticky_bucket(len(slots), self._fold_bucket)
        self._fold_bucket = size
        pad = np.zeros(size, dtype=np.int32)
        pad[:len(slots)] = slots
        host = jax.device_get(self.agg._gather_jit(self.table.accs, pad))
        return self.agg.finish_host(self._parts_of_words(
            [np.asarray(h) for h in host], len(slots)))

    def process_watermark(self, watermark, input_index=0):
        if not self.emit_on_watermark_only or not self._dirty:
            self._maybe_sweep_ttl()
            return []
        slots = np.fromiter(self._dirty, dtype=np.int64)
        self._dirty.clear()
        out = self._emit_slots(slots, self._values(slots))
        self._maybe_sweep_ttl()
        return [out] if out is not None else []

    # --------------------------------------------------------------- emission

    def _emit_slots(self, slots: np.ndarray,
                    results: Dict[str, np.ndarray]) -> Optional[RecordBatch]:
        """The changelog of ``slots`` whose new values are ``results``."""
        if len(slots) == 0:
            return None
        with flight.span("agg.emit") as span:
            out = self._changelog(slots, results)
            span.work = 0 if out is None else len(out)
        if out is not None:
            flight.instant("agg.changelog", work=len(out))
        return out

    def _changelog(self, slots: np.ndarray,
                   results: Dict[str, np.ndarray]) -> Optional[RecordBatch]:
        self._ensure_host_capacity(int(slots.max()) + 1)
        counts = self._row_counts[slots]
        live = counts > 0
        was_emitted = self._emitted_mask[slots]
        # lazily allocate last-emitted storage from the first result dtypes
        for name, col in results.items():
            if name not in self._last_emitted:
                self._last_emitted[name] = np.zeros(
                    len(self._row_counts), dtype=np.asarray(col).dtype)

        segments: List[Dict[str, np.ndarray]] = []

        def _segment(slot_sel: np.ndarray, kind: int, from_prev: bool):
            if not slot_sel.any():
                return
            sl = slots[slot_sel]
            if from_prev:
                vals = {n: self._last_emitted[n][sl] for n in results}
            else:
                vals = {n: np.asarray(results[n])[slot_sel] for n in results}
            segments.append({
                "slots": sl,
                ROWKIND_FIELD: np.full(len(sl), kind, dtype=np.int8),
                **vals,
            })

        upd = live & was_emitted
        if not self.ttl_ms:
            # an unchanged value emits nothing (the reference's
            # GroupAggFunction without state retention)
            changed = np.zeros(len(slots), dtype=bool)
            for name in results:
                changed |= (self._last_emitted[name][slots]
                            != np.asarray(results[name]))
            upd &= changed
        if self.generate_update_before:
            _segment(upd, ROWKIND_UPDATE_BEFORE, from_prev=True)
        _segment(~live & was_emitted, ROWKIND_DELETE, from_prev=True)
        _segment(live & ~was_emitted, ROWKIND_INSERT, from_prev=False)
        _segment(upd, ROWKIND_UPDATE_AFTER, from_prev=False)

        # roll the changelog bookkeeping forward
        for name in results:
            arr = self._last_emitted[name]
            arr[slots[live]] = np.asarray(results[name])[live]
        self._emitted_mask[slots] = live

        if not segments:
            return None
        all_slots = np.concatenate([s.pop("slots") for s in segments])
        kid = self.table.keys_of_slots(all_slots)
        if self._keys_hashed:
            kv = np.array([self._key_values.get(int(i)) for i in kid],
                          dtype=object)
        else:
            kv = kid
        cols: Dict[str, np.ndarray] = {
            KEY_ID_FIELD: kid,
            self.key_field: kv,
            TIMESTAMP_FIELD: np.full(len(all_slots), self._max_ts,
                                     dtype=np.int64),
        }
        for name, store in self._key_cols.items():
            cols[name] = store[all_slots]
        for name in segments[0]:
            cols[name] = np.concatenate([s[name] for s in segments])
        return RecordBatch(cols)

    # ------------------------------------------------------------- checkpoint

    def _host_state(self):
        # the changelog bookkeeping is stored LOGICALLY (keyed by key_id,
        # not by physical slot) so snapshots merge across subtasks and
        # restore into any slot layout (key-group re-assignment, multi-slot
        # union — same portability contract as the slot table rows)
        interesting = np.nonzero((self._row_counts != 0)
                                 | self._emitted_mask)[0]
        # minibatch emission state: slots whose change is still pending a
        # watermark flush must survive a restore or their final rows would
        # be silently lost (batch mode defers ALL emission to end-of-input)
        dirty = np.zeros(len(interesting), dtype=bool)
        if self._dirty:
            dirty = np.isin(interesting,
                            np.fromiter(self._dirty, dtype=np.int64))
        cl = {
            "key_id": self.table.keys_of_slots(interesting),
            "count": self._row_counts[interesting],
            "emitted": self._emitted_mask[interesting],
            "dirty": dirty,
            "last": {n: a[interesting]
                     for n, a in self._last_emitted.items()},
            "keys": {n: a[interesting] for n, a in self._key_cols.items()},
        }
        if self.ttl_ms:
            # stamps travel logically so restore resumes each key's
            # REMAINING lifetime (reference: TTL state restores with its
            # original timestamps)
            cl["ttl_last_update"] = self._last_update[interesting]
        return {
            "input_dtypes": dict(self._input_dtypes),
            "key_values": dict(self._key_values),
            "keys_hashed": self._keys_hashed,
            "max_ts": self._max_ts,
            "changelog": cl,
        }

    def snapshot_state(self):
        if self.table is None:
            return {"table": None}     # no batch yet: nothing bound
        return {"table": self.table.snapshot(), **self._host_state()}

    def snapshot_state_delta(self):
        """Incremental: dirty rows + tombstones only (see
        SlotTable.snapshot_delta)."""
        if self.table is None:
            return {"table": None}
        return {"table": self.table.snapshot_delta(), **self._host_state()}

    def snapshot_state_savepoint(self):
        """Full state without resetting the incremental base."""
        if self.table is None:
            return {"table": None}
        return {"table": self.table.snapshot(reset_dirty=False),
                **self._host_state()}

    def query_state(self, key_value, namespace=None):
        """Queryable-state point lookup (see WindowAggOperator)."""
        from flink_tpu.state.keygroups import hash_keys_to_i64

        key_id = int(hash_keys_to_i64(np.asarray([key_value]))[0])
        return self.table.query(key_id, namespace)

    def restore_state(self, state, key_group_filter=None):
        if state.get("table") is None:
            return
        if self.table is None:
            self._bind({n: np.dtype(d) for n, d in
                        state.get("input_dtypes", {}).items()})
        self.table.restore(state["table"],
                           key_group_filter=key_group_filter)
        self._key_values = dict(state.get("key_values", {}))
        self._keys_hashed = state.get("keys_hashed", False)
        self._max_ts = state.get("max_ts", 0)
        self._row_counts = np.zeros(0, dtype=np.int64)
        self._emitted_mask = np.zeros(0, dtype=bool)
        self._last_update = np.zeros(0, dtype=np.int64)
        self._last_emitted = {}
        cl = state.get("changelog")
        if cl is None and "row_counts" in state:
            # legacy (round-2 snapshot) slot-indexed format: only valid
            # when restoring into the same slot layout, which holds because
            # the table rows above restored in snapshot order — but NOT
            # under a key-group filter, which compacts the table and
            # misaligns every slot index
            if key_group_filter is not None:
                raise RuntimeError(
                    "legacy slot-indexed changelog state cannot be "
                    "restored with a key-group filter (stage-parallel "
                    "restore) — take a fresh savepoint with the current "
                    "version first")
            self._row_counts = np.asarray(state["row_counts"],
                                          dtype=np.int64)
            self._emitted_mask = np.asarray(state["emitted_mask"],
                                            dtype=bool)
            self._last_emitted = {
                n: np.asarray(a)
                for n, a in state.get("last_emitted", {}).items()}
            return
        if cl is None or len(np.asarray(cl.get("key_id", ()))) == 0:
            return
        key_ids = np.asarray(cl["key_id"], dtype=np.int64)
        counts = np.asarray(cl["count"], dtype=np.int64)
        emitted = np.asarray(cl["emitted"], dtype=bool)
        dirty = np.asarray(cl.get("dirty", np.zeros(len(key_ids), bool)),
                           dtype=bool)
        if key_group_filter is not None:
            from flink_tpu.state.keygroups import assign_key_groups

            groups = assign_key_groups(key_ids, self.table.max_parallelism)
            keep = np.isin(groups, np.asarray(sorted(key_group_filter)))
            key_ids, counts, emitted, dirty = (
                key_ids[keep], counts[keep], emitted[keep], dirty[keep])
            cl_last = {n: np.asarray(a)[keep]
                       for n, a in cl.get("last", {}).items()}
            cl_keys = {n: np.asarray(a)[keep]
                       for n, a in cl.get("keys", {}).items()}
        else:
            cl_last = {n: np.asarray(a) for n, a in cl.get("last", {}).items()}
            cl_keys = {n: np.asarray(a) for n, a in cl.get("keys", {}).items()}
        if len(key_ids) == 0:
            return
        # re-key the logical changelog onto this instance's slot layout
        ns = np.full(len(key_ids), _GLOBAL_NS, dtype=np.int64)
        slots = self.table.lookup_or_insert(key_ids, ns)
        self._ensure_host_capacity(int(slots.max()) + 1)
        self._row_counts[slots] = counts
        self._emitted_mask[slots] = emitted
        self._dirty.update(int(s) for s in slots[dirty])
        if self.ttl_ms and "ttl_last_update" in cl:
            stamps = np.asarray(cl["ttl_last_update"], dtype=np.int64)
            if key_group_filter is not None:
                stamps = stamps[keep]
            self._last_update[slots] = stamps
        for store, saved in ((self._last_emitted, cl_last),
                             (self._key_cols, cl_keys)):
            for n, a in saved.items():
                arr = np.zeros(len(self._row_counts), dtype=a.dtype)
                arr[slots] = a
                store[n] = arr
