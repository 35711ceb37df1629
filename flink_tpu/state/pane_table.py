"""Pane-layout keyed window state: a ring of slices × stable key rows.

The SlotTable (state/slot_table.py) allocates one slot per (key, slice)
pair, so firing a k-slice window needs a host-built [num_keys, k] slot
matrix shipped host->device every fire — on a transfer-constrained TPU
link that dominates the fire cost. This layout removes the matrix
entirely (reference analog: the pane/slice-sharing idea of
SliceAssigners.java taken to its natural TPU form):

- device arrays are ``[ring_rows, key_capacity]`` per accumulator leaf;
- a KEY owns a stable column (key row) across all slices (host index,
  keyed by key only);
- a live SLICE owns a ring row (host dict slice_end -> row; row 0 is the
  reserved always-identity row, the pad target for missing slices);
- scatter: ``acc[row[i], col[i]] op= v[i]`` — same host->device traffic
  as the slot layout (indices + values);
- FIRE: ``merge(acc[rows_of_window], axis=0)`` + finish (+ fused top-k
  projector) — the only host->device transfer is the [k] ring-row ids;
- freeing an expired slice is ONE index-free row reset;
- the incremental-snapshot unit is a slice row, and sealed slices never
  dirty again — a delta checkpoint ships just the active slice.

A presence plane (int8 max-scatter) distinguishes "key has data in this
slice" from identity values, so fires emit exactly the keys that
participated (SUM of 0.0 is not confused with absence).

Scope: aligned (non-merging) assigners on one device without a spill
tier; sessions, spill, and the mesh keep the slot layout.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.core.annotations import internal
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.ops.segment_ops import (
    pad_i32,
    sticky_bucket,
)
from flink_tpu.state.keygroups import assign_key_groups
from flink_tpu.state.slot_table import make_slot_index
from flink_tpu.stateplane import pane_programs
from flink_tpu.stateplane.families import pane_fence
from flink_tpu.windowing.aggregates import AggregateFunction

_INITIAL_RING = 8


def _pane_kernels(agg: AggregateFunction, projector=None):
    """(scatter2d, scatter2d_valued, fire_rows, reset_row, put_row,
    fold_rows) for [R, C] pane arrays — the stateplane delta-harvest
    bundle (bodies in ``flink_tpu/stateplane/pane.py``). The presence
    plane rides as an extra trailing array in ``accs``."""
    return pane_programs(agg, projector)


@internal
class PaneTable:
    """Ring-of-slices × key-rows window state (see module docstring)."""

    def __init__(self, agg: AggregateFunction, capacity: int = 1 << 16,
                 max_parallelism: int = 128, fire_projector=None,
                 memory=None, slices_for_window=None):
        self.agg = agg
        self.max_parallelism = max_parallelism
        self.fire_projector = fire_projector
        #: window_end -> slice ends (the assigner's mapping) — needed to
        #: rebuild window-partial rows from the authoritative panes
        #: after a restore or an internal compaction (preagg mode)
        self._slices_for_window = slices_for_window
        #: (MemoryManager, owner) — the DENSE [R, capacity] per-leaf
        #: footprint (plus the int8 presence plane) is managed
        #: (flink_tpu/core/memory.py), the layout most likely to exhaust
        #: HBM on high-ratio sliding windows
        self._memory = memory
        self.index = make_slot_index(capacity, on_grow=self._grow_cols)
        self.capacity = self.index.capacity
        self.R = _INITIAL_RING
        self._reserve_cells(self.R * self.capacity)
        self.accs = tuple(
            jnp.full((self.R, self.capacity), l.identity, dtype=l.dtype)
            for l in agg.leaves
        ) + (jnp.zeros((self.R, self.capacity), dtype=jnp.int8),)
        #: slice_end -> ring row (row 0 reserved identity)
        self.slice_row: Dict[int, int] = {}
        #: window_end -> ring row holding the window's RUNNING PARTIAL
        #: (incremental pane pre-aggregation: combined at absorb so a
        #: fire gathers exactly the one pane that closes). Derived
        #: state — snapshots ignore it, restore/compaction rebuild it
        #: from the panes.
        self.window_row: Dict[int, int] = {}
        self._free_rows: List[int] = list(range(self.R - 1, 0, -1))
        self._dirty_slices: set = set()
        self._freed_ns: List[int] = []
        self._scatter_bucket = 0
        #: exclusive bound of allocated key rows (keys are never freed, so
        #: allocations stay contiguous from 1)
        self._high_water = 1
        (self._scatter2d, self._scatter2d_valued, self._fire_rows,
         self._reset_row, self._put_row,
         self._fold_rows) = _pane_kernels(agg, fire_projector)

    # ---------------------------------------------------------------- sizing

    def _cell_bytes(self) -> int:
        return sum(np.dtype(l.dtype).itemsize
                   for l in self.agg.leaves) + 1  # + presence plane

    def _reserve_cells(self, cells: int) -> None:
        if self._memory is not None:
            manager, owner = self._memory
            manager.reserve(owner, cells * self._cell_bytes())

    def release_memory(self) -> None:
        if self._memory is not None:
            manager, owner = self._memory
            manager.release_all(owner)

    def _grow_cols(self, old: int, new: int) -> None:
        self._reserve_cells(self.R * (new - old))
        self.capacity = new
        grown = []
        for a, l in zip(self.accs[:-1], self.agg.leaves):
            pad = jnp.full((self.R, new - old), l.identity, dtype=l.dtype)
            grown.append(jnp.concatenate([a, pad], axis=1))
        pad = jnp.zeros((self.R, new - old), dtype=jnp.int8)
        self.accs = tuple(grown) + (
            jnp.concatenate([self.accs[-1], pad], axis=1),)

    def _take_row(self) -> int:
        if not self._free_rows:
            old = self.R
            self._reserve_cells(old * self.capacity)  # doubling the ring
            self.R = old * 2
            grown = []
            for a, l in zip(self.accs[:-1], self.agg.leaves):
                pad = jnp.full((old, self.capacity), l.identity,
                               dtype=l.dtype)
                grown.append(jnp.concatenate([a, pad], axis=0))
            pad = jnp.zeros((old, self.capacity), dtype=jnp.int8)
            self.accs = tuple(grown) + (
                jnp.concatenate([self.accs[-1], pad], axis=0),)
            self._free_rows = list(range(self.R - 1, old - 1, -1))
        return self._free_rows.pop()

    def _alloc_row(self, slice_end: int) -> int:
        row = self._take_row()
        self.slice_row[int(slice_end)] = row
        return row

    def _alloc_window_row(self, window_end: int) -> int:
        row = self._take_row()
        self.window_row[int(window_end)] = row
        return row

    @property
    def used_cols(self) -> int:
        """High-water key-row bound (exclusive); row 0 is reserved."""
        return self._high_water

    # ---------------------------------------------------------------- ingest

    #: upsert()/upsert_valued() take a precomputed ``slice_plan``
    #: (uniq, inverse) from WindowAssigner.slice_plan — saves a full
    #: sort of the batch (see SliceSharedWindower.process_batch)
    accepts_slice_plan = True

    def _flat_indices(self, key_ids: np.ndarray,
                      slice_ends: np.ndarray,
                      slice_plan=None) -> np.ndarray:
        """[n] fused (ring row, key col) -> flat i32 scatter indices — one
        index array over the host->device link instead of two (fill 0 =
        reserved identity row 0 / col 0)."""
        cols = self.index.lookup_or_insert(
            key_ids, np.zeros(len(key_ids), dtype=np.int64))
        if len(cols):
            self._high_water = max(self._high_water, int(cols.max()) + 1)
        # slice -> ring row: rows for the (few) unique slices via the host
        # dict, broadcast back per record with the unique-inverse (no
        # Python-level per-record loop)
        uniq, inv = slice_plan if slice_plan is not None else \
            np.unique(slice_ends, return_inverse=True)
        for se in uniq.tolist():
            if int(se) not in self.slice_row:
                self._alloc_row(int(se))
            self._dirty_slices.add(int(se))
        uniq_rows = np.fromiter(
            (self.slice_row[int(se)] for se in uniq.tolist()),
            dtype=np.int64, count=len(uniq))
        rows = uniq_rows[inv]
        self._check_flat_range()
        return (rows * self.capacity + cols).astype(np.int32)

    def ingest_indices(self, key_ids: np.ndarray, timestamps: np.ndarray,
                       offset: int, width: int):
        """Fused index build: ONE native sweep (sm_pane_ingest) replaces
        assign_slice_ends + slice_plan + lookup_or_insert + the flat
        fuse — the five memory-bound numpy passes that dominated ingest
        on large micro-batches. Returns (flat, uniq_ends, sinv) or None
        when the native library is absent or the batch has pathologically
        many distinct slice ends (callers fall back to the numpy path)."""
        ingest = getattr(self.index, "pane_ingest", None)
        if ingest is None:
            return None
        res = ingest(key_ids, timestamps, offset, width)
        if res is None:
            return None
        cols, sinv, uniq, max_col = res
        self._high_water = max(self._high_water, max_col + 1)
        rowmap = np.empty(len(uniq), dtype=np.int64)
        for j, se in enumerate(uniq.tolist()):
            se = int(se)
            if se not in self.slice_row:
                self._alloc_row(se)
            self._dirty_slices.add(se)
            rowmap[j] = self.slice_row[se]
        self._check_flat_range()
        flat = self.index.flat_fuse(cols, sinv, rowmap, self.capacity)
        return flat, uniq, sinv

    def scatter_flat(self, flat: np.ndarray,
                     values: Tuple[np.ndarray, ...],
                     valued: bool = False) -> None:
        """Scatter with a prebuilt flat index (see ingest_indices): pad
        to the sticky bucket (``prep.stage``; work: the bytes handed to
        the device, padding included), then dispatch."""
        with flight.span("prep.stage") as stage:
            size = sticky_bucket(len(flat), self._scatter_bucket)
            self._scatter_bucket = size
            padded_flat = pad_i32(flat, size, fill=0)
            if valued:
                from flink_tpu.ops.segment_ops import pad_values

                step = self._scatter2d_valued
                padded_vals = tuple(
                    pad_values(np.asarray(v, dtype=l.dtype), size,
                               l.identity)
                    for v, l in zip(values, self.agg.leaves))
            else:
                step = self._scatter2d
                padded_vals = self.agg.pad_input_values(values, size)
            stage.work = padded_flat.nbytes + sum(
                v.nbytes for v in padded_vals)
        with flight.span("device.dispatch"):
            self.accs = step(self.accs, padded_flat, padded_vals)

    def upsert(self, key_ids: np.ndarray, slice_ends: np.ndarray,
               values: Tuple[np.ndarray, ...], slice_plan=None,
               valued: bool = False) -> None:
        with flight.span("prep.resolve"):
            flat = self._flat_indices(key_ids, slice_ends, slice_plan)
        self.scatter_flat(flat, values, valued)

    def upsert_valued(self, key_ids: np.ndarray, slice_ends: np.ndarray,
                      values: Tuple[np.ndarray, ...],
                      slice_plan=None) -> None:
        """Fold locally pre-aggregated partials (every leaf valued; see
        flink_tpu.runtime.local_agg)."""
        self.upsert(key_ids, slice_ends, values, slice_plan, valued=True)

    # ------------------------------------- incremental pane pre-aggregation

    def has_window_partial(self, window_end: int) -> bool:
        return int(window_end) in self.window_row

    def _check_flat_range(self) -> None:
        if self.R * self.capacity > np.iinfo(np.int32).max:
            raise RuntimeError(
                f"pane table exceeds int32 flat-index range "
                f"(ring={self.R} x capacity={self.capacity}); lower "
                "state.slot-table.capacity or the window's slice count")

    def window_flat(self, cols: np.ndarray, sinv: np.ndarray,
                    wins_per_slice):
        """Flat scatter indices folding each record into its live
        windows' PARTIAL rows (combine-on-absorb). ``cols`` are the
        records' key columns (``flat %% capacity``), ``sinv`` the
        unique-slice inverse, ``wins_per_slice`` one list of window
        ends per unique slice — only windows that already HAVE a
        partial row receive direct folds (missing ones are rebuilt
        from the authoritative panes after the scatter). Returns
        ``(flat, rec_idx)`` or None when nothing folds."""
        chunks_f: List[np.ndarray] = []
        chunks_i: List[np.ndarray] = []
        order = np.argsort(sinv, kind="stable")
        counts = np.bincount(sinv, minlength=len(wins_per_slice))
        offs = np.concatenate(([0], np.cumsum(counts)))
        C = self.capacity
        self._check_flat_range()
        for j, wins in enumerate(wins_per_slice):
            if not wins:
                continue
            sel = order[offs[j]:offs[j + 1]]
            if not len(sel):
                continue
            c = cols[sel].astype(np.int64)
            for w in wins:
                row = self.window_row.get(int(w))
                if row is None:
                    continue
                # pad lanes (col 0) stay on the identity column of the
                # window row: (flat %% C) == 0 keeps them pure
                chunks_f.append((row * C + c).astype(np.int32))
                chunks_i.append(sel)
        if not chunks_f:
            return None
        return np.concatenate(chunks_f), np.concatenate(chunks_i)

    def scatter_combined(self, flat: np.ndarray, win,
                         values: Tuple[np.ndarray, ...],
                         valued: bool = False) -> None:
        """One scatter covering the pane cells AND the window-partial
        cells: the window half replicates each record's value through
        ``rec_idx`` (see window_flat), so the whole batch still costs
        ONE flat index array over the link and ONE dispatch."""
        if win is None:
            return self.scatter_flat(flat, values, valued)
        flat_w, rec_idx = win
        flat_all = np.concatenate([flat, flat_w])
        vals = tuple(np.concatenate([np.asarray(v), np.asarray(v)[rec_idx]])
                     for v in values)
        self.scatter_flat(flat_all, vals, valued)

    def rebuild_window_partials(self, window_ends) -> int:
        """(Re)build partial rows for pending windows that lack one —
        fold of the window's pane rows (the panes are authoritative:
        this is exactly the full-window harvest, landed into a ring row
        instead of the host). Runs after restore, after compaction, and
        for windows newly pending this batch (including late
        re-registrations under allowed lateness). Returns rows built."""
        if self._slices_for_window is None:
            return 0
        built = 0
        # sorted: ring-row allocation order must be deterministic
        # (window_ends may arrive as a set)
        for w in sorted(int(x) for x in window_ends):
            if w in self.window_row:
                continue
            rows = [self.slice_row.get(int(se), 0)
                    for se in self._slices_for_window(w)]
            if not any(rows):
                continue  # no pane data: the fire falls back / emits nothing
            dst = self._alloc_window_row(w)
            self.accs = self._fold_rows(
                self.accs, dst,
                jnp.asarray(np.asarray(rows, dtype=np.int32)))
            built += 1
        return built

    def release_window_row(self, window_end: int) -> None:
        """Reset + free a fired window's partial row (queue-ordered
        behind the fire kernel, so deferred harvests never race it)."""
        row = self.window_row.pop(int(window_end), None)
        if row is None:
            return
        self.accs = self._reset_row(self.accs, row)
        self._free_rows.append(row)

    def clear_window_rows(self) -> None:
        for w in list(self.window_row):
            self.release_window_row(w)

    def fire_partial(self, window_end: int
                     ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """Delta fire: gather ONE partial ring row — the pane that
        closes — instead of merging the window's k slice rows. The row
        is released after the fire (a fired window's partial is spent;
        a late re-registration rebuilds it from the retained panes)."""
        row = self.window_row.get(int(window_end))
        if row is None:
            return np.empty(0, dtype=np.int64), {}
        out = self._harvest_rows(np.asarray([row], dtype=np.int32))
        self.release_window_row(window_end)
        return out

    def fire_partial_async(self, window_end: int):
        """Async delta fire: PendingFire (or None) whose harvest yields
        (keys, result columns); the row release is dispatched right
        after the fire kernel (device-queue-ordered behind it)."""
        row = self.window_row.get(int(window_end))
        if row is None:
            return None
        pf = self._harvest_rows_async(np.asarray([row], dtype=np.int32))
        self.release_window_row(window_end)
        return pf

    def make_fence(self):
        """Dispatch-depth fence (see SlotTable.make_fence): a [1, 1] slice
        of the live accumulator, enqueued behind all prior work."""
        return pane_fence(self.agg.leaves[0].dtype.str)(self.accs[0])

    # ------------------------------------------------------------------ fire

    def fire_window(self, slice_ends: List[int]
                    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """(keys, result columns) for one window — missing slices hit the
        reserved identity row; the ONLY host->device payload is [k] row
        ids."""
        rows = np.asarray(
            [self.slice_row.get(int(se), 0) for se in slice_ends],
            dtype=np.int32)
        if not rows.any():
            return np.empty(0, dtype=np.int64), {}
        return self._harvest_rows(rows)

    def fire_window_async(self, slice_ends: List[int]):
        """Async-dispatch variant of fire_window: returns a PendingFire
        (or None for a no-op window) whose harvest yields (keys, result
        columns)."""
        rows = np.asarray(
            [self.slice_row.get(int(se), 0) for se in slice_ends],
            dtype=np.int32)
        if not rows.any():
            return None
        return self._harvest_rows_async(rows)

    def _harvest_rows(self, rows: np.ndarray
                      ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """Merge+finish the given ring rows and materialize (keys,
        result columns) — THE one sync harvest body, shared by the
        full-window fire (k slice rows) and the delta fire (one partial
        row), so projector/harvest semantics cannot drift between the
        two paths. One batched device_get: each independent read costs
        a full link RTT, batched reads pipeline into ~one."""
        used = self.used_cols
        out = self._fire_rows(self.accs, jnp.asarray(rows), used)
        if self.fire_projector is None:
            cols, valid = out
            names = list(cols)
            host = jax.device_get([valid] + [cols[n] for n in names])
            sel = host[0][:used]
            keys = self.index.slot_key[:used][sel]
            return keys, {name: c[:used][sel]
                          for name, c in zip(names, host[1:])}
        pidx, pcols, pvalid = out
        names = list(pcols)
        host = jax.device_get([pidx, pvalid] + [pcols[n] for n in names])
        pidx_h, sel = host[0], host[1]
        keys = self.index.slot_key[pidx_h[sel]]
        return keys, {name: c[sel]
                      for name, c in zip(names, host[2:])}

    def _harvest_rows_async(self, rows: np.ndarray):
        """Async form of :meth:`_harvest_rows`: dispatch + PendingFire.
        The key rows backing the result are snapshotted at dispatch
        (keys are append-only, so rows < used never mutate, but the
        copy also survives an index grow/realloc)."""
        from flink_tpu.runtime.pending import PendingFire

        used = self.used_cols
        out = self._fire_rows(self.accs, jnp.asarray(rows), used)
        if self.fire_projector is None:
            cols, valid = out
            names = list(cols.keys())
            keys_snap = self.index.slot_key[:used].copy()

            def build(host: List[np.ndarray]):
                sel = host[0][:used]
                return keys_snap[sel], {
                    name: col[:used][sel]
                    for name, col in zip(names, host[1:])}

            return PendingFire([valid] + [cols[n] for n in names], build)
        pidx, pcols, pvalid = out
        names = list(pcols.keys())
        keys_snap = self.index.slot_key[:used].copy()

        def build(host: List[np.ndarray]):
            pidx_h, sel = host[0], host[1]
            return keys_snap[pidx_h[sel]], {
                name: col[sel] for name, col in zip(names, host[2:])}

        return PendingFire([pidx, pvalid] + [pcols[n] for n in names],
                           build)

    # ----------------------------------------------------------------- frees

    def free_slices(self, slice_ends: List[int]) -> int:
        """Reset and release the expired slices' ring rows; returns how
        many rows went (what ``slice.retire`` counts in this layout)."""
        freed = 0
        for se in slice_ends:
            row = self.slice_row.pop(int(se), None)
            if row is None:
                continue
            freed += 1
            self.accs = self._reset_row(self.accs, row)
            self._free_rows.append(row)
            self._dirty_slices.discard(int(se))
            self._freed_ns.append(int(se))
        self._maybe_compact()
        return freed

    #: alias so PaneWindower shares SliceSharedWindower.on_watermark
    free_namespaces = free_slices

    #: no spill tier in the pane layout (the slot layout covers that)
    spill = frozenset()
    #: and no host-built slot matrix: a fire hands the device nothing
    #: (the slot layout counts its matrices' bytes under this name)
    fire_matrix_bytes = 0

    _COMPACT_MIN_KEYS = 4096

    def _maybe_compact(self) -> None:
        """Key columns are never freed inline (a key's column is shared by
        every live slice), so key churn would grow the table forever —
        when most allocated columns belong to departed keys, rebuild the
        table from its own logical snapshot (one state round-trip,
        amortized rare; the slot layout's free_namespaces analog).

        The aliveness probe reads a device reduction (one link RTT), so it
        only runs when the key high-water mark has grown >=1.5x since the
        last probe: compaction exists to reclaim columns as the table
        GROWS toward capacity — a stable keyset (hw flat) needs neither
        the probe nor the rebuild, and previously paid one blocking fetch
        per watermark advance for it."""
        hw = self._high_water
        if hw < self._COMPACT_MIN_KEYS:
            return
        if hw < getattr(self, "_compact_probed_hw", 0) * 3 // 2:
            return
        self._compact_probed_hw = hw
        live = sorted(self.slice_row)
        if live:
            rows = np.asarray([self.slice_row[se] for se in live],
                              dtype=np.int32)
            alive = int(np.asarray(
                (self.accs[-1][rows].max(axis=0) > 0)[:hw]).sum())
        else:
            alive = 0
        if alive * 2 > hw:
            return
        snap = self.snapshot(reset_dirty=False)
        dirty, freed = self._dirty_slices, self._freed_ns
        wins = sorted(self.window_row)  # derived rows: rebuilt below
        self.index = make_slot_index(self.index.capacity,
                                     on_grow=self._grow_cols)
        self.capacity = self.index.capacity
        self._high_water = 1
        self.slice_row = {}
        self.window_row = {}
        self._free_rows = list(range(self.R - 1, 0, -1))
        self.accs = tuple(
            jnp.full((self.R, self.capacity), l.identity, dtype=l.dtype)
            for l in self.agg.leaves
        ) + (jnp.zeros((self.R, self.capacity), dtype=jnp.int8),)
        self.restore(snap)
        # compaction must not eat incremental bookkeeping: every surviving
        # slice moved, so they are all dirty vs the last base
        self._dirty_slices = set(dirty) | set(self.slice_row)
        self._freed_ns = freed
        # window partials are derived state — refold them from the
        # compacted panes (preagg mode; no-op without the mapping)
        self.rebuild_window_partials(wins)

    # ------------------------------------------------------------ point query

    def query_windows(self, key_id: int, assigner) -> Dict[int, dict]:
        col = self.index.lookup(np.asarray([key_id], dtype=np.int64),
                                np.zeros(1, dtype=np.int64))[0]
        if col < 0:
            return {}
        live = sorted(self.slice_row)
        if not live:
            return {}
        rows = np.asarray([self.slice_row[se] for se in live],
                          dtype=np.int32)
        # ONE batched D2H for every leaf plane (per-plane np.asarray
        # pays one link round-trip per leaf)
        picked = jax.device_get([a[rows, int(col)] for a in self.accs])
        per_leaf, present = picked[:-1], picked[-1] > 0
        slice_vals = {
            se: tuple(pl[i] for pl in per_leaf)
            for i, se in enumerate(live) if present[i]
        }
        if not slice_vals:
            return {}
        windows = sorted({
            int(w) for se in slice_vals
            for w in assigner.window_ends_for_slice(se)})
        out = {}
        idents = tuple(l.identity for l in self.agg.leaves)
        host_merge = {"sum": np.add, "max": np.maximum, "min": np.minimum}
        for w in windows:
            acc = list(idents)
            hit = False
            for se in assigner.slice_ends_for_window(w):
                sv = slice_vals.get(int(se))
                if sv is None:
                    continue
                hit = True
                for i, l in enumerate(self.agg.leaves):
                    acc[i] = host_merge[l.reduce](acc[i], sv[i])
            if not hit:
                continue
            merged = tuple(np.asarray([v]) for v in acc)
            finished = self.agg.finish(merged)
            out[w] = {name: np.asarray(v)[0].item()
                      for name, v in finished.items()}
        return out

    # -------------------------------------------------------------- snapshot

    def snapshot(self, reset_dirty: bool = True) -> Dict[str, np.ndarray]:
        """Logical rows — SAME format as SlotTable.snapshot (key_id /
        namespace / key_group / leaf_i), so pane and slot checkpoints are
        mutually restorable."""
        live = sorted(self.slice_row)
        return self._snapshot_slices(live, reset_dirty=reset_dirty,
                                     delta=False)

    def snapshot_delta(self) -> Dict[str, np.ndarray]:
        """Sealed slices never dirty again — the delta is just the slices
        touched since the last snapshot plus freed tombstones."""
        dirty = sorted(self._dirty_slices)
        out = self._snapshot_slices(dirty, reset_dirty=True, delta=True)
        return out

    def _snapshot_slices(self, slices: List[int], reset_dirty: bool,
                         delta: bool) -> Dict[str, np.ndarray]:
        used = self.used_cols
        key_cols, ns_cols = [], []
        leaf_cols: List[List[np.ndarray]] = [[] for _ in self.agg.leaves]
        if slices:
            # ONE batched gather + D2H for every snapshotted slice row
            # (the per-slice-per-leaf np.asarray loop paid one link
            # round-trip for each)
            row_ids = np.asarray([self.slice_row[se] for se in slices],
                                 dtype=np.int32)
            rows_host = jax.device_get(
                [a[row_ids, :used] for a in self.accs])
        for j, se in enumerate(slices):
            present = rows_host[-1][j] > 0
            if not present.any():
                continue
            keys = self.index.slot_key[:used][present]
            key_cols.append(keys)
            ns_cols.append(np.full(len(keys), se, dtype=np.int64))
            for i in range(len(self.agg.leaves)):
                leaf_cols[i].append(rows_host[i][j][present])
        if key_cols:
            key_ids = np.concatenate(key_cols)
            out = {
                "key_id": key_ids,
                "namespace": np.concatenate(ns_cols),
                "key_group": assign_key_groups(key_ids,
                                               self.max_parallelism),
                **{f"leaf_{i}": np.concatenate(cols)
                   for i, cols in enumerate(leaf_cols)},
            }
        else:
            out = {
                "key_id": np.empty(0, dtype=np.int64),
                "namespace": np.empty(0, dtype=np.int64),
                "key_group": np.empty(0, dtype=np.int32),
                **{f"leaf_{i}": np.empty(0, dtype=l.dtype)
                   for i, l in enumerate(self.agg.leaves)},
            }
        if delta:
            out["__delta__"] = np.asarray(True)
            out["freed_namespaces"] = np.asarray(
                sorted(set(self._freed_ns)), dtype=np.int64)
        if reset_dirty:
            self._dirty_slices.clear()
            self._freed_ns.clear()
        return out

    def restore(self, snap: Dict[str, np.ndarray],
                key_group_filter=None) -> None:
        key_ids = np.asarray(snap["key_id"], dtype=np.int64)
        namespaces = np.asarray(snap["namespace"], dtype=np.int64)
        leaves = []
        for i, leaf in enumerate(self.agg.leaves):
            arr = np.asarray(snap[f"leaf_{i}"])
            want = np.dtype(leaf.dtype)
            if len(arr) and arr.dtype != want:
                # same schema-compatibility contract as SlotTable.restore:
                # a value-preserving cast migrates, a lossy one fails
                cast = arr.astype(want)
                if not np.array_equal(cast.astype(arr.dtype), arr):
                    raise RuntimeError(
                        f"state schema incompatible: snapshot leaf_{i} "
                        f"has dtype {arr.dtype}, the aggregate expects "
                        f"{want} and the values do not survive the cast")
                arr = cast
            leaves.append(arr.astype(want))
        if key_group_filter is not None and len(key_ids):
            groups = assign_key_groups(key_ids, self.max_parallelism)
            keep = np.isin(groups, np.asarray(sorted(key_group_filter)))
            key_ids, namespaces = key_ids[keep], namespaces[keep]
            leaves = [l[keep] for l in leaves]
        order = np.argsort(namespaces, kind="stable")
        key_ids, namespaces = key_ids[order], namespaces[order]
        leaves = [l[order] for l in leaves]
        bounds = np.nonzero(np.diff(namespaces))[0] + 1
        starts = np.concatenate(([0], bounds)) if len(key_ids) else []
        ends = np.concatenate((bounds, [len(key_ids)])) if len(key_ids) \
            else []
        for a, b in zip(list(starts), list(ends)):
            se = int(namespaces[a])
            row = self.slice_row.get(se)
            if row is None:
                row = self._alloc_row(se)
            cols = self.index.lookup_or_insert(
                key_ids[a:b], np.zeros(b - a, dtype=np.int64))
            if len(cols):
                self._high_water = max(self._high_water,
                                       int(cols.max()) + 1)
            self.accs = self._put_row(
                self.accs, row,
                jnp.asarray(cols.astype(np.int32)),
                tuple(jnp.asarray(l[a:b]) for l in leaves))
        self._dirty_slices.clear()
        self._freed_ns.clear()
