"""Device-resident key->slot state table.

This replaces the reference's per-key state backends (Heap hash table:
flink-runtime/.../state/heap/CopyOnWriteStateTable.java; RocksDB column
families keyed by keyGroup+key+namespace:
flink-state-backends/flink-statebackend-rocksdb/.../RocksDBKeyedStateBackend.java)
with a split design natural to XLA's static-shape world:

- **Host** (``NativeSlotIndex``, ``native/slotmap.cpp``; ``HostSlotIndex``
  is its pure-Python twin): an index ``(key_id, namespace) -> slot`` plus
  per-slot metadata (key id, namespace) in NumPy arrays, a free list, and
  each namespace's slots kept together for O(fired) window expiry — one
  hash table per namespace where the owner frees by namespace, so a slice
  leaves with its table.
- **Device** (``SlotTable``): the accumulator leaves — flat ``[capacity]``
  jnp arrays updated by donated scatter kernels (see
  ``flink_tpu.windowing.aggregates``). The mesh-sharded variant
  (``flink_tpu.parallel.sharded_windower``) keeps one HostSlotIndex per
  shard and a single ``[num_shards, capacity]`` device array sharded over
  the key-group mesh axis.

Slot 0 is reserved as the identity slot (padding target). Capacity grows by
doubling (a bounded number of XLA recompiles). The namespace doubles as the
window/slice id, mirroring the reference's namespace-per-window keyed state
(reference: streaming/runtime/operators/windowing/WindowOperator.java:382
``windowState.setCurrentNamespace(window)``).
"""

from __future__ import annotations

import ctypes as _ct
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.observe import flight_recorder as flight
from flink_tpu.state.keygroups import assign_key_groups
from flink_tpu.stateplane import flat_fence
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.ops.segment_ops import (
    pad_bucket_size,
    pad_i32,
    pad_values,
    sticky_bucket,
)


from flink_tpu.core.annotations import internal


def _coerce_snapshot_leaf(
        arr: np.ndarray, want: np.dtype) -> Optional[np.ndarray]:
    """Cast a snapshot leaf to the aggregate's dtype iff value-preserving.

    Returns the cast array, or None when the cast would lose values.
    Integer targets get an exact range (and integrality) check instead of
    relying on numpy's overflow-on-cast side effect; float targets use
    roundtrip equality (NaN-tolerant) with overflow warnings suppressed —
    an out-of-range value becomes inf and fails the roundtrip.
    """
    if np.issubdtype(want, np.integer):
        info = np.iinfo(want)
        if np.issubdtype(arr.dtype, np.floating):
            if not np.all(np.isfinite(arr)):
                return None
            if not np.all(np.trunc(arr) == arr):
                return None
            # exact endpoints in float space: info.min and info.max + 1 are
            # +-2**(bits-1), exactly representable in float64 — a plain
            # `arr <= info.max` would round the bound UP and let 2**63 wrap
            lo, hi = float(info.min), float(info.max + 1)
            if not np.all((arr >= lo) & (arr < hi)):
                return None
        else:
            # integer -> integer: compare extremes as Python ints (exact,
            # immune to uint64/int64 promotion pitfalls)
            if int(arr.min()) < info.min or int(arr.max()) > info.max:
                return None
        return arr.astype(want)
    with np.errstate(over="ignore", invalid="ignore"):
        cast = arr.astype(want)
        equal_nan = np.issubdtype(arr.dtype, np.inexact)
        back = cast.astype(arr.dtype)
        ok = (np.array_equal(back, arr, equal_nan=True) if equal_nan
              else np.array_equal(back, arr))
        return cast if ok else None


def unique_pairs(
    key_ids: np.ndarray, namespaces: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized grouping of (key, namespace) pairs.

    Returns (unique_keys, unique_namespaces, inverse) where
    ``inverse[i]`` is the unique-pair index of record ``i``.
    """
    n = len(key_ids)
    if n == 0:
        e = np.empty(0, dtype=np.int64)
        return e, e.copy(), np.empty(0, dtype=np.int64)
    order = np.lexsort((key_ids, namespaces))
    ks, ns = key_ids[order], namespaces[order]
    new_group = np.empty(n, dtype=bool)
    new_group[0] = True
    new_group[1:] = (ks[1:] != ks[:-1]) | (ns[1:] != ns[:-1])
    group_of_sorted = np.cumsum(new_group) - 1
    inverse = np.empty(n, dtype=np.int64)
    inverse[order] = group_of_sorted
    first_pos = order[new_group]
    return key_ids[first_pos], namespaces[first_pos], inverse


def group_rows(key_ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A batch's rows grouped by key id in one native hash pass
    (``sm_group_rows``, native/slotmap.cpp), with no sort: ``(group,
    first)``, each row's group numbered in the order the keys first
    appear, and each group's first row (ascending). Under
    ``FLINK_TPU_NO_NATIVE=1`` ``np.unique`` gives the same groups, in the
    same numbering."""
    from flink_tpu.native import load_slotmap, native_disabled

    keys = np.ascontiguousarray(key_ids, dtype=np.int64)
    lib = None if native_disabled() else load_slotmap()
    if lib is None:
        _, first, inverse = np.unique(keys, return_index=True,
                                      return_inverse=True)
        order = np.argsort(first)
        rank = np.empty(len(first), dtype=np.int64)
        rank[order] = np.arange(len(first))
        return rank[inverse.reshape(-1)], first[order].astype(np.int64)
    n = len(keys)
    group = np.empty(n, dtype=np.int64)
    first = np.empty(n, dtype=np.int64)
    k = lib.sm_group_rows(n, keys.ctypes.data_as(_I64P),
                          group.ctypes.data_as(_I64P),
                          first.ctypes.data_as(_I64P))
    if k < 0:
        raise ValueError(f"{n} rows: more than an int32 group number holds")
    return group, first[:k]


class SlotTableFullError(RuntimeError):
    """Device slot budget exhausted — the owner may evict and retry."""


def verify_slot_hints(index, key_ids: np.ndarray, namespaces: np.ndarray,
                      hints: np.ndarray) -> np.ndarray:
    """Resolve folded device-slot hints against the index's OWN metadata
    views: a hint is taken iff the index currently maps exactly that
    (key, ns) pair at that slot. Returns int32 slots with -1 where the
    hint is absent or stale — callers fall back to the hash probe there.

    Correct by construction: ``slot_key``/``slot_ns``/``slot_used`` ARE
    the table's contents, so a passing verification can never name a
    wrong row — a fold gone stale (eviction, fire, reshard, restore)
    fails the compare and costs one fallback probe, never a wrong
    gather. This is what makes the metadata-plane slot fold a pure
    cache: no invalidation protocol, no correctness coupling."""
    native = getattr(index, "verify_hints", None)
    if native is not None:
        return native(key_ids, namespaces, hints)
    hints = np.asarray(hints, dtype=np.int32)
    out = np.full(len(hints), -1, dtype=np.int32)
    hv = hints >= 0
    if not hv.any():
        return out
    hs = hints[hv]
    cap = index.capacity
    safe = np.minimum(hs, cap - 1)
    ok = ((hs < cap)
          & index.slot_used[safe]
          & (index.slot_key[safe]
             == np.asarray(key_ids, dtype=np.int64)[hv])
          & (index.slot_ns[safe]
             == np.asarray(namespaces, dtype=np.int64)[hv]))
    out[hv] = np.where(ok, hs, np.int32(-1))
    return out


def resolve_slot_hints(index, key_ids: np.ndarray, namespaces: np.ndarray,
                       hints: np.ndarray, skip=None) -> np.ndarray:
    """The verify-then-probe resolve every hint consumer runs: take the
    verified folds, hash-probe the unresolved remainder, and leave -1
    for pairs the index does not hold. ``skip``: rows the caller KNOWS
    cannot be present (fresh session ids) — they keep -1 without paying
    the probe. One copy of the pattern for the resolve, the fire and
    the single-device table paths."""
    pre = verify_slot_hints(index, key_ids, namespaces, hints)
    probe = pre < 0
    if skip is not None:
        probe &= ~skip
    if probe.any():
        pre[probe] = index.lookup(
            np.asarray(key_ids)[probe], np.asarray(namespaces)[probe])
    return pre


def fire_matrix_width(k: int, fullest: int) -> int:
    """Columns of the slot matrix a fire is handed where the fullest row
    of a ``k``-slice window holds ``fullest`` live cells: the next power
    of two, at least 2 (a stream's first one-slice windows then take the
    two-column program too, not one of their own) and at most ``k`` — 2,
    4 or 5 for ``k = 5``. Decided per fire from the matrix alone
    (``fire_width`` in native/slotmap.cpp is the same rule)."""
    return min(k, max(2, pad_bucket_size(fullest, minimum=1)))


def pack_slot_matrix(matrix: np.ndarray) -> np.ndarray:
    """``matrix`` as the fire gets it: each row's live cells in its first
    columns in the order of their slices, zeros behind them, cut to
    :func:`fire_matrix_width` of the fullest row; ``matrix`` itself where
    that is all its columns (``carry_copy_out`` in native/slotmap.cpp)."""
    rows, k = matrix.shape
    live = matrix != 0
    width = fire_matrix_width(k, int(live.sum(axis=1).max(initial=0)))
    if width >= k:
        return matrix
    order = np.argsort(~live, axis=1, kind="stable")[:, :width]
    return np.take_along_axis(matrix, order, axis=1)


class _DictSliceCarry:
    """What :meth:`HostSlotIndex.slice_matrix` keeps of the matrix it
    carries: the slices of the last call, each one's list object in the
    registry and how much of that list the matrix holds; the matrix in
    NumPy with a dict as the key -> row table (the native index has
    ``sm_carry_advance``)."""

    def __init__(self) -> None:
        self.ends: List[int] = []
        self.lists: List[Optional[List[np.ndarray]]] = []
        self.consumed: List[int] = []
        self._keys = np.empty(0, dtype=np.int64)
        self._mat = np.zeros((0, 0), dtype=np.int32)
        self._row_of: Dict[int, int] = {}

    def advance(self, k: int, shift: int,
                parts: List[Tuple[int, np.ndarray]], slot_key: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Drop the ``shift`` leftmost columns (all of them: start from
        nothing), sweep out the rows left empty, then enter ``parts``:
        (column, slots) runs whose keys are ``slot_key[slots]``. Returns
        the keys, the matrix as the fire gets it (packed and cut:
        :func:`pack_slot_matrix`; the carry's own keeps a column per
        slice, the shift depends on it) and the rows that left: swept out
        and not brought back by what entered (as ``sm_carry_advance``
        counts them; a matrix started from nothing sweeps none)."""
        left: set = set()
        if shift >= k or self._mat.shape[1] != k:
            keys = np.empty(0, dtype=np.int64)
            mat = np.zeros((0, k), dtype=np.int32)
            row_of: Dict[int, int] = {}
        else:
            keys, mat, row_of = self._keys, self._mat, self._row_of
            if shift:
                mat = np.concatenate(
                    [mat[:, shift:],
                     np.zeros((len(mat), shift), dtype=np.int32)], axis=1)
                live = mat.any(axis=1)
                if not live.all():
                    left = set(keys[~live].tolist())
                    keys, mat = keys[live], mat[live]
                    row_of = dict(zip(keys.tolist(), range(len(keys))))
        if parts:
            slots = np.concatenate([s for _, s in parts])
            cols = np.repeat([j for j, _ in parts],
                             [len(s) for _, s in parts])
            fresh: List[int] = []
            rows = np.empty(len(slots), dtype=np.int64)
            for i, key in enumerate(slot_key[slots].tolist()):
                r = row_of.get(key)
                if r is None:
                    r = row_of[key] = len(row_of)
                    fresh.append(key)
                rows[i] = r
            if fresh:
                keys = np.concatenate(
                    [keys, np.asarray(fresh, dtype=np.int64)])
                mat = np.concatenate(
                    [mat, np.zeros((len(fresh), k), dtype=np.int32)])
            mat[rows, cols] = slots
            left.difference_update(fresh)
        self._keys, self._mat, self._row_of = keys, mat, row_of
        packed = pack_slot_matrix(mat)
        return keys.copy(), (mat.copy() if packed is mat else packed), \
            len(left)


class HostSlotIndex:
    """Host half of the state table in pure Python: a dict
    (key, ns) -> slot, per-slot metadata in NumPy arrays, a free list,
    and a namespace -> slots registry (chunk lists, O(namespaces)) for
    window expiry. The fallback where the native index
    (:class:`NativeSlotIndex`) is unavailable, with identical results.

    Capacity growth is signalled via ``on_grow(old, new)`` so the owner can
    resize device arrays in lockstep.
    """

    def __init__(self, capacity: int,
                 on_grow: Optional[Callable[[int, int], None]] = None,
                 growable: bool = True,
                 full_hint: str = "raise state.slot-table.capacity",
                 max_capacity: int = 0,
                 track_namespaces: bool = True) -> None:
        self.capacity = max(int(capacity), 1024)
        self.on_grow = on_grow
        self.growable = growable
        self.full_hint = full_hint
        self.max_capacity = int(max_capacity or 0)
        self._index: Dict[Tuple[int, int], int] = {}
        self.slot_key = np.zeros(self.capacity, dtype=np.int64)
        self.slot_ns = np.zeros(self.capacity, dtype=np.int64)
        self.slot_used = np.zeros(self.capacity, dtype=bool)
        self._free: List[int] = list(range(self.capacity - 1, 0, -1))
        self._ns_slots: Dict[int, List[np.ndarray]] = {}
        #: False = the owner frees by SLOT and never asks for a
        #: namespace's slot list — skip the per-namespace bookkeeping
        #: entirely (the session tables: one row per ns, millions of ns;
        #: registry upkeep was O(sessions) Python per batch)
        self._track_ns = track_namespaces
        #: (key, namespace) pairs ``lookup_or_insert`` has given a new
        #: slot so far (the table states one batch's growth as its
        #: ``prep.resolve`` span's work)
        self.pairs_inserted = 0
        #: pairs that left with their namespace's whole table: none here,
        #: this index erases pair by pair (see ``NativeSlotIndex``)
        self.pairs_dropped = 0
        #: rows the carried fire matrix swept out so far: keys whose last
        #: cell left with its slice (:meth:`slice_matrix`)
        self.carry_rows_removed = 0
        #: the last fired window's slot matrix, carried to the next fire
        #: (:meth:`slice_matrix`); made on the first fire
        self._slice_carry: Optional[_DictSliceCarry] = None

    @property
    def num_used(self) -> int:
        return int(self.slot_used.sum())

    # ------------------------------------------- namespace -> slots registry

    @property
    def namespaces(self) -> List[int]:
        return list(self._ns_slots.keys())

    def slots_for_namespace(self, ns: int) -> np.ndarray:
        chunks = self._ns_slots.get(ns)
        if not chunks:
            return np.empty(0, dtype=np.int32)
        if len(chunks) > 1:
            # merged IN PLACE: a namespace's list object lives from its
            # first slot to its drain, which is how the carried fire
            # matrix tells a namespace from a later one of the same name
            chunks[:] = [np.concatenate(chunks)]
        return chunks[0]

    def slice_matrix(self, slice_ends
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
        """``(keys, [rows, width] slot matrix, cells resolved)`` over
        the ``k`` slices of a window: one row per key that holds a slot
        in any of them, the row's live slots in its first columns and the
        identity slot 0 behind them. Row order is arbitrary, and **column
        order is not the slices'**: a row's live cells stand left of
        every zero (in the order of their slices) and the columns past
        what the fullest row needs are left off — ``width`` is
        :func:`fire_matrix_width` of the fullest row, 2 where every key
        lives in one or two of the slices, ``k`` where some key lives in
        all of them (the matrix then goes out a column per slice, as it
        is kept). That is sound because all a fire does with a row is
        merge its cells with a commutative reduction (``MERGE_FN``: sum,
        max, min) whose identity slot 0 holds: the same cells in other
        columns, and fewer identities, reduce to the same value — bit for
        bit for integers, max and min; a float sum adds the same live
        values in the same order (a zero adds exactly) but XLA may pair
        them differently over another width.

        The matrix is carried from one call to the next. Where the
        slices asked for are the last call's moved on by some slices (or
        the same ones), the columns that left are dropped, rows left
        empty go, and only the cells that entered are resolved: the new
        slices' and whatever was appended to a kept slice since (a
        namespace's list only ever grows at its end until it is drained,
        so a consumed length per kept slice finds them). Anything else —
        other slices, a kept namespace drained since (a re-made one is
        another list object), a per-slot free — resolves every cell, from
        nothing. Either way the result is what a rebuild gives, and the
        arrays handed out are the caller's: no later call writes them.

        "Rows left empty go" is the part whose cost depends on the
        stream: a key that holds no slot in any kept slice gives up its
        row, so the key -> row table is rebuilt over the rows that stay
        (the native carry deletes each such key and moves the last row
        into its place). Under keys that live for the whole run that is
        a few rows per fire; under keys that live in one slice every row
        enters with one fire and leaves ``k`` fires later. The rows a
        call returned and the rows it swept out are stated by the
        owner's ``carry.rows`` / ``carry.removed`` instants (the latter
        from ``carry_rows_removed``)."""
        ends = [int(se) for se in slice_ends]
        k = len(ends)
        carry = self._slice_carry
        if carry is None:
            carry = self._slice_carry = _DictSliceCarry()
        reg = self._ns_slots
        shift = k
        if len(carry.ends) == k:
            for s in range(k):
                if carry.ends[s:] == ends[:k - s]:
                    shift = s
                    break
        if any(carry.consumed[j + shift]
               and reg.get(ends[j]) is not carry.lists[j + shift]
               for j in range(k - shift)):
            shift = k
        parts: List[Tuple[int, np.ndarray]] = []
        lists, consumed = [], []
        for j, se in enumerate(ends):
            slots = self.slots_for_namespace(se)
            seen = carry.consumed[j + shift] if j < k - shift else 0
            if len(slots) > seen:
                parts.append((j, slots[seen:]))
            lists.append(reg.get(se))
            consumed.append(len(slots))
        carry.ends, carry.lists, carry.consumed = ends, lists, consumed
        keys, matrix, removed = carry.advance(k, shift, parts,
                                              self.slot_key)
        self.carry_rows_removed += removed
        return keys, matrix, sum(len(slots) for _, slots in parts)

    def _registry_drain(self, namespaces: List[int]) -> Optional[np.ndarray]:
        """Remove and return all slots registered under ``namespaces``."""
        freed: List[np.ndarray] = []
        for ns in namespaces:
            chunks = self._ns_slots.pop(ns, None)
            if chunks:
                freed.extend(chunks)
        if not freed:
            return None
        return np.concatenate(freed)

    def _registry_remove_slots(self, slots: np.ndarray,
                               namespaces: np.ndarray) -> None:
        """Remove individual slots from their namespaces' chunk lists
        (TTL expiry and paged eviction free by slot, not by whole
        namespace)."""
        if not self._track_ns:
            return
        # a list thinned in its middle is no longer append-only
        self._slice_carry = None
        uniq, counts = np.unique(namespaces, return_counts=True)
        slots_per_ns = dict(zip(uniq.tolist(), counts.tolist()))
        for ns, freed_here in slots_per_ns.items():
            chunks = self._ns_slots.get(int(ns))
            if not chunks:
                continue
            total = (len(chunks[0]) if len(chunks) == 1
                     else sum(len(c) for c in chunks))
            if total <= freed_here:
                # every slot of the namespace is being freed (the session
                # case: one slot per sid) — O(1), no membership scan
                self._ns_slots.pop(int(ns), None)
                continue
            merged = np.concatenate(chunks) if len(chunks) > 1 \
                else chunks[0]
            kept = merged[~np.isin(merged, slots)]
            if len(kept):
                self._ns_slots[int(ns)] = [kept]
            else:
                self._ns_slots.pop(int(ns), None)

    # ---------------------------------------------------- pairs <-> slots

    def lookup_or_insert(self, key_ids: np.ndarray,
                         namespaces: np.ndarray) -> np.ndarray:
        """Vectorized (key, ns) -> slot mapping; allocates missing slots.

        The per-unique-pair Python dict probe is the only scalar loop on the
        hot path (bounded by distinct keys per batch, not records).
        """
        uk, un, inverse = unique_pairs(
            np.asarray(key_ids, dtype=np.int64),
            np.asarray(namespaces, dtype=np.int64),
        )
        m = len(uk)
        uslots = np.empty(m, dtype=np.int32)
        index = self._index
        new_by_ns: Dict[int, List[int]] = {}
        for j in range(m):
            pair = (int(uk[j]), int(un[j]))
            slot = index.get(pair)
            if slot is None:
                slot = self._allocate()
                index[pair] = slot
                self.slot_key[slot] = pair[0]
                self.slot_ns[slot] = pair[1]
                self.slot_used[slot] = True
                new_by_ns.setdefault(pair[1], []).append(slot)
                self.pairs_inserted += 1
            uslots[j] = slot
        if self._track_ns:
            for ns, slots in new_by_ns.items():
                self._ns_slots.setdefault(ns, []).append(
                    np.asarray(slots, dtype=np.int32))
        return uslots[inverse]

    def lookup(self, key_ids: np.ndarray,
               namespaces: np.ndarray) -> np.ndarray:
        """Read-only probe: slot per pair, -1 where absent (the queryable-
        state point-lookup path — never allocates)."""
        keys = np.asarray(key_ids, dtype=np.int64)
        nss = np.asarray(namespaces, dtype=np.int64)
        out = np.empty(len(keys), dtype=np.int32)
        index = self._index
        for j in range(len(keys)):
            out[j] = index.get((int(keys[j]), int(nss[j])), -1)
        return out

    def _allocate(self) -> int:
        if not self._free:
            self._grow()
        return self._free.pop()

    def _grow(self) -> None:
        # grow by doubling, clamped to max_capacity (matches the native
        # index): refusing a partial last step would make free_headroom
        # over-report and strand a mid-batch insert
        if not self.growable or (
                self.max_capacity and self.capacity >= self.max_capacity):
            raise SlotTableFullError(
                f"slot table full (capacity={self.capacity}) and not "
                f"growable; {self.full_hint}")
        old = self.capacity
        new_capacity = old * 2
        if self.max_capacity:
            new_capacity = min(new_capacity, self.max_capacity)
        extra = new_capacity - old
        self.slot_key = np.concatenate(
            [self.slot_key, np.zeros(extra, dtype=np.int64)])
        self.slot_ns = np.concatenate(
            [self.slot_ns, np.zeros(extra, dtype=np.int64)])
        self.slot_used = np.concatenate(
            [self.slot_used, np.zeros(extra, dtype=bool)])
        self._free.extend(range(new_capacity - 1, old - 1, -1))
        self.capacity = new_capacity
        if self.on_grow is not None:
            self.on_grow(old, new_capacity)

    def free_namespaces(self, namespaces: List[int]) -> Optional[np.ndarray]:
        """Release all slots of the given namespaces. Returns freed slots."""
        slots = self._registry_drain(namespaces)
        if slots is None:
            return None
        index = self._index
        sk, sn = self.slot_key, self.slot_ns
        for s in slots.tolist():
            index.pop((int(sk[s]), int(sn[s])), None)
        self.slot_used[slots] = False
        self._free.extend(slots.tolist())
        return slots

    def free_slots(self, slots: np.ndarray, keys=None, nss=None) -> None:
        """Release individual slots (TTL expiry — by entry, not by
        namespace). ``keys``/``nss`` let a caller that already holds the
        slots' pair columns skip the per-slot metadata gather."""
        slots = np.asarray(slots, dtype=np.int32)
        if not len(slots):
            return
        if nss is None:
            nss = self.slot_ns[slots]
        self._registry_remove_slots(slots, nss)
        if keys is None:
            keys = self.slot_key[slots]
        index = self._index
        for k, v in zip(np.asarray(keys).tolist(),
                        np.asarray(nss).tolist()):
            index.pop((int(k), int(v)), None)
        self.slot_used[slots] = False
        self._free.extend(slots.tolist())

    def used_slots(self) -> np.ndarray:
        return np.nonzero(self.slot_used)[0]

    def free_headroom(self) -> int:
        """Slots still allocatable (incl. future growth). Slot 0 reserved."""
        if self.growable:
            limit = self.max_capacity if self.max_capacity else (1 << 60)
        else:
            limit = self.capacity
        return limit - 1 - self.num_used


#: hoisted ctypes pointer types for the native probe wrappers — one
#: construction per process instead of several per call (the native
#: index is probed tens of thousands of times per bench second)
_I64P = _ct.POINTER(_ct.c_int64)
_I32P = _ct.POINTER(_ct.c_int32)
_U8P = _ct.POINTER(_ct.c_uint8)


class _NativeSliceCarry:
    """Handle of the carried matrix ``native/slotmap.cpp`` keeps for
    :meth:`NativeSlotIndex.slice_matrix` (keys, matrix, a key -> row table
    and what it consumed of each slice's table), with what sizes the next
    call's output: the rows it held and the index's ``pairs_inserted``
    then."""

    def __init__(self, lib) -> None:
        self._lib = lib
        self.h = lib.sm_carry_create()
        self.rows = 0
        self.inserted = 0

    def __del__(self):  # pragma: no cover - finalizer
        lib, h = getattr(self, "_lib", None), getattr(self, "h", None)
        if lib is not None and h:
            lib.sm_carry_destroy(h)
            self.h = None


class NativeSlotIndex:
    """C++-backed drop-in for HostSlotIndex (see native/slotmap.cpp).

    Slot metadata lives in C++-owned arrays exposed to NumPy zero-copy;
    every batch entry is one foreign call (each one is a GIL hand-over on
    the task loop). What the owner does chooses the map's form, once, here:

    - ``track_namespaces=True`` (the owner frees by namespace: window
      slices, keyed state, pane columns): one table per namespace, keyed
      by the key alone, with the namespace's slots dense in insertion
      order beside it — that table is the namespace registry. A retire
      drops tables (``free_namespaces`` → ``sm_drop_namespaces``), the
      fire's resolve reads a table's own arrays (``slice_matrix`` →
      ``sm_carry_advance``), a probe touches its slice's table alone.
    - ``track_namespaces=False`` (the owner frees by slot: the session
      tables, one row per namespace): one flat (key, namespace) table; no
      namespace is tracked, ``namespaces`` is empty.
    """

    def __init__(self, capacity: int,
                 on_grow: Optional[Callable[[int, int], None]] = None,
                 growable: bool = True,
                 full_hint: str = "raise state.slot-table.capacity",
                 max_capacity: int = 0,
                 track_namespaces: bool = True) -> None:
        from flink_tpu.native import load_slotmap

        self._lib = load_slotmap()
        assert self._lib is not None
        self.capacity = max(int(capacity), 1024)
        self.on_grow = on_grow
        self.growable = growable
        self.full_hint = full_hint
        self.max_capacity = int(max_capacity or 0)
        max_cap = (self.max_capacity or (1 << 28)) if growable \
            else self.capacity
        self._track_ns = track_namespaces
        self._h = self._lib.sm_create(self.capacity, max_cap,
                                      int(track_namespaces))
        self._wrap_views()
        #: (key, namespace) pairs given a new slot so far (as
        #: ``HostSlotIndex.pairs_inserted``)
        self.pairs_inserted = 0
        #: pairs that left with their namespace's whole table
        #: (``free_namespaces``): no hash, gather or shift per pair
        self.pairs_dropped = 0
        #: rows the carried fire matrix swept out so far (as
        #: ``HostSlotIndex.carry_rows_removed``)
        self.carry_rows_removed = 0
        self._slice_carry: Optional[_NativeSliceCarry] = None
        # _resolve_grouped's [3, max_uniq] namespaces / records / new
        # pairs of each, kept from batch to batch (a fresh buffer per
        # batch costs more in page faults than the sweep's arithmetic),
        # and the distinct count
        self._sweep_groups = np.empty(0, dtype=np.int64)
        self._sweep_k = _ct.c_int64()
        self._carry_cells = _ct.c_int64()
        self._carry_removed = _ct.c_int64()
        self._carry_width = _ct.c_int64()

    def _wrap_views(self) -> None:
        cap = int(self._lib.sm_capacity(self._h))
        self.capacity = cap
        self.slot_key = np.ctypeslib.as_array(
            self._lib.sm_slot_keys(self._h), shape=(cap,))
        self.slot_ns = np.ctypeslib.as_array(
            self._lib.sm_slot_namespaces(self._h), shape=(cap,))
        self.slot_used = np.ctypeslib.as_array(
            self._lib.sm_slot_used(self._h), shape=(cap,)).view(bool)
        # where the native side writes a namespace's slots (every slot
        # the index could hold; pages are touched as far as written)
        self._slots_out = np.empty(cap, dtype=np.int32)

    def __del__(self):  # pragma: no cover - finalizer
        lib, h = getattr(self, "_lib", None), getattr(self, "_h", None)
        if lib is not None and h:
            lib.sm_destroy(h)
            self._h = None

    @property
    def num_used(self) -> int:
        return int(self._lib.sm_used(self._h))

    # ------------------------------------------------ a namespace's table

    @property
    def namespaces(self) -> List[int]:
        """The live namespaces, in the order they were first given a
        slot."""
        out = np.empty(int(self._lib.sm_namespace_count(self._h)),
                       dtype=np.int64)
        self._lib.sm_namespaces(self._h, out.ctypes.data_as(_I64P))
        return out.tolist()

    def slots_for_namespace(self, ns: int) -> np.ndarray:
        """The namespace's slots in the order they were given out (a
        copy: the caller's)."""
        out = self._slots_out
        n = self._lib.sm_namespace_slots(
            self._h, int(ns), out.ctypes.data_as(_I32P), len(out))
        return out[:n].copy()

    def slice_matrix(self, slice_ends
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
        """As :meth:`HostSlotIndex.slice_matrix`, in one foreign call
        (``sm_carry_advance``): the cells that entered are read from each
        slice's own table, from where the carried matrix stopped; a table's
        generation tells a namespace from a later one of the same name,
        and any per-slot free starts the matrix from nothing. The copy
        handed out is packed and cut as there (``carry_copy_out``: live
        cells left of every zero, ``width`` columns, **column order not
        the slices'** — sound for the fire's commutative merges with the
        identity at slot 0); the carry's own matrix keeps a column per
        slice.

        "Rows left empty go" costs, per row, a backward-shift delete in
        the carry's key -> row table and the move of the last row into
        the hole (``carry_remove_row``), and its key's return later a
        fresh-row insert: a few hundred per fire where keys live for the
        whole run, every row of the slice that left where a key lives in
        one slice. ``carry_rows_removed`` counts them; the owner states
        each call's rows and removals as ``carry.rows`` /
        ``carry.removed`` instants."""
        ends = np.ascontiguousarray(slice_ends, dtype=np.int64)
        k = len(ends)
        carry = self._slice_carry
        if carry is None:
            carry = self._slice_carry = _NativeSliceCarry(self._lib)
        # the rows held plus one per pair inserted since covers an
        # advance; a rebuild says how many it needs and is asked again
        bound = carry.rows + self.pairs_inserted - carry.inserted
        while True:
            keys = np.empty(bound, dtype=np.int64)
            matrix = np.empty(bound * k, dtype=np.int32)
            rows = self._lib.sm_carry_advance(
                carry.h, self._h, k, ends.ctypes.data_as(_I64P), bound,
                keys.ctypes.data_as(_I64P), matrix.ctypes.data_as(_I32P),
                _ct.byref(self._carry_cells),
                _ct.byref(self._carry_removed),
                _ct.byref(self._carry_width))
            if rows >= 0:
                break
            bound = -rows
        carry.rows, carry.inserted = rows, self.pairs_inserted
        self.carry_rows_removed += self._carry_removed.value
        width = self._carry_width.value
        return (keys[:rows], matrix[:rows * width].reshape(rows, width),
                self._carry_cells.value)

    def free_namespaces(self, namespaces: List[int]) -> Optional[np.ndarray]:
        """Release all slots of the given namespaces: each one's table is
        dropped whole, one foreign call for them all. Returns the freed
        slots (None where the namespaces hold none)."""
        nss = np.ascontiguousarray(namespaces, dtype=np.int64)
        out = self._slots_out
        n = self._lib.sm_drop_namespaces(
            self._h, len(nss), nss.ctypes.data_as(_I64P),
            out.ctypes.data_as(_I32P))
        if not n:
            return None
        self.pairs_dropped += n
        return out[:n].copy()

    # ---------------------------------------------------- pairs <-> slots

    def lookup_or_insert(self, key_ids: np.ndarray,
                         namespaces: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(key_ids, dtype=np.int64)
        nss = np.ascontiguousarray(namespaces, dtype=np.int64)
        n = len(keys)
        if self._track_ns:
            # width 0: the values are the namespaces; any number of
            # distinct ones is taken
            return self._resolve_grouped(keys, nss, 0, 0, 0, max(n, 1))[0]
        out = np.empty(n, dtype=np.int32)
        is_new = np.empty(n, dtype=np.uint8)
        old_cap = self.capacity
        rc = self._lib.sm_lookup_or_insert(
            self._h, n,
            keys.ctypes.data_as(_I64P), nss.ctypes.data_as(_I64P),
            out.ctypes.data_as(_I32P), is_new.ctypes.data_as(_U8P))
        self._settle(rc, old_cap)
        self.pairs_inserted += int(np.count_nonzero(is_new))
        return out

    #: distinct slice ends beyond which :meth:`resolve_slices` leaves a
    #: batch to the caller's own path (timestamps wildly out of order)
    MAX_SWEPT_SLICES = 1024

    def resolve_slices(self, key_ids: np.ndarray, timestamps: np.ndarray,
                       offset: int, slice_width: int, live_from: int
                       ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]]:
        """``lookup_or_insert`` of a batch under its records' slice ends,
        straight from the timestamps: ``(slots, distinct slice ends
        ascending, records per distinct slice)``. The slice end is the
        assigner's (``ts - (ts - offset) mod slice_width + slice_width``).
        None, with nothing changed, where a slice end lies below
        ``live_from`` (a late record: the caller's own path drops and
        counts it) or the batch holds more than ``MAX_SWEPT_SLICES``
        distinct ones."""
        return self._resolve_grouped(
            np.ascontiguousarray(key_ids, dtype=np.int64),
            np.ascontiguousarray(timestamps, dtype=np.int64),
            int(offset), int(slice_width), int(live_from),
            self.MAX_SWEPT_SLICES)

    def _resolve_grouped(self, keys: np.ndarray, vals: np.ndarray,
                         offset: int, width: int, live_from: int,
                         max_uniq: int):
        """One ``sm_resolve_grouped`` call (native/slotmap.cpp): every
        record's slot, a new pair appended to its namespace's table in
        record order. One foreign call per batch (each is a GIL hand-over
        on the task loop). ``slots`` is the caller's; the per-namespace
        counts land in a buffer the index keeps."""
        n = len(keys)
        if len(vals) != n:
            raise ValueError(
                f"{n} keys against {len(vals)} timestamps / namespaces")
        slots = np.empty(n, dtype=np.int32)
        if 3 * max_uniq > len(self._sweep_groups):
            self._sweep_groups = np.empty(3 * max_uniq, dtype=np.int64)
        groups = self._sweep_groups
        old_cap = self.capacity
        rc = self._lib.sm_resolve_grouped(
            self._h, n, keys.ctypes.data_as(_I64P),
            vals.ctypes.data_as(_I64P), offset, width, live_from, max_uniq,
            slots.ctypes.data_as(_I32P), groups.ctypes.data_as(_I64P),
            _ct.byref(self._sweep_k))
        if rc == -2:
            return None
        k = self._sweep_k.value
        ends = groups[:k].copy()
        records = groups[max_uniq:max_uniq + k].copy()
        self.pairs_inserted += int(
            groups[2 * max_uniq:2 * max_uniq + k].sum())
        self._settle(rc, old_cap)
        return slots, ends, records

    def _settle(self, rc: int, old_cap: int) -> None:
        """After an inserting call returned ``rc``: a growth is passed on
        to the owner — also one that happened before the index filled
        (what was inserted until then stays, so index and the owner's
        arrays stay level) — and a full index raises."""
        if rc > 0 or (rc < 0 and int(self._lib.sm_capacity(self._h))
                      != old_cap):
            self._wrap_views()
            if self.on_grow is not None:
                self.on_grow(old_cap, self.capacity)
        if rc < 0:
            raise SlotTableFullError(
                f"slot table full (capacity={self.capacity}) and not "
                f"growable; {self.full_hint}")

    def pane_ingest(self, key_ids: np.ndarray, timestamps: np.ndarray,
                    offset: int, width: int, max_uniq: int = 4096):
        """Fused pane-table ingest (native/slotmap.cpp sm_pane_ingest):
        one native sweep computes slice ends, the key -> column probe
        (namespace 0: all pane-table entries live there) and the
        distinct-slice-end plan that previously took five separate numpy
        passes. Returns (cols, sinv, uniq, max_col) or None when the batch
        has pathologically many distinct slice ends (caller falls back to
        the unfused path)."""
        keys = np.ascontiguousarray(key_ids, dtype=np.int64)
        ts = np.ascontiguousarray(timestamps, dtype=np.int64)
        n = len(keys)
        cols = np.empty(n, dtype=np.int32)
        is_new = np.empty(n, dtype=np.uint8)
        sinv = np.empty(n, dtype=np.int32)
        uniq = np.empty(max_uniq, dtype=np.int64)
        out_k = _ct.c_int64()
        out_max_col = _ct.c_int64()
        old_cap = self.capacity
        rc = self._lib.sm_pane_ingest(
            self._h, n, keys.ctypes.data_as(_I64P), ts.ctypes.data_as(_I64P),
            int(offset), int(width), int(max_uniq),
            cols.ctypes.data_as(_I32P), is_new.ctypes.data_as(_U8P),
            sinv.ctypes.data_as(_I32P), uniq.ctypes.data_as(_I64P),
            _ct.byref(out_k), _ct.byref(out_max_col))
        if rc == -2:
            return None
        self._settle(rc, old_cap)
        return cols, sinv, uniq[:out_k.value], int(out_max_col.value)

    def flat_fuse(self, cols: np.ndarray, sinv: np.ndarray,
                  rowmap: np.ndarray, capacity: int) -> np.ndarray:
        """flat[i] = rowmap[sinv[i]] * capacity + cols[i] as int32, in one
        native pass (sm_flat_fuse)."""
        n = len(cols)
        out = np.empty(n, dtype=np.int32)
        rowmap = np.ascontiguousarray(rowmap, dtype=np.int64)
        self._lib.sm_flat_fuse(
            n, cols.ctypes.data_as(_I32P), sinv.ctypes.data_as(_I32P),
            rowmap.ctypes.data_as(_I64P), int(capacity),
            out.ctypes.data_as(_I32P))
        return out

    def lookup(self, key_ids: np.ndarray,
               namespaces: np.ndarray) -> np.ndarray:
        """Read-only probe via the native table: -1 where absent."""
        keys = np.ascontiguousarray(key_ids, dtype=np.int64)
        nss = np.ascontiguousarray(namespaces, dtype=np.int64)
        out = np.empty(len(keys), dtype=np.int32)
        self._lib.sm_lookup(self._h, len(keys),
                            keys.ctypes.data_as(_I64P),
                            nss.ctypes.data_as(_I64P),
                            out.ctypes.data_as(_I32P))
        return out

    def verify_hints(self, key_ids: np.ndarray, namespaces: np.ndarray,
                     hints: np.ndarray) -> np.ndarray:
        """Native form of :func:`verify_slot_hints` — one direct-indexed
        C pass over the table's own metadata (sm_verify)."""
        keys = np.ascontiguousarray(key_ids, dtype=np.int64)
        nss = np.ascontiguousarray(namespaces, dtype=np.int64)
        hints = np.ascontiguousarray(hints, dtype=np.int32)
        out = np.empty(len(keys), dtype=np.int32)
        self._lib.sm_verify(self._h, len(keys),
                            keys.ctypes.data_as(_I64P),
                            nss.ctypes.data_as(_I64P),
                            hints.ctypes.data_as(_I32P),
                            out.ctypes.data_as(_I32P))
        return out

    def free_slots(self, slots: np.ndarray, keys=None, nss=None) -> None:
        """Release individual slots (TTL expiry, paged eviction, fired
        sessions) via the native erase: each pair leaves its namespace's
        table, or the flat one. ``keys``/``nss`` let a caller that already
        holds the slots' pair columns skip the per-slot metadata
        gathers."""
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        if not len(slots):
            return
        if nss is None:
            nss = self.slot_ns[slots]
        if keys is None:
            keys = self.slot_key[slots]
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        nss = np.ascontiguousarray(nss, dtype=np.int64)
        out = np.empty(len(slots), dtype=np.int32)
        self._lib.sm_erase(
            self._h, len(slots),
            keys.ctypes.data_as(_I64P), nss.ctypes.data_as(_I64P),
            out.ctypes.data_as(_I32P))

    def used_slots(self) -> np.ndarray:
        return np.nonzero(self.slot_used)[0]

    def free_headroom(self) -> int:
        """Slots still allocatable (incl. future growth). Slot 0 reserved."""
        if self.growable:
            limit = self.max_capacity if self.max_capacity else (1 << 28)
        else:
            limit = self.capacity
        return limit - 1 - self.num_used


def resolve_slices_sharded(indexes: List, key_ids: np.ndarray,
                           timestamps: np.ndarray, group_shard: np.ndarray,
                           offset: int, slice_width: int, live_from: int,
                           dirty: Optional[np.ndarray] = None):
    """:meth:`NativeSlotIndex.resolve_slices` of one batch over a mesh's
    shard indexes, in one foreign call (``sm_resolve_grouped_sharded``,
    native/slotmap.cpp): a record goes straight to the index of its own
    shard, ``group_shard[key group of its key]`` (int32 per key group, -1
    where a group has no shard here). Each index is left as
    ``lookup_or_insert`` of its own records, in record order, leaves it.
    ``dirty``, a ``[len(indexes), capacity]`` bool map, gets every
    record's slot marked in its shard's row; after a growth inside the
    call the owner's ``on_grow`` has run and the marks are its to repeat.

    ``(shards, slots, distinct slice ends ascending, records per distinct
    slice, new pairs)``, shards and slots per record; None, with nothing
    changed, where an index is not a native slice-partitioned one, a
    slice end lies below ``live_from``, the batch holds more than
    ``MAX_SWEPT_SLICES`` distinct ones or a key's group has no shard."""
    if not all(type(idx) is NativeSlotIndex and idx._track_ns
               for idx in indexes):
        return None
    first, shard_count = indexes[0], len(indexes)
    keys = np.ascontiguousarray(key_ids, dtype=np.int64)
    ts = np.ascontiguousarray(timestamps, dtype=np.int64)
    group_shard = np.ascontiguousarray(group_shard, dtype=np.int32)
    n = len(keys)
    if len(ts) != n:
        raise ValueError(f"{n} keys against {len(ts)} timestamps")
    if dirty is not None and not (dirty.flags.c_contiguous
                                  and dirty.dtype == np.bool_):
        raise ValueError("dirty must be a C-contiguous bool map")
    max_uniq = NativeSlotIndex.MAX_SWEPT_SLICES
    if 3 * max_uniq > len(first._sweep_groups):
        first._sweep_groups = np.empty(3 * max_uniq, dtype=np.int64)
    groups = first._sweep_groups
    shards = np.empty(n, dtype=np.int32)
    slots = np.empty(n, dtype=np.int32)
    per_shard = np.empty((2, shard_count), dtype=np.int64)
    old_caps = [idx.capacity for idx in indexes]
    rc = first._lib.sm_resolve_grouped_sharded(
        (_ct.c_void_p * shard_count)(*[idx._h for idx in indexes]),
        shard_count, n, keys.ctypes.data_as(_I64P), ts.ctypes.data_as(_I64P),
        group_shard.ctypes.data_as(_I32P), len(group_shard), int(offset),
        int(slice_width), int(live_from), max_uniq,
        None if dirty is None else dirty.ctypes.data_as(_U8P),
        0 if dirty is None else dirty.shape[1],
        shards.ctypes.data_as(_I32P), slots.ctypes.data_as(_I32P),
        groups.ctypes.data_as(_I64P), _ct.byref(first._sweep_k),
        per_shard.ctypes.data_as(_I64P))
    if rc == -2:
        return None
    k = first._sweep_k.value
    ends, records = groups[:k].copy(), groups[max_uniq:max_uniq + k].copy()
    full = None
    for idx, fresh, grows, old_cap in zip(
            indexes, per_shard[0].tolist(), per_shard[1].tolist(), old_caps):
        idx.pairs_inserted += fresh
        try:
            idx._settle(grows, old_cap)
        except SlotTableFullError as e:
            # the others' growth reaches their owners first
            full = e
    if full is not None:
        raise full
    return shards, slots, ends, records, int(per_shard[0].sum())


def make_slot_index(capacity: int, on_grow=None, growable: bool = True,
                    full_hint: str = "raise state.slot-table.capacity",
                    max_capacity: int = 0,
                    track_namespaces: bool = True):
    """Native index when the C++ library is available, else pure Python."""
    from flink_tpu.native import slotmap_available

    cls = NativeSlotIndex if slotmap_available() else HostSlotIndex
    return cls(capacity, on_grow=on_grow, growable=growable,
               full_hint=full_hint, max_capacity=max_capacity,
               track_namespaces=track_namespaces)


class SpillTier:
    """Beyond-HBM state: whole namespaces evicted from the device table.

    Two levels — host memory, then a filesystem directory (any ``core.fs``
    scheme) once the host budget is exceeded. This is the role RocksDB /
    ForSt play for the reference (state far larger than memory,
    reference: RocksDBKeyedStateBackend.java;
    ForStStateExecutor.java:149 batch contract); the unit of movement is a
    namespace (window slice / session id), not a key, so reloads are one
    batched put kernel.
    """

    def __init__(self, spill_dir: Optional[str] = None,
                 host_max_bytes: int = 0):
        self.spill_dir = spill_dir
        self.host_max_bytes = host_max_bytes
        self._host: Dict[int, Dict[str, np.ndarray]] = {}
        self._host_bytes = 0
        self._fs: Dict[int, str] = {}  # ns -> file path
        self._dirty: set = set()  # namespaces changed since last snapshot
        self._seq = 0
        #: ns -> row count, maintained across host/fs moves so batch
        #: planners can estimate reload cost without touching the fs
        self._rows: Dict[int, int] = {}

    def __len__(self) -> int:
        return len(self._host) + len(self._fs)

    def __contains__(self, ns: int) -> bool:
        return ns in self._host or ns in self._fs

    @property
    def namespaces(self) -> List[int]:
        return list(self._host) + list(self._fs)

    @staticmethod
    def _entry_bytes(entry: Dict[str, np.ndarray]) -> int:
        return sum(a.nbytes for a in entry.values())

    def put(self, ns: int, entry: Dict[str, np.ndarray],
            dirty: bool) -> None:
        assert ns not in self, f"namespace {ns} spilled twice"
        self._host[ns] = entry
        self._host_bytes += self._entry_bytes(entry)
        self._rows[ns] = len(entry["key_id"])
        if dirty:
            self._dirty.add(ns)
        self._maybe_overflow_to_fs()

    def rows(self, ns: int) -> int:
        """Row count of a spilled namespace (0 if absent) — an O(1) read
        that never touches the filesystem."""
        return self._rows.get(ns, 0)

    def _maybe_overflow_to_fs(self) -> None:
        if not self.spill_dir or self.host_max_bytes <= 0:
            return
        from flink_tpu.core.fs import get_filesystem

        fs, local = get_filesystem(self.spill_dir)
        fs.mkdirs(local)
        while self._host_bytes > self.host_max_bytes and self._host:
            ns, entry = next(iter(self._host.items()))
            import io as _io

            buf = _io.BytesIO()
            np.savez(buf, **entry)
            self._seq += 1
            path = f"{local.rstrip('/')}/ns-{ns}-{self._seq}.npz"
            with fs.open(path, "wb") as f:
                f.write(buf.getvalue())
            self._fs[ns] = f"{self._scheme_prefix()}{path}"
            self._host_bytes -= self._entry_bytes(entry)
            del self._host[ns]

    def _scheme_prefix(self) -> str:
        if self.spill_dir and "://" in self.spill_dir:
            return self.spill_dir.split("://", 1)[0] + "://"
        return ""

    def pop(self, ns: int) -> Optional[Dict[str, np.ndarray]]:
        """Remove and return a spilled namespace (reload or free)."""
        entry = self._host.pop(ns, None)
        if entry is not None:
            self._host_bytes -= self._entry_bytes(entry)
        elif ns in self._fs:
            from flink_tpu.core.fs import get_filesystem

            path = self._fs.pop(ns)
            fs, local = get_filesystem(path)
            with fs.open(local, "rb") as f:
                loaded = np.load(f)
                entry = {k: loaded[k] for k in loaded.files}
            fs.delete(local)
        was_dirty = ns in self._dirty
        self._dirty.discard(ns)
        self._rows.pop(ns, None)
        if entry is not None:
            entry["__was_dirty__"] = np.asarray(was_dirty)
        return entry

    def peek(self, ns: int) -> Optional[Dict[str, np.ndarray]]:
        """Read a spilled namespace without removing it (snapshots)."""
        entry = self._host.get(ns)
        if entry is not None:
            return entry
        if ns in self._fs:
            from flink_tpu.core.fs import get_filesystem

            fs, local = get_filesystem(self._fs[ns])
            with fs.open(local, "rb") as f:
                loaded = np.load(f)
                return {k: loaded[k] for k in loaded.files}
        return None

    def drop(self, ns: int) -> None:
        """Discard a spilled namespace (window fully fired elsewhere)."""
        self.pop(ns)

    def discard(self, ns: int) -> None:
        """Delete a spilled namespace WITHOUT loading it — a page in
        the fs tier is unlinked, never read/deserialized (the hot-path
        reap of fully-dead pages must not pay a wasted disk read)."""
        entry = self._host.pop(ns, None)
        if entry is not None:
            self._host_bytes -= self._entry_bytes(entry)
        elif ns in self._fs:
            from flink_tpu.core.fs import get_filesystem

            path = self._fs.pop(ns)
            fs, local = get_filesystem(path)
            fs.delete(local)
        self._dirty.discard(ns)
        self._rows.pop(ns, None)

    def dirty_namespaces(self) -> List[int]:
        return list(self._dirty)

    def clear_dirty(self) -> None:
        self._dirty.clear()


@internal
class SlotTable:
    """Single-device keyed windowed state (host index + device accumulators).

    With ``max_device_slots`` set, the device table is an HBM-bounded cache
    over a host/filesystem ``SpillTier``: when full, the least-recently-
    touched namespaces are evicted wholesale (one gather + one reset
    kernel) and reload transparently on the next access (one put kernel).
    """

    def __init__(
        self,
        agg: AggregateFunction,
        capacity: int = 1 << 16,
        max_parallelism: int = 128,
        device=None,
        max_device_slots: int = 0,
        spill_dir: Optional[str] = None,
        spill_host_max_bytes: int = 0,
        memory=None,
        spill_layout: str = "namespaces",
        track_namespaces: bool = True,
    ) -> None:
        self.agg = agg
        self.max_parallelism = max_parallelism
        self.device = device
        self.max_device_slots = int(max_device_slots or 0)
        if self.max_device_slots:
            capacity = min(capacity, self.max_device_slots)
        #: (MemoryManager, owner) — managed accounting of the device
        #: accumulator footprint (reference: MemoryManager.java pages;
        #: here bytes, reserved at creation and each growth)
        self._memory = memory
        self.spill = SpillTier(spill_dir, spill_host_max_bytes)
        self._ns_touch: Dict[int, int] = {}
        self._touch_clock = 0
        # Spill layout (reference: RocksDBKeyedStateBackend.java —
        # block-granular storage under a small memory budget):
        # - "namespaces" (default): the unit of movement is one namespace
        #   (a window slice shared by many keys) — right when namespaces
        #   are large and few.
        # - "pages": the unit is an EVICTION COHORT of many rows —
        #   right when namespaces are tiny and numerous (sessions: one
        #   row per session id). Residency tracking is slot-granular
        #   (a per-slot touch clock), membership is a sorted array
        #   binary-searched per batch, and spill/reload moves tens of
        #   thousands of rows per entry instead of one. REQUIRES
        #   single-row namespaces (eviction would otherwise split a
        #   namespace across the device/page boundary).
        if spill_layout not in ("namespaces", "pages"):
            raise ValueError(
                f"spill_layout must be 'namespaces' or 'pages', got "
                f"{spill_layout!r}")
        self.spill_layout = spill_layout
        self._paged = spill_layout == "pages" and self.max_device_slots > 0
        if self._paged:
            from flink_tpu.state.paged_spill import PagedSpillMap

            #: membership map + dead set + counters for the paged layout
            #: (flink_tpu.state.paged_spill — shared with the mesh engine)
            self._pmap = PagedSpillMap()
        self.index = make_slot_index(
            capacity, on_grow=self._grow_device,
            max_capacity=self.max_device_slots,
            track_namespaces=track_namespaces,
            full_hint=("state spills to host beyond "
                       "state.slot-table.max-device-slots"
                       if self.max_device_slots
                       else "raise state.slot-table.capacity"))
        self._reserve_rows(self.index.capacity)
        if self._paged:
            # sized AFTER index creation: the index clamps capacity up
            # (>= 1024), and the touch clock must cover every slot
            self._slot_touch = np.zeros(self.index.capacity,
                                        dtype=np.int64)
        self.accs: Tuple[jnp.ndarray, ...] = agg.init_accumulators(
            self.index.capacity)
        if device is not None:
            # the state backend's whole decision (state/backends.py):
            # committing the accumulators pins every kernel that touches
            # them to this device — XLA computation follows placement
            self.accs = tuple(jax.device_put(a, device) for a in self.accs)
        # buckets are sticky: once a program of bucket B compiled, nearby
        # smaller batches reuse it instead of compiling a smaller program
        # (XLA compiles dominate cold cost; padded lanes hit identity slot 0;
        # sticky_bucket caps the padding waste at 4x)
        self._fire_bucket = 0
        self._scatter_bucket = 0
        self._reset_bucket = 0
        # incremental-snapshot bookkeeping (reference: the dirty-tracking
        # role of RocksDB's memtable/SST-diff in
        # RocksIncrementalSnapshotStrategy — here a host bitmap of slots
        # touched since the last snapshot + the namespaces freed since)
        self._dirty = np.zeros(self.index.capacity, dtype=bool)
        #: namespaces freed since the last snapshot, as int64 chunks, one
        #: per free; kept only while a delta can still be asked for
        #: (keep_tombstones): nothing else reads or clears them
        self._freed_ns: List[np.ndarray] = []
        #: per-(key, ns) tombstones from TTL expiry (free_slots) — the
        #: entry-granular analog of _freed_ns for incremental snapshots
        self._freed_pairs: List[Tuple[np.ndarray, np.ndarray]] = []
        self._keep_tombstones = True
        self._gather_bucket = 0
        #: bytes of padded fire slot matrices handed to the device so
        #: far (the windower states the growth over one watermark
        #: advance as its ``fire.dispatch`` span's work)
        self.fire_matrix_bytes = 0

    # ------------------------------------------------------------- memory

    def _row_bytes(self) -> int:
        return sum(np.dtype(leaf.dtype).itemsize
                   for leaf in self.agg.leaves)

    def _reserve_rows(self, rows: int) -> None:
        if self._memory is not None:
            manager, owner = self._memory
            manager.reserve(owner, rows * self._row_bytes())

    def release_memory(self) -> None:
        """Return this table's reservation to the pool (dispose path)."""
        if self._memory is not None:
            manager, owner = self._memory
            manager.release(owner, self.index.capacity
                            * self._row_bytes())

    # ------------------------------------------------------------------ info

    @property
    def capacity(self) -> int:
        return self.index.capacity

    @property
    def num_used(self) -> int:
        return self.index.num_used

    @property
    def namespaces(self) -> List[int]:
        """All live namespaces — device-resident AND spilled."""
        if getattr(self.index, "_track_ns", True):
            resident = self.index.namespaces
        else:  # registry-free: derive from the used-slot metadata
            used = self.index.used_slots()
            resident = np.unique(self.index.slot_ns[used]).tolist()
        if self._paged:
            return resident + self._pmap.live_ns().tolist()
        return resident + self.spill.namespaces

    # ------------------------------------------------------------- main path

    def lookup_or_insert(self, key_ids: np.ndarray,
                         namespaces: np.ndarray,
                         _pairs=None, hints=None) -> np.ndarray:
        if self.max_device_slots and self._paged:
            return self._lookup_or_insert_paged(key_ids, namespaces,
                                                _pairs, hints)
        if self.max_device_slots:
            # ``_pairs`` lets upsert() hand down its already-computed
            # unique (key, ns) pairs instead of re-sorting the batch
            if _pairs is None:
                uk, un, _ = unique_pairs(
                    np.asarray(key_ids, dtype=np.int64),
                    np.asarray(namespaces, dtype=np.int64))
            else:
                uk, un = _pairs
            touched = np.unique(un)
            self.ensure_resident(touched.tolist())
            self._touch(touched.tolist())
            # headroom pre-check: lookup_or_insert allocates incrementally,
            # so running out MID-batch would leave the index and the
            # namespace registry inconsistent — make room up front for
            # exactly the pairs that are genuinely new (a read-only probe).
            # Under ample headroom (the steady-state common case) skip the
            # probe — len(uk) over-counts but cheaply proves safety.
            if self.index.free_headroom() < len(uk):
                needed = int((self.index.lookup(uk, un) < 0).sum())
                if needed:
                    self._make_headroom(needed,
                                        protect=set(touched.tolist()))
        return self.index.lookup_or_insert(key_ids, namespaces)

    def _make_headroom(self, needed: int, protect: set) -> None:
        while self.index.free_headroom() < needed:
            self._evict_cold(protect=protect)

    # --------------------------------------------------- paged spill layout

    def _lookup_or_insert_paged(self, key_ids, namespaces,
                                _pairs=None, hints=None) -> np.ndarray:
        """Slot-clock variant of the spill-aware lookup: resident rows of
        THIS batch are stamped with a fresh clock (protecting them from
        the eviction the batch itself triggers), missing pairs reload by
        page, then the plain index insert runs.

        ``hints``: folded device slots from the session-metadata plane,
        aligned with ``key_ids`` — which must then already be UNIQUE
        pairs (the session contract: one row per sid). Verified hints
        skip the hash probe; the result path inserts only the misses,
        which is state-identical to the full lookup_or_insert (hits
        never allocate) but pays the native probe only for rows whose
        fold went stale."""
        key_ids = np.asarray(key_ids, dtype=np.int64)
        namespaces = np.asarray(namespaces, dtype=np.int64)
        self._touch_clock += 1
        clock = self._touch_clock
        if hints is not None:
            uk, un = key_ids, namespaces
            pre = resolve_slot_hints(self.index, uk, un, hints)
        else:
            if _pairs is None:
                uk, un, _ = unique_pairs(key_ids, namespaces)
            else:
                uk, un = _pairs
            pre = self.index.lookup(uk, un)
        hit = pre >= 0
        self._slot_touch[pre[hit]] = clock
        missing = ~hit
        if missing.any() and len(self._sp_ns):
            self._reload_pages_for(un[missing], clock)
            # re-probe: reloaded rows are resident now (fresh sessions
            # stay missing); skipping this when the reload happened to
            # drain the spilled map would overcount `needed` and evict
            # or fail spuriously
            pre = self.index.lookup(uk, un)
            missing = pre < 0
        needed = int(missing.sum())
        if needed and self.index.free_headroom() < needed:
            self._make_headroom_paged(needed)
        if hints is not None:
            # unique pairs: hits are final, only the misses insert
            slots = pre.astype(np.int32, copy=True)
            if missing.any():
                slots[missing] = self.index.lookup_or_insert(
                    uk[missing], un[missing])
        else:
            slots = self.index.lookup_or_insert(key_ids, namespaces)
        self._slot_touch[slots] = clock
        return slots

    # compat READ views over the PagedSpillMap (tests and older callers
    # inspect the raw arrays; the map itself is the shared
    # implementation). No setters: assigning a raw array would desync
    # the tombstone mask (sp_dead) the map keeps alongside — mutate
    # through the map's API instead.
    @property
    def _sp_ns(self) -> np.ndarray:
        return self._pmap.sp_ns

    @property
    def _sp_page(self) -> np.ndarray:
        return self._pmap.sp_page

    def spill_counters(self) -> Dict[str, int]:
        """Paged spill traffic counters (zeros when not paged)."""
        from flink_tpu.state.paged_spill import PagedSpillMap

        if self._paged:
            return self._pmap.counters()
        return PagedSpillMap.zero_counters()

    def _sp_sort(self) -> None:
        self._pmap.sort()

    def _spilled_mask(self, nss: np.ndarray) -> np.ndarray:
        """Vectorized membership: which of ``nss`` are spilled."""
        return self._pmap.spilled_mask(nss)

    def _reload_pages_for(self, nss: np.ndarray, clock: int) -> None:
        """Reload the requested rows from their pages — extraction by
        stored row index; the pages' other rows stay put as lazy
        tombstones and compact only past the dead-fraction threshold
        (see flink_tpu.state.paged_spill)."""
        from flink_tpu.state.paged_spill import reload_rows_for

        rl = reload_rows_for(self.spill, self._pmap, nss,
                             [l.dtype for l in self.agg.leaves])
        if rl is None:
            return
        keys, rns, dirty, vals = rl
        n = len(keys)
        if self.index.free_headroom() < n:
            self._make_headroom_paged(n)
        slots = self.index.lookup_or_insert(keys, rns)
        size = sticky_bucket(n, self._scatter_bucket)
        self._scatter_bucket = size
        padded_slots = pad_i32(slots, size, fill=0)
        pvals = tuple(
            np.concatenate([v, np.full(size - n, l.identity,
                                       dtype=l.dtype)])
            for v, l in zip(vals, self.agg.leaves))
        self.accs = self.agg._put_jit(
            self.accs, jnp.asarray(padded_slots),
            tuple(jnp.asarray(v) for v in pvals))
        # reloaded rows keep their dirtiness (not snapshotted since) and
        # take the current clock — the cohort is likely about to fire
        self._dirty[slots] = dirty
        self._slot_touch[slots] = clock

    def _make_headroom_paged(self, needed: int) -> None:
        while self.index.free_headroom() < needed:
            self._evict_cold_paged()

    def _drop_spilled_sessions(self, nss: np.ndarray) -> None:
        """Mark spilled sessions dead; reap pages left with no live
        mapping entries (flink_tpu.state.paged_spill)."""
        if not self._paged:
            return
        from flink_tpu.state.paged_spill import drop_spilled_sessions

        drop_spilled_sessions(self.spill, self._pmap,
                              np.asarray(nss, dtype=np.int64))

    def _evict_cold_paged(self) -> None:
        """Evict the coldest slots (touch < current clock) as ONE page:
        one gather + one reset kernel + one spill entry, however many
        sessions the cohort spans."""
        used = self.index.used_slots()
        touch = self._slot_touch[used]
        evictable = used[touch < self._touch_clock]
        if len(evictable) == 0:
            raise SlotTableFullError(
                "device slot budget exhausted and every resident row was "
                "touched by the current batch — raise "
                "state.slot-table.max-device-slots or reduce batch size")
        target = min(max(self.index.capacity // 8, 1024), len(evictable))
        et = self._slot_touch[evictable]
        if target < len(evictable):
            sel = np.argpartition(et, target - 1)[:target]
            chosen = evictable[sel]
        else:
            chosen = evictable
        chosen = np.asarray(chosen, dtype=np.int32)
        n = len(chosen)
        size = sticky_bucket(n, self._gather_bucket)
        self._gather_bucket = size
        gathered = self.agg._gather_jit(
            self.accs, jnp.asarray(pad_i32(chosen, size, fill=0)))
        from flink_tpu.state.paged_spill import spill_page

        gathered_host = jax.device_get(gathered)  # ONE batched D2H
        entry = {
            "key_id": np.asarray(self.index.slot_key[chosen]),
            "ns": np.asarray(self.index.slot_ns[chosen]),
            "dirty": self._dirty[chosen].copy(),
            **{f"leaf_{i}": g[:n]
               for i, g in enumerate(gathered_host)},
        }
        spill_page(self.spill, self._pmap, entry)
        self.index.free_slots(chosen)
        self._dirty[chosen] = False
        rsize = sticky_bucket(n, self._reset_bucket)
        self._reset_bucket = rsize
        self.accs = self.agg._reset_jit(
            self.accs, pad_i32(chosen, rsize, fill=0))

    def upsert(self, key_ids: np.ndarray, namespaces: np.ndarray,
               values: Tuple[np.ndarray, ...],
               valued: bool = False) -> None:
        """Spill-safe accumulate: when one batch's working set exceeds the
        device budget, it is processed in namespace groups so only one
        group must be resident at a time (a single namespace whose key set
        alone exceeds the budget is the irreducible limit of
        namespace-granular spill and fails loudly).

        ``valued`` marks locally pre-aggregated input (one explicit value
        per leaf per row; see flink_tpu.runtime.local_agg) — folded with
        scatter_valued instead of the map_input scatter."""
        emit = self.scatter_valued if valued else self.scatter
        namespaces = np.asarray(namespaces, dtype=np.int64)
        resolve = self._resolve
        if self.max_device_slots:
            # slots are consumed per unique (key, ns) PAIR, not per record
            # — chunk only when the pair working set exceeds the budget
            pair_k, pair_ns, _ = unique_pairs(
                np.asarray(key_ids, dtype=np.int64), namespaces)
            uniq_ns, counts = np.unique(pair_ns, return_counts=True)
            budget = max(self.max_device_slots // 2, 1024)
            if len(uniq_ns) > 1 and len(pair_ns) > budget:
                groups: List[List[int]] = []
                cur: List[int] = []
                cur_n = 0
                for ns, c in zip(uniq_ns.tolist(), counts.tolist()):
                    if cur and cur_n + c > budget:
                        groups.append(cur)
                        cur, cur_n = [], 0
                    cur.append(ns)
                    cur_n += c
                groups.append(cur)
                for g in groups:
                    mask = np.isin(namespaces, g)
                    pmask = np.isin(pair_ns, g)
                    slots = resolve(
                        key_ids[mask], namespaces[mask],
                        _pairs=(pair_k[pmask], pair_ns[pmask]))
                    emit(slots, tuple(np.asarray(v)[mask]
                                      for v in values))
                return
            slots = resolve(key_ids, namespaces, _pairs=(pair_k, pair_ns))
            emit(slots, values)
            return
        slots = resolve(key_ids, namespaces)
        emit(slots, values)

    def resolve_slices(self, key_ids: np.ndarray, timestamps: np.ndarray,
                       offset: int, slice_width: int, live_from: int
                       ) -> Optional[Tuple[np.ndarray, np.ndarray, int]]:
        """``(slots, distinct slice ends, pairs newly given a slot)`` of
        a batch in the index's one native sweep over keys and timestamps
        (``NativeSlotIndex.resolve_slices``) — or None, with nothing
        changed, where the batch has to take ``upsert``'s path under
        slice ends the caller computes: the index is the Python one; the
        table has a device-slot budget (the spill tiers make a batch's
        namespaces resident before the insert); the batch holds a slice
        end below ``live_from`` (a late record, dropped and counted
        there) or too many distinct ones."""
        sweep = getattr(self.index, "resolve_slices", None)
        if sweep is None or self.max_device_slots:
            return None
        before = self.index.pairs_inserted
        swept = sweep(key_ids, timestamps, offset, slice_width, live_from)
        if swept is None:
            return None
        return swept[0], swept[1], self.index.pairs_inserted - before

    def _resolve(self, key_ids, namespaces, _pairs=None) -> np.ndarray:
        """``lookup_or_insert`` under a ``prep.resolve`` span whose work
        is the pairs newly given a slot (the index's own count: no pass
        over the batch is added to find the distinct ones)."""
        with flight.span("prep.resolve") as span:
            before = self.index.pairs_inserted
            slots = self.lookup_or_insert(key_ids, namespaces,
                                          _pairs=_pairs)
            span.work = self.index.pairs_inserted - before
        return slots

    # ------------------------------------------------------------ spill tier

    def _touch(self, namespaces: List[int]) -> None:
        self._touch_clock += 1
        clock = self._touch_clock
        for ns in namespaces:
            self._ns_touch[int(ns)] = clock

    def ensure_resident(self, namespaces: List[int]) -> None:
        """Reload any spilled namespaces among ``namespaces`` back onto the
        device — ALL reloads batch into one insert + one put kernel (a
        session workload reloads thousands of one-row namespaces at once).
        Transparent to callers: after this, the index serves them like any
        resident namespace."""
        if not self.max_device_slots or len(self.spill) == 0:
            return
        todo = [int(ns) for ns in namespaces if int(ns) in self.spill]
        if not todo:
            return
        protect = set(int(n) for n in namespaces)
        key_chunks: List[np.ndarray] = []
        ns_chunks: List[np.ndarray] = []
        dirty_chunks: List[np.ndarray] = []
        leaf_chunks: List[List[np.ndarray]] = [[] for _ in self.agg.leaves]
        for ns in todo:
            entry = self.spill.pop(ns)
            m = len(entry["key_id"])
            if m == 0:
                continue
            key_chunks.append(np.asarray(entry["key_id"], dtype=np.int64))
            ns_chunks.append(np.full(m, ns, dtype=np.int64))
            dirty_chunks.append(np.full(
                m, bool(entry.get("__was_dirty__", False)), dtype=bool))
            for i, l in enumerate(self.agg.leaves):
                leaf_chunks[i].append(
                    np.asarray(entry[f"leaf_{i}"], dtype=l.dtype))
        if not key_chunks:
            return
        key_ids = np.concatenate(key_chunks)
        nss = np.concatenate(ns_chunks)
        was_dirty = np.concatenate(dirty_chunks)
        n = len(key_ids)
        self._make_headroom(n, protect=protect)
        slots = self.index.lookup_or_insert(key_ids, nss)
        size = sticky_bucket(n, self._scatter_bucket)
        self._scatter_bucket = size
        padded_slots = pad_i32(slots, size, fill=0)
        vals = tuple(
            np.concatenate([
                np.concatenate(leaf_chunks[i]),
                np.full(size - n, l.identity, dtype=l.dtype)])
            for i, l in enumerate(self.agg.leaves))
        self.accs = self.agg._put_jit(
            self.accs, jnp.asarray(padded_slots),
            tuple(jnp.asarray(v) for v in vals))
        # reloaded rows keep their dirtiness: rows dirty at spill time have
        # not been in any snapshot since
        self._dirty[slots] = was_dirty
        self._touch(todo)

    def _evict_cold(self, protect: set) -> None:
        """Evict the least-recently-touched namespaces to the spill tier
        until a workable fraction of the device table is free — ONE gather
        + ONE reset kernel for the whole eviction batch, however many
        namespaces it spans."""
        target_free = max(self.index.capacity // 8, 1024)
        candidates = sorted(
            (ns for ns in self.index.namespaces if int(ns) not in protect),
            key=lambda ns: self._ns_touch.get(int(ns), 0))
        if not candidates:
            raise SlotTableFullError(
                "device slot budget exhausted and every namespace in the "
                "current batch is protected — raise "
                "state.slot-table.max-device-slots or reduce batch size")
        chosen: List[Tuple[int, np.ndarray]] = []
        freed = 0
        for ns in candidates:
            if freed >= target_free:
                break
            slots = self.index.slots_for_namespace(int(ns))
            chosen.append((int(ns), slots))
            freed += len(slots)
        empty = [ns for ns, s in chosen if len(s) == 0]
        if empty:
            self.index.free_namespaces(empty)
        chosen = [(ns, s) for ns, s in chosen if len(s) > 0]
        if not chosen:
            return
        all_slots = np.concatenate([s for _, s in chosen])
        n = len(all_slots)
        size = sticky_bucket(n, self._gather_bucket)
        self._gather_bucket = size
        gathered = self.agg._gather_jit(
            self.accs, jnp.asarray(pad_i32(all_slots, size, fill=0)))
        # ONE batched D2H read for all leaves
        leaves_host = [g[:n] for g in jax.device_get(gathered)]
        off = 0
        for ns, slots in chosen:
            m = len(slots)
            entry = {
                "key_id": np.asarray(self.index.slot_key[slots]),
                **{f"leaf_{i}": leaves_host[i][off:off + m]
                   for i in range(len(leaves_host))},
            }
            self.spill.put(ns, entry,
                           dirty=bool(self._dirty[slots].any()))
            off += m
            self._ns_touch.pop(ns, None)
        # release the device slots: index entries go, values reset to
        # identity. NOT a logical free — no tombstone (rows live on in the
        # spill tier and reappear in snapshots from there).
        self.index.free_namespaces([ns for ns, _ in chosen])
        self._dirty[all_slots] = False
        rsize = sticky_bucket(n, self._reset_bucket)
        self._reset_bucket = rsize
        self.accs = self.agg._reset_jit(
            self.accs, pad_i32(all_slots, rsize, fill=0))

    def _grow_device(self, old: int, new: int) -> None:
        self._reserve_rows(new - old)
        self.accs = tuple(
            jnp.concatenate(
                [a, jnp.full((new - old,), leaf.identity, dtype=leaf.dtype)])
            for a, leaf in zip(self.accs, self.agg.leaves)
        )
        self._dirty = np.concatenate(
            [self._dirty, np.zeros(new - old, dtype=bool)])
        if self._paged:
            self._slot_touch = np.concatenate(
                [self._slot_touch, np.zeros(new - old, dtype=np.int64)])

    def _staged_scatter(self, slots: np.ndarray, pad_vals, step) -> None:
        """Pad to the sticky bucket (``prep.stage``; work: the bytes
        handed to the device, padding included), then dispatch ``step``
        (``device.dispatch``)."""
        n = len(slots)
        if n == 0:
            return
        with flight.span("prep.stage") as stage:
            self._dirty[slots] = True
            size = sticky_bucket(n, self._scatter_bucket)
            self._scatter_bucket = size
            padded_slots = pad_i32(slots, size, fill=0)
            padded_vals = pad_vals(size)
            stage.work = padded_slots.nbytes + sum(
                v.nbytes for v in padded_vals)
        with flight.span("device.dispatch"):
            self.accs = step(self.accs, padded_slots, padded_vals)

    def scatter(self, slots: np.ndarray, values: Tuple[np.ndarray, ...]) -> None:
        """Accumulate a batch: one donated XLA scatter per leaf."""
        self._staged_scatter(
            slots, lambda size: self.agg.pad_input_values(values, size),
            self.agg._scatter_jit)

    def make_fence(self):
        """A tiny non-donated device value enqueued AFTER everything
        dispatched so far: its readiness proves the device (and the
        host->device copies feeding it) caught up to this point. Used to
        bound how far the task loop's async dispatch runs ahead — without
        a bound, fire kernels queue behind seconds of scatter backlog and
        fire latency grows without limit (reference: checkpoint alignment
        bounds in-flight data the same way; here the scarce resource is
        the device queue)."""
        return flat_fence(self.agg.leaves[0].dtype.str)(self.accs[0])

    def scatter_valued(self, slots: np.ndarray,
                       values: Tuple[np.ndarray, ...]) -> None:
        """Merge pre-aggregated partials: every leaf valued, each folded
        by its own reduce kind (two-phase aggregation's global side). Pad
        lanes carry each leaf's identity into the reserved slot 0."""
        self._staged_scatter(
            slots, lambda size: tuple(
                pad_values(np.asarray(v, dtype=l.dtype), size, l.identity)
                for v, l in zip(values, self.agg.leaves)),
            self.agg._scatter_valued_jit)

    def upsert_valued(self, key_ids: np.ndarray, namespaces: np.ndarray,
                      values: Tuple[np.ndarray, ...]) -> None:
        """Upsert of locally pre-aggregated rows — upsert() with the
        valued fold, sharing its spill-safe namespace chunking (coalesced
        batch-mode blocks can merge combined rows from many batches, so
        the working set is NOT bounded by one batch's pairs)."""
        self.upsert(key_ids, namespaces, values, valued=True)

    def scatter_signed(self, slots: np.ndarray,
                       values: Tuple[np.ndarray, ...]) -> None:
        """Changelog fold: values carry their sign (+accumulate /
        -retract), every leaf valued (see AggregateFunction.map_input_signed).
        Pad lanes contribute 0 to the reserved identity slot."""
        self._staged_scatter(
            slots, lambda size: tuple(
                pad_values(np.asarray(v, dtype=l.dtype), size, 0)
                for v, l in zip(values, self.agg.leaves)),
            self.agg._scatter_signed_jit)

    # ------------------------------------------------------------- fire path

    def slots_for_namespace(self, ns: int) -> np.ndarray:
        return self.index.slots_for_namespace(ns)

    def keys_of_slots(self, slots: np.ndarray) -> np.ndarray:
        return self.index.slot_key[slots]

    def fire(self, slot_matrix: np.ndarray) -> Dict[str, np.ndarray]:
        """Merge+finish a [num_windows, k] matrix of slice slots.

        Missing slices point at slot 0 (identity). Returns host result
        columns.
        """
        w, k = slot_matrix.shape
        if w == 0:
            return {name: np.empty(0) for name in self.agg.output_names}
        out = self.agg._fire_jit(
            self.accs, jnp.asarray(self._pad_fire_matrix(slot_matrix)))
        # ONE batched D2H for all result columns
        return {name: col[:w]
                for name, col in jax.device_get(out).items()}

    def _pad_fire_matrix(self, slot_matrix: np.ndarray) -> np.ndarray:
        """Sticky-bucket zero-pad shared by every fire dispatch (sync,
        async and hybrid): one padding policy, one compiled-shape family
        per width of the matrix (``slice_matrix`` cuts it to its fullest
        row). The cells the program will gather — padded rows times
        columns, what its device time is proportional to — are stated as
        a ``fire.gather`` instant."""
        w, k = slot_matrix.shape
        wp = sticky_bucket(w, self._fire_bucket, minimum=64)
        self._fire_bucket = wp
        padded = np.zeros((wp, k), dtype=np.int32)
        padded[:w] = slot_matrix
        self.fire_matrix_bytes += padded.nbytes
        flight.instant("fire.gather", work=padded.size)
        return padded

    def fire_projected(self, slot_matrix: np.ndarray, keys: np.ndarray,
                       projector) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """Fire with a device-side FireProjector: merge+finish+project in
        ONE kernel, transferring only the projector's ``num_out`` rows to
        the host instead of the window's full [num_keys] result set (see
        flink_tpu.windowing.fire_projectors — the Q5 hot-items fire drops
        from ~100k transferred rows to k)."""
        w, k = slot_matrix.shape
        if w == 0:
            return np.empty(0, dtype=np.int64), {
                name: np.empty(0) for name in self.agg.output_names}
        pidx, pcols, pvalid = self.agg._fire_project_jit(projector)(
            self.accs, jnp.asarray(self._pad_fire_matrix(slot_matrix)), w)
        sel = np.asarray(pvalid)
        return (keys[np.asarray(pidx)[sel]],
                {name: np.asarray(c)[sel] for name, c in pcols.items()})

    def fire_async(self, slot_matrix: np.ndarray, keys: np.ndarray):
        """Dispatch a fire and return a PendingFire whose harvest yields
        (keys, result columns) — no synchronous device round trip (see
        flink_tpu.runtime.pending)."""
        from flink_tpu.runtime.pending import PendingFire

        w, _ = slot_matrix.shape
        if w == 0:
            return None
        out = self.agg._fire_jit(
            self.accs, jnp.asarray(self._pad_fire_matrix(slot_matrix)))
        names = list(out.keys())

        def build(host: List[np.ndarray]):
            return keys, {name: col[:w] for name, col in zip(names, host)}

        return PendingFire([out[n] for n in names], build)

    def fire_projected_async(self, slot_matrix: np.ndarray,
                             keys: np.ndarray, projector):
        """Async-dispatch variant of fire_projected: same kernel, but the
        host read of the projected rows is deferred to harvest time."""
        from flink_tpu.runtime.pending import PendingFire

        w, _ = slot_matrix.shape
        if w == 0:
            return None
        pidx, pcols, pvalid = self.agg._fire_project_jit(projector)(
            self.accs, jnp.asarray(self._pad_fire_matrix(slot_matrix)), w)
        names = list(pcols.keys())

        def build(host: List[np.ndarray]):
            pidx_h, pvalid_h = host[0], host[1]
            sel = pvalid_h
            return (keys[pidx_h[sel]],
                    {name: col[sel]
                     for name, col in zip(names, host[2:])})

        return PendingFire([pidx, pvalid] + [pcols[n] for n in names], build)

    def build_slice_matrix(self, slice_ends: List[int]
                           ) -> Tuple[Optional[np.ndarray],
                                      Optional[np.ndarray], int]:
        """(keys, [num_keys, width] slot matrix, cells resolved) for the
        resident slices of a window; (None, None, cells) where no key
        holds a slot. A row holds its key's live slots in its first
        columns and the identity slot 0 behind them, and the matrix is as
        wide as its fullest row needs (``slice_matrix`` of the index):
        **column order is not the slices'**, which no fire can tell —
        every merge it knows is commutative and slot 0 holds each leaf's
        identity. Shared by the device fire path and the hybrid (spill)
        fire path; the index carries the matrix from one window to the
        next, and what that cost in rows is stated as two instants:
        ``carry.rows`` (rows of the matrix returned) and
        ``carry.removed`` (rows the advance swept out)."""
        removed = self.index.carry_rows_removed
        keys, matrix, cells = self.index.slice_matrix(slice_ends)
        flight.instant("carry.rows", work=len(keys))
        flight.instant("carry.removed",
                       work=self.index.carry_rows_removed - removed)
        if len(keys) == 0:
            return None, None, cells
        return keys, matrix, cells

    def fire_hybrid(self, slice_ends: List[int]
                    ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """Window fire tolerating spilled slices: device-resident slices
        merge on device (one kernel), spilled slices merge on host, finish
        runs on host over the union. Returns (keys, result columns).

        This keeps the device budget independent of the window's slice
        count — a sliding window whose full slice set exceeds
        max-device-slots still fires correctly (reference: RocksDB windows
        never needed to fit in memory either)."""
        from flink_tpu.ops.segment_ops import HOST_COMBINE

        resident = [se for se in slice_ends if int(se) not in self.spill]
        spilled = [se for se in slice_ends if int(se) in self.spill]
        key_chunks: List[np.ndarray] = []
        leaf_chunks: List[List[np.ndarray]] = [[] for _ in self.agg.leaves]
        # device part
        keys, matrix, _ = self.build_slice_matrix(resident)
        if keys is not None:
            merged = self.agg._merge_jit(
                self.accs, jnp.asarray(self._pad_fire_matrix(matrix)))
            key_chunks.append(keys)
            for i, m in enumerate(jax.device_get(merged)):
                leaf_chunks[i].append(m[:len(keys)])
        # host part (spilled slices)
        for se in spilled:
            entry = self.spill.peek(int(se))
            if entry is None or len(entry["key_id"]) == 0:
                continue
            key_chunks.append(np.asarray(entry["key_id"], dtype=np.int64))
            for i, l in enumerate(self.agg.leaves):
                leaf_chunks[i].append(
                    np.asarray(entry[f"leaf_{i}"], dtype=l.dtype))
        if not key_chunks:
            return np.empty(0, dtype=np.int64), {}
        all_keys = np.concatenate(key_chunks)
        uniq, inv = np.unique(all_keys, return_inverse=True)
        out_leaves = []
        for i, l in enumerate(self.agg.leaves):
            acc = np.full(len(uniq), l.identity, dtype=l.dtype)
            HOST_COMBINE[l.reduce].at(acc, inv,
                                      np.concatenate(leaf_chunks[i]))
            out_leaves.append(acc)
        finished = self.agg.finish(tuple(out_leaves))
        return uniq, {name: np.asarray(col)
                      for name, col in finished.items()}

    def keep_tombstones(self, keep: bool) -> None:
        """Whether a delta snapshot can still be asked of this table. A
        job that takes none (execution.checkpointing.incremental off)
        says so once, and the frees of a run that lasts for days leave
        nothing behind; ``snapshot_delta`` then refuses."""
        self._keep_tombstones = bool(keep)
        if not keep:
            self._freed_ns.clear()
            self._freed_pairs.clear()

    def _tombstone(self, namespaces) -> None:
        if self._keep_tombstones and len(namespaces):
            # a copy: the caller's array may be a view of a buffer it
            # writes again
            self._freed_ns.append(np.array(namespaces, dtype=np.int64))

    def mark_dirty(self, slots: np.ndarray) -> None:
        """For external kernels that mutate ``accs`` directly (e.g. session
        merges): keep incremental snapshots correct."""
        self._dirty[slots] = True

    def free_index_only(self, namespaces: List[int]) -> Optional[np.ndarray]:
        """Release the host index entries of namespaces whose device values
        were already neutralized by a caller-owned kernel (session merges).
        Still records tombstones for incremental snapshots."""
        slots = self.index.free_namespaces(namespaces)
        self._tombstone(namespaces)
        if slots is not None:
            self._dirty[slots] = False
        return slots

    def free_index_only_slots(self, slots: np.ndarray,
                              namespaces) -> None:
        """Slot-addressed free_index_only for registry-free tables: the
        caller (session merge path) already holds the absorbed rows'
        slots; device values were neutralized by its merge kernel."""
        slots = np.asarray(slots, dtype=np.int32)
        self._tombstone(namespaces)
        self.index.free_slots(slots)
        self._dirty[slots] = False

    def free_rows(self, slots: np.ndarray, namespaces) -> None:
        """Slot-addressed free_namespaces (fired sessions): the caller
        resolved the rows this batch, so no registry walk is needed.
        Resets the device values and records namespace tombstones."""
        slots = np.asarray(slots, dtype=np.int32)
        if not len(slots):
            return
        nss = np.asarray(namespaces, dtype=np.int64)
        self._tombstone(nss)
        self._drop_spilled_sessions(nss)
        self.index.free_slots(slots)
        self._dirty[slots] = False
        size = sticky_bucket(len(slots), self._reset_bucket)
        self._reset_bucket = size
        self.accs = self.agg._reset_jit(
            self.accs, pad_i32(slots, size, fill=0))

    def free_slots(self, slots: np.ndarray) -> None:
        """Release individual entries (TTL expiry of idle keys).

        Unlike free_namespaces (whole windows), this frees by (key, ns)
        pair and records entry-granular tombstones so incremental
        snapshot chains don't resurrect expired keys (reference:
        TtlStateFactory + RocksDB compaction-filter cleanup)."""
        slots = np.asarray(slots, dtype=np.int32)
        if not len(slots):
            return
        if self._keep_tombstones:
            self._freed_pairs.append((self.index.slot_key[slots].copy(),
                                      self.index.slot_ns[slots].copy()))
        self.index.free_slots(slots)
        self._dirty[slots] = False
        size = sticky_bucket(len(slots), self._reset_bucket)
        self._reset_bucket = size
        self.accs = self.agg._reset_jit(self.accs,
                                        pad_i32(slots, size, fill=0))

    def free_namespaces(self, namespaces: List[int]) -> int:
        """Release all slots of the given namespaces (windows fully
        fired); returns how many (key, namespace) pairs were erased. The
        pairs that left with their namespace's whole table (the native
        index's drop; none on the Python index) are stated as a
        ``retire.drop`` instant."""
        dropped = self.index.pairs_dropped
        slots = self.index.free_namespaces(namespaces)
        if self.index.pairs_dropped > dropped:
            flight.instant("retire.drop",
                           work=self.index.pairs_dropped - dropped)
        self._tombstone(namespaces)
        if self._paged:
            self._drop_spilled_sessions(
                np.asarray(namespaces, dtype=np.int64))
        elif len(self.spill):
            for ns in namespaces:
                if int(ns) in self.spill:
                    self.spill.drop(int(ns))
        if not self._paged:
            for ns in namespaces:
                self._ns_touch.pop(int(ns), None)
        if slots is None:
            return 0
        self._dirty[slots] = False
        size = sticky_bucket(len(slots), self._reset_bucket)
        self._reset_bucket = size
        self.accs = self.agg._reset_jit(self.accs, pad_i32(slots, size, fill=0))
        return len(slots)

    # ------------------------------------------------------------ point query

    def query(self, key_id: int, namespace: Optional[int] = None
              ) -> Dict[int, Dict[str, float]]:
        """Point lookup for queryable state: finished result columns for the
        key, per namespace (reference: flink-queryable-state KvState lookup
        against the live backend). Read-only — including the sticky fire
        bucket, which belongs to the hot window-fire path."""
        nss = ([int(namespace)] if namespace is not None
               else [int(n) for n in self.namespaces])
        if not nss:
            return {}
        vals = self._key_values_per_namespace(int(key_id), nss)
        out: Dict[int, Dict[str, float]] = {}
        for ns, leaves in vals.items():
            finished = self.agg.finish(leaves)
            out[ns] = {name: np.asarray(col).item()
                       for name, col in finished.items()}
        return out

    def _key_values_per_namespace(
            self, key_id: int, nss: List[int]
    ) -> Dict[int, Tuple[np.ndarray, ...]]:
        """One key's raw accumulator leaves per namespace — device-resident
        namespaces read via one gather kernel, spilled ones from their host
        entries (no residency change: queries must not thrash the cache)."""
        if self._paged:
            sp = self._spilled_mask(np.asarray(nss, dtype=np.int64))
            resident = [ns for ns, s in zip(nss, sp) if not s]
            spilled = [ns for ns, s in zip(nss, sp) if s]
        else:
            resident = [ns for ns in nss if int(ns) not in self.spill]
            spilled = [ns for ns in nss if int(ns) in self.spill]
        out: Dict[int, Tuple[np.ndarray, ...]] = {}
        if resident:
            keys = np.full(len(resident), key_id, dtype=np.int64)
            slots = self.index.lookup(
                keys, np.asarray(resident, dtype=np.int64))
            hit = slots >= 0
            if hit.any():
                hs = slots[hit].astype(np.int32)
                size = pad_bucket_size(len(hs), minimum=64)
                gathered = self.agg._gather_jit(
                    self.accs, jnp.asarray(pad_i32(hs, size, fill=0)))
                leaves = [g[:len(hs)] for g in jax.device_get(gathered)]
                for j, ns in enumerate(n for n, h in zip(resident, hit)
                                       if h):
                    out[int(ns)] = tuple(l[j:j + 1] for l in leaves)
        for ns in spilled:
            if self._paged:
                # session id -> its page (read-only: queries must not
                # change residency)
                page = self._pmap.page_of(int(ns))
                entry = self.spill.peek(page) if page is not None else None
                if entry is None:
                    continue
                pos = np.nonzero(
                    (np.asarray(entry["key_id"], dtype=np.int64)
                     == key_id)
                    & (np.asarray(entry["ns"], dtype=np.int64)
                       == int(ns)))[0]
            else:
                entry = self.spill.peek(int(ns))
                if entry is None:
                    continue
                pos = np.nonzero(np.asarray(entry["key_id"],
                                            dtype=np.int64) == key_id)[0]
            if len(pos) == 0:
                continue
            j = int(pos[0])
            out[int(ns)] = tuple(
                np.asarray(entry[f"leaf_{i}"], dtype=l.dtype)[j:j + 1]
                for i, l in enumerate(self.agg.leaves))
        return out

    def query_batch_pairs(
            self, key_ids: np.ndarray, namespaces: np.ndarray
    ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Raw accumulator leaves for N ``(key, namespace)`` pairs — the
        serving-plane primitive: device-resident pairs read through ONE
        gather kernel + ONE batched device read for the whole batch
        (per-pair reads pay one link round-trip each — the TRC01 class),
        spilled pairs from their host tiers. Returns ``(found, leaves)``
        where ``found`` is the per-pair hit mask and ``leaves`` are
        [N]-shaped per-leaf value arrays (identity where not found).
        Read-only: no residency change, no sticky-bucket mutation."""
        key_ids = np.asarray(key_ids, dtype=np.int64)
        namespaces = np.asarray(namespaces, dtype=np.int64)
        n = len(key_ids)
        leaves_out = [np.full(n, l.identity, dtype=l.dtype)
                      for l in self.agg.leaves]
        found = np.zeros(n, dtype=bool)
        if n == 0:
            return found, leaves_out
        slots = self.index.lookup(key_ids, namespaces)
        hit = slots >= 0
        if hit.any():
            hs = slots[hit].astype(np.int32)
            size = pad_bucket_size(len(hs), minimum=64)
            gathered = self.agg._gather_jit(
                self.accs, jnp.asarray(pad_i32(hs, size, fill=0)))
            g_host = jax.device_get(gathered)  # ONE batched D2H
            for i, g in enumerate(g_host):
                leaves_out[i][hit] = g[:int(hit.sum())]
            found |= hit
        miss = np.nonzero(~hit)[0]
        if len(miss) and (self._paged or len(self.spill)):
            from flink_tpu.state.paged_spill import read_spilled_rows

            def _take_row(j, entry, src):
                for i, l in enumerate(self.agg.leaves):
                    leaves_out[i][j] = np.asarray(
                        entry[f"leaf_{i}"], dtype=l.dtype)[src]
                found[j] = True

            read_spilled_rows(
                self.spill, self._pmap if self._paged else None,
                self._paged,
                [(j, int(key_ids[j]), int(namespaces[j]))
                 for j in miss.tolist()],
                _take_row)
        return found, leaves_out

    def query_windows(self, key_id: int, assigner
                      ) -> Dict[int, Dict[str, float]]:
        """Point lookup composing WINDOW results from per-slice partial
        accumulators (slice sharing: a sliding window's value = merge of k
        slices — reference: SliceAssigners slice/window mapping). Returns
        {window_end -> finished result columns} for the key. Read-only."""
        from flink_tpu.ops.segment_ops import HOST_COMBINE

        live_ns = [int(n) for n in self.namespaces]
        if not live_ns:
            return {}
        slice_vals = self._key_values_per_namespace(int(key_id), live_ns)
        if not slice_vals:
            return {}
        windows = sorted({
            int(w)
            for se in slice_vals
            for w in assigner.window_ends_for_slice(se)})
        out: Dict[int, Dict[str, float]] = {}
        for w in windows:
            leaves = [np.full(1, l.identity, dtype=l.dtype)
                      for l in self.agg.leaves]
            for se in assigner.slice_ends_for_window(w):
                sv = slice_vals.get(int(se))
                if sv is None:
                    continue
                leaves = [HOST_COMBINE[l.reduce](acc, v) for acc, v, l in
                          zip(leaves, sv, self.agg.leaves)]
            finished = self.agg.finish(tuple(leaves))
            out[w] = {name: np.asarray(col).item()
                      for name, col in finished.items()}
        return out

    # ---------------------------------------------------------- snapshot/restore

    def snapshot(self, reset_dirty: bool = True) -> Dict[str, np.ndarray]:
        """Materialize state as host arrays, filtered to used slots.

        The snapshot is *logical* (key, ns, key_group, leaf values) — slot
        numbers are not part of the format, so restore can re-shard by key
        group (the reference's rescale-by-key-group-range contract,
        reference: KeyGroupRangeAssignment.java + state/restore pipeline).
        With ``reset_dirty`` (the default) the snapshot establishes a new
        incremental base; savepoints pass False so a mid-run savepoint does
        not silently shrink the next delta checkpoint's contents.
        """
        used = self.index.used_slots()
        accs_host = jax.device_get(list(self.accs))  # ONE batched D2H
        flight.add_work("checkpoint.snapshot",
                        sum(a.nbytes for a in accs_host))
        key_ids = self.index.slot_key[used]
        out = {
            "key_id": key_ids,
            "namespace": self.index.slot_ns[used],
            **{
                f"leaf_{i}": accs_host[i][used]
                for i in range(len(self.accs))
            },
        }
        # spilled namespaces are part of the logical state (chunks
        # collected first, ONE concatenate — thousands of one-row session
        # namespaces would otherwise make this O(N^2))
        key_chunks = [out["key_id"]]
        ns_chunks = [out["namespace"]]
        leaf_chunks = [[out[f"leaf_{i}"]] for i in range(len(self.accs))]
        for pid_or_ns in self.spill.namespaces:
            entry = self.spill.peek(int(pid_or_ns))
            keys = np.asarray(entry["key_id"], dtype=np.int64)
            if "ns" in entry:  # paged layout: entry carries its ns column
                rns = np.asarray(entry["ns"], dtype=np.int64)
                # lazy tombstones: reloaded/freed rows stay physically
                # in the page; only rows still MAPPED to it are state
                alive = self._pmap.live_row_mask(int(pid_or_ns), rns)
                keys, rns = keys[alive], rns[alive]
                sel = alive
            else:
                rns = np.full(len(keys), int(pid_or_ns), dtype=np.int64)
                sel = slice(None)
            key_chunks.append(keys)
            ns_chunks.append(rns)
            for i in range(len(self.accs)):
                leaf_chunks[i].append(
                    np.asarray(entry[f"leaf_{i}"],
                               dtype=self.agg.leaves[i].dtype)[sel])
        out["key_id"] = np.concatenate(key_chunks)
        out["namespace"] = np.concatenate(ns_chunks)
        for i in range(len(self.accs)):
            out[f"leaf_{i}"] = np.concatenate(leaf_chunks[i])
        out["key_group"] = assign_key_groups(out["key_id"],
                                             self.max_parallelism)
        flight.instant("checkpoint.rows", work=len(out["key_id"]))
        if reset_dirty:
            self._dirty[:] = False
            self._freed_ns.clear()
            self._freed_pairs.clear()
            self.spill.clear_dirty()
        return out

    def snapshot_delta(self) -> Dict[str, np.ndarray]:
        """Incremental snapshot: only rows dirtied since the last snapshot
        plus the namespaces freed since (tombstones). Restore applies deltas
        on top of the last full snapshot
        (reference: RocksIncrementalSnapshotStrategy — upload only new SSTs;
        here: transfer only dirty slots off the device)."""
        if not self._keep_tombstones:
            raise RuntimeError(
                "a delta snapshot of a table that keeps no tombstones "
                "(keep_tombstones(False): the job said it takes no "
                "incremental checkpoint) would resurrect freed rows")
        dirty_used = np.nonzero(self._dirty & self.index.slot_used)[0] \
            .astype(np.int32)
        freed = (np.unique(np.concatenate(self._freed_ns))
                 if self._freed_ns else np.empty(0, dtype=np.int64))
        n = len(dirty_used)
        if n:
            size = sticky_bucket(n, self._gather_bucket)
            self._gather_bucket = size
            gathered = jax.device_get(self.agg._gather_jit(
                self.accs, jnp.asarray(pad_i32(dirty_used, size, fill=0))))
            flight.add_work("checkpoint.snapshot",
                            sum(g.nbytes for g in gathered))
            leaves = [g[:n] for g in gathered]
        else:
            leaves = [np.empty(0, dtype=l.dtype) for l in self.agg.leaves]
        key_ids = self.index.slot_key[dirty_used]
        namespaces = self.index.slot_ns[dirty_used]
        # spilled-but-dirty namespaces were changed since the last snapshot
        # and must travel in this delta too (paged layout: only the dirty
        # ROWS of a dirty page — pages are immutable once spilled, so the
        # per-row dirty column captured at eviction stays authoritative)
        for pid_or_ns in self.spill.dirty_namespaces():
            entry = self.spill.peek(int(pid_or_ns))
            if entry is None:
                continue
            keys = np.asarray(entry["key_id"], dtype=np.int64)
            if "ns" in entry:
                rns_all = np.asarray(entry["ns"], dtype=np.int64)
                # dirty rows that are also LIVE (tombstoned rows are
                # either resident again — the resident copy travels —
                # or freed, so their stale page copy must not)
                sel = (np.asarray(entry["dirty"], dtype=bool)
                       & self._pmap.live_row_mask(int(pid_or_ns),
                                                  rns_all))
                keys = keys[sel]
                rns = rns_all[sel]
            else:
                sel = slice(None)
                rns = np.full(len(keys), int(pid_or_ns), dtype=np.int64)
            key_ids = np.concatenate([key_ids, keys])
            namespaces = np.concatenate([namespaces, rns])
            leaves = [np.concatenate([
                leaves[i],
                np.asarray(entry[f"leaf_{i}"],
                           dtype=self.agg.leaves[i].dtype)[sel]])
                for i in range(len(leaves))]
        if self._freed_pairs:
            tomb_k = np.concatenate([p[0] for p in self._freed_pairs])
            tomb_n = np.concatenate([p[1] for p in self._freed_pairs])
        else:
            tomb_k = np.empty(0, dtype=np.int64)
            tomb_n = np.empty(0, dtype=np.int64)
        out = {
            "__delta__": np.asarray(True),
            "key_id": key_ids,
            "namespace": namespaces,
            "key_group": assign_key_groups(key_ids, self.max_parallelism),
            "freed_namespaces": freed,
            "tombstone_key_id": tomb_k,
            "tombstone_namespace": tomb_n,
            **{f"leaf_{i}": leaves[i] for i in range(len(leaves))},
        }
        flight.instant("checkpoint.rows", work=len(key_ids))
        flight.instant("checkpoint.tombstones",
                       work=len(freed) + len(tomb_k))
        self._dirty[:] = False
        self._freed_ns.clear()
        self._freed_pairs.clear()
        self.spill.clear_dirty()
        return out

    def restore(self, snap: Dict[str, np.ndarray],
                key_group_filter=None) -> None:
        """Load a logical snapshot, optionally keeping only owned key groups."""
        key_ids = np.asarray(snap["key_id"], dtype=np.int64)
        namespaces = np.asarray(snap["namespace"], dtype=np.int64)
        groups = np.asarray(snap["key_group"], dtype=np.int32)
        leaves = [np.asarray(snap[f"leaf_{i}"]) for i in range(len(self.agg.leaves))]
        # serializer-compatibility check (reference: TypeSerializerSnapshot
        # resolveSchemaCompatibility): leaf dtypes must match the
        # aggregate's accumulator layout. A value-preserving cast counts as
        # compatible-after-migration (bootstrap writers use natural Python
        # dtypes); anything lossy fails precisely instead of silently
        # reinterpreting values.
        for i, (arr, leaf) in enumerate(zip(leaves, self.agg.leaves)):
            want = np.dtype(leaf.dtype)
            if len(arr) and arr.dtype != want:
                cast = _coerce_snapshot_leaf(arr, want)
                if cast is None:
                    raise RuntimeError(
                        f"state schema incompatible: snapshot leaf_{i} has "
                        f"dtype {arr.dtype}, the aggregate expects {want} "
                        "and the values do not survive the cast — migrate "
                        "the snapshot (checkpoint.storage."
                        "register_migration) or restore with the original "
                        "aggregate types")
                leaves[i] = cast
        if key_group_filter is not None:
            mask = np.array([g in key_group_filter for g in groups], dtype=bool)
            key_ids, namespaces = key_ids[mask], namespaces[mask]
            leaves = [l[mask] for l in leaves]
        if self.max_device_slots and self._paged and len(key_ids):
            # paged restore: rows land in page-sized spill entries (ns
            # column per row) and reload lazily by page — same bounded-
            # device contract, thousands of sessions per entry
            from flink_tpu.state.paged_spill import restore_into_pages

            restore_into_pages(
                self.spill, self._pmap, key_ids, namespaces, leaves,
                page_rows=max(self.index.capacity // 8, 1024))
        elif self.max_device_slots and len(key_ids):
            # spill-enabled restore: rows land in the spill tier grouped by
            # namespace and reload lazily on first access — a snapshot far
            # larger than HBM restores with bounded device memory
            order = np.argsort(namespaces, kind="stable")
            s_ns = namespaces[order]
            s_keys = key_ids[order]
            s_leaves = [l[order] for l in leaves]
            bounds = np.nonzero(np.diff(s_ns))[0] + 1
            starts = np.concatenate(([0], bounds))
            ends = np.concatenate((bounds, [len(s_ns)]))
            for a, b in zip(starts.tolist(), ends.tolist()):
                ns = int(s_ns[a])
                entry = {"key_id": s_keys[a:b],
                         **{f"leaf_{i}": s_leaves[i][a:b]
                            for i in range(len(s_leaves))}}
                if ns in self.spill:
                    self.spill.drop(ns)
                self.spill.put(ns, entry, dirty=False)
                # the namespace registry must know spilled namespaces'
                # windows; registry entries are created on reload
        elif len(key_ids):
            slots = self.lookup_or_insert(key_ids, namespaces)
            # one batched D2H read, then writable copies (mutated below)
            accs_host = [np.array(a)
                         for a in jax.device_get(list(self.accs))]
            for acc, vals in zip(accs_host, leaves):
                acc[slots] = vals
            self.accs = tuple(jnp.asarray(a) for a in accs_host)
        # restored state IS the new incremental base
        self._dirty[:] = False
        self._freed_ns.clear()
        self._freed_pairs.clear()
        self.spill.clear_dirty()
