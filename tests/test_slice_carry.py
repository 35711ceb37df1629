"""The fire path's carried slot matrix (``slice_matrix`` of both index
classes, state/slot_table.py; ``sm_carry_advance``, native/slotmap.cpp).

A window's (keys, slot matrix) is kept after the fire and the next
window's is made from it: the column of the slice that left is dropped,
only the cells that entered are resolved. Each way of being fast and
wrong is pinned here: the carried matrix must equal a from-nothing
rebuild after every fire, on both index classes, through late cells,
re-fires of older windows, drained and re-made namespaces, per-slot
frees, spill eviction and restore; rows that went empty must vanish; what
was handed out must never be written again; and the engines' sink rows
must be bit-identical to a run that rebuilds on every fire.

The matrix handed out is packed: a row's live slots stand left of every
zero and the columns past what the fullest row needs are left off, so
rows are compared as multisets of live slots.
"""

import numpy as np
import pytest

import flink_tpu.state.slot_table as slot_table_mod
from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.native import slotmap_available
from flink_tpu.state.slot_table import (
    HostSlotIndex,
    NativeSlotIndex,
    SlotTable,
    fire_matrix_width,
)
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.windowing.windower import SliceSharedWindower

K = 5          # slices per window
LATENESS = 2   # slices kept beyond the window (re-fires of older windows)


@pytest.fixture(params=["host", "native"])
def index_cls(request, monkeypatch):
    """Every table and engine of the test is built on this index class."""
    if request.param == "native" and not slotmap_available():
        pytest.skip("native slotmap unavailable")
    cls = HostSlotIndex if request.param == "host" else NativeSlotIndex
    monkeypatch.setattr(
        slot_table_mod, "make_slot_index",
        lambda capacity, on_grow=None, growable=True, full_hint="",
        max_capacity=0, track_namespaces=True: cls(
            capacity, on_grow=on_grow, growable=growable,
            full_hint=full_hint, max_capacity=max_capacity,
            track_namespaces=track_namespaces))
    return cls


def rebuilt(index, ends):
    """The window's rows made from nothing, the plain way: one dict of
    key -> its live slots (a row is a multiset of them: sorted)."""
    rows = {}
    for ns in ends:
        for slot in index.slots_for_namespace(ns).tolist():
            rows.setdefault(int(index.slot_key[slot]), []).append(slot)
    return {(key, *sorted(row)) for key, row in rows.items()}


def as_rows(keys, matrix):
    """The matrix's rows as (key, its live slots sorted); holds that live
    cells stand left of every zero and that the matrix is as wide as its
    fullest row needs (a full-width matrix may be in slice order)."""
    if keys is None:
        return set()
    assert matrix.shape[0] == len(keys)
    live = matrix != 0
    if len(keys):
        fullest = int(live.sum(axis=1).max())
        # no wider than that row needs (the rule maps its own result
        # to itself, whatever number of slices the window has)
        assert matrix.shape[1] == fire_matrix_width(matrix.shape[1],
                                                    fullest)
        if fullest < matrix.shape[1]:
            assert (live[:, :-1] >= live[:, 1:]).all(), \
                "a live cell right of a zero"
    return {(int(key), *sorted(int(s) for s in row if s))
            for key, row in zip(keys, matrix)}


class Driver:
    """A table under a random walk of everything that touches the
    registry, with a model of which (key, slice) pairs are live."""

    def __init__(self, seed):
        self.rng = np.random.default_rng(seed)
        self.table = self._new_table()
        self.model = set()          # live (key, slice) pairs
        self.cur = K + LATENESS     # newest slice
        self.fires = 0
        self.reused = 0
        self.handed = None          # (keys, copy) of the previous fire

    @staticmethod
    def _new_table():
        return SlotTable(SumAggregate("v"), capacity=1024,
                         max_device_slots=1 << 14)

    def live_slices(self):
        return list(range(self.cur - K - LATENESS + 1, self.cur + 1))

    def ingest(self, ns, n):
        keys = self.rng.integers(0, 400, size=n).astype(np.int64)
        self.table.ensure_resident([ns])
        slots = self.table.lookup_or_insert(
            keys, np.full(n, ns, dtype=np.int64))
        self.table.scatter(slots, (np.ones(n, dtype=np.float32),))
        self.model.update((int(k), ns) for k in keys)

    def fire(self, last):
        ends = list(range(last - K + 1, last + 1))
        table = self.table
        resident = [e for e in ends if e not in table.spill]
        keys, matrix, cells = table.build_slice_matrix(resident)
        got = as_rows(keys, matrix)
        index = table.index
        assert got == rebuilt(index, resident)
        # against the model, not the registry: every cell names the slot
        # of exactly that (key, slice) pair, and no live pair is missing
        pairs = set()
        for key, *row in got:
            assert any(row), "an all-identity row"
            for slot in row:
                assert index.slot_used[slot]
                assert index.slot_key[slot] == key
                assert index.slot_ns[slot] in resident
                pairs.add((key, int(index.slot_ns[slot])))
            assert len({int(index.slot_ns[slot]) for slot in row}) \
                == len(row), "two cells of one slice in a row"
        assert pairs == {p for p in self.model if p[1] in resident}
        assert len(got) == (0 if keys is None else len(keys))
        live_cells = sum(len(index.slots_for_namespace(e))
                         for e in resident)
        assert 0 <= cells <= live_cells
        self.reused += cells < live_cells
        # what fire n was handed is as it was after fire n + 1
        if self.handed is not None:
            for arr, copy in self.handed:
                np.testing.assert_array_equal(arr, copy)
        self.handed = None if keys is None else [
            (keys, keys.copy()), (matrix, matrix.copy())]
        self.fires += 1

    def step(self):
        rng, table = self.rng, self.table
        op = rng.choice(
            ["advance", "late", "refire", "ttl", "spill", "restore",
             "same"],
            p=[0.45, 0.2, 0.1, 0.07, 0.07, 0.05, 0.06])
        if op == "advance":
            self.cur += 1
            self.ingest(self.cur, int(rng.integers(20, 300)))
            if rng.random() < 0.3:      # late, into a kept slice
                self.ingest(self.cur - int(rng.integers(1, K)),
                            int(rng.integers(1, 30)))
            self.fire(self.cur)
            retired = self.cur - K - LATENESS + 1
            table.free_namespaces([retired])
            self.model = {p for p in self.model if p[1] != retired}
        elif op == "late":
            self.ingest(self.cur - int(rng.integers(0, K + LATENESS - 1)),
                        int(rng.integers(1, 40)))
            self.fire(self.cur)
        elif op == "refire":            # an older window, then back
            self.fire(self.cur - int(rng.integers(1, LATENESS + 1)))
            self.fire(self.cur)
        elif op == "same":
            self.fire(self.cur)
        elif op == "ttl":
            table.ensure_resident(self.live_slices())
            used = np.nonzero(table.index.slot_used)[0]
            used = used[used > 0]
            if len(used):
                gone = rng.choice(used, size=max(1, len(used) // 20),
                                  replace=False).astype(np.int32)
                index = table.index
                self.model -= {(int(k), int(n)) for k, n in zip(
                    index.slot_key[gone], index.slot_ns[gone])}
                table.free_slots(gone)
            self.fire(self.cur)
        elif op == "spill":
            # evict the coldest namespaces (drained), fire over what is
            # still resident as fire_hybrid does (or not at all: the
            # next fire then meets same-named namespaces that are other
            # lists), reload (re-made lists)
            if table.index.namespaces:
                table._evict_cold(protect=set())
            if rng.random() < 0.5:
                self.fire(self.cur)
            table.ensure_resident(self.live_slices())
            self.fire(self.cur)
        elif op == "restore":
            table.ensure_resident(self.live_slices())
            snap = table.snapshot()
            self.table = self._new_table()
            self.table.restore(snap)
            self.table.ensure_resident(self.live_slices())
            self.handed = None
            self.fire(self.cur)


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_carried_matrix_equals_a_rebuild_after_every_fire(index_cls, seed):
    d = Driver(seed)
    assert type(d.table.index) is index_cls
    for ns in d.live_slices():
        d.ingest(ns, 150)
    d.fire(d.cur)
    for _ in range(120):
        d.step()
    assert d.fires > 120
    assert d.reused > d.fires // 2      # the carry did engage


def test_only_what_entered_is_resolved(index_cls):
    index = index_cls(1 << 12)

    def put(ns, keys):
        index.lookup_or_insert(np.asarray(keys, dtype=np.int64),
                               np.full(len(keys), ns, dtype=np.int64))

    for ns in range(K):
        put(ns, range(ns * 10, ns * 10 + 100))
    ends = list(range(K))
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == 500                         # first window: everything
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    assert index.slice_matrix(ends)[2] == 0     # the same window again
    # the next window: the slice that entered, nothing else
    index.free_namespaces([0])
    put(K, range(40, 100))
    ends = list(range(1, K + 1))
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == 60
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # keys 0..9 lived in slice 0 alone: their rows are gone
    assert not set(range(10)) & set(keys.tolist())
    # a late cell in a kept slice is the only thing resolved
    put(3, [7])
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == 1 and 7 in keys.tolist()
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # two slices on at once
    index.free_namespaces([1, 2])
    put(K + 1, range(5))
    put(K + 2, range(300, 310))
    ends = list(range(3, K + 3))
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == 15
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # an older window: from nothing
    ends = list(range(2, K + 2))
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == sum(len(index.slots_for_namespace(e)) for e in ends)
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # a kept namespace drained and made again under the same name is
    # another list: its old column must not survive
    index.free_namespaces([4])
    put(4, range(200, 203))
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == sum(len(index.slots_for_namespace(e)) for e in ends)
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # a per-slot free drops the carry
    slots = index.slots_for_namespace(5)[:3]
    index.free_slots(slots)
    keys, matrix, cells = index.slice_matrix(ends)
    assert cells == sum(len(index.slots_for_namespace(e)) for e in ends)
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    # another number of slices (the hybrid fire's resident subset)
    keys, matrix, cells = index.slice_matrix(ends[1:])
    assert matrix.shape[1] == fire_matrix_width(
        K - 1, int((matrix != 0).sum(axis=1).max()))
    assert as_rows(keys, matrix) == rebuilt(index, ends[1:])
    # nothing live, and no slice at all (every slice of a window spilled)
    keys, matrix, cells = index.slice_matrix([90, 91])
    assert len(keys) == 0 and matrix.shape == (0, 2) and cells == 0
    keys, matrix, cells = index.slice_matrix([])
    assert len(keys) == 0 and matrix.shape == (0, 0) and cells == 0
    keys, matrix, cells = index.slice_matrix(ends)
    assert as_rows(keys, matrix) == rebuilt(index, ends)


def _put(index, ns, keys):
    index.lookup_or_insert(np.asarray(list(keys), dtype=np.int64),
                           np.full(len(keys), ns, dtype=np.int64))


def _live_cells(index, ends):
    return sum(len(index.slots_for_namespace(e)) for e in ends)


# what happens between two fires -> (the next window's slices, whether
# the matrix may be carried on: the cells it resolves are then the ones
# that entered, else every live cell of the window)
BETWEEN_FIRES = {
    "a_slide": (
        lambda ix: (ix.free_namespaces([0]), _put(ix, K, range(40, 90))),
        list(range(1, K + 1)), 50),
    "a_late_batch_into_a_kept_slice": (
        lambda ix: _put(ix, 2, [5, 6, 7, 50, 51]),
        list(range(K)), 3),                 # 5, 6, 7 are new there
    "a_kept_slice_dropped_and_made_again": (
        lambda ix: (ix.free_namespaces([3]), _put(ix, 3, range(30, 60))),
        list(range(K)), None),
    "the_same_with_a_slide": (
        lambda ix: (ix.free_namespaces([0, 3]), _put(ix, 3, [1, 2]),
                    _put(ix, K, range(9))),
        list(range(1, K + 1)), None),
    "a_kept_slice_dropped_for_good": (
        lambda ix: ix.free_namespaces([2]),
        list(range(K)), None),
    "a_per_slot_free": (
        lambda ix: ix.free_slots(ix.slots_for_namespace(1)[10:20]),
        list(range(K)), None),
    "a_per_slot_free_in_a_slice_the_window_lacks": (
        lambda ix: ix.free_slots(ix.slots_for_namespace(77)[:2]),
        list(range(K)), None),
    "a_per_slot_free_that_empties_its_slice": (
        lambda ix: ix.free_slots(ix.slots_for_namespace(78)),
        list(range(K)), None),
    "nothing": (lambda ix: None, list(range(K)), 0),
}


@pytest.mark.parametrize("between", sorted(BETWEEN_FIRES))
def test_matrix_equals_a_rebuild_whatever_happened_since(index_cls,
                                                         between):
    """Fire, let one thing happen to the index, fire again: the second
    matrix is a rebuild's, and it was carried on exactly where that is
    safe — the native index tells a table from a later one of its name
    by its generation, the Python one a list from a later list."""
    happen, ends, entered = BETWEEN_FIRES[between]
    index = index_cls(1 << 12)
    for ns in range(K):
        _put(index, ns, range(ns * 10, ns * 10 + 100))
    _put(index, 77, range(500, 520))
    _put(index, 78, range(3))
    first = list(range(K))
    keys, matrix, cells = index.slice_matrix(first)
    assert cells == 100 * K
    assert as_rows(keys, matrix) == rebuilt(index, first)
    handed = (keys.copy(), matrix.copy())
    happen(index)
    got_keys, got_matrix, cells = index.slice_matrix(ends)
    assert as_rows(got_keys, got_matrix) == rebuilt(index, ends)
    assert cells == (_live_cells(index, ends) if entered is None
                     else entered)
    # the first fire's arrays are its caller's
    np.testing.assert_array_equal(keys, handed[0])
    np.testing.assert_array_equal(matrix, handed[1])
    # and on from there: a slide after whatever happened
    index.free_namespaces([ends[0]])
    _put(index, ends[-1] + 1, range(250, 300))
    ends = ends[1:] + [ends[-1] + 1]
    keys, matrix, cells = index.slice_matrix(ends)
    assert as_rows(keys, matrix) == rebuilt(index, ends)
    assert cells == 50


def test_carry_grows_with_the_rows(index_cls):
    """More rows than the carry's first allocation, then many of them
    leaving at once (the key -> row table's deletions and its growth)."""
    index = index_cls(1 << 15)
    rng = np.random.default_rng(5)
    for w in range(8):
        n = 6000 if w % 3 == 0 else 50
        index.lookup_or_insert(
            rng.integers(0, 5000, size=n).astype(np.int64),
            np.full(n, w + 2, dtype=np.int64))
        ends = [w, w + 1, w + 2]
        keys, matrix, _ = index.slice_matrix(ends)
        assert as_rows(keys, matrix) == rebuilt(index, ends)
        assert len(set(keys.tolist())) == len(keys)
        index.free_namespaces([w])


@pytest.mark.parametrize("per_slice, spill_over", [
    (1, 0), (300, 0), (300, 25), (5000, 110)])
def test_full_turnover_every_row_enters_once_and_leaves(per_slice,
                                                         spill_over):
    """Keys that live in one slice (``spill_over`` of them also in the
    next, as an auction in flight over a slide's edge) and never come
    back: every row of the matrix enters with one fire and leaves K fires
    later. The native carry and the Python one hold the rows of a rebuild
    at every fire, count the same rows swept out, and leak none."""
    if not slotmap_available():
        pytest.skip("native slotmap unavailable")
    native, plain = NativeSlotIndex(1 << 16), HostSlotIndex(1 << 16)
    fresh = HostSlotIndex(1 << 16)      # fires from nothing every time
    live = {}                           # slice -> its keys
    removed_before = 0
    for s in range(20):
        keys = list(range(s * per_slice - (spill_over if s else 0),
                          (s + 1) * per_slice))
        live[s] = set(keys)
        for index in (native, plain, fresh):
            _put(index, s, keys)
        ends = list(range(s - K + 1, s + 1))
        want = rebuilt(fresh, ends)
        fresh._slice_carry = None
        assert as_rows(*fresh.slice_matrix(ends)[:2]) == want
        for index in (native, plain):
            got_keys, got_matrix, cells = index.slice_matrix(ends)
            assert as_rows(got_keys, got_matrix) == want
            assert cells == len(keys)
            # no row leaks: the matrix holds the live keys, no more
            assert len(got_keys) == len(set().union(
                *(live[e] for e in ends if e in live)))
        assert native.carry_rows_removed == plain.carry_rows_removed
        # the rows that left: the keys of the slice that left which the
        # slice after it does not hold
        left = len(live[s - K] - live[s - K + 1]) if s >= K else 0
        assert native.carry_rows_removed - removed_before == left
        removed_before = native.carry_rows_removed
        assert fresh.carry_rows_removed == 0    # nothing to sweep
        for index in (native, plain, fresh):
            index.free_namespaces([s - K - LATENESS + 1])
    assert native.carry_rows_removed == 15 * per_slice - spill_over


# ---------------------------------------------------------------- engines


def kb(keys, values, ts):
    return RecordBatch.from_pydict(
        {KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
         "v": np.asarray(values, dtype=np.float32)},
        timestamps=ts)


def stream(seed, steps=14):
    """Out-of-order float events over HOP(100, 500) with lateness: late
    cells in kept slices and re-fires of older windows both occur."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(steps):
        n = 300
        keys = rng.integers(0, 120, n)
        vals = rng.random(n).astype(np.float32) * 1e3
        ts = rng.integers(max(0, s * 100 - 250), s * 100 + 100, n)
        out.append((keys, vals, ts, s * 100 + 40))
    return out


def run_engine(make, always_rebuild, monkeypatch, async_ok):
    """Sink rows of one run, as sorted tuples with the float's bits."""
    with monkeypatch.context() as m:
        for cls in (HostSlotIndex, NativeSlotIndex) * always_rebuild:
            def from_nothing(self, slice_ends, carried=cls.slice_matrix):
                self._slice_carry = None
                return carried(self, slice_ends)

            m.setattr(cls, "slice_matrix", from_nothing)
        engine = make()
        fired, pending = [], []
        for keys, vals, ts, wm in stream(11):
            engine.process_batch(kb(keys, vals, ts))
            out = engine.on_watermark(wm, async_ok=async_ok)
            if async_ok:
                # harvested two watermarks later: the keys a fire was
                # handed are read after later fires advanced the matrix
                pending.append(out)
                if len(pending) > 2:
                    fired.extend(p.harvest() for p in pending.pop(0))
            else:
                fired.extend(out)
        out = engine.on_watermark(10 ** 9, async_ok=async_ok)
        if async_ok:
            pending.append(out)
            for group in pending:
                fired.extend(p.harvest() for p in group)
        else:
            fired.extend(out)
    rows = []
    for b in fired:
        if b is None:
            continue
        for r in b.to_rows():
            rows.append((r["window_end"], r[KEY_ID_FIELD],
                         np.float32(r["sum_v"]).tobytes()))
    return sorted(rows)


@pytest.mark.parametrize("async_ok", [False, True])
def test_single_device_sink_rows_bit_identical(index_cls, monkeypatch,
                                               async_ok):
    def make():
        return SliceSharedWindower(
            SlidingEventTimeWindows.of(500, 100), SumAggregate("v"),
            capacity=1 << 12, allowed_lateness=200)

    from flink_tpu.observe import flight_recorder as flight

    want = run_engine(make, True, monkeypatch, async_ok)
    flight.recorder().clear()
    got = run_engine(make, False, monkeypatch, async_ok)
    assert len(got) > 1000
    assert got == want
    # the stream does step back: windows at or under the newest fired one
    # fire again on a carried matrix, for records the engine counted late
    late = flight.recorder().kind_totals()
    windows = len({row[0] for row in got})
    assert late["fire.late"]["work"] >= windows
    assert late["late.records"]["work"] > late["fire.late"]["work"]


@pytest.mark.parametrize("async_ok", [False, True])
def test_mesh_sink_rows_bit_identical(index_cls, monkeypatch, async_ok):
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine

    def make():
        return MeshWindowEngine(
            SlidingEventTimeWindows.of(500, 100), SumAggregate("v"),
            make_mesh(2), capacity_per_shard=1 << 12,
            allowed_lateness=200)

    want = run_engine(make, True, monkeypatch, async_ok)
    got = run_engine(make, False, monkeypatch, async_ok)
    assert len(got) > 1000
    assert got == want
    # and the mesh agrees with the single device on which rows exist
    single = run_engine(
        lambda: SliceSharedWindower(
            SlidingEventTimeWindows.of(500, 100), SumAggregate("v"),
            capacity=1 << 12, allowed_lateness=200),
        False, monkeypatch, async_ok)
    assert [r[:2] for r in got] == [r[:2] for r in single]


def test_window_after_swept_batches_carries_its_matrix(index_cls):
    """Batches resolved by the native sweep append each slice's new slots
    to the registry as the lookup did (a namespace's list only grows at
    its end), so the fire after them resolves the slice that entered —
    ``fire.shard``'s work — and not the window, and what it fires on
    equals a rebuild."""
    from flink_tpu.observe import flight_recorder as flight

    w = SliceSharedWindower(SlidingEventTimeWindows.of(K * 100, 100),
                            SumAggregate("v"), capacity=1 << 12)
    assert type(w.table.index) is index_cls
    index = w.table.index
    seen = []
    build = w.table.build_slice_matrix

    def checked(ends):
        keys, matrix, cells = build(ends)
        assert as_rows(keys, matrix) == rebuilt(index, ends)
        seen.append(cells)
        return keys, matrix, cells

    w.table.build_slice_matrix = checked
    rng = np.random.default_rng(8)
    rec = flight.recorder()
    rec.clear()
    entered = []
    batches = 0
    for s in range(12):
        # two in-order batches per slice: the second appends to the
        # slice's list a chunk of its own
        before = index.pairs_inserted
        for half in range(2):
            n = 200
            ts = np.sort(rng.integers(s * 100 + half * 50,
                                      s * 100 + half * 50 + 50, n))
            w.process_batch(kb(rng.integers(0, 150, n), np.ones(n), ts))
            batches += 1
        entered.append(index.pairs_inserted - before)
        assert len(w.on_watermark(s * 100 + 99)) == 1
    kt = rec.kind_totals()
    rec.clear()
    swept = kt.get("resolve.sweep", {"count": 0})["count"]
    assert swept == (batches if index_cls is NativeSlotIndex else 0)
    # window s holds slices s-K+1 .. s: each fire resolved exactly the
    # pairs its newest slice was given, the first one included (from
    # nothing, when that slice is all there is)
    assert seen == entered and min(entered) > 100
    assert kt["fire.shard"]["count"] == 12
    assert kt["fire.shard"]["work"] == sum(entered) \
        == kt["prep.resolve"]["work"]
    live = sum(len(index.slots_for_namespace(ns))
               for ns in index.namespaces)
    assert seen[-1] < live / 3          # a rebuild would resolve these
