"""The fire's slot matrix is as wide as its fullest row, not as wide as
the window has slices (``slice_matrix`` of both index classes,
state/slot_table.py; ``carry_copy_out``, native/slotmap.cpp): live cells
stand left of every zero, the columns past ``fire_matrix_width`` of the
fullest row are left off. Held here: the width rule on both indexes, that
every fire the table knows returns over the packed, cut matrix what it
returns over the one with a column per slice, the same across a mesh
whose shards return different widths, and the ``fire.gather`` instant
that says how many cells a fire program was handed.
"""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD
from flink_tpu.native import slotmap_available
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.state.slot_table import (
    HostSlotIndex,
    NativeSlotIndex,
    SlotTable,
    fire_matrix_width,
    pack_slot_matrix,
)
from flink_tpu.windowing.aggregates import (
    CountAggregate,
    MaxAggregate,
    MinAggregate,
    SumAggregate,
)
from flink_tpu.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.windowing.fire_projectors import TopKFireProjector
from flink_tpu.windowing.windower import SliceSharedWindower
from tests.test_slice_carry import _put as put, kb

K = 5

needs_native = pytest.mark.skipif(not slotmap_available(),
                                  reason="native slotmap unavailable")


def per_slice_matrix(index, ends):
    """The window's ``(keys, matrix)`` with a column per slice, the plain
    way: what ``slice_matrix`` returned before it packed."""
    rows = {}
    for j, ns in enumerate(ends):
        for slot in index.slots_for_namespace(ns).tolist():
            rows.setdefault(int(index.slot_key[slot]), [0] * len(ends))[j] \
                = slot
    keys = np.fromiter(rows, dtype=np.int64, count=len(rows))
    return keys, np.asarray(list(rows.values()),
                            dtype=np.int32).reshape(len(rows), len(ends))


def fill(index, k, fullest, rows=40):
    """``rows`` keys over slices 0..k-1: key i in slice i % k alone, and
    key 1000 in the last ``fullest`` slices (the fullest row stands last
    in the first slice's table: a scan meets every short row first)."""
    for ns in range(k):
        keys = [i for i in range(rows) if i % k == ns]
        if ns >= k - fullest:
            keys.append(1000)
        put(index, ns, keys)


# ------------------------------------------------------- (c) the width rule


@pytest.mark.parametrize("k, widths", [
    (5, [2, 2, 4, 4, 5]), (2, [2, 2]), (1, [1]), (3, [2, 2, 3]),
    (4, [2, 2, 4, 4]), (8, [2, 2, 4, 4, 8, 8, 8, 8]),
    (7, [2, 2, 4, 4, 7, 7, 7]),
    # past eight slices the native pack runs its runtime-sized loop
    (12, [2, 2, 4, 4, 8, 8, 8, 8, 12, 12, 12, 12])])
def test_the_width_is_the_next_power_of_two_of_the_fullest_row(k, widths):
    assert [fire_matrix_width(k, f) for f in range(1, k + 1)] == widths
    assert fire_matrix_width(k, 0) == min(k, 2)
    for fullest, width in enumerate(widths, start=1):
        indexes = [HostSlotIndex(1 << 10)]
        if slotmap_available():
            indexes.append(NativeSlotIndex(1 << 10))
        got = []
        for index in indexes:
            fill(index, k, fullest)
            keys, matrix, cells = index.slice_matrix(list(range(k)))
            assert matrix.shape == (41, width) and matrix.dtype == np.int32
            assert matrix.flags.c_contiguous
            assert cells == 40 + fullest
            live = matrix != 0
            assert int(live.sum(axis=1).max()) == fullest
            if width < k:
                assert (live[:, :-1] >= live[:, 1:]).all()
            want_keys, want = per_slice_matrix(index, list(range(k)))
            by_key = dict(zip(want_keys.tolist(), want.tolist()))
            for key, row in zip(keys.tolist(), matrix.tolist()):
                # the live slots, in the order of their slices
                assert [s for s in row if s] == [s for s in by_key[key] if s]
            got.append(sorted(zip(keys.tolist(), map(tuple, matrix.tolist()))))
        assert all(g == got[0] for g in got)    # native == Python


@pytest.mark.parametrize("index_cls", [
    HostSlotIndex, pytest.param(NativeSlotIndex, marks=needs_native)])
def test_a_full_row_hands_the_matrix_out_a_column_per_slice(index_cls):
    """A key in all k slices (met first or last): width k, and the matrix
    is the carry's own — every cell in its slice's column."""
    for full_key_first in (True, False):
        index = index_cls(1 << 10)
        for ns in range(K):
            keys = [7] if full_key_first else []
            keys += [100 + ns, 200 + ns]
            if not full_key_first:
                keys.append(7)
            put(index, ns, keys)
        ends = list(range(K))
        keys, matrix, _ = index.slice_matrix(ends)
        assert matrix.shape == (11, K)
        want_keys, want = per_slice_matrix(index, ends)
        assert sorted(zip(keys.tolist(), map(tuple, matrix.tolist()))) \
            == sorted(zip(want_keys.tolist(), map(tuple, want.tolist())))


@pytest.mark.parametrize("index_cls", [
    HostSlotIndex, pytest.param(NativeSlotIndex, marks=needs_native)])
def test_the_width_follows_the_matrix_from_fire_to_fire(index_cls):
    """Decided per fire from the matrix itself: it widens with a key that
    comes back, narrows when that key's cells leave, and a window asked
    for again gets the same."""
    index = index_cls(1 << 10)
    seen = []
    for s in range(12):
        keys = [s * 10 + i for i in range(6)]
        if s in (3, 4, 5, 6):          # key 5000 lives in slices 3..6
            keys.append(5000)
        put(index, s, keys)
        ends = list(range(s - K + 1, s + 1))
        for _ in range(2):
            got_keys, matrix, _ = index.slice_matrix(ends)
            live = matrix != 0
            fullest = int(live.sum(axis=1).max())
            assert matrix.shape[1] == fire_matrix_width(K, fullest)
            assert (live[:, :-1] >= live[:, 1:]).all()
            want_keys, want = per_slice_matrix(index, ends)
            assert sorted(
                (k, *sorted(s for s in row if s))
                for k, row in zip(got_keys.tolist(), matrix.tolist())) \
                == sorted(
                    (k, *sorted(s for s in row if s))
                    for k, row in zip(want_keys.tolist(), want.tolist()))
        seen.append(matrix.shape[1])
        index.free_namespaces([s - K])
    assert seen == [2, 2, 2, 2, 2, 4, 4, 4, 4, 2, 2, 2]


def test_pack_slot_matrix_keeps_each_rows_cells_in_order():
    m = np.array([[0, 3, 0, 9, 0], [5, 0, 0, 0, 0], [0, 0, 0, 0, 8],
                  [0, 4, 6, 0, 0]], dtype=np.int32)
    np.testing.assert_array_equal(
        pack_slot_matrix(m), [[3, 9], [5, 0], [8, 0], [4, 6]])
    m[1, 1:3] = (1, 2)
    np.testing.assert_array_equal(
        pack_slot_matrix(m), [[3, 9, 0, 0], [5, 1, 2, 0], [8, 0, 0, 0],
                              [4, 6, 0, 0]])
    m[1] = (5, 1, 2, 7, 9)
    assert pack_slot_matrix(m) is m
    empty = np.zeros((0, 5), dtype=np.int32)
    assert pack_slot_matrix(empty).shape == (0, 2)
    assert pack_slot_matrix(np.zeros((0, 0), dtype=np.int32)).shape == (0, 0)
    one = np.array([[4], [0]], dtype=np.int32)
    assert pack_slot_matrix(one) is one


# ------------------------------------------ (b) a fire cannot tell the two


AGGS = {
    "sum": lambda: SumAggregate("v", dtype=np.int32),
    "count": lambda: CountAggregate(),
    "max": lambda: MaxAggregate("v"),
    "min": lambda: MinAggregate("v"),
}


@pytest.mark.parametrize("fullest", [1, 2, 3, 5])
@pytest.mark.parametrize("leaf", sorted(AGGS))
def test_a_fire_over_the_cut_matrix_equals_the_full_width_fire(leaf,
                                                               fullest):
    """sum / max / min leaves, fullest rows of 1, 2, 3 and 5 cells
    (widths 2, 2, 4 and 5): the plain fire, the async one, the projected
    one and its async form return, key for key, what they return over the
    matrix with a column per slice."""
    agg = AGGS[leaf]()
    table = SlotTable(agg, capacity=1 << 12)
    rng = np.random.default_rng(fullest * 7 + len(leaf))
    for ns in range(K):
        keys = rng.choice(300, size=60, replace=False) + 1000 * ns
        if ns >= K - fullest:
            keys = np.concatenate([keys, [9077, 9078, 9079]])
        for _ in range(3):              # several records per cell
            slots = table.lookup_or_insert(
                keys.astype(np.int64), np.full(len(keys), ns, np.int64))
            values = tuple(
                rng.integers(-50, 50, len(keys)).astype(l.dtype)
                for l in agg.leaves if l.const is None)
            table.scatter(slots, values)
    ends = list(range(K))
    full_keys, full = per_slice_matrix(table.index, ends)
    keys, cut, _ = table.build_slice_matrix(ends)
    assert cut.shape == (len(full_keys), fire_matrix_width(K, fullest))
    (name,) = agg.output_names

    def by_key(ks, cols):
        return dict(zip(ks.tolist(), cols[name].tolist()))

    want = by_key(full_keys, table.fire(full))
    assert len(want) == 60 * K + 3
    assert by_key(keys, table.fire(cut)) == want
    got_keys, got = table.fire_async(cut, keys).harvest()
    assert by_key(got_keys, got) == want
    # projected: the 16 largest, ties broken by the row's place — the
    # two matrices list the keys in different orders, so hold the values
    # and that every key returned carries its own
    projector = TopKFireProjector(name, k=16)
    top = sorted(want.values(), reverse=True)[:16]
    for got_keys, got in (
            table.fire_projected(full, full_keys, projector),
            table.fire_projected(cut, keys, projector),
            table.fire_projected_async(cut, keys, projector).harvest()):
        assert sorted(got[name].tolist(), reverse=True) == top
        assert all(want[k] == v for k, v in by_key(got_keys, got).items())
        assert len(set(got_keys.tolist())) == 16


# ------------------------------------------- (d) shards of different widths


def mesh_stream(steps=12):
    """HOP(100, 500): keys that live in one slice, and keys 0..5 in every
    slice — whichever shards own those hold full rows, the others rows of
    one cell."""
    rng = np.random.default_rng(21)
    for s in range(steps):
        keys = np.concatenate(
            [10_000 * (s + 1) + rng.integers(0, 400, 250), np.arange(6)])
        vals = rng.integers(1, 9, len(keys))
        ts = rng.integers(s * 100, s * 100 + 100, len(keys))
        yield keys, vals, ts, s * 100 + 99


def run_mesh(make, per_slice, monkeypatch, widths=None):
    with monkeypatch.context() as m:
        for cls in (HostSlotIndex, NativeSlotIndex):
            def spy(self, slice_ends, packed=cls.slice_matrix):
                keys, matrix, cells = packed(self, slice_ends)
                if per_slice:
                    keys, matrix = per_slice_matrix(
                        self, [int(e) for e in slice_ends])
                elif widths is not None:
                    widths.append(matrix.shape[1] if len(keys) else 0)
                return keys, matrix, cells

            m.setattr(cls, "slice_matrix", spy)
        engine = make()
        fired = []
        for keys, vals, ts, wm in mesh_stream():
            engine.process_batch(kb(keys, vals, ts))
            fired.extend(engine.on_watermark(wm))
        fired.extend(engine.on_watermark(10 ** 9))
    rows = []
    for b in fired:
        for r in b.to_rows():
            rows.append((r["window_end"], r[KEY_ID_FIELD],
                         np.float32(r["sum_v"]).tobytes()))
    return sorted(rows)


@pytest.mark.parametrize("spill", [False, True])
def test_a_mesh_fire_over_shards_of_different_widths(monkeypatch, spill):
    """Each shard's matrix is as wide as its own fullest row; the block
    handed to the fire step takes the widest. Sink rows equal, bit for
    bit, a run whose shards hand out a column per slice — the device fire
    and the hybrid (spill) fire."""
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine

    def make():
        return MeshWindowEngine(
            SlidingEventTimeWindows.of(500, 100), SumAggregate("v"),
            make_mesh(4), capacity_per_shard=1 << 12,
            **({"max_device_slots": 1 << 10} if spill else {}))

    widths = []
    got = run_mesh(make, False, monkeypatch, widths)
    want = run_mesh(make, True, monkeypatch)
    assert len(got) > 2000
    assert got == want
    per_fire = [set(widths[i:i + 4]) - {0}
                for i in range(0, len(widths), 4)]
    assert any(len(w) > 1 for w in per_fire), per_fire
    assert {2, 4, 5} <= set(widths)


# ------------------------------------------------ (e) the fire.gather instant


def assert_gathers(cells, rows, width):
    """One fire's ``fire.gather`` work: ``width`` columns of the sticky
    row bucket that holds its ``rows`` (a power of two from 64, kept
    while it wastes at most four times the padding)."""
    wp = cells // width
    assert cells == wp * width and wp & (wp - 1) == 0
    assert max(rows, 64) <= wp < 8 * max(rows, 64)


def test_every_fire_states_the_cells_it_gathers():
    """One ``fire.gather`` instant per fire program, inside
    ``fire.dispatch``: padded rows times the matrix's columns."""
    w = SliceSharedWindower(SlidingEventTimeWindows.of(K * 100, 100),
                            SumAggregate("v"), capacity=1 << 12)
    rec = flight.recorder()
    rec.clear()
    rng = np.random.default_rng(3)
    for s in range(9):
        n = 300
        keys = 1000 * s + rng.integers(0, 200, n)
        if s >= 5:
            keys[:4] = (1, 2, 3, 4)             # from slice 5 on, every slice
        w.process_batch(kb(keys, np.ones(n),
                           np.sort(rng.integers(s * 100, s * 100 + 100, n))))
        assert len(w.on_watermark(s * 100 + 99)) == 1
    gathers = [r for r in rec.snapshot() if r.kind == "fire.gather"]
    rows = [r.work for r in rec.snapshot() if r.kind == "carry.rows"]
    rec.clear()
    assert len(gathers) == len(rows) == 9
    assert all(r.instant and r.parent == "fire.dispatch" for r in gathers)
    # windows 0..4 hold keys of one slice each, 5.. a key in 1, 2, 3, 4, 5
    widths = [2, 2, 2, 2, 2, 2, 2, 4, 4]
    for r, n, width in zip(gathers, rows, widths):
        assert_gathers(r.work, n, width)
