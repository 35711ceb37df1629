import numpy as np

from flink_tpu.state.slot_table import SlotTable, unique_pairs
from flink_tpu.windowing.aggregates import (
    AvgAggregate,
    CountAggregate,
    MaxAggregate,
    MultiAggregate,
    SumAggregate,
)
from flink_tpu.core.records import RecordBatch


def make_batch(keys, values, ts=None):
    cols = {"v": np.asarray(values, dtype=np.float32)}
    b = RecordBatch.from_pydict(cols, timestamps=ts)
    return b


def test_unique_pairs():
    k = np.array([1, 2, 1, 1, 2], dtype=np.int64)
    n = np.array([10, 10, 10, 20, 10], dtype=np.int64)
    uk, un, inv = unique_pairs(k, n)
    assert len(uk) == 3
    pairs = set(zip(uk.tolist(), un.tolist()))
    assert pairs == {(1, 10), (2, 10), (1, 20)}
    # inverse maps each record to its pair
    for i in range(5):
        assert (uk[inv[i]], un[inv[i]]) == (k[i], n[i])


def test_scatter_and_fire_sum():
    agg = SumAggregate("v")
    t = SlotTable(agg, capacity=1024)
    keys = np.array([7, 8, 7, 9], dtype=np.int64)
    ns = np.array([100, 100, 100, 100], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    assert slots[0] == slots[2]
    assert slots.min() >= 1  # slot 0 reserved
    t.scatter(slots, agg.map_input(make_batch(keys, [1, 2, 3, 4])))
    s = t.slots_for_namespace(100)
    res = t.fire(s[:, None])
    by_key = dict(zip(t.keys_of_slots(s).tolist(), res["sum_v"].tolist()))
    assert by_key == {7: 4.0, 8: 2.0, 9: 4.0}


def test_free_namespaces_resets_and_reuses():
    agg = SumAggregate("v")
    t = SlotTable(agg, capacity=1024)
    keys = np.array([1, 2], dtype=np.int64)
    ns = np.array([5, 5], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    t.scatter(slots, (np.array([10.0, 20.0], dtype=np.float32),))
    t.free_namespaces([5])
    assert t.num_used == 0
    # reused slots must start from identity
    slots2 = t.lookup_or_insert(keys, ns)
    t.scatter(slots2, (np.array([1.0, 1.0], dtype=np.float32),))
    res = t.fire(t.slots_for_namespace(5)[:, None])
    assert sorted(res["sum_v"].tolist()) == [1.0, 1.0]


def test_growth():
    agg = CountAggregate()
    t = SlotTable(agg, capacity=1024)
    keys = np.arange(5000, dtype=np.int64)
    ns = np.zeros(5000, dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    assert t.capacity >= 5000
    assert len(np.unique(slots)) == 5000
    t.scatter(slots, agg.map_input(RecordBatch.from_pydict({"x": np.zeros(5000)})))
    res = t.fire(t.slots_for_namespace(0)[:, None])
    assert res["count"].sum() == 5000


def test_multi_aggregate():
    agg = MultiAggregate([SumAggregate("v"), MaxAggregate("v"), AvgAggregate("v"),
                          CountAggregate()])
    t = SlotTable(agg, capacity=1024)
    keys = np.array([1, 1, 2], dtype=np.int64)
    ns = np.array([0, 0, 0], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    b = make_batch(keys, [3.0, 5.0, 7.0])
    t.scatter(slots, agg.map_input(b))
    s = t.slots_for_namespace(0)
    res = t.fire(s[:, None])
    by_key = {k: i for i, k in enumerate(t.keys_of_slots(s).tolist())}
    assert res["sum_v"][by_key[1]] == 8.0
    assert res["max_v"][by_key[1]] == 5.0
    assert res["avg_v"][by_key[1]] == 4.0
    assert res["count"][by_key[2]] == 1


def test_snapshot_restore_roundtrip():
    agg = SumAggregate("v")
    t = SlotTable(agg, capacity=1024)
    keys = np.array([1, 2, 3], dtype=np.int64)
    ns = np.array([100, 100, 200], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    t.scatter(slots, (np.array([1.0, 2.0, 3.0], dtype=np.float32),))
    snap = t.snapshot()

    t2 = SlotTable(agg, capacity=1024)
    t2.restore(snap)
    s = t2.slots_for_namespace(100)
    res = t2.fire(s[:, None])
    by_key = dict(zip(t2.keys_of_slots(s).tolist(), res["sum_v"].tolist()))
    assert by_key == {1: 1.0, 2: 2.0}


def test_snapshot_restore_key_group_filter():
    from flink_tpu.state.keygroups import assign_key_groups

    agg = SumAggregate("v")
    t = SlotTable(agg, capacity=1024, max_parallelism=16)
    keys = np.arange(100, dtype=np.int64)
    ns = np.zeros(100, dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    t.scatter(slots, (np.ones(100, dtype=np.float32),))
    snap = t.snapshot()

    owned = set(range(0, 8))
    t2 = SlotTable(agg, capacity=1024, max_parallelism=16)
    t2.restore(snap, key_group_filter=owned)
    groups = assign_key_groups(keys, 16)
    expected = int((np.isin(groups, list(owned))).sum())
    assert t2.num_used == expected


def test_const_leaf_keeps_slot0_identity():
    """COUNT's const-1 input must not pollute the reserved identity slot 0:
    padded scatter lanes target slot 0, and fire matrices read slot 0 for
    missing slices — it must stay at the identity element."""
    import jax.numpy as jnp

    agg = MultiAggregate([CountAggregate(), SumAggregate("v")])
    t = SlotTable(agg, capacity=1024)
    keys = np.array([7, 8, 7], dtype=np.int64)
    ns = np.array([100, 100, 100], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    # scatter pads to a 256 bucket -> 253 padded lanes target slot 0
    t.scatter(slots, agg.map_input(make_batch(keys, [1.0, 2.0, 3.0])))
    assert int(np.asarray(t.accs[0])[0]) == 0  # count leaf identity
    assert float(np.asarray(t.accs[1])[0]) == 0.0
    # fire with a missing-slice column (slot 0) must not inflate counts
    s = t.slots_for_namespace(100)
    matrix = np.zeros((len(s), 2), dtype=np.int32)
    matrix[:, 0] = s
    res = t.fire(matrix)
    by_key = dict(zip(t.keys_of_slots(s).tolist(), res["count"].tolist()))
    assert by_key == {7: 2, 8: 1}


def test_avg_aggregate_const_count():
    agg = AvgAggregate("v")
    t = SlotTable(agg, capacity=1024)
    keys = np.array([1, 1, 2], dtype=np.int64)
    ns = np.array([5, 5, 5], dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    t.scatter(slots, agg.map_input(make_batch(keys, [2.0, 4.0, 10.0])))
    s = t.slots_for_namespace(5)
    res = t.fire(s[:, None])
    by_key = dict(zip(t.keys_of_slots(s).tolist(), res["avg_v"].tolist()))
    assert by_key == {1: 3.0, 2: 10.0}


def test_monotonic_fire_bucket_reuses_shape():
    agg = SumAggregate("v")
    t = SlotTable(agg, capacity=4096)
    keys = np.arange(1, 201, dtype=np.int64)
    ns = np.full(200, 1, dtype=np.int64)
    slots = t.lookup_or_insert(keys, ns)
    t.scatter(slots, (np.ones(200, dtype=np.float32),))
    t.fire(slots[:, None])            # bucket -> 256
    assert t._fire_bucket == 256
    small = t.fire(slots[:3][:, None])  # smaller fire reuses the 256 bucket
    assert t._fire_bucket == 256
    assert len(small["sum_v"]) == 3


# --------------------------------------------- the batch sweep's entry
#
# ``SlotTable.resolve_slices``: one native sweep over keys and timestamps
# where the table's own state allows it, None (and nothing changed)
# where the batch has to take ``upsert``'s path.

import pytest

import flink_tpu.state.slot_table as slot_table_mod
from flink_tpu.core.records import KEY_ID_FIELD
from flink_tpu.native import slotmap_available
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.state.slot_table import HostSlotIndex, NativeSlotIndex
from flink_tpu.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
)
from flink_tpu.windowing.windower import SliceSharedWindower

needs_native = pytest.mark.skipif(
    not slotmap_available(), reason="native slotmap not built")
EVERYTHING_LIVE = -(1 << 62)


def use_index(monkeypatch, cls):
    monkeypatch.setattr(
        slot_table_mod, "make_slot_index",
        lambda capacity, on_grow=None, growable=True, full_hint="",
        max_capacity=0, track_namespaces=True: cls(
            capacity, on_grow=on_grow, growable=growable,
            full_hint=full_hint, max_capacity=max_capacity,
            track_namespaces=track_namespaces))


@needs_native
@pytest.mark.parametrize("index, budget, taken", [
    ("native", 0, True),
    ("native", 1 << 14, False),     # spill tiers: resident before insert
    ("host", 0, False),             # the Python index has one path
    ("host", 1 << 14, False),
])
def test_the_sweep_is_taken_where_index_and_budget_allow(
        monkeypatch, index, budget, taken):
    use_index(monkeypatch,
              NativeSlotIndex if index == "native" else HostSlotIndex)
    t = SlotTable(SumAggregate("v"), capacity=1024,
                  max_device_slots=budget)
    keys = np.array([7, 8, 7, 9, 7], dtype=np.int64)
    ts = np.array([10, 20, 30, 140, 150], dtype=np.int64)
    got = t.resolve_slices(keys, ts, 0, 100, EVERYTHING_LIVE)
    if not taken:
        assert got is None and t.num_used == 0
        assert t.index.pairs_inserted == 0 and not t.index.namespaces
        return
    slots, ends, inserted = got
    assert ends.tolist() == [100, 200] and inserted == 4
    assert slots[0] == slots[2] != slots[4] and slots.min() >= 1
    np.testing.assert_array_equal(
        slots, t.lookup_or_insert(keys, np.array([100] * 3 + [200] * 2)))
    assert t.index.namespaces == [100, 200]
    assert t.slots_for_namespace(100).tolist() == slots[[0, 1]].tolist()
    assert t.slots_for_namespace(200).tolist() == slots[[3, 4]].tolist()
    # a late record: nothing changes, and the caller's path is asked for
    assert t.resolve_slices(keys, ts, 0, 100, 200) is None
    assert t.num_used == 4
    t.scatter(slots, (np.ones(5, dtype=np.float32),))
    res = t.fire(np.array([[slots[0], slots[4]]], dtype=np.int32))
    assert res["sum_v"].tolist() == [3.0]


def kb(keys, values, ts):
    return RecordBatch.from_pydict(
        {KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
         "v": np.asarray(values, dtype=np.float32)},
        timestamps=ts)


def window_stream(kind, seed):
    """(keys, values, timestamps, watermark) per batch over 100 ms
    slices: in order; out of order inside the allowed lateness; with
    records past it (dropped); and all three by turns."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(16):
        n = int(rng.integers(50, 400))
        lo = s * 100
        back = {"in_order": 0, "disordered": 150, "late": 900,
                "mixed": (0, 150, 900)[s % 3]}[kind]
        ts = rng.integers(max(0, lo - back), lo + 100, n)
        if back == 0:
            ts = np.sort(ts)
        out.append((rng.integers(0, 60, n), rng.random(n) * 100, ts,
                    lo + 60))
    return out


def run_windower(w, stream):
    rows = []
    for keys, vals, ts, wm in stream:
        w.process_batch(kb(keys, vals, ts))
        for b in w.on_watermark(wm):
            rows.extend((r["window_end"], r[KEY_ID_FIELD],
                         np.float32(r["sum_v"]).tobytes())
                        for r in b.to_rows())
    live = {int(ns): sorted(
        w.table.index.slot_key[w.table.slots_for_namespace(ns)].tolist())
        for ns in w.table.namespaces}
    for b in w.on_watermark(10 ** 9):
        rows.extend((r["window_end"], r[KEY_ID_FIELD],
                     np.float32(r["sum_v"]).tobytes()) for r in b.to_rows())
    return sorted(rows), live, w.late_records_dropped


@needs_native
@pytest.mark.parametrize("assigner", ["hop", "tumble"])
@pytest.mark.parametrize("kind", ["in_order", "disordered", "late", "mixed"])
def test_windower_results_are_the_same_on_every_path(
        monkeypatch, kind, assigner):
    """The sweep against the path it falls back to (same native index,
    the sweep refused) and against the Python index: the same sink rows
    bit for bit, the same live (slice -> keys) registry before the
    flush, the same count of late records dropped; with the native
    index on both sides the same slot under every pair too."""
    def make():
        a = (SlidingEventTimeWindows.of(500, 100) if assigner == "hop"
             else TumblingEventTimeWindows.of(100, 30))
        return SliceSharedWindower(a, SumAggregate("v"), capacity=1024,
                                   allowed_lateness=200)

    stream = window_stream(kind, seed=len(kind))
    swept = make()
    rec = flight.recorder()
    rec.clear()
    got = run_windower(swept, stream)
    sweeps = rec.kind_totals().get("resolve.sweep", {"count": 0, "work": 0})
    rec.clear()
    # every batch without a late record took the sweep and said so
    if kind in ("in_order", "disordered"):
        assert sweeps["count"] == len(stream)
        assert sweeps["work"] == sum(len(b[0]) for b in stream)
    else:
        assert 0 < sweeps["count"] < len(stream)
    with monkeypatch.context() as m:
        m.setattr(SlotTable, "resolve_slices", lambda self, *a: None)
        plain = make()
        want = run_windower(plain, stream)
        assert "resolve.sweep" not in rec.kind_totals()
    with monkeypatch.context() as m:
        use_index(m, HostSlotIndex)
        host = make()
        assert type(host.table.index) is HostSlotIndex
        want_host = run_windower(host, stream)
    assert len(got[0]) > 300
    assert got == want == want_host
    assert (got[2] > 0) == (kind in ("late", "mixed"))
    assert swept.table.index.pairs_inserted \
        == plain.table.index.pairs_inserted \
        == host.table.index.pairs_inserted > 0
