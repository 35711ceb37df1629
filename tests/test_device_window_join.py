"""The device window join (``MeshWindowJoinEngine`` behind
``DeviceWindowJoinOperator``) against the host ``WindowJoinOperator`` and a
plain NumPy join, on seeded random inputs: every pair of a window, once,
whatever the keys, the windows or the order the two sides arrive in."""

import warnings

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import Source
from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.joins import operators as join_ops
from flink_tpu.joins.engine import MeshWindowJoinEngine, fine_bucket
from flink_tpu.runtime.join_operators import WindowJoinOperator
from flink_tpu.runtime.operators import OperatorContext
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.assigners import (
    SlidingEventTimeWindows,
    TumblingEventTimeWindows,
    TumblingProcessingTimeWindows,
)

#: two keys that share their low 32 bits: a join on 32 bits would pair them
WIDE = (1 << 33) + 5, (1 << 40) + 5


class Rows(Source):
    """A bounded source handing out fixed batches in order."""

    bounded = True

    def __init__(self, batches):
        self.batches = list(batches)

    def poll_batch(self, max_records):
        return self.batches.pop(0) if self.batches else None


def side(keys, ts, value_name, values):
    return RecordBatch.from_pydict(
        {"k": np.asarray(keys, dtype=np.int64),
         value_name: np.asarray(values)}, timestamps=np.asarray(ts))


def numpy_join(left, right, window_of):
    """Every (left, right) pair with equal keys in one window:
    ``(window_end, key, left value, right value)``, sorted."""
    out = []
    for lk, lt, lv in zip(*left):
        for rk, rt, rv in zip(*right):
            for w in window_of(lt) & window_of(rt):
                if lk == rk:
                    out.append((w, int(lk), lv, rv))
    return sorted(out)


def tumbling(size):
    return lambda t: {(int(t) // size + 1) * size}


def sliding(size, slide):
    def of(t):
        last = (int(t) // slide + 1) * slide
        return {e for e in range(last, last + size, slide) if e - size <= t}
    return of


def run(batches, assigner, mode, micro_batch=64, delay=0, parallelism=1):
    """The two streams through ``env.execute()`` under ``join.mode``;
    the sink's rows as ``(window_end, k, lv, rv)``, sorted, and the
    sink."""
    env = StreamExecutionEnvironment(Configuration({
        "join.mode": mode, "execution.micro-batch.size": micro_batch,
        "state.slot-table.capacity": 1 << 12,
        "parallelism.default": parallelism}))
    sink = CollectSink()
    streams = [env.from_source(
        Rows(b), WatermarkStrategy.for_bounded_out_of_orderness(delay))
        for b in batches]
    (streams[0].join(streams[1]).where("k").equal_to("k")
     .window(assigner).apply().sink_to(sink))
    env.execute("wj-" + mode)
    rows = []
    for b in sink.batches:
        rows.extend(zip(b["window_end"].tolist(), b["k"].tolist(),
                        b["lv"].tolist(), b["rv"].tolist()))
    return sorted(rows), sink


def random_sides(seed, n_left, n_right, keys, t_max, hot=None):
    rng = np.random.default_rng(seed)
    lk = rng.integers(0, keys, n_left)
    rk = rng.integers(0, keys, n_right)
    if hot is not None:
        lk[rng.random(n_left) < 0.3] = hot
        rk[rng.random(n_right) < 0.5] = hot
    lt = np.sort(rng.integers(0, t_max, n_left))
    rt = np.sort(rng.integers(0, t_max, n_right))
    lv = rng.integers(0, 1 << 30, n_left).astype(np.int32)
    rv = rng.random(n_right)                      # float64: two words
    return (lk, lt, lv.tolist()), (rk, rt, rv.tolist())


def batches_of(rows, name, size):
    k, t, v = rows
    return [side(k[a:a + size], t[a:a + size], name, v[a:a + size])
            for a in range(0, len(k), size)]


CASES = {
    # many-to-many keys and one hot key with hundreds of matches a window
    "hot_many_to_many": dict(seed=1, n_left=600, n_right=900, keys=20,
                             t_max=5000, hot=7,
                             assigner=TumblingEventTimeWindows.of(1000),
                             windows=tumbling(1000)),
    "sliding": dict(seed=2, n_left=400, n_right=500, keys=15, t_max=4000,
                    assigner=SlidingEventTimeWindows.of(1000, 250),
                    windows=sliding(1000, 250)),
    "sparse_keys": dict(seed=3, n_left=300, n_right=300, keys=400,
                        t_max=3000, assigner=TumblingEventTimeWindows.of(500),
                        windows=tumbling(500)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_equals_host_and_numpy(case):
    c = dict(CASES[case])
    assigner, window_of = c.pop("assigner"), c.pop("windows")
    left, right = random_sides(**c)
    want = numpy_join(left, right, window_of)
    host, _ = run([batches_of(left, "lv", 97), batches_of(right, "rv", 61)],
                  assigner, "host")
    device, _ = run([batches_of(left, "lv", 97),
                     batches_of(right, "rv", 61)], assigner, "device")
    assert len(want) > 0
    assert device == host == want


def test_the_join_over_four_shards_equals_numpy():
    """Key groups spread over four shards: each shard's pages, slots and
    pairs are its own, the output their union."""
    c = dict(CASES["hot_many_to_many"])
    assigner, window_of = c.pop("assigner"), c.pop("windows")
    left, right = random_sides(**c)
    got, _ = run([batches_of(left, "lv", 97), batches_of(right, "rv", 61)],
                 assigner, "device", parallelism=4)
    assert got == numpy_join(left, right, window_of)


def test_keys_that_share_their_low_32_bits_do_not_pair():
    left = ([WIDE[0], WIDE[1], 5], [10, 20, 30], [1, 2, 3])
    right = ([WIDE[0], 5, WIDE[1], WIDE[1]], [15, 25, 35, 45],
             [0.5, 0.25, 0.125, 0.0625])
    want = numpy_join(left, right, tumbling(100))
    got, _ = run([[side(*left[:2], "lv", left[2])],
                  [side(*right[:2], "rv", right[2])]],
                 TumblingEventTimeWindows.of(100), "device")
    assert got == want
    assert sorted(r[1] for r in got) == [5, WIDE[0], WIDE[1], WIDE[1]]


def test_a_side_empty_in_a_window_and_rows_at_the_window_edges():
    # window [0, 100): left only; [100, 200): both, right at 199 (its last
    # millisecond) and left at 100 (its first); 200 is the next window's
    left = ([1, 1, 2], [50, 100, 200], [10, 11, 12])
    right = ([1, 1, 2], [199, 200, 300], [0.5, 0.75, 1.0])
    want = numpy_join(left, right, tumbling(100))
    got, sink = run([[side(*left[:2], "lv", left[2])],
                     [side(*right[:2], "rv", right[2])]],
                    TumblingEventTimeWindows.of(100), "device")
    assert got == want == [(200, 1, 11, 0.5)]
    (b,) = sink.batches
    assert b[TIMESTAMP_FIELD].tolist() == [199]
    assert b["window_start"].tolist() == [100]


def _empty(name, dtype):
    return RecordBatch.from_pydict(
        {"k": np.zeros(0, np.int64), name: np.zeros(0, dtype)},
        timestamps=np.zeros(0, np.int64))


@pytest.mark.parametrize("first", ["left", "right"])
def test_either_side_may_arrive_first(first):
    left, right = random_sides(seed=4, n_left=200, n_right=200, keys=10,
                               t_max=2000)
    want = numpy_join(left, right, tumbling(400))
    lb, rb = batches_of(left, "lv", 200), batches_of(right, "rv", 200)
    # one input's first batch is empty: the other's opens every window
    if first == "left":
        batches = [lb, [_empty("rv", np.float64)] + rb]
    else:
        batches = [[_empty("lv", np.int32)] + lb, rb]
    got, _ = run(batches, TumblingEventTimeWindows.of(400), "device",
                 micro_batch=512)
    assert got == want and len(want) > 0


def test_the_end_of_input_flush_fires_every_open_window():
    """No watermark passes the last windows: the final one does."""
    left, right = random_sides(seed=5, n_left=150, n_right=150, keys=6,
                               t_max=3000)
    want = numpy_join(left, right, tumbling(1000))
    got, _ = run([batches_of(left, "lv", 150), batches_of(right, "rv", 150)],
                 TumblingEventTimeWindows.of(1000), "device",
                 delay=10_000)
    assert {w for w, *_ in got} == {1000, 2000, 3000}
    assert got == want


def test_a_fallback_shape_is_counted_and_runs_on_the_host():
    left, right = random_sides(seed=6, n_left=50, n_right=50, keys=5,
                               t_max=100)
    before = join_ops.fallbacks()
    env = StreamExecutionEnvironment()
    a = env.from_source(Rows(batches_of(left, "lv", 50)))
    b = env.from_source(Rows(batches_of(right, "rv", 50)))
    with pytest.warns(RuntimeWarning, match="processing-time"):
        t = (a.join(b).where("k").equal_to("k")
             .window(TumblingProcessingTimeWindows.of(1000)).apply()
             .transformation)
    assert join_ops.fallbacks() == before + 1
    assert isinstance(t.operator_factory(), WindowJoinOperator)


def test_a_string_column_moves_the_join_to_the_host_state_and_all():
    """The left's rows are on the device when the right's first rows
    bring a column the device cannot carry: they go with the join."""
    left = side([1, 2, 1], [10, 20, 30], "lv", [1, 2, 3])
    right = RecordBatch.from_pydict(
        {"k": np.array([1, 2], np.int64),
         "rv": np.array(["x", "y"], dtype=object)},
        timestamps=np.array([40, 50], np.int64))
    before = join_ops.fallbacks()
    env = StreamExecutionEnvironment(Configuration(
        {"execution.micro-batch.size": 8}))
    sink = CollectSink()
    a = env.from_source(Rows([left]),
                        WatermarkStrategy.for_bounded_out_of_orderness(0))
    b = env.from_source(Rows([_empty("rv", object), right]),
                        WatermarkStrategy.for_bounded_out_of_orderness(0))
    (a.join(b).where("k").equal_to("k")
     .window(TumblingEventTimeWindows.of(100)).apply().sink_to(sink))
    with pytest.warns(RuntimeWarning, match="cannot ride the device"):
        env.execute("to-host")
    assert join_ops.fallbacks() == before + 1
    got = sorted((k, lv, rv) for s in sink.batches
                 for k, lv, rv in zip(s["k"].tolist(), s["lv"].tolist(),
                                      s["rv"].tolist()))
    assert got == [(1, 1, "x"), (1, 3, "x"), (2, 2, "y")]


# ---------------------------------------------------------------- engine


def engine(capacity=1 << 12, size=1000):
    return MeshWindowJoinEngine(TumblingEventTimeWindows.of(size),
                                ("k", "k"), capacity_per_shard=capacity)


def keyed(keys, ts, name, values):
    b = side(keys, ts, name, values)
    return b.with_column(KEY_ID_FIELD, b["k"])


def test_a_batch_touches_only_its_own_rows_in_the_index():
    """The index work of a batch is its rows and the pages they reach,
    whatever is live already: eight times the rows live, the same work."""
    e = engine(capacity=1 << 16)
    per_batch = []
    rng = np.random.default_rng(7)
    for i in range(16):
        n = 1000
        before = e.index[0].touched
        e.input_side = 0
        e.process_batch(keyed(rng.integers(0, 50, n), np.full(n, 10 + i),
                              "lv", np.arange(n, dtype=np.int32)))
        per_batch.append(e.index[0].touched - before)
    assert e.index[0].slices[0][1000].rows == 16_000
    pages = -(-1000 // e.page_rows) + 1
    assert all(1000 < w <= 1000 + pages for w in per_batch), per_batch
    assert max(per_batch[8:]) <= max(per_batch[:2])


def test_a_fired_window_leaves_as_its_pages():
    e = engine()
    for s, (name, v) in enumerate((("lv", np.int32), ("rv", np.float64))):
        e.input_side = s
        e.process_batch(keyed([1, 2, 3], [5, 6, 7], name,
                              np.arange(3).astype(v)))
    free = [len(i._free[0]) for i in e.index]
    (out,) = e.on_watermark(2000)
    assert len(out) == 3
    assert [len(i._free[0]) for i in e.index] == [f + 1 for f in free]
    assert e.index[0].live_slices() == [] == e.index[1].live_slices()


def test_more_pairs_than_sized_run_again_wider():
    """A window's output is sized from its rows; a key with more pairs
    than rows takes a second, wider run at the harvest."""
    e = engine()
    n = 300
    e.input_side = 0
    e.process_batch(keyed(np.full(n, 9), np.full(n, 5), "lv",
                          np.arange(n, dtype=np.int32)))
    e.input_side = 1
    e.process_batch(keyed(np.full(n, 9), np.full(n, 6), "rv",
                          np.arange(n, dtype=np.float64)))
    (out,) = e.on_watermark(1000)
    assert len(out) == n * n
    assert sorted(zip(out["lv"].tolist(), out["rv"].tolist())) == sorted(
        (i, float(j)) for i in range(n) for j in range(n))


def test_the_snapshot_is_the_host_operators_and_restores_either_way():
    left, right = random_sides(seed=8, n_left=120, n_right=120, keys=8,
                               t_max=1900)
    e = engine()
    for s, rows, name in ((0, left, "lv"), (1, right, "rv")):
        e.input_side = s
        for b in batches_of(rows, name, 40):
            e.process_batch(b.with_column(KEY_ID_FIELD, b["k"]))
    snap = e.snapshot()
    host = WindowJoinOperator(TumblingEventTimeWindows.of(1000),
                              key_fields=("k", "k"))
    host.open(OperatorContext())
    host.restore_state(snap)
    again = engine()
    again.restore(snap)

    def rows(batches):
        return sorted(r for b in batches for r in zip(
            *(b[c].tolist() for c in ("window_end", "k", "lv", "rv"))))

    want = numpy_join(left, right, tumbling(1000))
    assert rows(host.process_watermark(5000)) == want
    assert rows(again.on_watermark(5000)) == want


# ------------------------------------------- rows staged behind a match


class HeldFires:
    """A test double on ``PendingFire.ready``: every fire reads not ready
    while ``held``; with ``looks``, a fire reads not ready for its first
    ``looks`` looks and then as it is."""

    def __init__(self, monkeypatch, looks=None):
        from flink_tpu.runtime.pending import PendingFire

        real = PendingFire.ready
        self.held = looks is None
        seen = {}

        def ready(pf):
            seen[pf] = seen.get(pf, 0) + 1
            if self.held or seen[pf] <= (looks or 0):
                return False
            return real(pf)

        monkeypatch.setattr(PendingFire, "ready", ready)


def pairs_of(batches):
    return sorted(r for b in batches if b is not None for r in zip(
        *(b[c].tolist() for c in ("window_end", "k", "lv", "rv"))))


def tumbling_join(left, right, size):
    """``numpy_join`` for tumbling windows, by (window, key) groups: fast
    enough for thousands of rows a side."""
    from collections import defaultdict

    lefts = defaultdict(list)
    for k, t, v in zip(*left):
        lefts[(int(t) // size + 1) * size, int(k)].append(v)
    return sorted((w, k, lv, rv) for k, t, rv in zip(*right)
                  for w in [(int(t) // size + 1) * size]
                  for lv in lefts.get((w, int(k)), ()))


def next_window(seed, n_left, n_right, keys):
    """Random rows of the window [1000, 2000)."""
    left, right = random_sides(seed, n_left, n_right, keys, t_max=1000)
    return tuple((k, t + 1000, v) for k, t, v in (left, right))


def stage(e, left, right, size=250):
    for s, rows, name in ((0, left, "lv"), (1, right, "rv")):
        e.input_side = s
        for b in batches_of(rows, name, size):
            assert e.process_batch(b.with_column(KEY_ID_FIELD, b["k"])) \
                is False
    e.input_side = 0


#: the first window's pairs, as ``staged_engine`` ingests it
FIRST = [(1000, 1, 0, 0.0), (1000, 2, 1, 1.0), (1000, 3, 2, 2.0)]


def staged_engine(monkeypatch, capacity=1 << 12):
    """An engine whose first window is fired asynchronously and held not
    ready: what it ingests next waits in its stage."""
    hold = HeldFires(monkeypatch)
    e = engine(capacity)
    for s, (name, v) in enumerate((("lv", np.int32), ("rv", np.float64))):
        e.input_side = s
        assert e.process_batch(keyed([1, 2, 3], [5, 6, 7], name,
                                     np.arange(3).astype(v)))
    (pf,) = e.on_watermark(1000, async_ok=True)
    assert e.match_running() and not e.staged
    return e, pf, hold


def test_rows_staged_over_several_chunks_pair_as_numpy(monkeypatch):
    e, pf, hold = staged_engine(monkeypatch)
    left, right = next_window(21, 3000, 2500, keys=300)
    stage(e, left, right)
    # 8 buckets of 256 lanes a chunk: 3,000 and 2,500 rows take two each
    assert [(len(s.blocks), s.lanes, s.fill) for s in e._stage] == [
        (2, 2048, [3000]), (2, 2048, [2500])]
    hold.held = False
    assert pairs_of([pf.harvest()]) == FIRST
    want = tumbling_join(left, right, 1000)
    assert len(want) > 10_000
    assert pairs_of(e.on_watermark(2000)) == want
    assert not e.staged


def test_a_grow_while_rows_are_staged_keeps_the_old_capacity_slot(
        monkeypatch):
    """Two pages a side; the first window holds one. 100 staged rows take
    the other, then 200 more grow the planes to four pages: the stage
    goes up first, its padding lanes on the slot 256 that the old planes
    drop, and the row that takes slot 256 after the grow keeps it."""
    e, pf, hold = staged_engine(monkeypatch, capacity=256)
    assert (e.capacity, e.page_rows) == (256, 128)
    keys = np.arange(300) * 3 + 10
    left = (keys, np.full(300, 1500), np.arange(300, dtype=np.int32))
    right = (keys[::2], np.full(150, 1600), np.arange(150) / 4)
    e.input_side = 0
    assert e.process_batch(keyed(keys[:100], left[1][:100], "lv",
                                 left[2][:100])) is False
    assert e.staged and e.capacity == 256
    assert e.process_batch(keyed(keys[100:], left[1][100:], "lv",
                                 left[2][100:])) is False
    # the stage went up before the grow; the 200 rows are staged anew
    assert e.capacity == 512 and e._stage[0].fill == [200]
    e.input_side = 1
    assert e.process_batch(keyed(*right[:2], "rv", right[2])) is False
    hold.held = False
    assert e.flush() and not e.staged
    # slot 256 opens the first new page: the 200's 29th row (100 + 28
    # rows fill the last old page)
    k0 = np.asarray(e._planes[0][0])[0]
    assert k0[256] == keys[128] and (k0[384:428] == keys[256:]).all()
    assert pairs_of([pf.harvest()]) == FIRST
    assert pairs_of(e.on_watermark(2000)) == numpy_join(
        left, right, tumbling(1000))


def test_a_snapshot_while_rows_are_staged_holds_them(monkeypatch):
    e, pf, hold = staged_engine(monkeypatch)
    left, right = next_window(22, 400, 500, keys=30)
    stage(e, left, right, size=97)
    snap = e.snapshot()
    assert not e.staged
    (sides,) = snap["buf"].values()
    assert [sum(len(c["k"]) for c in parts) for parts in sides] == [400,
                                                                    500]
    again = engine()
    again.restore(snap)
    hold.held = False
    assert pairs_of([pf.harvest()]) == FIRST
    want = tumbling_join(left, right, 1000)
    assert pairs_of(again.on_watermark(2000)) == want
    assert pairs_of(e.on_watermark(2000)) == want


def test_a_fire_dispatched_while_rows_are_staged_sees_them(monkeypatch):
    e, pf, hold = staged_engine(monkeypatch)
    left, right = next_window(23, 400, 500, keys=30)
    stage(e, left, right, size=61)
    (pf2,) = e.on_watermark(2000, async_ok=True)
    assert not e.staged
    # two fires out: the device is a window behind, and a batch goes up
    e.input_side = 0
    assert e.process_batch(keyed([5], [2500], "lv", np.array([9], np.int32)))
    assert not e.staged and not e.match_running()
    assert pairs_of([pf.harvest()]) == FIRST
    # one out again, and held: the next batch is staged
    assert e.match_running()
    assert e.process_batch(keyed([6], [2600], "lv",
                                 np.array([8], np.int32))) is False
    hold.held = False
    assert pairs_of([pf2.harvest()]) == tumbling_join(left, right, 1000)
    assert e.staged and not e.match_running()


def test_synchronous_fires_stage_nothing(monkeypatch):
    from flink_tpu.observe import flight_recorder as flight

    HeldFires(monkeypatch)
    rec = flight.recorder()
    rec.clear()
    e = engine()
    for s, (name, v) in enumerate((("lv", np.int32), ("rv", np.float64))):
        e.input_side = s
        e.process_batch(keyed([1, 2, 3], [5, 6, 7], name,
                              np.arange(3).astype(v)))
    assert pairs_of(e.on_watermark(1000)) == FIRST
    left, right = next_window(24, 200, 200, keys=20)
    for s, rows, name in ((0, left, "lv"), (1, right, "rv")):
        e.input_side = s
        for b in batches_of(rows, name, 50):
            assert e.process_batch(b.with_column(KEY_ID_FIELD, b["k"]))
            assert not e.match_running() and not e.staged
    assert "join.staged" not in rec.kind_totals()
    # and a job whose executor fires synchronously
    c = dict(CASES["hot_many_to_many"])
    assigner, window_of = c.pop("assigner"), c.pop("windows")
    left, right = random_sides(**c)
    env = StreamExecutionEnvironment(Configuration({
        "execution.window.async-fires": False,
        "execution.micro-batch.size": 64,
        "state.slot-table.capacity": 1 << 12}))
    sink = CollectSink()
    a, b = (env.from_source(Rows(bs), WatermarkStrategy
                            .for_bounded_out_of_orderness(0))
            for bs in (batches_of(left, "lv", 97),
                       batches_of(right, "rv", 61)))
    a.join(b).where("k").equal_to("k").window(assigner).apply() \
        .sink_to(sink)
    env.execute("wj-sync")
    assert pairs_of(sink.batches) == numpy_join(left, right, window_of)
    assert "join.staged" not in rec.kind_totals()
    rec.clear()


def test_join_staged_counts_exactly_the_rows_staged(monkeypatch):
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    e, pf, hold = staged_engine(monkeypatch)
    rec.clear()
    left, right = next_window(25, 300, 200, keys=20)
    stage(e, left, right, size=70)
    # a batch with three rows behind the fired window: they are dropped,
    # not staged
    e.input_side = 1
    assert e.process_batch(keyed([1, 2, 3, 4], [900, 950, 999, 1999], "rv",
                                 np.arange(4) / 2)) is False
    kt = rec.kind_totals()["join.staged"]
    assert kt["work"] == sum(sum(s.fill) for s in e._stage) == 501
    assert kt["count"] == 5 + 3 + 1
    assert e.late_records_dropped == 3
    hold.held = False
    pf.harvest()
    e.flush()
    assert "join.staged" in rec.kind_totals()
    assert rec.kind_totals()["join.staged"]["work"] == 501
    rec.clear()


@pytest.mark.parametrize("parallelism", [1, 4])
def test_a_job_whose_fires_land_late_stages_and_equals_numpy(
        monkeypatch, parallelism):
    """Each fire reads not ready for its first four looks: the batches
    that arrive meanwhile are staged, and go up at the poll that finds
    the fire landed, before its harvest."""
    from flink_tpu.observe import flight_recorder as flight

    HeldFires(monkeypatch, looks=4)
    rec = flight.recorder()
    rec.clear()
    c = dict(CASES["hot_many_to_many"])
    assigner, window_of = c.pop("assigner"), c.pop("windows")
    left, right = random_sides(**c)
    got, _ = run([batches_of(left, "lv", 97), batches_of(right, "rv", 61)],
                 assigner, "device", parallelism=parallelism)
    assert got == numpy_join(left, right, window_of)
    assert rec.kind_totals()["join.staged"]["work"] > 0
    rec.clear()


def test_fine_buckets_pad_by_at_most_a_quarter_and_stick():
    assert fine_bucket(6_000_000) == 6_291_456
    assert fine_bucket(123, minimum=8) == 128
    assert fine_bucket(367, minimum=8) == 384
    assert fine_bucket(17_500, cached=32_768) == 32_768   # a tail batch
    assert fine_bucket(200_000, cached=32_768) == 229_376
    for n in range(300, 5000, 37):
        assert n <= fine_bucket(n) <= n + n // 4 + 1


# ---------------------------------------------------------------- kernel

I32 = np.iinfo(np.int32)


def words(rng, n, n_values, keys):
    """A side's int32 word columns: the key's two words from the ``[n,
    2]`` array ``keys``, then ``n_values`` random value words."""
    keys = np.asarray(keys, dtype=np.int32).reshape(n, 2)
    return [keys[:, 0], keys[:, 1]] + [
        rng.integers(I32.min, I32.max, n, dtype=np.int32, endpoint=True)
        for _ in range(n_values)]


def key_pairs(low, high):
    return np.stack([low, high], axis=1)


def brute_join(left, lvalid, right, rvalid):
    """Every valid (left, right) row pair whose two key words are equal,
    as sorted ``(key words, left value words, right value words)``."""
    eq = ((left[0][:, None] == right[0][None, :])
          & (left[1][:, None] == right[1][None, :])
          & lvalid[:, None] & rvalid[None, :])
    i, j = np.nonzero(eq)
    return sorted(zip(*(c.tolist() for c in (
        [left[0][i], left[1][i]] + [w[i] for w in left[2:]]
        + [w[j] for w in right[2:]]))))


def unique_left_keys(rng):
    """The Q8 shape: a person's id once, three in four auctions on a hot
    seller of its stride, the rest on recent or not yet made ids."""
    ids = 1000 + np.arange(400)
    epoch = rng.integers(0, 400, 1200)
    seller = np.where(rng.random(1200) < 0.75, (epoch // 100) * 100,
                      epoch - 50 + rng.integers(0, 60, 1200)) + 1000
    return (words(rng, 400, 1, key_pairs(ids, 0 * ids)), 1,
            words(rng, 1200, 1, key_pairs(seller, 0 * seller)))


def many_to_many(rng):
    """Both sides draw from 25 keys; left 1 value word, right 2 (an int32
    and a float64 column)."""
    lk, rk = rng.integers(0, 25, 300), rng.integers(0, 25, 450)
    return (words(rng, 300, 1, key_pairs(lk, lk * 7)), 1,
            words(rng, 450, 2, key_pairs(rk, rk * 7)))


def one_hot_key(rng):
    """Key 7 holds 6 left rows and 500 right rows: every one of those
    left rows has 500 partners."""
    lk = np.r_[np.full(6, 7), rng.integers(100, 200, 200)]
    rk = np.r_[np.full(500, 7), rng.integers(100, 200, 300)]
    return (words(rng, len(lk), 1, key_pairs(lk, 0 * lk)), 1,
            words(rng, len(rk), 1, key_pairs(rk, 0 * rk)))


def padding_between_rows(rng):
    """Pages of 16 lanes, each filled part way, and every padding lane
    holding a real key's words: padding sorts inside real keys' runs."""
    def side(pages, n_values):
        fill = rng.integers(0, 17, pages)
        valid = (np.arange(16)[None, :] < fill[:, None]).reshape(-1)
        k = rng.integers(0, 12, pages * 16)
        return words(rng, pages * 16, n_values, key_pairs(k, -k)), valid
    (left, lvalid), (right, rvalid) = side(20, 1), side(30, 2)
    return left, lvalid, right, rvalid


def extreme_words(rng):
    """Key words at the ends of int32 and around zero, in every pairing."""
    ends = np.array([I32.min, I32.min + 1, -1, 0, 1, I32.max - 1, I32.max])
    lk = rng.choice(ends, (300, 2))
    rk = rng.choice(ends, (400, 2))
    return words(rng, 300, 1, lk), 1, words(rng, 400, 1, rk)


def shared_low_word(rng):
    """Keys that share their low word (or their high one) and differ in
    the other: a join on one word would pair them."""
    lk = key_pairs(np.full(200, 5), rng.integers(0, 8, 200))
    rk = key_pairs(np.full(300, 5), rng.integers(4, 12, 300))
    lk[::3, 0], lk[::3, 1] = rng.integers(0, 8, len(lk[::3])), 9
    rk[::4, 0], rk[::4, 1] = rng.integers(4, 12, len(rk[::4])), 9
    return words(rng, 200, 1, lk), 1, words(rng, 300, 2, rk)


def no_partner(rng):
    """Even keys on the left, odd on the right: not one pair."""
    lk, rk = 2 * rng.integers(0, 50, 200), 2 * rng.integers(0, 50, 300) + 1
    return (words(rng, 200, 1, key_pairs(lk, 0 * lk)), 1,
            words(rng, 300, 1, key_pairs(rk, 0 * rk)))


def left_wider(rng):
    """Two value words on the left, none on the right: the sort's value
    columns are the wider side's."""
    lk, rk = rng.integers(0, 30, 250), rng.integers(0, 30, 250)
    return (words(rng, 250, 2, key_pairs(lk, lk)), 1,
            words(rng, 250, 0, key_pairs(rk, rk)))


KERNEL_CASES = {f.__name__: f for f in (
    unique_left_keys, many_to_many, one_hot_key, padding_between_rows,
    extreme_words, shared_low_word, no_partner, left_wider)}


def kernel_case(name, seed=11):
    """``(left, left valid, right, right valid)`` of a case; a side
    given its valid mask as 1 is valid all through and gets 37 padding
    lanes of random words appended."""
    rng = np.random.default_rng(seed)
    left, lvalid, right, *rvalid = KERNEL_CASES[name](rng)
    rvalid = rvalid[0] if rvalid else 1
    sides = []
    for cols, valid in ((left, lvalid), (right, rvalid)):
        if isinstance(valid, int):
            pad = words(rng, 37, len(cols) - 2, rng.integers(
                I32.min, I32.max, (37, 2), dtype=np.int32, endpoint=True))
            valid = np.r_[np.ones(len(cols[0]), bool), np.zeros(37, bool)]
            cols = [np.r_[c, p] for c, p in zip(cols, pad)]
        sides += [cols, valid]
    return sides


def run_window_pairs(left, lvalid, right, rvalid, out_rows):
    import jax

    from flink_tpu.joins.kernels import window_pairs

    pairs, approx, *cols = jax.jit(window_pairs, static_argnums=4)(
        left, lvalid, right, rvalid, out_rows)
    cols = [np.asarray(c) for c in cols]
    return int(pairs), float(approx), cols


@pytest.mark.parametrize("short", [False, True], ids=["sized", "short"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_window_pairs_equal_a_brute_force_join(case, short):
    """The kernel's pairs as a multiset, and its count, against every
    pair of equal keys found by comparing each row with each. ``short``:
    an output narrower than the pairs still counts them all (the count
    the harvest re-runs on) and holds a part of them."""
    left, lvalid, right, rvalid = kernel_case(case)
    want = brute_join(left, lvalid, right, rvalid)
    out_rows = max(len(want) // 3, 1) if short else len(want) + 29
    pairs, approx, cols = run_window_pairs(left, lvalid, right, rvalid,
                                           out_rows)
    assert pairs == len(want) and approx == len(want)
    assert len(cols) == 2 + (len(left) - 2) + (len(right) - 2)
    got = list(zip(*(c[:min(pairs, out_rows)].tolist() for c in cols)))
    if short:
        from collections import Counter
        assert not Counter(got) - Counter(want)
    else:
        assert sorted(got) == want
    if case == "no_partner":
        assert pairs == 0
    if case == "one_hot_key":
        assert pairs >= 6 * 500


def test_window_pairs_equal_the_q8_reference_on_a_window_of_the_generator():
    """One NEXmark Q8 window at a sixteenth of the benchmark cell's rows
    (500,000 persons and auctions), words from the benchmark's own
    generator, through the kernel: the (id, name, reserve) multiset is
    the plain reference's, and every row of the window finds its
    place."""
    import json
    import os

    from benchmark.jobs import q8
    from flink_tpu.joins.engine import _KEY, _to_words

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nexmark-q8-new-users.json")) as f:
        o = dict(json.load(f)["job_options"])
    o["event_rate"] //= 16
    n = q8.boundary_events(o)
    assert n == 500_000
    seed = 2_147_542_123
    cols, _ = q8.make_generator(seed, o)(0, n)
    person = cols["kind"] == 0

    def side(key, value):
        # laid out as a fire finds them: real rows, then padding lanes
        width = fine_bucket(len(key))
        out = [np.zeros(width, np.int32) for _ in range(3)]
        for w, c in zip(out, _to_words(key, _KEY) + [value]):
            w[:len(key)] = c
        return out, np.arange(width) < len(key)

    left, lvalid = side(cols["id"][person], cols["name"][person])
    right, rvalid = side(cols["seller"][~person], cols["reserve"][~person])
    want = q8.reference_rows(seed, n, o)
    pairs, _, (k0, k1, name, reserve) = run_window_pairs(
        left, lvalid, right, rvalid, fine_bucket(int(person.size)))
    assert pairs == len(want["id"]) >= q8.pairs_floor_per_window(o)
    key = np.stack([k0[:pairs], k1[:pairs]], axis=1).view(np.int64)[:, 0]
    got = sorted(zip(key.tolist(), name[:pairs].tolist(),
                     reserve[:pairs].tolist()))
    assert got == sorted(zip(want["id"].tolist(), want["name"].tolist(),
                             want["reserve"].tolist()))


def test_late_rows_behind_a_fired_window_are_dropped_and_counted():
    e = engine()
    e.input_side = 0
    e.process_batch(keyed([1], [5], "lv", np.array([1], np.int32)))
    e.input_side = 1
    e.process_batch(keyed([1], [6], "rv", np.array([0.5])))
    assert len(e.on_watermark(1500)[0]) == 1
    e.process_batch(keyed([1, 1], [7, 1600], "rv", np.array([0.1, 0.2])))
    assert e.late_records_dropped == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert e.on_watermark(3000) == []     # no left row in [1000, 2000)
