"""NEXmark Query 4 through the SQL path on the CPU, against its plain
NumPy reference (``benchmark/jobs/q4.py``): the seeded stream at the job's
CPU size, hand-made streams that reach each rule of the query, and the
device regular join against the host join it replaces, row for row."""

import numpy as np
import pytest

from benchmark.harness import manifest
from benchmark.harness.traffic import StampingSink
from benchmark.jobs import q4
from flink_tpu.connectors.sources import Source
from flink_tpu.core.records import (
    KEY_ID_FIELD,
    TIMESTAMP_FIELD,
    RecordBatch,
)

CONFIG = manifest.config(manifest.manifest(), "nexmark-q4-category-avg")
#: the configuration's options and job options at the CPU size
OPTIONS = dict(CONFIG["options"], **q4.TINY["options"])
O = dict(CONFIG["job_options"], **q4.TINY["job_options"])


class _Batches(Source):
    """The rows ``cols`` offered in batches cut at ``cuts`` (row
    counts), each stamped with its ``dateTime``."""

    bounded = True

    def __init__(self, cols, cuts):
        self.cols, self.cuts, self.at = cols, list(cuts), 0

    def poll_batch(self, max_records):
        if not self.cuts:
            return None
        end = self.cuts.pop(0)
        cols = {c: v[self.at:end] for c, v in self.cols.items()}
        self.at = end
        return RecordBatch.from_pydict(cols, timestamps=cols["dateTime"])

    def snapshot_position(self):
        return {"at": self.at}

    def restore_position(self, pos):
        self.at = pos["at"]


def run_sql(cols, cuts, options=None):
    """The sink's changelog of the job over ``cols`` in batches cut at
    ``cuts``."""
    from flink_tpu import Configuration, StreamExecutionEnvironment

    env = StreamExecutionEnvironment(Configuration(dict(options or OPTIONS)))
    results, _ = q4.build(env, _Batches(cols, cuts), O)
    sink = StampingSink(q4.SINK_COLUMNS)
    results.sink_to(sink)
    env.execute("q4-test")
    return sink.result()


def stream(*rows):
    """Generator-shaped columns from ``("A", id, category, dateTime,
    expires)`` and ``("B", auction, price, dateTime)`` rows, in order."""
    cols = {c: np.zeros(len(rows), dtype=np.int64)
            for c in ("id", "category", "expires", "auction", "price",
                      "dateTime")}
    cols["kind"] = np.zeros(len(rows), dtype=np.int8)
    for i, r in enumerate(rows):
        if r[0] == "A":
            (cols["id"][i], cols["category"][i], cols["dateTime"][i],
             cols["expires"][i]) = r[1:]
        else:
            cols["kind"][i] = 1
            cols["auction"][i], cols["price"][i], cols["dateTime"][i] = r[1:]
    return cols


def reference(cols, stamps, acc="int64"):
    """``q4.tables_of`` over explicit rows."""
    a = np.flatnonzero(cols["kind"] == 0)
    b = np.flatnonzero(cols["kind"] == 1)
    pick = lambda rows: {c: v[rows] for c, v in cols.items()}  # noqa: E731
    return q4.tables_of(pick(a), a, [(pick(b), b)], stamps, acc)


def verdict(got, cols, cuts, acc="int64", lose=None):
    """The job's changelog against the reference after every batch
    (``cuts``) and at every stamp the job wrote (with the rows ``lose``
    left out of the reference)."""
    n = len(cols["kind"])
    ends = np.asarray(got["window_end"])
    stamps = np.unique(np.r_[ends, list(cuts), n])
    if lose is not None:
        keep = np.ones(n, dtype=bool)
        keep[lose] = False
        cols = {c: np.where(keep, v, 0) if c != "kind" else
                np.where(keep, v, 2) for c, v in cols.items()}
    return q4._verdict(got, stamps, reference(cols, stamps, acc))


def exact(v):
    assert v["failed"] == 0 and v["attempted"] > 0, v
    assert all(c["value"] <= c["limit"] for c in v["numbers"].values()), v


def wrong(v):
    assert v["failed"] > 0, v
    assert any(c["value"] > c["limit"] for c in v["numbers"].values()), v


# ---------------------------------------------------- the seeded stream


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
def test_the_seeded_stream_equals_its_reference(seed):
    n = 12 * q4.boundary_events(O)
    cols, _ = q4.make_generator(seed, O)(0, n)
    got = run_sql(cols, range(q4.boundary_events(O), n + 1,
                              q4.boundary_events(O)))
    v = q4.check(got, seed, n, O)
    exact(v)
    assert v["attempted"] >= 12
    # the job's changelog holds retractions: every rising maximum is one
    assert (got["kind"] == q4.UPDATE_BEFORE).any()


@pytest.mark.parametrize("native", [True, False], ids=["native", "numpy"])
@pytest.mark.parametrize("seed", [5, 2_147_483_677])
def test_the_hash_grouped_folds_emit_the_sort_grouped_changelog(
        seed, native, monkeypatch):
    """Both GROUP BYs (the MAX per auction, the AVG that retracts) with
    each batch's rows grouped by key in one hash pass emit, row for row
    and in the same order, what the sort by slot emitted."""
    from flink_tpu.runtime.group_agg import GroupAggOperator
    from tests.test_wide_aggregates import SortGroupedFold

    if not native:
        monkeypatch.setenv("FLINK_TPU_NO_NATIVE", "1")
    n = 8 * q4.boundary_events(O)
    cols, _ = q4.make_generator(seed, O)(0, n)
    cuts = range(q4.boundary_events(O), n + 1, q4.boundary_events(O))
    got = run_sql(cols, cuts)
    monkeypatch.setattr(GroupAggOperator, "process_batch",
                        SortGroupedFold.process_batch)
    want = run_sql(cols, cuts)
    assert (got["kind"] == q4.UPDATE_BEFORE).any()
    assert list(got) == list(want)
    for c in want:
        assert got[c].dtype == want[c].dtype, c
        np.testing.assert_array_equal(got[c], want[c], err_msg=c)


def test_an_average_emitted_every_other_batch_reads_wrong(monkeypatch):
    """A second GROUP BY that folds every batch but emits only every
    other one — each emission merging two batches' changes, its
    retractions still those of the rows it last emitted — writes a
    well-formed changelog whose every table is right at its own stamps:
    the comparison after every micro-batch still finds it out."""
    from flink_tpu.runtime.group_agg import GroupAggOperator

    real = GroupAggOperator.process_batch

    def every_other(self, batch, input_index=0):
        if self.key_columns or self.key_field != "category":
            return real(self, batch, input_index)
        self._batches = getattr(self, "_batches", 0) + 1
        self.emit_on_watermark_only = True
        try:
            out = real(self, batch, input_index)
            if self._batches % 2 == 0:
                out += self.process_watermark(0)
        finally:
            self.emit_on_watermark_only = False
        return out

    monkeypatch.setattr(GroupAggOperator, "process_batch", every_other)
    step = q4.boundary_events(O)
    n = 12 * step
    cols, _ = q4.make_generator(3, O)(0, n)
    got = run_sql(cols, range(step, n + 1, step))
    v = q4.check(got, 3, n, O)
    wrong(v)
    assert v["numbers"]["changelog_faults"]["value"] == 0, v
    assert v["numbers"]["tables_wrong"]["value"] >= 5, v
    # held to its own stamps alone it would pass
    own = np.unique(np.asarray(got["window_end"]))
    assert q4._verdict(got, own, q4.tables(3, n, O, own))["failed"] == 0


# ------------------------------------------------- hand-made streams


def test_bids_before_their_auction_join_it_when_it_arrives():
    cols = stream(("B", 1000, 50, 5), ("B", 1000, 70, 6), ("B", 1000, 90, 4),
                  ("A", 1000, 10, 5, 10), ("A", 1001, 10, 5, 10),
                  ("B", 1001, 40, 7))
    cuts = [3, 6]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    # the bid at 4 is before the auction's dateTime: (70 + 40) // 2
    assert got["final"][-1] == 55


def test_an_auction_behind_the_watermark_still_joins():
    # the bids' event times are far behind the watermark when their
    # auction comes: the regular join drops no row for that
    late = [("B", 7, 30 + i, 100 + i) for i in range(5)]
    filler = [("A", 2000 + i, 11, 20_000 + i, 20_010 + i) for i in range(40)]
    cols = stream(*late, *filler, ("A", 7, 12, 100, 200))
    cuts = [len(late), len(late) + len(filler),
            len(late) + len(filler) + 1]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    assert dict(zip(got["id"][-1:], got["final"][-1:])) == {12: 34}
    # a run that dropped the late auction reads wrong
    wrong(verdict(got, cols, cuts, lose=[len(cols["kind"]) - 1]))


def test_a_duplicate_auction_id_in_another_category_makes_two_groups():
    cols = stream(("A", 5, 10, 0, 100), ("A", 5, 11, 0, 100),
                  ("A", 6, 10, 0, 100), ("B", 5, 40, 1, ), ("B", 6, 10, 2),
                  ("B", 5, 60, 3))
    cuts = [3, 6]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    table = reference(cols, [6])[0]
    assert table == {10: (60 + 10) // 2, 11: 60}


def test_bids_just_outside_the_auctions_life_are_not_joined():
    cols = stream(("A", 1, 10, 100, 200), ("B", 1, 999, 99),
                  ("B", 1, 998, 201), ("B", 1, 5, 100), ("B", 1, 7, 200))
    cuts = [2, 5]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    assert got["final"][-1] == 7


def test_sums_past_32_bits_and_float32_precision_are_exact():
    # three maxima of ~1.5e9 in one category: the sum passes 2^31 and is
    # no float32 (past 2^24 a float32 rounds it)
    big = 1_500_000_001
    cols = stream(*[("A", 10 + i, 13, 0, 10) for i in range(3)],
                  *[("B", 10 + i, big + 2 * i, 1) for i in range(3)],
                  ("A", 20, 14, 0, 10), ("B", 20, (1 << 24) + 1, 2),
                  ("A", 21, 14, 0, 10), ("B", 21, (1 << 24) + 2, 2))
    cuts = [3, 6, 10]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    assert dict(zip(got["id"], got["final"]))[13] == big + 2
    # an int32 sum wraps and a float32 one rounds: both read wrong
    wrong(verdict(got, cols, cuts, acc="int32"))
    wrong(verdict(got, cols, cuts, acc="float32"))


def test_rising_maxima_retract_from_the_average():
    cols = stream(("A", 1, 10, 0, 100), ("A", 2, 10, 0, 100),
                  ("B", 1, 10, 1), ("B", 2, 15, 1),
                  ("B", 1, 20, 2),
                  ("B", 1, 30, 3), ("B", 1, 25, 4))
    cuts = [4, 5, 7]
    got = run_sql(cols, cuts)
    exact(verdict(got, cols, cuts))
    kinds = got["kind"].tolist()
    assert kinds[0] == q4.INSERT and q4.UPDATE_BEFORE in kinds \
        and q4.UPDATE_AFTER in kinds
    # (10 + 15) // 2, (20 + 15) // 2, (30 + 15) // 2: the 25 bid moves
    # nothing, and an unchanged average is not emitted again
    assert got["final"][got["kind"] != q4.UPDATE_BEFORE].tolist() == \
        [12, 17, 22]


# --------------------------------- the device join against the host's


def _side(rng, n, keys, names):
    cols = {KEY_ID_FIELD: rng.integers(0, keys, n).astype(np.int64),
            TIMESTAMP_FIELD: rng.integers(0, 1000, n).astype(np.int64)}
    for name, make in names.items():
        cols[name] = make(n)
    return RecordBatch(cols)


def _rows(batches):
    """Every row of the batches as a sorted list of tuples (columns in
    name order)."""
    batches = [b for b in batches if len(b)]
    if not batches:
        return []
    b = RecordBatch.concat(batches)
    names = sorted(b.names())
    return sorted(zip(*[np.asarray(b[n]).tolist() for n in names]))


def _drive(op, batches):
    from flink_tpu.runtime.operators import OperatorContext

    op.open(OperatorContext())
    out = []
    for side, b in batches:
        out += op.process_batch(b, side)
        out += op.process_watermark(0)
    return out + op.close()


@pytest.mark.parametrize("keep", [(None, None), ({"v"}, {"w"})])
def test_the_device_join_equals_the_host_join_and_its_filter(keep):
    from flink_tpu.joins.operators import DeviceRegularJoinOperator
    from flink_tpu.runtime.join_operators import IntervalJoinOperator

    rng = np.random.default_rng(7)
    left = {"t": lambda n: rng.integers(0, 1000, n).astype(np.int64),
            "e": lambda n: rng.integers(0, 1500, n).astype(np.int64),
            "v": lambda n: rng.integers(-3, 4, n).astype(np.int32),
            "f": lambda n: rng.random(n).astype(np.float32)}
    right = {"t": lambda n: rng.integers(0, 1000, n).astype(np.int64),
             "w": lambda n: rng.integers(-(1 << 40), 1 << 40, n),
             "g": lambda n: rng.random(n).astype(np.float32)}
    batches = [(s, _side(rng, int(rng.integers(1, 90)), 25,
                         left if s == 0 else right))
               for s in rng.integers(0, 2, 70).tolist()]
    residual = ((">=", ("col", 1, "t"), ("col", 0, "t")),
                ("<=", ("col", 1, "t"), ("col", 0, "e")),
                ("<>", ("col", 0, "v"), ("lit", 2)),
                ("<", ("col", 1, "g"), ("col", 0, "f")))

    def keeps(b):
        return ((b["t_r"] >= b["t_l"]) & (b["t_r"] <= b["e"])
                & (b["v"] != 2) & (b["g"] < b["f"]))

    host = IntervalJoinOperator(-(1 << 60), 1 << 60)
    want = [b.filter(keeps(b)) for b in _drive(host, batches)]
    device = DeviceRegularJoinOperator(
        residual=residual, keep=keep, capacity=256,
        host_factory=lambda: IntervalJoinOperator(-(1 << 60), 1 << 60),
        residual_filter=keeps)
    got = _drive(device, batches)
    assert device.host is None          # it stayed on the device
    assert device.windower.planes[0].shape[1] > 256     # and grew
    if keep[0] is not None:
        names = {KEY_ID_FIELD, TIMESTAMP_FIELD, "v", "w"}
        want = [b.select(*names) for b in want]
        assert {n for b in got for n in b.names()} == names
    assert _rows(got) == _rows(want) and len(_rows(got)) > 100


def test_a_column_the_device_cannot_compare_moves_the_join_to_the_host():
    from flink_tpu.joins import operators as jo
    from flink_tpu.runtime.join_operators import IntervalJoinOperator

    rng = np.random.default_rng(3)
    left = {"t": lambda n: rng.integers(0, 9, n).astype(np.float64)}
    right = {"t": lambda n: rng.integers(0, 9, n).astype(np.float64)}
    batches = [(s, _side(rng, 20, 5, left if s == 0 else right))
               for s in (0, 1, 0, 1)]

    def keeps(b):
        return b["t_l"] <= b["t_r"]

    before = jo.fallbacks()
    with pytest.warns(RuntimeWarning, match="regular join on the host"):
        got = _drive(jo.DeviceRegularJoinOperator(
            residual=(("<=", ("col", 0, "t"), ("col", 1, "t")),),
            host_factory=lambda: IntervalJoinOperator(-(1 << 60), 1 << 60),
            residual_filter=keeps), batches)
    assert jo.fallbacks() == before + 1
    want = [b.filter(keeps(b)) for b in _drive(
        IntervalJoinOperator(-(1 << 60), 1 << 60), batches)]
    assert _rows(got) == _rows(want) and _rows(got)


@pytest.mark.parametrize("restore_on", ["device", "host"])
def test_the_held_rows_survive_a_snapshot_and_restore(restore_on):
    from flink_tpu.joins.operators import DeviceRegularJoinOperator
    from flink_tpu.runtime.join_operators import IntervalJoinOperator
    from flink_tpu.runtime.operators import OperatorContext

    rng = np.random.default_rng(9)
    cols = {"t": lambda n: rng.integers(0, 1000, n).astype(np.int64)}
    batches = [(s, _side(rng, 40, 15, cols)) for s in (1, 1, 0, 1, 0, 0, 1)]
    residual = ((">=", ("col", 1, "t"), ("col", 0, "t")),)

    def keeps(b):
        return b["t_r"] >= b["t_l"]

    def device():
        return DeviceRegularJoinOperator(
            residual=residual,
            host_factory=lambda: IntervalJoinOperator(-(1 << 60), 1 << 60),
            residual_filter=keeps)

    whole = _drive(device(), batches + batches)
    first = device()
    part = _drive(first, batches)
    later = device() if restore_on == "device" else IntervalJoinOperator(
        -(1 << 60), 1 << 60)
    later.open(OperatorContext())
    later.restore_state(first.snapshot_state())
    rest = []
    for side, b in batches:
        rest += later.process_batch(b, side) + later.process_watermark(0)
    rest += later.close()
    if restore_on == "host":
        rest = [b.filter(keeps(b)) for b in rest]
    assert _rows(part + rest) == _rows(whole) and _rows(rest)
