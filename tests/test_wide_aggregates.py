"""Exact 64-bit group aggregates on the x32 device (``Int64Aggregate``:
SQL's BIGINT MAX / MIN / SUM / AVG), the binding of an aggregate to its
input's type, the loud refusal of a leaf the device would truncate, and
the float callers that keep their float32 leaves."""

import numpy as np
import pytest

from flink_tpu.core.records import (
    KEY_ID_FIELD,
    ROWKIND_DELETE,
    ROWKIND_FIELD,
    ROWKIND_INSERT,
    ROWKIND_UPDATE_AFTER,
    ROWKIND_UPDATE_BEFORE,
    RecordBatch,
    rowkind_signs,
)
from flink_tpu.runtime.group_agg import GroupAggOperator
from flink_tpu.runtime.operators import OperatorContext
from flink_tpu.windowing.aggregates import (
    AvgAggregate,
    CountAggregate,
    Int64Aggregate,
    MaxAggregate,
    MinAggregate,
    MultiAggregate,
    SumAggregate,
)


def _op(agg, **kw):
    op = GroupAggOperator(agg, "k", capacity=1 << 10, **kw)
    op.open(OperatorContext())
    return op


def _batch(keys, values, kinds=None):
    cols = {"k": np.asarray(keys, dtype=np.int64),
            KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
            "x": np.asarray(values)}
    if kinds is not None:
        cols[ROWKIND_FIELD] = np.asarray(kinds, dtype=np.int8)
    return RecordBatch(cols)


def _final(outs, column):
    """Each key's last emitted value (UPDATE_BEFORE rows skipped)."""
    last = {}
    for b in outs:
        kinds = np.asarray(b[ROWKIND_FIELD])
        for k, v, kind in zip(b["k"].tolist(), b[column].tolist(),
                              kinds.tolist()):
            if kind != ROWKIND_UPDATE_BEFORE:
                last[k] = v
    return last


@pytest.mark.parametrize("kind, fold", [
    ("max", max), ("min", min), ("sum", sum),
    ("avg", lambda v: int(abs(sum(v)) // len(v)) * (1 if sum(v) >= 0
                                                      else -1))])
def test_each_64_bit_aggregate_is_exact_past_every_32_bit_word(kind, fold):
    rng = np.random.default_rng(11)
    op = _op(MultiAggregate([Int64Aggregate(kind, "x", "r")]))
    keys = rng.integers(0, 5, 3000)
    # values that carry across the low word, and signs on both sides
    values = rng.integers(-(1 << 50), 1 << 50, 3000) | 0xFFFFFFFF
    outs = []
    for a in range(0, 3000, 700):
        outs += op.process_batch(_batch(keys[a:a + 700], values[a:a + 700]))
    got = _final(outs, "r")
    want = {int(k): fold([int(v) for v in values[keys == k]])
            for k in np.unique(keys)}
    assert got == want
    assert all(isinstance(v, int) for v in got.values())


def test_avg_retracts_with_its_changelog_signs_and_truncates_toward_zero():
    op = _op(MultiAggregate([Int64Aggregate("avg", "x", "r")]))
    big = 1 << 40
    op.process_batch(_batch([1, 1, 1], [big + 1, -7, 3]))
    # an updating input: retract -7, add -9 (UPDATE_BEFORE / AFTER)
    outs = op.process_batch(_batch(
        [1, 1], [-7, -9], [ROWKIND_UPDATE_BEFORE, ROWKIND_UPDATE_AFTER]))
    assert _final(outs, "r") == {1: (big + 1 - 9 + 3) // 3}
    outs = op.process_batch(_batch(
        [1, 1], [big + 1, -(big + 6)],
        [ROWKIND_UPDATE_BEFORE, ROWKIND_UPDATE_AFTER]))
    # (-(2^40 + 6) - 9 + 3) / 3 in Java's long division: toward zero
    assert _final(outs, "r") == {1: -((big + 12) // 3)}


def test_max_over_a_retraction_is_refused():
    op = _op(MultiAggregate([Int64Aggregate("max", "x", "r")]))
    op.process_batch(_batch([1], [5]))
    with pytest.raises(ValueError, match="retractable"):
        op.process_batch(_batch([1], [5], [ROWKIND_UPDATE_BEFORE]))


@pytest.mark.parametrize("dtype, leaf", [
    (np.int64, None), (np.int32, np.int32), (np.int16, np.int32),
    (np.uint32, None), (np.float64, "refused"), (np.float32, np.float32)])
def test_max_and_min_take_their_inputs_type_when_bound(dtype, leaf):
    for cls in (MaxAggregate, MinAggregate):
        unbound = cls("x", dtype=None)
        if leaf == "refused":
            with pytest.raises(TypeError, match="float32"):
                unbound.bind({"x": np.dtype(dtype)})
            continue
        bound = unbound.bind({"x": np.dtype(dtype)})
        if leaf is None:
            assert isinstance(bound, Int64Aggregate)
        else:
            assert [l.dtype for l in bound.leaves] == [np.dtype(leaf)]


@pytest.mark.parametrize("dtype, leaf", [
    (np.int64, None), (np.int32, None), (np.int8, None), (np.uint32, None),
    (np.float64, "refused"), (np.float32, np.float32)])
def test_sum_takes_its_inputs_type_when_bound(dtype, leaf):
    unbound = SumAggregate("x", dtype=None)
    assert unbound.retractable
    if leaf == "refused":
        with pytest.raises(TypeError, match="float32"):
            unbound.bind({"x": np.dtype(dtype)})
        return
    bound = unbound.bind({"x": np.dtype(dtype)})
    if leaf is None:
        # every integer SUM is a BIGINT: an int32 sum wraps past 2^31
        assert isinstance(bound, Int64Aggregate) and bound.kind == "sum"
        assert bound.result_dtype == np.int64
    else:
        assert [l.dtype for l in bound.leaves] == [np.dtype(leaf)]


def _sql_sum(rows, query):
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.table.environment import StreamTableEnvironment

    t_env = StreamTableEnvironment(StreamExecutionEnvironment(
        Configuration({"execution.micro-batch.size": 2})))
    t_env.create_temporary_view(
        "t", t_env.from_collection(rows, timestamp_field="ts"))
    return {r["k"]: r["s"] for r in t_env.execute_sql(query).collect()}


@pytest.mark.parametrize("value, dtype", [
    ((1 << 24) + 1, np.int64),      # sums past 2^24: float32 rounds them
    ((1 << 30) + 1, np.int64),      # past 2^31: an int32 sum wraps
    ((1 << 30) + 1, np.int32),      # SUM(INT) past 2^31 is still exact
    (-((1 << 40) + 3), np.int64)])
def test_sql_sum_of_an_integer_column_is_exact(value, dtype):
    rows = [{"k": i % 2, "x": dtype(value), "ts": i} for i in range(7)]
    got = _sql_sum(rows, "SELECT k, SUM(x) AS s FROM t GROUP BY k")
    assert got == {0: 4 * value, 1: 3 * value}
    assert float(np.float32(4 * value)) != 4 * value \
        or int(np.int32(np.int64(4 * value))) != 4 * value


def test_sql_sum_of_a_double_column_is_refused():
    rows = [{"k": 1, "x": 0.1, "ts": 0}]
    with pytest.raises(TypeError, match="float64"):
        _sql_sum(rows, "SELECT k, SUM(x) AS s FROM t GROUP BY k")
    # made explicit, the float32 sum runs
    assert _sql_sum(rows, "SELECT k, SUM(CAST(x AS FLOAT)) AS s FROM t "
                          "GROUP BY k") == {1: np.float32(0.1)}


def test_avg_of_an_integer_column_is_an_integer_of_its_type():
    bound = AvgAggregate("x", dtype=None).bind({"x": np.dtype(np.int32)})
    assert isinstance(bound, Int64Aggregate)
    op = _op(MultiAggregate([AvgAggregate("x", "r", dtype=None)]))
    outs = op.process_batch(_batch([1, 1], np.array([2, 3], np.int32)))
    assert outs[0]["r"].dtype == np.int32 and outs[0]["r"].tolist() == [2]


def test_the_float_callers_keep_their_float32_leaves():
    assert MaxAggregate("x").leaves[0].dtype == np.float32
    assert MinAggregate("x").leaves[0].dtype == np.float32
    assert [l.dtype for l in AvgAggregate("x").leaves] == \
        [np.dtype(np.float32)] * 2
    assert SumAggregate("x").leaves[0].dtype == np.float32
    op = _op(MultiAggregate([AvgAggregate("x", "r"),
                             MaxAggregate("x", output="m")]))
    outs = op.process_batch(_batch([1, 1], np.array([1.0, 2.0])))
    assert outs[0]["r"].dtype == np.float32
    assert outs[0]["r"].tolist() == [1.5] and outs[0]["m"].tolist() == [2.0]


def test_a_64_bit_aggregate_shares_its_table_with_float_and_count_ones():
    op = _op(MultiAggregate([Int64Aggregate("max", "x", "m"),
                             SumAggregate("f", output="s"),
                             CountAggregate("c")]))
    b = _batch([1, 1, 2], [(1 << 45) + 1, 3, 4]).with_column(
        "f", np.array([0.5, 0.25, 1.0], np.float32))
    got = {k: (m, s, c) for k, m, s, c in zip(*(
        o.tolist() for o in (lambda o: (o["k"], o["m"], o["s"], o["c"]))(
            op.process_batch(b)[0])))}
    assert got == {1: ((1 << 45) + 1, 0.75, 2), 2: (4, 1.0, 1)}


def test_a_leaf_the_device_would_truncate_is_refused():
    with pytest.raises(TypeError, match="half its width"):
        _op(MultiAggregate([SumAggregate("x", np.int64)]))
    with pytest.raises(TypeError, match="uint64"):
        MaxAggregate("x", dtype=None).bind({"x": np.dtype(np.uint64)})


def test_an_unchanged_value_emits_nothing():
    op = _op(MultiAggregate([Int64Aggregate("max", "x", "r")]))
    first = op.process_batch(_batch([1], [9]))
    assert first[0][ROWKIND_FIELD].tolist() == [ROWKIND_INSERT]
    assert op.process_batch(_batch([1], [4])) == []
    assert op.process_batch(_batch([1], [12]))[0][
        ROWKIND_FIELD].tolist() == [ROWKIND_UPDATE_BEFORE,
                                    ROWKIND_UPDATE_AFTER]


def test_the_operator_restores_its_bound_table():
    op = _op(MultiAggregate([MaxAggregate("x", output="r", dtype=None)]))
    op.process_batch(_batch([1, 2], [1 << 40, 7]))
    snap = op.snapshot_state()
    fresh = _op(MultiAggregate([MaxAggregate("x", output="r", dtype=None)]))
    fresh.restore_state(snap)
    outs = fresh.process_batch(_batch([1, 2], [(1 << 40) + 1, 3]))
    assert _final(outs, "r") == {1: (1 << 40) + 1}


# ------------------- the fold's rows grouped by key, against the sort


class SortGroupedFold(GroupAggOperator):
    """The fold as it grouped a batch before the hash pass, kept as the
    reference: every row looked up in the table and checked against its
    slot's key columns, the partials by a stable sort by slot and
    ``reduceat``, the row counts by ``np.add.at`` over the rows."""

    def process_batch(self, batch, input_index=0):
        if len(batch) == 0:
            return []
        if self.table is None:
            self._bind({n: np.asarray(batch[n]).dtype
                        for n in batch.names()})
        if batch.has_timestamps:
            self._max_ts = max(self._max_ts, int(batch.timestamps.max()))
        keys = batch.columns.get(self.key_field)
        if keys is not None and keys.dtype.kind not in "iu":
            self._keys_hashed = True
            uniq, first = np.unique(batch.key_ids, return_index=True)
            for i, j in zip(uniq.tolist(), first.tolist()):
                self._key_values.setdefault(i, keys[j])
        slots = self.table.lookup_or_insert(
            batch.key_ids, np.zeros(len(batch), dtype=np.int64))
        self._ensure_host_capacity(int(slots.max()) + 1)
        new = (self._row_counts[slots] == 0) & ~self._emitted_mask[slots]
        for name in self.key_columns:
            v = np.asarray(batch[name])
            store = self._key_cols.setdefault(
                name, np.zeros(len(self._row_counts), dtype=v.dtype))
            store[slots[new]] = v[new]
            if not np.array_equal(store[slots], v):
                raise RuntimeError("two GROUP BY keys hash to one key id")
        kinds = batch.row_kinds
        signs = None if kinds is None else rowkind_signs(np.asarray(kinds))
        if signs is not None and not (signs < 0).any():
            signs = None
        if signs is not None and not self.agg.retractable:
            raise ValueError("requires retractable accumulators")
        emit = not self.emit_on_watermark_only
        if self.agg.wide:
            order = np.argsort(slots, kind="stable")
            ordered = slots[order]
            starts = np.flatnonzero(np.r_[True, ordered[1:] != ordered[:-1]])
            parts = []
            for (_, r, dt), v in zip(self.agg.parts,
                                     self.agg.part_values(batch)):
                v = np.asarray(v, dtype=np.int64 if np.dtype(dt).kind
                               in "iu" else np.float64)
                if signs is not None:
                    v = v * signs.astype(v.dtype)
                fn = {"sum": np.add, "max": np.maximum, "min": np.minimum}[r]
                parts.append(fn.reduceat(v[order], starts))
            uniq, results = self._fold_partials(ordered[starts], parts)
        else:
            if signs is None:
                self.table.scatter(slots, self.agg.map_input(batch))
            else:
                self.table.scatter_signed(
                    slots, self.agg.map_input_signed(batch, signs))
            uniq = np.unique(slots)
            results = self._values(uniq) if emit else None
        np.add.at(self._row_counts, slots,
                  1 if signs is None else signs.astype(np.int64))
        if not emit:
            self._dirty.update(uniq.tolist())
            return []
        out = self._emit_slots(uniq, results)
        return [out] if out is not None else []


def _same_changelog(got, want):
    """Row for row, in the same order, every column and its type; an
    integer exactly, a float32 to a few of its ulps (the partials of a
    float part are summed in row order now, pairwise by ``reduceat``
    before)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert sorted(g.names()) == sorted(w.names())
        for name in w.names():
            a, b = np.asarray(g[name]), np.asarray(w[name])
            assert a.dtype == b.dtype, name
            if a.dtype.kind == "f":
                tol = 8 * np.finfo(a.dtype).eps
                np.testing.assert_allclose(a, b, rtol=tol, atol=tol,
                                           err_msg=name)
            else:
                np.testing.assert_array_equal(a, b, err_msg=name)


def _signed_stream(rng, n_batches, retract):
    """Batches of a few hundred rows over ≈60 keys, with two key columns
    each key id is made from, and UPDATE_BEFORE / DELETE rows where
    ``retract``."""
    out = []
    for _ in range(n_batches):
        n = int(rng.integers(1, 600))
        a = rng.integers(-30, 30, n)
        b = rng.integers(0, 2, n)
        kid = a * 7 + b
        cols = {"k": kid, KEY_ID_FIELD: kid, "a": a, "b": b,
                "x": rng.integers(-(1 << 50), 1 << 50, n) | 0xFFFFFFFF,
                "f": rng.standard_normal(n).astype(np.float32)}
        if retract:
            cols[ROWKIND_FIELD] = rng.choice(np.array(
                [ROWKIND_INSERT, ROWKIND_UPDATE_AFTER, ROWKIND_UPDATE_BEFORE,
                 ROWKIND_DELETE], dtype=np.int8), n, p=[.5, .2, .2, .1])
        out.append(RecordBatch(cols))
    return out


@pytest.mark.parametrize("aggs, retract", [
    (lambda: [Int64Aggregate("max", "x", "r")], False),
    (lambda: [Int64Aggregate("min", "x", "r")], False),
    (lambda: [Int64Aggregate("sum", "x", "r")], True),
    (lambda: [Int64Aggregate("avg", "x", "r")], True),
    (lambda: [Int64Aggregate("max", "x", "m"), Int64Aggregate("avg", "x", "r"),
              CountAggregate("c")], False),
    (lambda: [Int64Aggregate("sum", "x", "s"), Int64Aggregate("avg", "x", "r"),
              CountAggregate("c")], True),
    (lambda: [CountAggregate("c")], True),
    (lambda: [CountAggregate("c"), SumAggregate("f", output="s")], True),
    (lambda: [MaxAggregate("f", output="m")], False),
    (lambda: [Int64Aggregate("max", "x", "m"), SumAggregate("f", output="s")],
     False),
], ids=["max", "min", "sum-signed", "avg-signed", "max-avg-count",
        "sum-avg-count-signed", "count-signed", "count-float-sum-signed",
        "float-max", "max-float-sum"])
@pytest.mark.parametrize("seed", [1, 2_147_483_701])
def test_the_hash_grouped_fold_emits_the_sort_grouped_changelog(
        aggs, retract, seed):
    rng = np.random.default_rng(seed)
    got_op = _op(MultiAggregate(aggs()), key_columns=("a", "b"))
    want_op = SortGroupedFold(MultiAggregate(aggs()), "k", capacity=1 << 10,
                              key_columns=("a", "b"))
    want_op.open(OperatorContext())
    for batch in _signed_stream(rng, 12, retract):
        _same_changelog(got_op.process_batch(batch),
                        want_op.process_batch(batch))
    np.testing.assert_array_equal(got_op._row_counts, want_op._row_counts)


def _keyed(kid, a, x):
    return RecordBatch({"k": np.asarray(kid), KEY_ID_FIELD: np.asarray(kid),
                        "a": np.asarray(a), "b": np.zeros(len(kid), int),
                        "x": np.asarray(x)})


@pytest.mark.parametrize("agg", [
    lambda: MultiAggregate([Int64Aggregate("max", "x", "r")]),
    lambda: MultiAggregate([CountAggregate("c")])], ids=["wide", "count"])
def test_a_collision_inside_one_batch_on_a_new_slot_is_refused(agg):
    op = _op(agg(), key_columns=("a", "b"))
    op.process_batch(_keyed([1, 2], [10, 20], [5, 6]))
    # key id 7 is new; its first row says a=1, a later one a=2, with
    # other keys between them
    with pytest.raises(RuntimeError, match="hash to one key id"):
        op.process_batch(_keyed([7, 1, 2, 7], [1, 10, 20, 2], [1, 2, 3, 4]))


@pytest.mark.parametrize("agg", [
    lambda: MultiAggregate([Int64Aggregate("max", "x", "r")]),
    lambda: MultiAggregate([CountAggregate("c")])], ids=["wide", "count"])
def test_a_collision_across_batches_is_refused(agg):
    op = _op(agg(), key_columns=("a", "b"))
    op.process_batch(_keyed([7, 8], [1, 3], [5, 6]))
    # every row of the batch agrees with its group's first row; the
    # key's kept columns do not
    with pytest.raises(RuntimeError, match="hash to one key id"):
        op.process_batch(_keyed([8, 7, 7], [3, 2, 2], [1, 2, 3]))


def test_each_fold_records_the_distinct_keys_it_took_to_its_table():
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    rec.clear()
    op = _op(MultiAggregate([Int64Aggregate("max", "x", "r")]))
    op.process_batch(_batch([4, 4, 9, 4, -1], [1, 2, 3, 4, 5]))
    op.process_batch(_batch([9, 9], [7, 8]))
    totals = rec.kind_totals()
    assert totals["agg.groups"]["count"] == totals["agg.fold"]["count"] == 2
    assert totals["agg.groups"]["work"] == 3 + 1
    assert totals["agg.fold"]["work"] == 5 + 2
    assert {r.parent for r in rec.snapshot()
            if r.kind == "agg.groups"} == {"agg.fold"}
