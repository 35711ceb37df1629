"""Exact 64-bit group aggregates on the x32 device (``Int64Aggregate``:
SQL's BIGINT MAX / MIN / SUM / AVG), the binding of an aggregate to its
input's type, the loud refusal of a leaf the device would truncate, and
the float callers that keep their float32 leaves."""

import numpy as np
import pytest

from flink_tpu.core.records import (
    KEY_ID_FIELD,
    ROWKIND_FIELD,
    ROWKIND_INSERT,
    ROWKIND_UPDATE_AFTER,
    ROWKIND_UPDATE_BEFORE,
    RecordBatch,
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
