"""Retraction / changelog semantics for unbounded GROUP BY.

reference: GroupAggFunction.java:85 emits UPDATE_BEFORE/UPDATE_AFTER pairs
(and DELETE on count-to-zero) so downstream operators compose over updating
results. The classic probe is the two-level "count of counts" aggregate,
which silently double-counts without retractions.
"""

import numpy as np
import pytest

from flink_tpu.core.records import (
    ROWKIND_DELETE,
    ROWKIND_FIELD,
    ROWKIND_INSERT,
    ROWKIND_UPDATE_AFTER,
    ROWKIND_UPDATE_BEFORE,
    RecordBatch,
)
from flink_tpu.runtime.group_agg import GroupAggOperator
from flink_tpu.windowing.aggregates import (
    AvgAggregate,
    CountAggregate,
    MaxAggregate,
    MultiAggregate,
    SumAggregate,
)


class _Ctx:
    parallelism = 1
    max_parallelism = 128


def _batch(keys, vals=None, kinds=None, ts=0):
    cols = {
        "__key_id__": np.asarray(keys, dtype=np.int64),
        "k": np.asarray(keys, dtype=np.int64),
        "__ts__": np.full(len(keys), ts, dtype=np.int64),
    }
    if vals is not None:
        cols["v"] = np.asarray(vals, dtype=np.float32)
    if kinds is not None:
        cols[ROWKIND_FIELD] = np.asarray(kinds, dtype=np.int8)
    return RecordBatch(cols)


def _rows(batches):
    out = []
    for b in batches:
        out.extend(b.to_rows())
    return out


class TestChangelogEmission:
    def test_insert_then_update_pair(self):
        op = GroupAggOperator(CountAggregate(), "k")
        op.open(_Ctx())
        out1 = _rows(op.process_batch(_batch([7])))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out1] == \
            [(ROWKIND_INSERT, 1)]
        out2 = _rows(op.process_batch(_batch([7])))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out2] == \
            [(ROWKIND_UPDATE_BEFORE, 1), (ROWKIND_UPDATE_AFTER, 2)]

    def test_delete_on_count_to_zero(self):
        op = GroupAggOperator(CountAggregate(), "k")
        op.open(_Ctx())
        op.process_batch(_batch([5]))
        out = _rows(op.process_batch(
            _batch([5], kinds=[ROWKIND_DELETE])))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out] == \
            [(ROWKIND_DELETE, 1)]
        # reappearing key is a fresh INSERT
        out2 = _rows(op.process_batch(_batch([5])))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out2] == \
            [(ROWKIND_INSERT, 1)]

    def test_upsert_mode_suppresses_update_before(self):
        op = GroupAggOperator(CountAggregate(), "k",
                              generate_update_before=False)
        op.open(_Ctx())
        op.process_batch(_batch([1]))
        out = _rows(op.process_batch(_batch([1])))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out] == \
            [(ROWKIND_UPDATE_AFTER, 2)]

    def test_minibatch_emits_net_change_per_watermark(self):
        op = GroupAggOperator(CountAggregate(), "k",
                              emit_on_watermark_only=True)
        op.open(_Ctx())
        assert op.process_batch(_batch([3])) == []
        assert op.process_batch(_batch([3, 3])) == []
        out = _rows(op.process_watermark(100))
        # one INSERT with the net value — intermediate states skipped
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out] == \
            [(ROWKIND_INSERT, 3)]
        op.process_batch(_batch([3]))
        out2 = _rows(op.process_watermark(200))
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out2] == \
            [(ROWKIND_UPDATE_BEFORE, 3), (ROWKIND_UPDATE_AFTER, 4)]

    def test_retraction_input_folds_signed(self):
        op = GroupAggOperator(
            MultiAggregate([SumAggregate("v", output="s"),
                            CountAggregate(output="n")]), "k")
        op.open(_Ctx())
        op.process_batch(_batch([1, 1], vals=[10.0, 20.0]))
        out = _rows(op.process_batch(_batch(
            [1, 1], vals=[10.0, 15.0],
            kinds=[ROWKIND_UPDATE_BEFORE, ROWKIND_UPDATE_AFTER])))
        ua = [r for r in out if r[ROWKIND_FIELD] == ROWKIND_UPDATE_AFTER]
        assert len(ua) == 1
        assert ua[0]["s"] == pytest.approx(35.0)  # 10+20-10+15
        assert ua[0]["n"] == 2

    def test_non_retractable_agg_rejects_updates(self):
        op = GroupAggOperator(MaxAggregate("v"), "k")
        op.open(_Ctx())
        with pytest.raises(ValueError, match="retractable"):
            op.process_batch(_batch([1], vals=[5.0],
                                    kinds=[ROWKIND_UPDATE_BEFORE]))

    def test_changelog_state_survives_restore(self):
        op = GroupAggOperator(CountAggregate(), "k")
        op.open(_Ctx())
        op.process_batch(_batch([9]))
        snap = op.snapshot_state()
        op2 = GroupAggOperator(CountAggregate(), "k")
        op2.open(_Ctx())
        op2.restore_state(snap)
        out = _rows(op2.process_batch(_batch([9])))
        # restored operator knows key 9 was emitted -> UB/UA, not INSERT
        assert [(r[ROWKIND_FIELD], r["count"]) for r in out] == \
            [(ROWKIND_UPDATE_BEFORE, 1), (ROWKIND_UPDATE_AFTER, 2)]


def make_tenv():
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.table.environment import StreamTableEnvironment

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 4,  # force multi-batch updates
    }))
    return StreamTableEnvironment(env)


def _bid_rows(pairs):
    return [{"auction": a, "price": float(p), "ts": t}
            for a, p, t in pairs]


class TestTwoLevelSql:
    def test_count_of_counts(self):
        """SELECT c, COUNT(*) FROM (per-auction counts) GROUP BY c — wrong
        without retractions (stale groups keep phantom members)."""
        t_env = make_tenv()
        pairs = [(a, 1, i * 100) for i, a in enumerate(
            [1, 2, 3, 1, 2, 1, 4, 4, 4, 4])]
        t_env.create_temporary_view(
            "bid", t_env.from_collection(_bid_rows(pairs),
                                         timestamp_field="ts"))
        out = t_env.execute_sql(
            "SELECT c, COUNT(*) AS n FROM "
            "(SELECT auction, COUNT(*) AS c FROM bid GROUP BY auction) "
            "GROUP BY c").collect()
        # final counts: a1=3, a2=2, a3=1, a4=4 -> c=3:1, c=2:1, c=1:1, c=4:1
        got = {r["c"]: r["n"] for r in out}
        assert got == {3: 1, 2: 1, 1: 1, 4: 1}

    def test_sum_over_updating_counts(self):
        t_env = make_tenv()
        pairs = [(a, 1, i * 100) for i, a in enumerate([1, 1, 2, 2, 2])]
        t_env.create_temporary_view(
            "bid", t_env.from_collection(_bid_rows(pairs),
                                         timestamp_field="ts"))
        out = t_env.execute_sql(
            "SELECT SUM(c) AS total, AVG(c) AS mean FROM "
            "(SELECT auction, COUNT(*) AS c FROM bid GROUP BY auction)"
        ).collect()
        assert len(out) == 1
        assert out[0]["total"] == 5  # 2 + 3
        # COUNT(*) is an integer, so AVG over it is one: (2 + 3) / 2
        # truncated, as SQL's AVG(INT) / AVG(BIGINT) is
        assert out[0]["mean"] == 2

    def test_max_over_updating_input_rejected(self):
        from flink_tpu.table.planner import PlanError

        t_env = make_tenv()
        t_env.create_temporary_view(
            "bid", t_env.from_collection(
                _bid_rows([(1, 1, 0)]), timestamp_field="ts"))
        with pytest.raises(PlanError, match="retractable"):
            t_env.execute_sql(
                "SELECT MAX(c) AS m FROM "
                "(SELECT auction, COUNT(*) AS c FROM bid "
                "GROUP BY auction)")

    def test_window_over_updating_input_rejected(self):
        from flink_tpu.table.planner import PlanError

        t_env = make_tenv()
        t_env.create_temporary_view(
            "bid", t_env.from_collection(
                _bid_rows([(1, 1, 0)]), timestamp_field="ts"))
        t_env.execute_sql(
            "CREATE VIEW counts AS SELECT auction, COUNT(*) AS c "
            "FROM bid GROUP BY auction")
        with pytest.raises(PlanError, match="updating"):
            t_env.execute_sql(
                "SELECT window_end, COUNT(*) AS n FROM TABLE("
                "TUMBLE(TABLE counts, DESCRIPTOR(ts), "
                "INTERVAL '10' SECOND)) "
                "GROUP BY window_start, window_end")

    def test_single_level_unchanged(self):
        """Plain GROUP BY still materializes the same final table."""
        t_env = make_tenv()
        pairs = [(1, 10, 1000), (2, 20, 2000), (1, 30, 3000)]
        t_env.create_temporary_view(
            "bid", t_env.from_collection(_bid_rows(pairs),
                                         timestamp_field="ts"))
        out = t_env.execute_sql(
            "SELECT auction, SUM(CAST(price AS FLOAT)) AS total FROM bid "
            "GROUP BY auction").collect()
        got = {r["auction"]: r["total"] for r in out}
        assert got == {1: 40.0, 2: 20.0}
