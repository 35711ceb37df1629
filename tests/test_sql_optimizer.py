"""SQL logical optimizer (flink_tpu/table/optimizer.py) + INSERT INTO.

reference parity: Calcite rule sets (FlinkStreamRuleSets — FILTER_INTO_JOIN,
ReduceExpressionsRule) and TableEnvironment.executeSql INSERT INTO.

Pins: constant folding (arithmetic, boolean identities, BETWEEN/IN),
filter pushdown into INNER-join sides (both), LEFT-join (preserved side
only), pushdown through non-agg subqueries, the rank/Top-N guard
(ROW_NUMBER subqueries must keep their rownum filter outside), and
results unchanged by optimization (rewrites are semantics-preserving).
"""

import numpy as np
import pytest

from flink_tpu.table import sql_parser as ast
from flink_tpu.table.expressions import BinaryOp, Column, Literal
from flink_tpu.table.optimizer import (
    fold_constants,
    optimize,
    split_conjuncts,
)


def parse(sql):
    return ast.parse(sql)


class TestFolding:
    def test_arithmetic(self):
        stmt = parse("SELECT a FROM t WHERE a > 1 + 2 * 3")
        out = optimize(stmt)
        conj = split_conjuncts(out.where)
        assert conj == [BinaryOp(">", Column("a"), Literal(7))]

    def test_boolean_identities(self):
        stmt = parse("SELECT a FROM t WHERE a > 1 AND 1 = 1")
        out = optimize(stmt)
        # TRUE conjunct dissolves entirely
        assert out.where == BinaryOp(">", Column("a"), Literal(1))

    def test_between_in_fold(self):
        stmt = parse("SELECT a FROM t WHERE 5 BETWEEN 1 AND 9 AND a < 2")
        out = optimize(stmt)
        assert out.where == BinaryOp("<", Column("a"), Literal(2))


class TestJoinPushdown:
    def _joined(self, sql):
        return optimize(parse(sql))

    def test_inner_both_sides(self):
        out = self._joined(
            "SELECT b.x FROM b JOIN c ON b.k = c.k "
            "WHERE b.x > 5 AND c.y < 3 AND b.x < c.y + 100")
        assert isinstance(out.table, ast.Join)
        # one-sided conjuncts moved into SubQuery wrappers
        assert isinstance(out.table.left, ast.SubQuery)
        assert isinstance(out.table.right, ast.SubQuery)
        assert out.table.left.alias == "b"
        assert out.table.right.alias == "c"
        # the cross-side conjunct stays above
        kept = split_conjuncts(out.where)
        assert len(kept) == 1

    def test_left_join_preserved_side_only(self):
        out = self._joined(
            "SELECT b.x FROM b LEFT JOIN c ON b.k = c.k "
            "WHERE b.x > 5 AND c.y < 3")
        assert isinstance(out.table.left, ast.SubQuery)
        # null-supplying side's predicate must NOT sink below the join
        assert isinstance(out.table.right, ast.NamedTable)
        assert len(split_conjuncts(out.where)) == 1

    def test_unqualified_not_pushed(self):
        out = self._joined(
            "SELECT b.x FROM b JOIN c ON b.k = c.k WHERE x > 5")
        assert isinstance(out.table.left, ast.NamedTable)
        assert isinstance(out.table.right, ast.NamedTable)
        assert out.where is not None


class TestSubqueryPushdown:
    def test_pushed_through_projection(self):
        out = optimize(parse(
            "SELECT v FROM (SELECT a + 1 AS v FROM t) WHERE v > 10"))
        assert out.where is None
        inner = out.table.query
        # v > 10 became a + 1 > 10 inside
        assert inner.where == BinaryOp(
            ">", BinaryOp("+", Column("a"), Literal(1)), Literal(10))

    def test_rank_pattern_not_pushed(self):
        sql = ("SELECT * FROM (SELECT a, ROW_NUMBER() OVER ("
               "PARTITION BY k ORDER BY a DESC) AS rn FROM t) "
               "WHERE rn <= 3")
        out = optimize(parse(sql))
        assert out.where is not None  # stayed outside

    def test_agg_subquery_not_pushed(self):
        sql = ("SELECT s FROM (SELECT k, SUM(v) AS s FROM t GROUP BY k) "
               "WHERE s > 10")
        out = optimize(parse(sql))
        assert out.where is not None


class TestSemanticsPreserved:
    def _env(self):
        from flink_tpu import StreamExecutionEnvironment, Configuration
        from flink_tpu.table.environment import StreamTableEnvironment

        env = StreamExecutionEnvironment(Configuration(
            {"execution.micro-batch.size": 64}))
        return StreamTableEnvironment(env)

    def test_join_results_identical(self, monkeypatch):
        rows_l = [{"k": i % 5, "x": float(i), "t": i * 10}
                  for i in range(200)]
        rows_r = [{"k": i % 5, "y": float(i % 7), "t": i * 10}
                  for i in range(200)]

        def run(optimized):
            if not optimized:
                import flink_tpu.table.environment as te

                monkeypatch.setattr(te, "optimize", lambda s: s)
            t_env = self._env()
            t_env.create_temporary_view(
                "L", t_env.from_collection(rows_l, timestamp_field="t"))
            t_env.create_temporary_view(
                "R", t_env.from_collection(rows_r, timestamp_field="t"))
            res = t_env.execute_sql(
                "SELECT L.k, L.x, R.y FROM L JOIN R ON L.x = R.y "
                "WHERE L.x > 2 AND R.y < 5").collect()
            monkeypatch.undo()
            return sorted((r["k_l"], r["x"], r["y"]) for r in res)

        assert run(True) == run(False) and len(run(True)) > 0


class TestExplain:
    def test_explain_shows_optimized_and_physical(self):
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.table.environment import StreamTableEnvironment

        t_env = StreamTableEnvironment(StreamExecutionEnvironment(
            Configuration({})))
        rows = [{"auction": 1, "price": 2.0, "t": 0}]
        t_env.create_temporary_view(
            "bid", t_env.from_collection(rows, timestamp_field="t"))
        text = t_env.execute_sql(
            "EXPLAIN SELECT auction, COUNT(*) AS n FROM TABLE(TUMBLE("
            "TABLE bid, DESCRIPTOR(t), INTERVAL '10' SECOND)) "
            "WHERE price > 1 AND 1 = 1 "
            "GROUP BY auction, window_start, window_end")
        assert "Optimized Logical Plan" in text
        assert "1 = 1" not in text          # folded away
        assert "(price > 1)" in text        # kept
        assert "Physical Plan" in text
        assert "HASH key=auction" in text   # the keyed exchange
        # explain_sql() works without the EXPLAIN keyword too
        text2 = t_env.explain_sql("SELECT auction FROM bid")
        assert "Optimized Logical Plan" in text2

    def test_explain_join_pushdown_visible(self):
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.table.environment import StreamTableEnvironment

        t_env = StreamTableEnvironment(StreamExecutionEnvironment(
            Configuration({})))
        rows = [{"k": 1, "x": 2.0, "t": 0}]
        t_env.create_temporary_view(
            "L", t_env.from_collection(rows, timestamp_field="t"))
        t_env.create_temporary_view(
            "R", t_env.from_collection(rows, timestamp_field="t"))
        text = t_env.execute_sql(
            "EXPLAIN SELECT L.x FROM L JOIN R ON L.x = R.x "
            "WHERE L.x > 5")
        # the one-sided predicate sank into the left branch's subquery
        assert "JOIN" in text and "WHERE (L.x > 5)" in text


class TestCatalogStatements:
    def test_show_tables_and_describe(self):
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.table.environment import StreamTableEnvironment

        t_env = StreamTableEnvironment(StreamExecutionEnvironment(
            Configuration({})))
        rows = [{"a": 1, "p": 2.0, "t": 0}]
        t_env.create_temporary_view(
            "bids", t_env.from_collection(rows, timestamp_field="t"))
        t_env.create_temporary_view(
            "asks", t_env.from_collection(rows, timestamp_field="t"))
        assert t_env.execute_sql("SHOW TABLES") == ["asks", "bids"]
        d = t_env.execute_sql("DESCRIBE bids")
        assert d["columns"] == ["a", "p", "t"]
        assert d["time_field"] == "t" and d["changelog"] is False
        # DESC shorthand; unknown table fails with the known list
        assert t_env.execute_sql("DESC asks")["name"] == "asks"
        from flink_tpu.table.planner import PlanError

        with pytest.raises(PlanError, match="not registered"):
            t_env.execute_sql("DESCRIBE nope")


class TestUnionAll:
    def _env(self):
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.table.environment import StreamTableEnvironment

        return StreamTableEnvironment(StreamExecutionEnvironment(
            Configuration({"execution.micro-batch.size": 64})))

    def test_union_all_sql(self):
        t_env = self._env()
        a = [{"k": i, "v": float(i), "t": i * 10} for i in range(50)]
        b = [{"k": i + 100, "v": float(i), "t": i * 10} for i in range(30)]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        t_env.create_temporary_view(
            "B", t_env.from_collection(b, timestamp_field="t"))
        rows = t_env.execute_sql(
            "SELECT k, v FROM A WHERE v > 10 UNION ALL "
            "SELECT k, v FROM B").collect()
        exp = [r for r in a if r["v"] > 10] + b
        assert sorted(r["k"] for r in rows) == sorted(r["k"] for r in exp)

    def test_union_trailing_order_limit(self):
        t_env = self._env()
        a = [{"k": i, "v": float(i), "t": i * 10} for i in range(20)]
        b = [{"k": i, "v": float(i + 100), "t": i * 10} for i in range(20)]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        t_env.create_temporary_view(
            "B", t_env.from_collection(b, timestamp_field="t"))
        rows = t_env.execute_sql(
            "SELECT v FROM A UNION ALL SELECT v FROM B "
            "ORDER BY v DESC LIMIT 3").collect()
        assert [r["v"] for r in rows] == [119.0, 118.0, 117.0]

    def test_union_distinct_rejected(self):
        from flink_tpu.table.sql_parser import SqlParseError, parse

        with pytest.raises(SqlParseError, match="UNION ALL"):
            parse("SELECT a FROM t UNION SELECT a FROM u")

    def test_mismatched_columns_rejected(self):
        from flink_tpu.table.planner import PlanError

        t_env = self._env()
        a = [{"k": 1, "v": 1.0, "t": 0}]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        with pytest.raises(PlanError, match="identical columns"):
            t_env.execute_sql(
                "SELECT k FROM A UNION ALL SELECT v FROM A").collect()

    def test_union_of_changelog_branch_rejected(self):
        from flink_tpu.table.planner import PlanError

        t_env = self._env()
        a = [{"k": i % 3, "v": float(i), "t": i * 10} for i in range(30)]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        with pytest.raises(PlanError, match="changelog"):
            t_env.execute_sql(
                "SELECT k, SUM(v) AS s FROM A GROUP BY k UNION ALL "
                "SELECT k, SUM(v) AS s FROM A GROUP BY k").collect()

    def test_subquery_order_limit_rejected(self):
        from flink_tpu.table.planner import PlanError

        t_env = self._env()
        a = [{"k": i, "v": float(i), "t": i * 10} for i in range(20)]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        with pytest.raises(PlanError, match="outermost"):
            t_env.execute_sql(
                "SELECT k FROM (SELECT k FROM A ORDER BY k LIMIT 3)"
            ).collect()

    def test_mixed_time_branches_rejected(self):
        t_env = self._env()
        a = [{"k": 1, "v": 1.0, "t": 0}]
        b = [{"k": 2, "v": 2.0}]
        t_env.create_temporary_view(
            "A", t_env.from_collection(a, timestamp_field="t"))
        t_env.create_temporary_view(
            "B", t_env.from_collection(b), columns=["k", "v"])
        # the union's runtime guard names the cause (plan-time can't see
        # it: projections legitimately drop the time-field marker while
        # the timestamp column still rides along)
        with pytest.raises(Exception, match="event time"):
            t_env.execute_sql(
                "SELECT k, v FROM A UNION ALL SELECT k, v FROM B"
            ).collect()

    def test_fluent_union_all(self):
        t_env = self._env()
        a = [{"k": i, "v": float(i), "t": i * 10} for i in range(10)]
        b = [{"k": i + 50, "v": float(i), "t": i * 10} for i in range(10)]
        ta = t_env.from_collection(a, timestamp_field="t")
        tb = t_env.from_collection(b, timestamp_field="t")
        rows = ta.union_all(tb).execute().collect()
        assert sorted(r["k"] for r in rows) == sorted(
            [r["k"] for r in a] + [r["k"] for r in b])


class TestInsertInto:
    def test_insert_into_sink(self):
        from flink_tpu.connectors.sinks import CollectSink

        t_env = self._env() if hasattr(self, "_env") else None
        from flink_tpu import StreamExecutionEnvironment, Configuration
        from flink_tpu.table.environment import StreamTableEnvironment

        env = StreamExecutionEnvironment(Configuration(
            {"execution.micro-batch.size": 64}))
        t_env = StreamTableEnvironment(env)
        rows = [{"k": i % 3, "v": float(i), "t": i * 10}
                for i in range(100)]
        t_env.create_temporary_view(
            "src", t_env.from_collection(rows, timestamp_field="t"))
        sink = CollectSink()
        t_env.create_sink_table("out", sink, columns=["k", "doubled"])
        t_env.execute_sql(
            "INSERT INTO out SELECT k, v * 2 AS doubled FROM src "
            "WHERE v > 50")
        got = sink.result().to_rows()
        exp = [(r["k"], r["v"] * 2) for r in rows if r["v"] > 50]
        assert sorted((g["k"], g["doubled"]) for g in got) == sorted(exp)

    def test_updating_query_into_append_sink_rejected(self):
        from flink_tpu import StreamExecutionEnvironment, Configuration
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.table.environment import StreamTableEnvironment
        from flink_tpu.table.planner import PlanError

        t_env = StreamTableEnvironment(
            StreamExecutionEnvironment(Configuration({})))
        rows = [{"k": i % 3, "v": float(i), "t": i * 10}
                for i in range(30)]
        t_env.create_temporary_view(
            "src", t_env.from_collection(rows, timestamp_field="t"))
        t_env.create_sink_table("out", CollectSink(), columns=["k", "s"])
        with pytest.raises(PlanError, match="append-only"):
            t_env.execute_sql(
                "INSERT INTO out SELECT k, SUM(v) AS s FROM src "
                "GROUP BY k")

    def test_updating_query_into_changelog_sink(self):
        from flink_tpu import StreamExecutionEnvironment, Configuration
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.core.records import ROWKIND_FIELD
        from flink_tpu.table.environment import StreamTableEnvironment

        class ChangelogSink(CollectSink):
            supports_changelog = True

        t_env = StreamTableEnvironment(StreamExecutionEnvironment(
            Configuration({"execution.micro-batch.size": 8})))
        rows = [{"k": i % 3, "v": float(i), "t": i * 10}
                for i in range(30)]
        t_env.create_temporary_view(
            "src", t_env.from_collection(rows, timestamp_field="t"))
        sink = ChangelogSink()
        t_env.create_sink_table("out", sink, columns=["k", "s"])
        t_env.execute_sql(
            "INSERT INTO out SELECT k, SUM(CAST(v AS FLOAT)) AS s FROM src "
            "GROUP BY k")
        batch = sink.result()
        # the row-kind column must survive so the consumer can apply
        # retractions; folding the changelog gives the true final sums
        assert ROWKIND_FIELD in batch.columns
        final = {}
        for r in batch.to_rows():
            final[r["k"]] = r["s"]
        exp = {}
        for r in rows:
            exp[r["k"]] = exp.get(r["k"], 0.0) + r["v"]
        assert {k: round(v, 3) for k, v in final.items()} == \
            {k: round(v, 3) for k, v in exp.items()}

    def test_unregistered_target_fails(self):
        from flink_tpu import StreamExecutionEnvironment, Configuration
        from flink_tpu.table.environment import StreamTableEnvironment
        from flink_tpu.table.planner import PlanError

        t_env = StreamTableEnvironment(
            StreamExecutionEnvironment(Configuration({})))
        rows = [{"k": 1, "v": 1.0, "t": 0}]
        t_env.create_temporary_view(
            "src", t_env.from_collection(rows, timestamp_field="t"))
        with pytest.raises(PlanError, match="not a registered sink"):
            t_env.execute_sql("INSERT INTO nowhere SELECT k FROM src")
