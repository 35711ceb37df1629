"""Fluent (programmatic) Table API — flink_tpu/table/fluent.py.

reference parity: flink-table-api-java Table/Expressions DSL
(select/where/groupBy/window/join/orderBy/fetch/distinct with Tumble/
Slide/Session group windows). Every fluent query must plan through the
SAME AST/planner as its SQL spelling — pinned by comparing each fluent
query against the equivalent SQL string.
"""

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.table.environment import StreamTableEnvironment
from flink_tpu.table.fluent import Session, Slide, Tumble, col, count_star, lit


def _t_env():
    return StreamTableEnvironment(StreamExecutionEnvironment(
        Configuration({"execution.micro-batch.size": 128})))


def _bids(t_env, n=4000):
    rng = np.random.default_rng(3)
    rows = [{"auction": int(rng.integers(30)),
             "price": np.float32(rng.integers(1, 100)),
             "t": i * 5} for i in range(n)]
    table = t_env.from_collection(rows, timestamp_field="t")
    t_env.create_temporary_view("bid", table)
    return table


def _sorted(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


class TestProjectionFilter:
    def test_select_where_matches_sql(self):
        t_env = _t_env()
        bids = _bids(t_env)
        fluent = (bids.where((col("price") > 50) & (col("auction") < 10))
                  .select(col("auction"),
                          (col("price") * 2).alias("double_price"))
                  .execute().collect())
        sql = t_env.execute_sql(
            "SELECT auction, price * 2 AS double_price FROM bid "
            "WHERE price > 50 AND auction < 10").collect()
        assert _sorted(fluent) == _sorted(sql) and len(fluent) > 0

    def test_distinct_and_fetch(self):
        t_env = _t_env()
        bids = _bids(t_env, 500)
        fluent = bids.select(col("auction")).distinct().execute().collect()
        sql = t_env.execute_sql(
            "SELECT DISTINCT auction FROM bid").collect()
        assert _sorted(fluent) == _sorted(sql)
        limited = (bids.select(col("auction"), col("price"))
                   .order_by(col("price").desc()).fetch(5)
                   .execute().collect())
        sql_l = t_env.execute_sql(
            "SELECT auction, price FROM bid ORDER BY price DESC "
            "LIMIT 5").collect()
        assert [r["price"] for r in limited] == [r["price"] for r in sql_l]


class TestGroupBy:
    def test_plain_group_by(self):
        t_env = _t_env()
        bids = _bids(t_env)
        fluent = (bids.group_by(col("auction"))
                  .select(col("auction"), col("price").sum().alias("total"),
                          count_star().alias("n"))
                  .execute().collect())
        sql = t_env.execute_sql(
            "SELECT auction, SUM(price) AS total, COUNT(*) AS n "
            "FROM bid GROUP BY auction").collect()
        assert _sorted(fluent) == _sorted(sql) and len(fluent) > 5


class TestGroupWindows:
    def test_tumble_matches_sql(self):
        t_env = _t_env()
        bids = _bids(t_env)
        fluent = (bids.window(Tumble.over(2000).on(col("t")).alias("w"))
                  .group_by("w", col("auction"))
                  .select(col("auction"), col("window_end"),
                          count_star().alias("bids"))
                  .execute().collect())
        sql = t_env.execute_sql(
            "SELECT auction, window_end, COUNT(*) AS bids "
            "FROM TABLE(TUMBLE(TABLE bid, DESCRIPTOR(t), "
            "INTERVAL '2' SECOND)) "
            "GROUP BY auction, window_start, window_end").collect()
        assert _sorted(fluent) == _sorted(sql) and len(fluent) > 10

    def test_slide_window(self):
        t_env = _t_env()
        bids = _bids(t_env)
        fluent = (bids.window(Slide.over(4000, 2000).on(col("t"))
                              .alias("w"))
                  .group_by("w", col("auction"))
                  .select(col("auction"), col("window_start"),
                          col("price").max().alias("top"))
                  .execute().collect())
        sql = t_env.execute_sql(
            "SELECT auction, window_start, MAX(price) AS top "
            "FROM TABLE(HOP(TABLE bid, DESCRIPTOR(t), "
            "INTERVAL '2' SECOND, INTERVAL '4' SECOND)) "
            "GROUP BY auction, window_start, window_end").collect()
        assert _sorted(fluent) == _sorted(sql)


class TestExpressions:
    def test_reflected_arithmetic(self):
        t_env = _t_env()
        bids = _bids(t_env, 200)
        rows = (bids.select(col("auction"),
                            (100 - col("price")).alias("inv"),
                            (2 * col("price")).alias("dbl"))
                .execute().collect())
        sql = t_env.execute_sql(
            "SELECT auction, 100 - price AS inv, 2 * price AS dbl "
            "FROM bid").collect()
        assert _sorted(rows) == _sorted(sql)


class TestJoin:
    def test_same_named_keys_via_qualified_cols(self):
        """col('L.k') == col('R.k') — the common join shape where both
        sides share the key column name."""
        t_env = _t_env()
        rng = np.random.default_rng(4)
        left = [{"k": int(rng.integers(6)), "x": float(i), "t": i * 7}
                for i in range(150)]
        right = [{"k": int(rng.integers(6)), "y": float(i), "t": i * 7}
                 for i in range(150)]
        lt = t_env.from_collection(left, timestamp_field="t").alias("L")
        rt = t_env.from_collection(right, timestamp_field="t").alias("R")
        t_env.create_temporary_view("L", lt)
        t_env.create_temporary_view("R", rt)
        fluent = lt.join(rt, col("L.k") == col("R.k")).execute().collect()
        sql = t_env.execute_sql(
            "SELECT * FROM L JOIN R ON L.k = R.k").collect()
        assert len(fluent) == len(sql) > 0
        assert _sorted(fluent) == _sorted(sql)
    def test_inner_join_matches_sql(self):
        t_env = _t_env()
        rng = np.random.default_rng(9)
        left = [{"k": int(rng.integers(8)), "x": float(i % 11), "t": i * 7}
                for i in range(300)]
        right = [{"k": int(rng.integers(8)), "y": float(i % 11),
                  "t": i * 7} for i in range(300)]
        lt = t_env.from_collection(left, timestamp_field="t").alias("L")
        rt = t_env.from_collection(right, timestamp_field="t").alias("R")
        t_env.create_temporary_view("L", lt)
        t_env.create_temporary_view("R", rt)
        fluent = (lt.join(rt, col("x") == col("y"))
                  .execute().collect())
        sql = t_env.execute_sql(
            "SELECT * FROM L JOIN R ON L.x = R.y").collect()
        assert len(fluent) == len(sql) > 0
        assert _sorted(fluent) == _sorted(sql)
