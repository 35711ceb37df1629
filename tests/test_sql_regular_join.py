"""SQL's regular join: the comma join as ``q4.sql`` writes it, planned
onto the device regular join with its residual compared in the probe, the
host path it keeps for ``join.mode=host`` and for a residual it cannot
compare, and integer GROUP BY keys hashed into one."""

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.table import sql_parser as ast
from flink_tpu.table.environment import StreamTableEnvironment
from flink_tpu.table.planner import PlanError

QUERY = ("SELECT A.k, A.x, B.y FROM a A, b B "
         "WHERE A.k = B.k AND B.t BETWEEN A.t AND A.e AND A.x <> 3")


def _tenv(**options):
    env = StreamExecutionEnvironment(Configuration(dict(
        {"execution.micro-batch.size": 16}, **options)))
    t = StreamTableEnvironment(env)
    rng = np.random.default_rng(5)
    a = [{"k": int(rng.integers(0, 12)), "x": int(rng.integers(0, 6)),
          "t": i, "e": i + int(rng.integers(0, 40))} for i in range(90)]
    b = [{"k": int(rng.integers(0, 12)), "y": int(rng.integers(1 << 40)),
          "t": int(rng.integers(0, 120))} for _ in range(150)]
    t.create_temporary_view("a", t.from_collection(a, timestamp_field="t"))
    t.create_temporary_view("b", t.from_collection(b, timestamp_field="t"))
    return t


def test_a_comma_join_is_the_inner_join_of_its_where():
    stmt = ast.parse(QUERY)
    assert stmt.where is None
    assert isinstance(stmt.table, ast.Join) and stmt.table.kind == "INNER"
    assert stmt.table.condition is not None


def test_a_comma_join_takes_two_tables_and_a_condition():
    with pytest.raises(ast.SqlParseError, match="two tables"):
        ast.parse("SELECT * FROM a, b, c WHERE a.k = b.k")
    with pytest.raises(PlanError, match="cross join"):
        _tenv().sql_query("SELECT A.k FROM a A, b B")


def test_the_regular_join_plans_on_the_device_with_its_residual():
    plan = _tenv().explain_sql(QUERY)
    assert "sql_device_join" in plan and "residual" not in plan
    host = _tenv(**{"join.mode": "host"}).explain_sql(QUERY)
    assert "sql_device_join" not in host and "sql_join_residual" in host


def test_the_device_and_host_joins_answer_alike():
    from flink_tpu.joins import operators as jo

    before = jo.fallbacks()
    got = _tenv().execute_sql(QUERY).collect()
    want = _tenv(**{"join.mode": "host"}).execute_sql(QUERY).collect()
    assert jo.fallbacks() == before
    key = lambda r: tuple(r.values())  # noqa: E731
    assert sorted(map(key, got)) == sorted(map(key, want)) and got


def test_a_residual_the_device_cannot_compare_stays_on_the_host():
    from flink_tpu.joins import operators as jo

    query = ("SELECT A.k, B.y FROM a A, b B "
             "WHERE A.k = B.k AND B.t + 1 > A.t")
    before = jo.fallbacks()
    with pytest.warns(RuntimeWarning, match="regular join on the host"):
        plan = _tenv().explain_sql(query)
    assert jo.fallbacks() == before + 1 and "sql_join_residual" in plan


def test_integer_group_keys_are_hashed_into_one_and_come_back():
    query = "SELECT k, x, COUNT(*) AS n, MAX(e) AS m FROM a GROUP BY k, x"
    rows = _tenv().execute_sql(query).collect()
    t = _tenv()
    want = {}
    for r in t.lookup("a").stream.execute_and_collect().to_rows():
        n, m = want.get((r["k"], r["x"]), (0, None))
        want[(r["k"], r["x"])] = (n + 1, r["e"] if m is None
                                  else max(m, r["e"]))
    assert {(r["k"], r["x"]): (r["n"], r["m"]) for r in rows} == want
    assert all(isinstance(r["m"], int) for r in rows)


def test_two_keys_whose_hash_collides_are_refused():
    from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
    from flink_tpu.runtime.group_agg import GroupAggOperator
    from flink_tpu.runtime.operators import OperatorContext
    from flink_tpu.windowing.aggregates import CountAggregate

    op = GroupAggOperator(CountAggregate(), "g", key_columns=("a", "b"))
    op.open(OperatorContext())
    batch = RecordBatch({"g": np.array([7, 7]), KEY_ID_FIELD: np.array([7, 7]),
                         "a": np.array([1, 2]), "b": np.array([3, 3])})
    with pytest.raises(RuntimeError, match="hash to one key id"):
        op.process_batch(batch)
