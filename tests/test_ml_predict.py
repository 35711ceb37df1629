"""ML model functions (reference: flink-models + MLPredictRunner /
AsyncMLPredictRunner + CREATE MODEL DDL + SQL ML_PREDICT)."""

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.ml import (
    FunctionModel,
    JaxModel,
    MLPredictOperator,
    RemoteModel,
)
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.table.environment import StreamTableEnvironment

#: a module-level factory for the CREATE MODEL 'python' provider
def doubler_model():
    return FunctionModel(
        lambda ins: {"doubled": ins["x"] * 2},
        input_names=["x"], output_names=["doubled"])


def _rows(n=20):
    return [{"price": float(i), "qty": i % 5, "ts": i * 100}
            for i in range(n)]


def make_tenv(**conf):
    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 7, **conf}))
    return StreamTableEnvironment(env), env


class TestModels:
    def test_jax_model_batched_inference(self):
        import jax.numpy as jnp

        # a tiny linear model: y = x @ w + b, jitted, sticky-padded
        params = {"w": jnp.asarray([[2.0], [1.0]]), "b": jnp.asarray(0.5)}
        model = JaxModel(
            lambda p, x: (x @ p["w"])[:, 0] + p["b"],
            params, input_names=["x"], output_names=["y"])
        for n in (5, 9, 6):  # varying batch sizes share one executable
            x = np.arange(2 * n, dtype=np.float32).reshape(n, 2)
            out = model.predict({"x": x})
            np.testing.assert_allclose(
                out["y"], x @ np.array([[2.0], [1.0]])[:, 0] + 0.5,
                rtol=1e-5)

    def test_operator_appends_outputs(self):
        from flink_tpu.core.records import RecordBatch
        from flink_tpu.runtime.operators import OperatorContext

        op = MLPredictOperator(doubler_model(), input_fields=["price"])
        op.open(OperatorContext())
        batch = RecordBatch.from_pydict(
            {"price": np.arange(4, dtype=np.float32)})
        out = op.process_batch(batch)[0]
        np.testing.assert_array_equal(out["doubled"],
                                      np.arange(4, dtype=np.float32) * 2)
        assert "price" in out.columns  # inputs preserved

    def test_descriptor_arity_checked(self):
        with pytest.raises(ValueError, match="expects 1 inputs"):
            MLPredictOperator(doubler_model(),
                              input_fields=["a", "b"])


class TestDataStreamApi:
    @pytest.mark.parametrize("asynchronous", [False, True])
    def test_ml_predict_in_pipeline(self, asynchronous):
        t_env, env = make_tenv()
        sink = CollectSink()
        env.from_source(
            __import__("flink_tpu.connectors.sources",
                       fromlist=["CollectionSource"])
            .CollectionSource.of_rows(_rows(), batch_size=7),
            WatermarkStrategy.for_monotonous_timestamps()
            .with_timestamp_field("ts")) \
            .ml_predict(doubler_model(), input_fields=["price"],
                        asynchronous=asynchronous) \
            .sink_to(sink)
        env.execute("ml")
        rows = sink.result().to_rows()
        assert len(rows) == 20
        assert all(r["doubled"] == r["price"] * 2 for r in rows)

    def test_remote_model_async_bounded(self):
        """RemoteModel through the async runner: calls overlap but results
        stay ordered."""
        import time

        calls = []

        def client(inputs):
            calls.append(len(inputs["x"]))
            time.sleep(0.01)
            return {"score": inputs["x"] + 1}

        model = RemoteModel(client, input_names=["x"],
                            output_names=["score"])
        t_env, env = make_tenv()
        sink = CollectSink()
        from flink_tpu.connectors.sources import CollectionSource

        env.from_source(
            CollectionSource.of_rows(
                [{"price": float(i)} for i in range(30)], batch_size=5),
            WatermarkStrategy.for_monotonous_timestamps()) \
            .ml_predict(model, input_fields=["price"],
                        asynchronous=True, capacity=3) \
            .sink_to(sink)
        env.execute("remote")
        rows = sink.result().to_rows()
        assert [r["score"] for r in rows] == [float(i) + 1
                                              for i in range(30)]
        assert sum(calls) == 30


class TestSqlMlPredict:
    def test_ml_predict_tvf(self):
        t_env, env = make_tenv()
        t_env.create_temporary_view(
            "orders", t_env.from_collection(_rows(), timestamp_field="ts"))
        t_env.create_temporary_model("scorer", doubler_model())
        out = t_env.execute_sql(
            "SELECT price, doubled FROM ML_PREDICT(TABLE orders, "
            "MODEL scorer, DESCRIPTOR(price)) WHERE doubled > 10"
        ).collect()
        assert len(out) == 14  # price > 5
        assert all(r["doubled"] == r["price"] * 2 for r in out)

    def test_ml_predict_feeds_aggregate(self):
        t_env, env = make_tenv()
        t_env.create_temporary_view(
            "orders", t_env.from_collection(_rows(), timestamp_field="ts"))
        t_env.create_temporary_model("scorer", doubler_model())
        out = t_env.execute_sql(
            "SELECT qty, SUM(CAST(doubled AS FLOAT)) AS s FROM ML_PREDICT("
            "TABLE orders, MODEL scorer, DESCRIPTOR(price)) "
            "GROUP BY qty").collect()
        got = {r["qty"]: r["s"] for r in out}
        want = {}
        for r in _rows():
            want[r["qty"]] = want.get(r["qty"], 0.0) + r["price"] * 2
        assert got == want

    def test_create_model_ddl(self):
        t_env, env = make_tenv()
        t_env.create_temporary_view(
            "orders", t_env.from_collection(_rows(), timestamp_field="ts"))
        t_env.execute_sql(
            "CREATE MODEL scorer WITH ('provider' = 'python', "
            "'entry' = 'tests.test_ml_predict:doubler_model')")
        out = t_env.execute_sql(
            "SELECT doubled FROM ML_PREDICT(TABLE orders, MODEL scorer, "
            "DESCRIPTOR(price))").collect()
        assert len(out) == 20

    def test_unknown_model_precise_error(self):
        t_env, env = make_tenv()
        t_env.create_temporary_view(
            "orders", t_env.from_collection(_rows(), timestamp_field="ts"))
        with pytest.raises(KeyError, match="unknown model 'nope'"):
            t_env.execute_sql(
                "SELECT * FROM ML_PREDICT(TABLE orders, MODEL nope, "
                "DESCRIPTOR(price))")

    def test_unknown_provider_rejected(self):
        t_env, env = make_tenv()
        with pytest.raises(ValueError, match="unknown model provider"):
            t_env.execute_sql(
                "CREATE MODEL m WITH ('provider' = 'openai')")
