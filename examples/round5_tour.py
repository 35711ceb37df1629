"""Tour of the round-5 surface: DataStream V2, async keyed state, the
bucketed exactly-once filesystem warehouse, and State TTL.

Run: python examples/round5_tour.py
(Runs on JAX's default backend; ``JAX_PLATFORMS=cpu`` for a machine
without an accelerator.)
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from flink_tpu import Configuration
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.datastream.v2 import (
    ExecutionEnvironment,
    OneInputStreamProcessFunction,
)
from flink_tpu.state.keyed_state import ReducingStateDescriptor


class RunningTotals(OneInputStreamProcessFunction):
    """V2 process function using ASYNC keyed state: the adds and the
    read coalesce into batched kernels; the future's callback emits."""

    def open(self, ctx):
        self.desc = ReducingStateDescriptor("total", np.add, np.float64,
                                            0.0)

    def process_batch(self, batch, out, ctx):
        st = ctx.async_state(self.desc)
        keys = batch[KEY_ID_FIELD]
        st.add(keys, np.asarray(batch["value"]))

        def emit(totals, b=batch):
            out.collect(b.with_column("running_total", totals))

        st.get(keys).then(emit)


def main() -> None:
    print("== DataStream V2 + async keyed state ==")
    env = ExecutionEnvironment.get_instance(Configuration({
        "execution.micro-batch.size": 8192}))
    sink = CollectSink()
    (env.from_source(DataGenSource(total_records=100_000, num_keys=100,
                                   events_per_second_of_eventtime=50_000),
                     name="orders")
        .key_by("key")
        .process(RunningTotals())
        .to_sink(sink))
    env.execute("v2-running-totals")
    b = sink.result()
    print(f"  {len(b)} rows; max running total "
          f"{float(np.asarray(b['running_total']).max()):.1f}")

    print("== bucketed exactly-once warehouse (SQL) ==")
    from flink_tpu.connectors.filesystem import read_committed_rows
    from flink_tpu.connectors.kafka import FakeBroker
    from flink_tpu.datastream.environment import (
        StreamExecutionEnvironment,
    )
    from flink_tpu.table.environment import StreamTableEnvironment

    warehouse = tempfile.mkdtemp(prefix="flink-tpu-warehouse-")
    broker = FakeBroker.get("default")
    broker.create_topic("trades", 1)
    rng = np.random.default_rng(1)
    n = 20_000
    ts = np.arange(n, dtype=np.int64) * 2
    broker.append("trades", 0, RecordBatch.from_pydict(
        {"sym": rng.integers(0, 8, n), "px": rng.random(n),
         "ts": ts}, timestamps=ts))

    env1 = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 2048,
        # State TTL: idle GROUP BY accumulators expire after 10 min
        "table.exec.state.ttl": 600_000}))
    tenv = StreamTableEnvironment(env1)
    tenv.execute_sql(
        "CREATE TABLE trades (sym BIGINT, px DOUBLE, ts BIGINT, "
        "WATERMARK FOR ts AS ts) "
        "WITH ('connector'='kafka', 'topic'='trades')")
    tenv.execute_sql(
        "CREATE TABLE warehouse (sym BIGINT, window_end BIGINT, "
        "vwap DOUBLE) "
        f"WITH ('connector'='filesystem', 'path'='{warehouse}', "
        "'format'='json', 'sink.bucket-by'='sym')")
    tenv.execute_sql("""
        INSERT INTO warehouse
        SELECT sym, window_end, AVG(px) AS vwap
        FROM TABLE(TUMBLE(TABLE trades, DESCRIPTOR(ts),
                          INTERVAL '5' SECOND))
        GROUP BY sym, window_start, window_end
    """)
    buckets = sorted(os.listdir(warehouse))
    rows = read_committed_rows(warehouse)
    print(f"  {len(rows)} committed rows across buckets {buckets}")

    print("== reading the warehouse back through SQL ==")
    env2 = StreamExecutionEnvironment(Configuration({}))
    tenv2 = StreamTableEnvironment(env2)
    tenv2.execute_sql(
        "CREATE TABLE warehouse (sym BIGINT, window_end BIGINT, "
        "vwap DOUBLE) "
        f"WITH ('connector'='filesystem', 'path'='{warehouse}', "
        "'format'='json')")
    got = tenv2.execute_sql(
        "SELECT sym, COUNT(*) AS windows FROM warehouse GROUP BY sym"
    ).collect()
    print(f"  per-symbol window counts: "
          f"{ {r['sym']: r['windows'] for r in got} }")
    print("done.")


if __name__ == "__main__":
    main()
