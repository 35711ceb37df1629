"""Multi-stage DAGs in the stage-parallel executor.

reference parity targets: DefaultExecutionGraph runs ANY DAG at any
per-vertex parallelism (flink-runtime/.../executiongraph/
DefaultExecutionGraph.java, Execution.java:572 deploy()): chains of keyed
exchanges (agg -> re-key -> agg), side outputs across the exchange
(OutputTag routing in OperatorChain), diamonds (one source fanning out to
a windowed branch and a join — Nexmark Q7's exact shape), and the
mesh x stage composition (a keyed subtask opening its engine over a
private sub-mesh)."""

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.assigners import TumblingEventTimeWindows


def _env(stage_parallelism, extra=None):
    conf = {
        "execution.micro-batch.size": 1000,
        "state.slot-table.capacity": 8192,
    }
    if stage_parallelism:
        conf["execution.stage-parallelism"] = stage_parallelism
    conf.update(extra or {})
    return StreamExecutionEnvironment(Configuration(conf))


def _two_stage_pipeline(env, sink, total=30_000, keys=300,
                        fail_after=None):
    """Stage 1: per-key 1 s window sums; stage 2: re-key the fired rows
    by window_start and sum the sums — a chain of two keyed exchanges."""
    src = DataGenSource(total_records=total, num_keys=keys,
                        events_per_second_of_eventtime=10_000, seed=5)
    ds = env.from_source(
        src, WatermarkStrategy.for_bounded_out_of_orderness(0))
    if fail_after is not None:
        from tests.test_checkpointing import FailingMap

        ds = ds.map(FailingMap(fail_after), name="failmap")
    (ds.key_by("key").window(TumblingEventTimeWindows.of(1000))
       .sum("value")
       .key_by("window_start").window(TumblingEventTimeWindows.of(1000))
       .sum("sum_value")
       .sink_to(sink))


def _stage2_rows(sink):
    return {(r["window_start"], r["window_end"]):
            round(r["sum_sum_value"], 2)
            for r in sink.result().to_rows()}


class TestTwoExchangePipeline:
    def test_plan_has_two_stages(self):
        from flink_tpu.cluster.stage_executor import plan_stages

        env = _env(0)
        sink = CollectSink()
        _two_stage_pipeline(env, sink, total=100, keys=5)
        plan = plan_stages(env.get_stream_graph())
        assert len(plan.stages) == 2
        assert plan.stages[0].out_key_field == "window_start"
        assert plan.stages[0].outputs[0].target_stage == 1
        assert not plan.stages[1].outputs
        assert plan.stages[1].chain[-1].kind == "sink"

    def test_matches_single_slot(self):
        env0 = _env(0)
        s0 = CollectSink()
        _two_stage_pipeline(env0, s0)
        env0.execute("single")
        expected = _stage2_rows(s0)

        env = _env(4, {"execution.source-parallelism": 2})
        sink = CollectSink()
        _two_stage_pipeline(env, sink)
        result = env.execute("staged")
        assert result.metrics["keyed_stages"] == 2
        assert len(result.metrics["per_stage_records_in"]) == 2
        got = _stage2_rows(sink)
        assert set(got) == set(expected)
        for k in expected:
            assert got[k] == pytest.approx(expected[k], rel=1e-4), k

    def test_every_n_batches_trigger_holds_the_source(self, tmp_path):
        """execution.checkpointing.every-n-source-batches is a
        deterministic trigger: the source holds at every N-th batch
        until the coordinator's barrier is served, however late the
        coordinator's clock polls. 30 batches at N=5 are six
        checkpoints, the last one cut at the end of the input."""
        from flink_tpu.checkpoint.storage import CheckpointStorage

        ckpt = str(tmp_path / "ckpts")
        env = _env(4, {"state.checkpoints.dir": ckpt,
                       "execution.checkpointing.every-n-source-batches": 5,
                       "execution.checkpointing.retained": 10})
        sink = CollectSink()
        _two_stage_pipeline(env, sink)
        env.execute("every-n")
        assert CheckpointStorage(ckpt).latest_checkpoint_id() == 6

    def test_crash_restore_matches_clean_run(self, tmp_path):
        ckpt = str(tmp_path / "ckpts")
        env0 = _env(0)
        s0 = CollectSink()
        _two_stage_pipeline(env0, s0)
        env0.execute("clean")
        expected = _stage2_rows(s0)

        conf = {"state.checkpoints.dir": ckpt,
                "execution.checkpointing.every-n-source-batches": 5}
        env1 = _env(4, conf)
        s1 = CollectSink()
        _two_stage_pipeline(env1, s1, fail_after=20_000)
        with pytest.raises(RuntimeError, match="injected failure"):
            env1.execute("crashing")
        from flink_tpu.checkpoint.storage import CheckpointStorage

        # the crash is in batch 21: checkpoints 1-3 were cut at batches
        # 5, 10 and 15 whatever the load, the fourth races the crash
        assert CheckpointStorage(ckpt).latest_checkpoint_id() in (3, 4)

        env2 = _env(4, conf)
        s2 = CollectSink()
        src = DataGenSource(total_records=30_000, num_keys=300,
                            events_per_second_of_eventtime=10_000, seed=5)
        ds = env2.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(0))
        ds = ds.map(lambda b: b, name="failmap")
        (ds.key_by("key").window(TumblingEventTimeWindows.of(1000))
           .sum("value")
           .key_by("window_start")
           .window(TumblingEventTimeWindows.of(1000))
           .sum("sum_value").sink_to(s2))
        env2.execute("restored", restore_from=ckpt)
        got = _stage2_rows(s1)
        got.update(_stage2_rows(s2))
        assert set(got) == set(expected)
        for k in expected:
            assert got[k] == pytest.approx(expected[k], rel=1e-3), k


class TestSideOutputAcrossExchange:
    def test_side_output_from_keyed_stage(self):
        """A process fn chained after the keyed window splits its output:
        main rows to one sink, tagged rows to a side sink — both running
        inside the keyed subtasks (OutputTag routing across the
        exchange)."""
        from flink_tpu.runtime.process import OutputTag, ProcessFunction

        BIG = OutputTag("big")

        class SplitBig(ProcessFunction):
            def process_batch(self, batch, ctx):
                big = batch["sum_value"] > 50.0
                ctx.output(BIG, batch.filter(big))
                ctx.collect(batch.filter(~big))

        def build(env, main_sink, side_sink):
            src = DataGenSource(total_records=20_000, num_keys=100,
                                events_per_second_of_eventtime=10_000,
                                seed=5)
            m = (env.from_source(
                    src,
                    WatermarkStrategy.for_bounded_out_of_orderness(0))
                 .key_by("key")
                 .window(TumblingEventTimeWindows.of(1000))
                 .sum("value")
                 .process(SplitBig()))
            m.sink_to(main_sink)
            m.get_side_output(BIG).sink_to(side_sink)

        env0 = _env(0)
        m0, s0 = CollectSink(), CollectSink()
        build(env0, m0, s0)
        env0.execute("single")

        env = _env(4, {"execution.source-parallelism": 2})
        m1, s1 = CollectSink(), CollectSink()
        build(env, m1, s1)
        env.execute("staged")

        def rows(sink):
            return {(int(r["key"]), int(r["window_start"])):
                    float(r["sum_value"])
                    for r in sink.result().to_rows()}

        for got, want in ((rows(m1), rows(m0)), (rows(s1), rows(s0))):
            assert set(got) == set(want)
            assert len(got) > 0
            for k in want:
                assert got[k] == pytest.approx(want[k], rel=1e-4), k


class TestQ7Diamond:
    """build_q7 itself (not a stand-in): one source fans out to the
    const-key windowed MAX branch AND the window join — a diamond with a
    join fed by a source branch and an upstream keyed stage."""

    def _rows(self, sink):
        return sorted((int(r["window_end"]), int(r["auction"]),
                       round(float(r["price"]), 3))
                      for r in sink.result().to_rows())

    def test_q7_stage_parallel_matches_single_slot_and_oracle(self):
        from flink_tpu.benchmarks.nexmark import (
            BidSource,
            build_q7,
            oracle_q7,
        )

        def run(conf):
            env = StreamExecutionEnvironment(Configuration(conf))
            sink = CollectSink()
            src = BidSource(total_records=30_000, num_auctions=50,
                            events_per_second_of_eventtime=10_000)
            build_q7(env, src, size_ms=2_000).sink_to(sink)
            env.execute("q7")
            return sink

        base = {"execution.micro-batch.size": 1000}
        single = self._rows(run(base))
        staged = self._rows(run({**base,
                                 "execution.stage-parallelism": 4,
                                 "execution.source-parallelism": 2}))
        assert staged == single
        assert len(staged) > 0

        # oracle cross-check on the raw stream
        src = BidSource(total_records=30_000, num_auctions=50,
                        events_per_second_of_eventtime=10_000)
        src.open(0, 1)
        bids = []
        while True:
            b = src.poll_batch(10_000)
            if b is None:
                break
            bids += list(zip(b.columns["auction"].tolist(),
                             b.columns["bidder"].tolist(),
                             b.columns["price"].tolist(),
                             b.timestamps.tolist()))
        oracle = oracle_q7(bids, 2_000)
        got_by_window = {}
        for we, auction, price in staged:
            got_by_window.setdefault(we, set()).add(auction)
        # only COMPLETE windows fire (the stream ends mid-window)
        for we in got_by_window:
            price, pairs = oracle[we]
            assert got_by_window[we] == {a for a, _ in pairs}, we


class TestMeshByStage:
    """execution.stage-mesh-devices: each keyed subtask opens its window
    engine over a private sub-mesh, sharding WITHIN its key-group range
    (subtask expansion x SPMD — the composition the executor docstring
    promises)."""

    def _pipeline(self, env, sink):
        src = DataGenSource(total_records=30_000, num_keys=300,
                            events_per_second_of_eventtime=10_000, seed=5)
        (env.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(0))
           .key_by("key").window(TumblingEventTimeWindows.of(1000))
           .sum("value").sink_to(sink))

    def _rows(self, sink):
        return {(r["key"], r["window_start"]): round(r["sum_value"], 2)
                for r in sink.result().to_rows()}

    def test_two_subtasks_by_four_devices_matches_single_slot(self):
        env0 = _env(0)
        s0 = CollectSink()
        self._pipeline(env0, s0)
        env0.execute("single")
        expected = self._rows(s0)

        env = _env(2, {"execution.stage-mesh-devices": 4})
        sink = CollectSink()
        self._pipeline(env, sink)
        env.execute("mesh-stage")
        got = self._rows(sink)
        assert set(got) == set(expected)
        for k in expected:
            assert got[k] == pytest.approx(expected[k], rel=1e-3), k

    def test_crash_restore(self, tmp_path):
        ckpt = str(tmp_path / "ckpts")
        env0 = _env(0)
        s0 = CollectSink()
        self._pipeline(env0, s0)
        env0.execute("clean")
        expected = self._rows(s0)

        conf = {"execution.stage-mesh-devices": 4,
                "state.checkpoints.dir": ckpt,
                "execution.checkpointing.every-n-source-batches": 5}
        env1 = _env(2, conf)
        s1 = CollectSink()
        src = DataGenSource(total_records=30_000, num_keys=300,
                            events_per_second_of_eventtime=10_000, seed=5)
        from tests.test_checkpointing import FailingMap

        (env1.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(0))
           .map(FailingMap(20_000), name="failmap")
           .key_by("key").window(TumblingEventTimeWindows.of(1000))
           .sum("value").sink_to(s1))
        with pytest.raises(RuntimeError, match="injected failure"):
            env1.execute("crashing")

        env2 = _env(2, conf)
        s2 = CollectSink()
        src2 = DataGenSource(total_records=30_000, num_keys=300,
                             events_per_second_of_eventtime=10_000, seed=5)
        (env2.from_source(
            src2, WatermarkStrategy.for_bounded_out_of_orderness(0))
           .map(lambda b: b, name="failmap")
           .key_by("key").window(TumblingEventTimeWindows.of(1000))
           .sum("value").sink_to(s2))
        env2.execute("restored", restore_from=ckpt)
        got = self._rows(s1)
        got.update(self._rows(s2))
        assert set(got) == set(expected)
        for k in expected:
            assert got[k] == pytest.approx(expected[k], rel=1e-3), k


class TestBidSourceSplits:
    def test_parallel_splits_union_to_single_stream(self):
        from flink_tpu.benchmarks.nexmark import BidSource

        def collect(par):
            rows = []
            for i in range(par):
                s = BidSource(total_records=10_000, num_auctions=50,
                              events_per_second_of_eventtime=10_000)
                s.open(i, par)
                while True:
                    b = s.poll_batch(3_000)
                    if b is None:
                        break
                    rows += list(zip(
                        b.columns["auction"].tolist(),
                        np.round(b.columns["price"], 4).tolist(),
                        b.timestamps.tolist()))
            return sorted(rows)

        assert collect(1) == collect(2) == collect(4)
