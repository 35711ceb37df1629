"""Flight recorder: the always-on span plane + its exporters.

reference test model: the reference's metric/trace reporting tests
(SURVEY §5 — spans, latency markers, the webmonitor), applied to the
per-batch recorder of flink_tpu.observe.flight_recorder.
"""

import threading
import time

import numpy as np
import pytest

from flink_tpu.observe import KNOWN_SPAN_KINDS
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.observe.export import (
    chrome_trace,
    register_flight_metrics,
    validate_trace_schema,
)
from flink_tpu.observe.flight_recorder import FlightRecorder


@pytest.fixture()
def rec():
    r = flight.recorder()
    r.clear()
    return r


class TestRecorder:
    def test_span_records_duration_and_attribution(self, rec):
        flight.set_job("t-job")
        flight.set_batch(41)
        with flight.span("batch.ingest", batch=42):
            time.sleep(0.002)
        got = [r for r in rec.snapshot() if r.kind == "batch.ingest"]
        assert got, "span not recorded"
        r = got[-1]
        assert r.job == "t-job"
        assert r.batch_id == 42
        assert not r.instant
        assert r.duration_s >= 0.002

    def test_ambient_context_inherited_by_nested_spans(self, rec):
        flight.set_job("ambient-job")
        flight.set_batch(7)
        flight.set_watermark(1234)
        with flight.span("fire.dispatch"):
            flight.instant("watchdog.miss", shard=3)
        miss = [r for r in rec.snapshot()
                if r.kind == "watchdog.miss"][-1]
        assert miss.job == "ambient-job"
        assert miss.batch_id == 7
        assert miss.watermark == 1234
        assert miss.shard == 3
        assert miss.instant

    def test_unknown_kind_raises(self, rec):
        with pytest.raises(KeyError):
            rec.span("no.such.kind")
        with pytest.raises(KeyError):
            rec.instant("no.such.kind")

    def test_disabled_region_records_nothing(self, rec):
        before = len(rec.snapshot())
        with flight.disabled():
            with flight.span("batch.ingest"):
                pass
            flight.instant("watchdog.miss")
        assert len(rec.snapshot()) == before

    def test_drop_oldest_bounds_memory(self):
        # private instance: fill one thread's ring past capacity — the
        # ring wraps (drop-oldest), never grows
        r = FlightRecorder(KNOWN_SPAN_KINDS)
        cap = r._ring().mask + 1
        for _ in range(cap + 100):
            r.instant("d2h.transfer")
        assert r.dropped() == 100
        assert len(r.snapshot()) == cap

    def test_kind_totals_aggregates(self, rec):
        for _ in range(5):
            with flight.span("serving.lookup"):
                pass
        stats = rec.kind_totals()["serving.lookup"]
        assert stats["count"] >= 5
        assert stats["total_s"] >= 0
        assert stats["p99_ms"] >= stats["p50_ms"] >= 0

    def test_span_contexts_are_pooled(self, rec):
        # entering/exiting spans reuses the per-thread pool — the hot
        # path must not grow an object per span
        ring = rec._ring()
        with flight.span("emit"):
            pass
        n = len(ring.pool)
        for _ in range(50):
            with flight.span("emit"):
                pass
        assert len(ring.pool) == n

    def test_registry_matches_recorder(self, rec):
        assert rec.kinds == KNOWN_SPAN_KINDS
        assert len(set(KNOWN_SPAN_KINDS)) == len(KNOWN_SPAN_KINDS)


class TestChromeExport:
    def test_pid_per_job_tid_per_shard(self, rec):
        with flight.span("batch.ingest", job="job-a", batch=1):
            pass
        with flight.span("fire.shard", job="job-b", shard=3):
            pass
        trace = chrome_trace(
            [r for r in rec.snapshot()
             if r.job in ("job-a", "job-b")], anchor=rec.anchor)
        evs = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        pids = {e["pid"] for e in evs}
        assert len(pids) == 2, "one pid per job"
        shard_ev = next(e for e in evs if e["name"] == "fire.shard")
        assert shard_ev["tid"] == 4  # shard 3 -> tid 4 (0 is host)
        names = {(e["pid"], e["args"]["name"])
                 for e in trace["traceEvents"] if e["ph"] == "M"
                 and e["name"] == "process_name"}
        assert {n for _, n in names} == {"job-a", "job-b"}
        thread_names = {e["args"]["name"]
                        for e in trace["traceEvents"]
                        if e["ph"] == "M" and e["name"] == "thread_name"}
        assert "shard-3" in thread_names
        # shard-less spans ride PER-THREAD host tracks (concurrent
        # threads must not interleave complete events on one tid)
        assert any(n.startswith("host:") for n in thread_names)

    def test_instants_are_instant_events(self, rec):
        flight.instant("chaos.inject", job="job-i", shard=1)
        trace = chrome_trace(
            [r for r in rec.snapshot() if r.job == "job-i"])
        ev = next(e for e in trace["traceEvents"]
                  if e["name"] == "chaos.inject")
        assert ev["ph"] == "i"
        assert ev["args"]["shard"] == 1

    def test_schema_validation_catches_drift(self):
        good = {"traceEvents": [
            {"ph": "X", "name": "batch.ingest", "dur": 5, "ts": 0,
             "pid": 1, "tid": 0, "args": {"batch": 3}}]}
        assert validate_trace_schema(good, KNOWN_SPAN_KINDS) == []
        bad_kind = {"traceEvents": [
            {"ph": "X", "name": "not.registered", "dur": 5, "ts": 0,
             "pid": 1, "tid": 0, "args": {}}]}
        assert validate_trace_schema(bad_kind, KNOWN_SPAN_KINDS)
        no_batch = {"traceEvents": [
            {"ph": "X", "name": "batch.ingest", "dur": 5, "ts": 0,
             "pid": 1, "tid": 0, "args": {"batch": -1}}]}
        assert validate_trace_schema(no_batch, KNOWN_SPAN_KINDS)


def _nested(rec):
    # host prep excludes device and fence: they are children
    with flight.span("batch.ingest") as outer:
        with flight.span("prep.stage"):
            time.sleep(0.001)
        with flight.span("device.dispatch"):
            with flight.span("fire.harvest"):
                time.sleep(0.001)
        with flight.span("device.fence_wait"):
            time.sleep(0.001)
        time.sleep(0.001)
    return outer


def _external_child(rec):
    with flight.span("batch.ingest") as outer:
        time.sleep(0.003)
        # timed by the caller, recorded after the fact: [now - d, now]
        flight.instant("device.dispatch", duration_s=0.002)
    return outer


def _external_child_clipped(rec):
    with flight.span("batch.ingest") as outer:
        # reported longer than the enclosing span has been open (a
        # compile that began before it): only the overlap is a child
        flight.instant("xla.compile", duration_s=5.0)
        time.sleep(0.001)
    return outer


def _child_on_another_thread(rec):
    def other():
        with flight.span("fire.harvest"):
            time.sleep(0.002)

    with flight.span("batch.ingest") as outer:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    return outer


def _nothing(rec):
    return None


class TestSelfTime:
    """``self_s`` = a span's duration minus what its children on the
    same thread covered — what the bench drivers' "host prep =
    batch.ingest - device - fence" subtraction used to approximate."""

    @pytest.mark.parametrize("case", [
        _nested, _external_child, _external_child_clipped,
        _child_on_another_thread, _nothing], ids=lambda f: f.__name__)
    def test_self_is_total_minus_children_on_the_thread(self, rec, case):
        outer = case(rec)
        kt = rec.kind_totals()
        if outer is None:
            # an empty recorder aggregates to nothing, not to zeros
            assert kt == {}
            return
        recs = rec.snapshot()
        ingest = next(r for r in recs if r.kind == "batch.ingest")
        assert ingest.duration_s == pytest.approx(outer.duration_s)
        me = threading.current_thread().name
        covered = sum(
            min(r.duration_s, r.t1 - ingest.t0) for r in recs
            if r.parent == "batch.ingest" and r.thread == me)
        got = kt["batch.ingest"]
        assert got["total_s"] == pytest.approx(ingest.duration_s)
        assert got["self_s"] == pytest.approx(
            ingest.duration_s - covered, abs=1e-9)
        assert 0.0 < got["self_s"] <= got["total_s"]
        if case is _nested:
            # grandchildren are the child's, not the parent's
            assert kt["device.dispatch"]["self_s"] == pytest.approx(
                kt["device.dispatch"]["total_s"]
                - kt["fire.harvest"]["total_s"], abs=1e-9)
            assert covered == pytest.approx(
                kt["prep.stage"]["total_s"]
                + kt["device.dispatch"]["total_s"]
                + kt["device.fence_wait"]["total_s"], abs=1e-9)
        if case is _external_child:
            assert covered == pytest.approx(0.002)
            assert kt["device.dispatch"]["self_s"] == pytest.approx(0.002)
        if case is _external_child_clipped:
            assert covered < 0.1
        if case is _child_on_another_thread:
            assert covered == 0.0
            assert got["self_s"] == pytest.approx(got["total_s"])
            assert kt["fire.harvest"]["total_s"] >= 0.002

    def test_parent_in_records_and_chrome_trace(self, rec):
        with flight.span("op.watermark", job="p-job"):
            with flight.span("fire.dispatch", job="p-job"):
                flight.instant("d2h.transfer", job="p-job")
        by_kind = {r.kind: r for r in rec.snapshot() if r.job == "p-job"}
        assert by_kind["op.watermark"].parent is None
        assert by_kind["fire.dispatch"].parent == "op.watermark"
        assert by_kind["d2h.transfer"].parent == "fire.dispatch"
        trace = chrome_trace(list(by_kind.values()))
        args = {e["name"]: e["args"] for e in trace["traceEvents"]
                if e["ph"] != "M"}
        assert "parent" not in args["op.watermark"]
        assert args["fire.dispatch"]["parent"] == "op.watermark"
        assert args["d2h.transfer"]["parent"] == "fire.dispatch"

    def test_work_summed_per_kind(self, rec):
        for n in (3, 4):
            with flight.span("sink.write") as s:
                s.work = n
        flight.instant("serving.cache_hit", work=5)
        with flight.span("prep.stage"):
            pass
        kt = rec.kind_totals()
        assert kt["sink.write"]["work"] == 7
        assert kt["serving.cache_hit"]["work"] == 5
        assert kt["prep.stage"]["work"] == 0
        works = sorted(r.work for r in rec.snapshot()
                       if r.kind == "sink.write")
        assert works == [3, 4]
        trace = chrome_trace(rec.snapshot())
        assert sorted(e["args"]["work"] for e in trace["traceEvents"]
                      if e.get("name") == "sink.write") == [3, 4]

    def test_span_that_raises_still_pops_the_stack(self, rec):
        with flight.span("op.process"):
            with pytest.raises(RuntimeError):
                with flight.span("batch.ingest"):
                    raise RuntimeError("boom")
            # the failed span is closed: the next one is op.process's
            # child, and nothing is left open underneath it
            with flight.span("sink.write"):
                pass
        assert rec._ring().open is None
        by_kind = {r.kind: r for r in rec.snapshot()}
        assert by_kind["batch.ingest"].parent == "op.process"
        assert by_kind["sink.write"].parent == "op.process"
        kt = rec.kind_totals()
        assert kt["op.process"]["self_s"] == pytest.approx(
            kt["op.process"]["total_s"] - kt["batch.ingest"]["total_s"]
            - kt["sink.write"]["total_s"], abs=1e-9)

    def test_page_faults_summed_for_spans_that_ask(self, rec):
        import mmap

        def touch_fresh_pages():
            # a new anonymous mapping: every page written faults once
            with mmap.mmap(-1, 1 << 22) as m:
                m.write(b"\1" * (1 << 22))

        with flight.span("slice.retire", faults=True):
            touch_fresh_pages()
        with flight.span("fire.shard"):
            touch_fresh_pages()
        kt = rec.kind_totals()
        assert kt["slice.retire"]["minor_faults"] >= 2
        assert kt["slice.retire"]["major_faults"] >= 0
        assert kt["fire.shard"]["minor_faults"] == 0

    def test_recorder_off_spans_swallow_work_and_timed_ones_time(self, rec):
        with flight.disabled():
            with flight.span("sink.write") as s:
                s.work = 9
            assert s.work == 0 and s.duration_s == 0.0
            with flight.span("op.process", timed=True) as t:
                time.sleep(0.001)
            assert t.duration_s >= 0.001
        assert rec.kind_totals() == {}

    def test_lifecycle_spans_mirrored_into_a_profiler_session(
            self, rec, tmp_path):
        import glob

        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.process_batch"):
                with flight.span("batch.ingest"):
                    with flight.span("prep.resolve"):
                        time.sleep(0.001)
            with flight.span("checkpoint.write"):   # it stops the loop
                pass
            with flight.span("reshard.handoff"):    # control plane: not
                pass
        finally:
            jax.profiler.stop_trace()
        with flight.span("batch.ingest"):   # no session: not mirrored
            pass
        (path,) = glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        rows = {}
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("flink.", "bench.")):
                        rows.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        assert sorted(rows) == ["bench.process_batch",
                                "flink.batch.ingest",
                                "flink.checkpoint.write",
                                "flink.prep.resolve"]
        (bench,), (ingest,), (resolve,) = (
            rows["bench.process_batch"], rows["flink.batch.ingest"],
            rows["flink.prep.resolve"])
        # one clock: the program's spans nest inside the benchmark's
        assert bench[0] <= ingest[0] <= resolve[0]
        assert resolve[1] <= ingest[1] <= bench[1]


class TestMetricExport:
    def test_flight_group_gauges_render(self, rec):
        from flink_tpu.metrics import MetricRegistry, PrometheusReporter

        with flight.span("checkpoint.write"):
            pass
        registry = MetricRegistry()
        register_flight_metrics(
            registry.root_group("job", "x"), rec)
        snap = registry.snapshot()
        assert snap["job.x.flight.checkpoint_write_count"] >= 1
        assert "job.x.flight.records_dropped" in snap
        rep = PrometheusReporter()
        rep.open(registry)
        text = rep.render()
        assert "checkpoint_write_p99_ms" in text


class TestProbeCorrelation:
    def test_compile_event_lands_in_timeline(self, rec):
        from flink_tpu.observe import recompile_sentinel as rs

        flight.install_probes()
        before = rec.kind_totals().get("xla.compile",
                                       {}).get("count", 0)
        # drive the monitoring listener directly: one "real" backend
        # compile of 12.5 ms
        rs._on_duration_event(
            "/jax/core/compile/backend_compile_duration", 0.0125)
        got = [r for r in rec.snapshot() if r.kind == "xla.compile"]
        assert got, "compile not correlated into the timeline"
        assert got[-1].duration_s == pytest.approx(0.0125, abs=1e-6)
        after = rec.kind_totals()["xla.compile"]["count"]
        assert after == before + 1

    def test_watchdog_miss_instant(self, rec):
        from flink_tpu.runtime.watchdog import DeviceWatchdog

        clock = [0.0]
        wd = DeviceWatchdog(num_shards=2, deadline_ms=1.0,
                            clock=lambda: clock[0])
        with wd.section("probe", shard=1):
            clock[0] += 0.5  # 500 ms >> the 1 ms deadline
        misses = [r for r in rec.snapshot()
                  if r.kind == "watchdog.miss"]
        assert misses and misses[-1].shard == 1

    def test_chaos_injection_instant(self, rec):
        import flink_tpu.chaos as chaos

        plan = chaos.FaultPlan(rules=[
            chaos.FaultRule("serving.lookup", nth=1)])
        with chaos.chaos_active(plan, seed=7):
            with pytest.raises(chaos.InjectedFault):
                chaos.fault_point("serving.lookup", shard=2)
        inj = [r for r in rec.snapshot() if r.kind == "chaos.inject"]
        assert inj and inj[-1].shard == 2


class TestExecutorIntegration:
    def test_job_spans_latency_markers_and_flight_metrics(self, tmp_path):
        from flink_tpu.core.config import Configuration
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        rec = flight.recorder()
        rec.clear()
        conf = Configuration({
            "state.checkpoints.dir": str(tmp_path / "ckpt"),
            "execution.checkpointing.every-n-source-batches": 1,
        })
        env = StreamExecutionEnvironment(conf)
        sink = CollectSink()
        rows = [{"k": i % 3, "v": 1, "ts": i * 100} for i in range(200)]
        env.from_collection(rows, timestamp_field="ts") \
            .key_by("k").window(TumblingEventTimeWindows.of(1000)) \
            .sum("v").sink_to(sink)
        result = env.execute("flight-job")
        kinds = {r.kind for r in rec.snapshot()
                 if r.job == "flight-job"}
        # executor lifecycle spans, attributed to THIS job
        assert {"op.process", "op.watermark", "emit",
                "checkpoint.write"} <= kinds
        snap = result.registry.snapshot()
        # latency markers: per-operator histogram + watermark lag
        marker_keys = [k for k in snap
                       if k.endswith("latency.markerLatencyMs.count")]
        assert marker_keys and any(snap[k] > 0 for k in marker_keys)
        assert any(k.endswith("latency.watermarkLagMs") for k in snap)
        # per-span-kind aggregates at the REGISTRY ROOT: the recorder
        # is process-global, so the rollups are not claimed by one job
        assert snap["flight.op_process_count"] > 0

    def test_restore_records_checkpoint_restore_span(self, tmp_path):
        from flink_tpu.core.config import Configuration
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        ckpt = tmp_path / "ckpt"
        conf = Configuration({
            "state.checkpoints.dir": str(ckpt),
            "execution.checkpointing.every-n-source-batches": 1,
        })

        def build(env):
            sink = CollectSink()
            rows = [{"k": i % 3, "v": 1, "ts": i * 100}
                    for i in range(100)]
            env.from_collection(rows, timestamp_field="ts") \
                .key_by("k").window(TumblingEventTimeWindows.of(1000)) \
                .sum("v").sink_to(sink)

        env = StreamExecutionEnvironment(conf)
        build(env)
        env.execute("restore-a")
        import os

        chks = sorted(p for p in os.listdir(ckpt)
                      if p.startswith("chk-"))
        rec = flight.recorder()
        rec.clear()
        env2 = StreamExecutionEnvironment(conf)
        build(env2)
        result = env2.execute("restore-b",
                              restore_from=str(ckpt / chks[-1]))
        assert [r for r in rec.snapshot()
                if r.kind == "checkpoint.restore"]
        assert result.traces.spans("recovery")


class TestShardedCheckpointSpans:
    def test_write_and_restore_report_spans(self, tmp_path):
        from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage
        from flink_tpu.metrics.traces import TraceCollector

        tc = TraceCollector()
        storage = ShardedCheckpointStorage(str(tmp_path), traces=tc)
        units = {
            (0, 63): {"table": {"key_id": np.arange(3)}},
            (64, 127): {"table": {"key_id": np.arange(2)}},
        }
        storage.write_checkpoint(1, "job", units,
                                 {(0, 63): 10, (64, 127): 10})
        writes = tc.spans("checkpoint")
        assert writes and writes[-1].attributes["units"] == 2
        assert writes[-1].attributes["checkpointId"] == 1
        found = storage.latest_units_for_groups(range(0, 40))
        assert found is not None and found[0] == 1
        restores = tc.spans("recovery")
        assert restores
        assert restores[-1].attributes["checkpointId"] == 1
        assert restores[-1].duration_ms >= 0

    def test_default_collector_used_when_unthreaded(self, tmp_path):
        from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage
        from flink_tpu.metrics.traces import default_collector

        storage = ShardedCheckpointStorage(str(tmp_path))
        before = len(default_collector().spans("checkpoint"))
        storage.write_checkpoint(
            1, "job", {(0, 7): {"table": {}}}, {(0, 7): 0})
        assert len(default_collector().spans("checkpoint")) == before + 1


def _mesh_session_pass(mesh, host_topology=None, steps=6):
    """A tiny steady pass of the mesh session engine, built here: fixed
    batch shape, one watermark advance and harvest per batch, the
    end-of-input flush last. Returns the rows fired."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    # a device-slot budget arms the paged layout, whose fire path is
    # the one with per-shard attribution
    eng = MeshSessionEngine(16_000, SumAggregate("v"), mesh,
                            capacity_per_shard=1 << 14,
                            max_device_slots=1 << 14,
                            host_topology=host_topology)
    rng = np.random.default_rng(5)
    fired, t, n = 0, 0, 4096
    for _ in range(steps):
        ts = t + np.arange(n, dtype=np.int64) * 8
        eng.process_batch(RecordBatch({
            KEY_ID_FIELD: rng.integers(0, 20_000, n).astype(np.int64),
            "v": np.ones(n, dtype=np.float32), TIMESTAMP_FIELD: ts}))
        t = int(ts[-1]) + 1
        for pf in eng.on_watermark(t - 16_000, async_ok=True):
            fired += len(pf.harvest())
    for pf in eng.on_watermark(1 << 60, async_ok=True):
        fired += len(pf.harvest())
    return fired


class TestMeshSessionCapture:
    """The capture of a steady pass of the mesh session engine: what the
    exporters and the recorder's call sites must agree on, and that a
    warm pass compiles nothing."""

    @pytest.fixture(scope="class")
    def capture(self):
        from flink_tpu.parallel.mesh import make_mesh

        flight.install_probes()
        mesh = make_mesh(4)
        rec = flight.recorder()
        flight.set_job("capture-job")
        _mesh_session_pass(mesh)  # warm: every shape compiles here
        rec.clear()
        fired = _mesh_session_pass(mesh)  # fresh engine, warm programs
        totals = rec.kind_totals()
        trace = chrome_trace(rec.snapshot(), anchor=rec.anchor)
        rec.clear()
        return fired, totals, trace

    @pytest.mark.parametrize("claim", [
        "schema", "lifecycle", "shard", "no_compile"])
    def test_steady_pass(self, capture, claim):
        fired, totals, trace = capture
        events = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        assert fired > 0 and len(events) >= 50  # not a vacuous capture
        if claim == "schema":
            # every event a registered kind, batch.ingest with its
            # batch, fire.dispatch with its watermark
            assert validate_trace_schema(trace, KNOWN_SPAN_KINDS) == []
            assert any(e["name"] == "batch.ingest" for e in events)
            assert any(e["name"] == "fire.dispatch" for e in events)
        elif claim == "lifecycle":
            assert {"batch.ingest", "fire.dispatch", "fire.harvest",
                    "device.dispatch"} <= set(totals)
        elif claim == "shard":
            shards = {e["args"]["shard"] for e in events
                      if e["name"] == "fire.shard"}
            assert shards and min(shards) >= 0
        else:
            assert totals.get("xla.compile", {}).get("count", 0) == 0

    def test_two_level_exchange_stages_are_distinct_spans(self):
        """With the (2 x P/2) topology armed the ICI route and the DCN
        hop are two span kinds, each with time in it."""
        from flink_tpu.parallel.mesh import HostTopology, make_mesh

        rec = flight.recorder()
        rec.clear()
        _mesh_session_pass(make_mesh(4), HostTopology(2, 2), steps=3)
        totals = rec.kind_totals()
        for kind in ("exchange.stage1", "exchange.stage2"):
            assert totals[kind]["count"] > 0
            assert totals[kind]["total_s"] > 0


class TestServingCapture:
    def test_lookups_attribute_to_job_and_generation(self):
        """A tenant's lookups leave serving.lookup spans naming the job
        with the replica generation in the batch field, and boundary
        publishes leave serving.replica_publish spans."""
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.runtime.watermarks import WatermarkStrategy
        from flink_tpu.tenancy.session_cluster import SessionCluster
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        rec = flight.recorder()
        rec.clear()
        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 4096,
            "parallelism.default": 4,
        }))
        (env.add_source(
            DataGenSource(total_records=32768, num_keys=128,
                          events_per_second_of_eventtime=50_000, seed=7),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
            .key_by("key")
            .window(TumblingEventTimeWindows.of(60_000))
            .sum("value").sink_to(CollectSink()))
        cluster = SessionCluster(quantum_records=4096)
        cluster.submit(env, "trace-job")
        rounds = 0
        while cluster.step_round() and rounds < 8:
            rounds += 1
            try:
                # fresh keys each round miss the cache and reach the
                # worker flush, which is where the span is
                cluster.lookup_batch(
                    "trace-job", "window_agg(SumAggregate)",
                    list(range(16)) + list(range(rounds * 64,
                                                 rounds * 64 + 32)))
            except RuntimeError:
                pass  # rounds before the first publish
        cluster.run(timeout_s=120)
        cluster.serving.shutdown_workers()
        spans = rec.snapshot()
        assert [s for s in spans if s.kind == "serving.replica_publish"]
        assert [s for s in spans if s.kind == "serving.lookup"
                and s.job == "trace-job" and s.batch_id >= 1]
