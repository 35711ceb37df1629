"""NEXmark Query 8 as the benchmark runs it (``benchmark/jobs/q8.py``): the
generator's ratios, the job through ``env.execute()`` against its plain
reference at a tiny size, the control, and faults planted in the
program's own classes that the comparison has to catch."""

import copy

import numpy as np
import pytest

from benchmark import control
from benchmark.harness import manifest, runner
from benchmark.harness.traffic import TimedSource
from benchmark.jobs import q8 as job
from flink_tpu.core.records import RecordBatch
from flink_tpu.joins.engine import MeshWindowJoinEngine
from flink_tpu.joins.side_table import WindowPages
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.windowing.assigners import TumblingEventTimeWindows
from flink_tpu.windowing.bookkeeping import SliceBookkeeper

MAN = manifest.manifest()
CONFIG = "nexmark-q8-new-users"
SEED = 2_147_483_659
#: the tiny cut's rows: six windows of 40,000
EVENTS = 240_000


def config(**job_options):
    cfg = copy.deepcopy(manifest.config(MAN, CONFIG))
    cfg["options"].update(job.TINY["options"])
    cfg["job_options"].update(job.TINY["job_options"])
    cfg["job_options"].update(job_options)
    return cfg


def run(cfg, seed=SEED, events=EVENTS):
    """The job over a bounded stream; ``(sink columns, rows handed over)``."""
    o = cfg["job_options"]
    source = TimedSource(job.make_generator(seed, o), {"mode": "backlog"},
                         job.boundary_events(o), min_events=events)
    sink, *_ = runner.execute_job(job, cfg, source)
    return sink.result(), source.log.events


def verdict(cfg, seed=SEED):
    got, n = run(cfg, seed)
    return job.compare(got, job.reference_rows(seed, n, cfg["job_options"]),
                       cfg["job_options"])


# ------------------------------------------------------ (1) the generator


def test_the_generators_ratios_are_the_sources():
    o = manifest.config(MAN, CONFIG)["job_options"]
    first, n = 40_000_000, 2_000_000
    cols, ts = job.make_generator(SEED, o)(first, n)
    rows = np.arange(first, first + n)
    epoch = rows // 4
    person = cols["kind"] == 0
    # one person and three auctions an epoch of 50 events: 1/50 and 3/50
    assert person.mean() == pytest.approx(1 / 4)
    assert (ts == (epoch * 50 + rows % 4) * 1000 // o["event_rate"]).all()
    assert (cols["id"][person] == 1000 + epoch[person]).all()
    seller = cols["seller"][~person] - 1000
    e = epoch[~person]
    hot = seller == (e // 100) * 100
    assert hot.mean() == pytest.approx(0.75 + 0.25 * 1 / 1010, abs=0.003)
    assert (e - seller).max() <= 999 and (seller - e).max() <= 10
    assert (e[~hot] - seller[~hot]).max() == 999
    reserve = cols["reserve"][~person]
    assert reserve.min() >= 200 and reserve.max() < 2 * 10 ** 8
    names = cols["name"][person]
    assert names.min() >= 0 and names.max() < 99
    assert len(np.unique(names)) == 99


def test_the_stream_is_in_event_time_order_and_windows_are_row_ranges():
    o = manifest.config(MAN, CONFIG)["job_options"]
    assert job.boundary_events(o) == 8_000_000
    assert job.first_index_with_ts(20_000, o) == 16_000_000
    cols, ts = job.make_generator(SEED, o)(7_999_990, 20)
    assert (np.diff(ts) >= 0).all()
    assert ts[9] < 10_000 <= ts[10]


# ------------------------------------------- (2) the job against its reference


def test_a_tiny_run_equals_its_reference():
    from flink_tpu.observe import KNOWN_SPAN_KINDS
    from flink_tpu.observe.export import chrome_trace, validate_trace_schema

    rec = flight.recorder()
    rec.clear()
    cfg = config()
    got, n = run(cfg)
    want = job.reference_rows(SEED, n, cfg["job_options"])
    v = job.compare(got, want, cfg["job_options"])
    assert v["numbers"]["rows_wrong"]["value"] == 0 and v["failed"] == 0
    assert v["attempted"] == n // job.boundary_events(cfg["job_options"])
    # nearly every auction finds its seller in its window
    assert len(want["id"]) > 0.98 * 3 / 4 * n
    kt = rec.kind_totals()
    for kind in ("join.index", "join.match", "join.rows", "join.pairs",
                 "join.retire"):
        assert kt[kind]["count"] > 0, kind
    # every row indexed once, every row of a fired window counted and
    # dropped once, every pair counted once
    assert kt["join.index"]["work"] == n
    assert kt["join.rows"]["work"] == kt["join.retire"]["work"] == n
    assert kt["join.pairs"]["work"] == len(want["id"])
    assert kt["join.match"]["work"] >= kt["join.pairs"]["work"]
    # each kind inside the span the metrics' readers take it from
    records = rec.snapshot()

    def inside(kind, outer):
        spans = [r for r in records if r.kind == outer]
        for r in (r for r in records if r.kind == kind):
            assert any(s.thread == r.thread and s.t0 <= r.t0
                       and r.t0 + r.duration_s <= s.t0 + s.duration_s
                       for s in spans), (kind, outer)

    inside("join.index", "batch.ingest")
    inside("join.match", "fire.dispatch")
    inside("join.rows", "join.match")
    inside("join.pairs", "fire.harvest")
    assert validate_trace_schema(
        chrome_trace(records, anchor=rec.anchor), KNOWN_SPAN_KINDS) == []
    rec.clear()


def test_a_tiny_run_with_rows_staged_behind_each_match_is_correct(
        monkeypatch):
    """Each fire reads not ready for its first three looks (a test double
    on ``PendingFire.ready``), so the batches that arrive behind it are
    staged on the host whatever the CPU's speed: the run is exact, every
    staged row was indexed, and the stage took rows."""
    from flink_tpu.runtime.pending import PendingFire

    real, looks = PendingFire.ready, {}

    def ready(pf):
        looks[pf] = looks.get(pf, 0) + 1
        return looks[pf] > 3 and real(pf)

    monkeypatch.setattr(PendingFire, "ready", ready)
    rec = flight.recorder()
    rec.clear()
    v = verdict(config())
    assert v["numbers"]["rows_wrong"]["value"] == 0 and v["failed"] == 0
    kt = rec.kind_totals()
    assert 0 < kt["join.staged"]["work"] < kt["join.index"]["work"]
    rec.clear()


def test_a_window_aggregate_pushes_and_awaits_one_fence_per_batch(
        monkeypatch):
    """The aggregates' side of ``AsyncFireOperator`` is the join's to
    leave alone: one fence pushed per batch, one wait per push past the
    dispatch-ahead bound, and no hook of the join's on their path."""
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.joins.operators import DeviceWindowJoinOperator
    from flink_tpu.runtime.operators import AsyncFireOperator, \
        WindowAggOperator
    from flink_tpu.runtime.watermarks import WatermarkStrategy

    for name in ("_push_fence", "_await_fences", "poll_pending_output",
                 "_harvest_one"):
        assert getattr(WindowAggOperator, name) \
            is getattr(AsyncFireOperator, name), name
    assert DeviceWindowJoinOperator.poll_pending_output \
        is not AsyncFireOperator.poll_pending_output
    counts = {"push": 0, "await": 0, "batches": 0}
    push, wait = AsyncFireOperator._push_fence, \
        AsyncFireOperator._await_fences
    process = WindowAggOperator.process_batch

    def counted_push(self, fence):
        counts["push"] += fence is not None
        push(self, fence)

    def counted_await(self):
        counts["await"] += 1
        wait(self)

    def counted_process(self, batch, input_index=0):
        counts["batches"] += 1
        return process(self, batch, input_index)

    monkeypatch.setattr(AsyncFireOperator, "_push_fence", counted_push)
    monkeypatch.setattr(AsyncFireOperator, "_await_fences", counted_await)
    monkeypatch.setattr(WindowAggOperator, "process_batch", counted_process)
    env = StreamExecutionEnvironment(Configuration(
        {"execution.micro-batch.size": 8192}))
    sink = CollectSink()
    env.add_source(DataGenSource(total_records=100_000, num_keys=500,
                                 events_per_second_of_eventtime=100_000),
                   WatermarkStrategy.for_bounded_out_of_orderness(0)) \
        .key_by("key").window(TumblingEventTimeWindows.of(200)) \
        .sum("value").sink_to(sink)
    env.execute("fences")
    assert counts["batches"] == 13 and len(sink.rows()) > 0
    assert counts["push"] == counts["batches"]
    assert counts["await"] == counts["push"] - 4


def test_host_mode_runs_the_numpy_operator_to_the_same_rows():
    from benchmark.harness.traffic import StampingSink
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.runtime.join_operators import WindowJoinOperator

    cfg = config()
    o = cfg["job_options"]
    env = StreamExecutionEnvironment(Configuration(
        dict(cfg["options"], **{"join.mode": "host"})))
    source = TimedSource(job.make_generator(SEED, o), {"mode": "backlog"},
                         job.boundary_events(o), min_events=EVENTS)
    pairs, t = job.build(env, source, o)
    assert isinstance(t.operator_factory(), WindowJoinOperator)
    sink = StampingSink(job.SINK_COLUMNS)
    pairs.sink_to(sink)
    env.execute("q8-host")
    n = source.log.events
    assert job.compare(sink.result(), job.reference_rows(SEED, n, o),
                       o)["failed"] == 0


def test_the_control_fails_through_benchmark_control():
    # at the cell's own size: two micro-batches' rows, one of them lost
    assert control.main(["--workload", "q8-backlog", "--events", "262144",
                         "--seeds", "1", "2"]) == 0


# --------------------------------------- (3) faults planted in the program


def _pair_twice(monkeypatch):
    build = MeshWindowJoinEngine._output

    def output(self, window_end, words):
        b = build(self, window_end, words)
        return RecordBatch.concat([b, b.slice(0, 1)])

    monkeypatch.setattr(MeshWindowJoinEngine, "_output", output)


def _boundary_row_in_the_next_window(monkeypatch):
    assign = TumblingEventTimeWindows.assign_slice_ends

    def assign_slice_ends(self, timestamps):
        ends = assign(self, timestamps)
        last_ms = np.asarray(timestamps) == ends - 1
        return np.where(last_ms, ends + self.size, ends)

    monkeypatch.setattr(TumblingEventTimeWindows, "assign_slice_ends",
                        assign_slice_ends)


def _fired_rows_left_live(monkeypatch):
    """The retire skipped, and a window read as every slice still live
    up to its end: the last window's rows join the next one."""
    window = WindowPages.window
    monkeypatch.setattr(SliceBookkeeper, "expired_slices",
                        lambda self, watermark: [])

    def everything_live(self, shard, slice_ends):
        live = [se for se in self.slices[shard] if se <= max(slice_ends)]
        return window(self, shard, sorted(live))

    monkeypatch.setattr(WindowPages, "window", everything_live)


def _hot_key_cut_to_a_band(monkeypatch, band=64):
    build = MeshWindowJoinEngine._output

    def output(self, window_end, words):
        b = build(self, window_end, words)
        key = b["id"]
        order = np.argsort(key, kind="stable")
        first = np.searchsorted(key[order], key[order])
        keep = np.zeros(len(b), dtype=bool)
        keep[order] = np.arange(len(b)) - first < band
        return b.filter(keep)

    monkeypatch.setattr(MeshWindowJoinEngine, "_output", output)


@pytest.mark.parametrize("plant", [
    _pair_twice, _boundary_row_in_the_next_window, _fired_rows_left_live,
    _hot_key_cut_to_a_band],
    ids=["pair-twice", "boundary-row-next-window", "fired-rows-left-live",
         "hot-key-band"])
def test_a_planted_fault_is_not_correct(plant, monkeypatch):
    plant(monkeypatch)
    v = verdict(config())
    assert v["failed"] > 0
    assert v["numbers"]["rows_wrong"]["value"] > 0
