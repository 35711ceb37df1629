"""The waiting as the flight recorder sees it: who stands blocked at the
source hand-over (``loop.wait_source`` / ``source.wait_loop`` /
``source.queue_wait``), how long a fire is out of the host's hands and how
long it may have lain landed (``fire.in_flight`` / ``fire.poll_gap``), and
a result window's path from its closing batch to the sink
(``window.emit``) — every record of that path under the window's own
watermark. Counts, orders and identities on the CPU; a duration belongs to
the chip."""

import time
from collections import defaultdict

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.cluster.local_executor import _SourcePump
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.observe import KNOWN_SPAN_KINDS
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.observe.export import chrome_trace, validate_trace_schema
from flink_tpu.runtime.operators import WindowAggOperator
from flink_tpu.runtime.pending import PendingFire
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.aggregates import CountAggregate
from flink_tpu.windowing.assigners import TumblingEventTimeWindows

BATCH = 512
BATCHES = 8
KEYS = 40
WINDOW_MS = 100           # one batch spans one window


class SleepySource(DataGenSource):
    """A source slower than the job: every poll takes ``nap`` seconds."""

    def __init__(self, nap, **kw):
        super().__init__(**kw)
        self.nap = nap

    def poll_batch(self, max_records):
        time.sleep(self.nap)
        return super().poll_batch(max_records)


def run_job(name, nap=0.0, in_flight=BATCHES, slow_map=0.0):
    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": BATCH,
        "execution.pipeline.in-flight-batches": in_flight}))
    sink = CollectSink()
    stream = env.add_source(
        SleepySource(nap, total_records=BATCH * BATCHES, num_keys=KEYS,
                     events_per_second_of_eventtime=BATCH * 1000
                     // WINDOW_MS),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
    if slow_map:
        def dawdle(batch):
            time.sleep(slow_map)
            return batch
        stream = stream.map(dawdle, name="dawdle")
    (stream.key_by("key")
        .window(TumblingEventTimeWindows.of(WINDOW_MS))
        .aggregate(CountAggregate())
        .sink_to(sink))
    rec = flight.recorder()
    rec.clear()
    result = env.execute(name)
    rows = sorted((r["window_end"], r["key"], r["count"])
                  for r in sink.rows())
    return rec.kind_totals(), rec.snapshot(), rows, result


@pytest.fixture(scope="module")
def warm():
    """The job once, so that no later run compiles while it is timed."""
    return run_job("waits-warm")[2]


# ------------------------------------------- (a) the source hand-over


def test_a_slow_source_leaves_the_loop_waiting_and_never_the_pump(warm):
    # a queue as deep as the stream: the pump can never find it full
    kt, records, rows, _ = run_job("waits-idle", nap=0.02)
    assert rows == warm
    idle = kt["loop.wait_source"]
    assert idle["count"] >= BATCHES and idle["total_s"] > 0.05
    assert "source.wait_loop" not in kt
    waits = [r for r in records if r.kind == "loop.wait_source"]
    assert waits and all(not r.instant and r.thread == "MainThread"
                         for r in waits)
    # every batch taken says how long it lay, under its own sequence
    taken = [r for r in records if r.kind == "source.queue_wait"]
    assert [r.batch_id for r in taken] == list(range(1, BATCHES + 1))
    assert kt["source.queue_wait"]["count"] == BATCHES
    assert all(r.duration_s > 0 and r.watermark is not None for r in taken)
    # the loop outruns this source: no batch lay behind another
    assert kt["source.queue_wait"]["work"] == 0


def test_a_slow_loop_back_pressures_the_pump(warm):
    kt, records, rows, _ = run_job("waits-pressed", in_flight=1,
                                   slow_map=0.02)
    assert rows == warm
    pressed = kt["source.wait_loop"]
    assert pressed["count"] >= BATCHES - 3 and pressed["total_s"] > 0.05
    assert pressed["work"] == pressed["count"]
    held = [r for r in records if r.kind == "source.wait_loop"]
    assert held and all(r.thread.startswith("source-pump-") for r in held)
    # a batch that waited for its slot then lay in it: the queue wait of
    # the later batches is about a turn of the slow loop
    lain = sorted(r.duration_s for r in records
                  if r.kind == "source.queue_wait")
    assert lain[len(lain) // 2] > 0.01


def test_a_turn_that_finds_a_batch_waiting_records_neither():
    class T:
        name = "unit"
        source = None

        class watermark_strategy:
            @staticmethod
            def create():
                return None

    rec = flight.recorder()
    pump = _SourcePump(T, batch_size=4, in_flight=2)
    rec.clear()
    assert pump._put(("batch", 17, {"i": 1}, time.perf_counter()))
    entry = pump.poll(timeout=0.002)
    assert entry[:3] == ("batch", 17, {"i": 1})
    kt = rec.kind_totals()
    assert "loop.wait_source" not in kt and "source.wait_loop" not in kt
    (lay,) = [r for r in rec.snapshot() if r.kind == "source.queue_wait"]
    assert lay.batch_id == 1 and lay.watermark == 17 and lay.work == 0
    # and an empty queue is waited at only where a timeout was given
    rec.clear()
    assert pump.poll() is None
    assert "loop.wait_source" not in rec.kind_totals()
    assert pump.poll(timeout=0.002) is None
    assert rec.kind_totals()["loop.wait_source"]["count"] == 1
    rec.clear()


# ----------------------------------------- (b) one result window's path


PATH = ("source.queue_wait", "op.watermark", "fire.dispatch",
        "fire.in_flight", "fire.harvest", "sink.write", "window.emit")


def _by_watermark(records):
    by = defaultdict(lambda: defaultdict(list))
    for r in records:
        if r.kind in PATH and r.watermark is not None \
                and r.thread == "MainThread":
            by[r.watermark][r.kind].append(r)
    return by


def _assert_paths(records, windows):
    by = _by_watermark(records)
    fired = {wm: kinds for wm, kinds in by.items() if "window.emit" in kinds}
    # the end-of-input flush fires under a watermark no batch brought
    whole = {wm: k for wm, k in fired.items() if "source.queue_wait" in k}
    assert len(whole) >= windows - 2 and len(fired) >= len(whole)
    for wm, kinds in whole.items():
        # one window per batch: one record of each kind, in path order
        # (every operator of the chain sees the watermark; the window
        # operator's advance is the one the dispatch lies in)
        (dispatch,) = kinds["fire.dispatch"]
        kinds["op.watermark"] = [
            r for r in kinds["op.watermark"]
            if r.t0 <= dispatch.t0 and dispatch.t1 <= r.t1]
        path = [kinds[k] for k in PATH]
        assert all(len(rs) == 1 for rs in path), (wm, dict(kinds))
        lay, advance, dispatch, flown, harvest, write, emit = (
            rs[0] for rs in path)
        assert lay.t1 <= advance.t0 <= dispatch.t0 <= dispatch.t1 \
            <= advance.t1
        assert dispatch.t0 <= flown.t0 <= dispatch.t1   # dispatched_at
        assert flown.t1 <= harvest.t0 <= harvest.t1 <= write.t0 \
            <= write.t1 <= emit.t1
        # the emission starts where the source handed the batch over, and
        # so holds the queue wait, the flight and the harvest
        assert emit.t0 == pytest.approx(lay.t0, abs=1e-9)
        assert emit.duration_s >= lay.duration_s + flown.duration_s \
            + harvest.duration_s
    return fired


def test_every_record_of_a_windows_path_shares_its_watermark(warm):
    kt, records, rows, _ = run_job("waits-path")
    assert rows == warm
    windows = len({r[0] for r in rows})
    assert windows == BATCHES
    _assert_paths(records, windows)
    assert kt["window.emit"]["count"] == kt["fire.harvest"]["count"] \
        == kt["fire.in_flight"]["count"] == kt["fire.poll_gap"]["count"] \
        == kt["sink.write"]["count"] == windows
    trace = chrome_trace(records)
    assert validate_trace_schema(trace, KNOWN_SPAN_KINDS) == []


def test_with_fires_pending_each_harvest_and_sink_write_keeps_its_own(
        warm, monkeypatch):
    """No fire lands until the job drains: every window is harvested at
    the end, turns after its dispatch, when the ambient watermark is the
    flush's. Each harvest, each sink write and each emission still carries
    the watermark that fired its window."""
    depth = []
    harvest = PendingFire.harvest

    def counting(self):
        depth.append(self.watermark)
        return harvest(self)

    monkeypatch.setattr(PendingFire, "ready", lambda self: False)
    monkeypatch.setattr(PendingFire, "harvest", counting)
    kt, records, rows, _ = run_job("waits-pending")
    assert rows == warm
    windows = len({r[0] for r in rows})
    fired = _assert_paths(records, windows)
    assert len(set(depth)) >= 3 and len(fired) == len(set(depth))
    # they were all in flight at once: the first dispatched was harvested
    # after the last was dispatched
    flights = sorted((r for r in records if r.kind == "fire.in_flight"),
                     key=lambda r: r.t0)
    assert flights[0].t1 > flights[-1].t0
    # and each was looked at and found not ready, then waited for
    gaps = [r for r in records if r.kind == "fire.poll_gap"]
    assert len(gaps) == len(flights)
    assert all(not g.instant and g.duration_s > 0 for g in gaps)
    trace = chrome_trace(records)
    assert validate_trace_schema(trace, KNOWN_SPAN_KINDS) == []


def test_the_schema_wants_the_fires_watermark_on_its_harvest_and_emission():
    for kind in ("fire.dispatch", "fire.in_flight", "fire.harvest",
                 "window.emit"):
        ev = {"ph": "X", "name": kind, "dur": 5, "ts": 0, "pid": 1,
              "tid": 0, "args": {"batch": 3}}
        assert validate_trace_schema({"traceEvents": [ev]},
                                     KNOWN_SPAN_KINDS) \
            == [f"{kind} without watermark"]
        ev["args"]["watermark"] = 99
        assert validate_trace_schema({"traceEvents": [ev]},
                                     KNOWN_SPAN_KINDS) == []


# ------------------------------------- (c), (d) in flight and the poll gap


class FakeBuffer:
    """A device buffer as ``PendingFire`` sees one."""

    def __init__(self, ready=True, wait_s=0.0):
        self.ready, self.wait_s = ready, wait_s

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        time.sleep(self.wait_s)
        self.ready = True

    def __array__(self, dtype=None, copy=None):
        return np.zeros(3, dtype=np.int32)


def _operator():
    return WindowAggOperator(TumblingEventTimeWindows.of(WINDOW_MS),
                             CountAggregate(), "key")


def _flight_and_gap(rec):
    (flown,) = [r for r in rec.snapshot() if r.kind == "fire.in_flight"]
    (gap,) = [r for r in rec.snapshot() if r.kind == "fire.poll_gap"]
    return flown, gap


def test_a_fire_ready_at_the_first_look_has_no_poll_gap():
    rec = flight.recorder()
    rec.clear()
    op = _operator()
    flight.set_watermark(4242)
    flight.set_origin(time.perf_counter())
    op._pending.append(PendingFire([np.arange(3)], lambda host: None))
    flight.set_watermark(7)                 # the loop has moved on since
    assert list(op.poll_pending_output()) == []     # an empty result
    flown, gap = _flight_and_gap(rec)
    assert flown.duration_s > 0 and not flown.instant
    assert not gap.instant and gap.duration_s == 0   # a sample of 0
    assert flown.watermark == gap.watermark == 4242
    (harvest,) = [r for r in rec.snapshot() if r.kind == "fire.harvest"]
    assert harvest.watermark == 4242 and harvest.t0 >= flown.t1
    # (d) the operator's own sample: dispatch -> the harvest's end
    kt = rec.kind_totals()["fire.poll_gap"]
    assert kt["count"] == 1 and kt["total_s"] == kt["p50_ms"] == 0.0
    (sample,) = op.fire_latencies_ms
    assert op.fires_total == 1
    assert sample >= (flown.duration_s + harvest.duration_s) * 1e3
    assert sample == pytest.approx(
        (harvest.t1 - flown.t0) * 1e3, abs=0.5)
    rec.clear()


def test_the_poll_gap_runs_from_the_last_look_that_said_no():
    rec = flight.recorder()
    rec.clear()
    op = _operator()
    first, second = FakeBuffer(ready=False), FakeBuffer(ready=False)
    op._pending.append(PendingFire([first], lambda host: None))
    op._pending.append(PendingFire([second], lambda host: None))
    time.sleep(0.002)
    assert list(op.poll_pending_output()) == [] and len(op._pending) == 2
    looked = time.perf_counter()
    # the look at the head is a look at what was dispatched behind it
    assert all(0 < pf.unready_at <= looked for pf in op._pending)
    time.sleep(0.005)
    first.ready = second.ready = True
    assert list(op.poll_pending_output()) == [] and not op._pending
    flights = [r for r in rec.snapshot() if r.kind == "fire.in_flight"]
    gaps = [r for r in rec.snapshot() if r.kind == "fire.poll_gap"]
    assert len(flights) == len(gaps) == 2
    for flown, gap in zip(flights, gaps):
        assert 0.005 <= gap.duration_s <= flown.duration_s
        assert flown.duration_s >= 0.007
    kt = rec.kind_totals()
    assert kt["fire.poll_gap"]["total_s"] <= kt["fire.in_flight"]["total_s"]
    rec.clear()


@pytest.mark.parametrize("looked_before", [False, True])
def test_a_blocking_harvest_counts_its_wait_as_the_gap(looked_before):
    rec = flight.recorder()
    rec.clear()
    op = _operator()
    op._pending.append(
        PendingFire([FakeBuffer(ready=False, wait_s=0.01)],
                    lambda host: None))
    if looked_before:
        assert list(op.poll_pending_output()) == []
        time.sleep(0.003)
    assert list(op.poll_pending_output(wait=True)) == []
    flown, gap = _flight_and_gap(rec)
    least = 0.013 if looked_before else 0.01
    assert least <= gap.duration_s <= flown.duration_s
    # the wait is in the flight, not in the harvest
    (harvest,) = [r for r in rec.snapshot() if r.kind == "fire.harvest"]
    assert harvest.duration_s < 0.01 <= flown.duration_s
    rec.clear()


def test_past_the_bound_on_pending_fires_the_poll_does_not_wait_to_look():
    rec = flight.recorder()
    rec.clear()
    op = _operator()
    op._max_pending = 2
    for _ in range(4):
        op._pending.append(
            PendingFire([FakeBuffer(ready=False)], lambda host: None))
    assert list(op.poll_pending_output()) == []
    assert len(op._pending) == 2            # harvested down to the bound
    assert rec.kind_totals()["fire.harvest"]["count"] == 2
    rec.clear()


def test_the_window_group_counts_every_harvested_fire(warm):
    kt, _, rows, result = run_job("waits-gauges")
    assert rows == warm
    snap = result.registry.snapshot()
    (count,) = [v for k, v in snap.items() if k.endswith("window.fireCount")]
    (p50,) = [v for k, v in snap.items()
              if k.endswith("window.fireLatencyP50Ms")]
    assert count == kt["fire.harvest"]["count"] == kt["fire.in_flight"][
        "count"]
    assert p50 > 0


# ------------------------------------------------- (e) the recorder off


def test_with_the_recorder_off_the_job_runs_and_nothing_is_recorded(warm):
    """``FLINK_TPU_FLIGHT_RECORDER=0`` sets the one module flag that
    ``flight.disabled()`` sets."""
    rec = flight.recorder()
    with flight.disabled():
        kt, records, rows, _ = run_job("waits-off", nap=0.002)
        assert flight.fire_context() == (flight.WM_NONE, 0.0)
    assert rows == warm
    assert kt == {} and records == []
    rec.clear()
