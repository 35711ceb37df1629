"""The single-device window path as the flight recorder sees it: a small
NEXmark-Q5-shaped job (keyBy -> HOP window -> COUNT with a device top-k
fire -> sink) through ``env.execute()``, every span kind of the batch and
fire lifecycle recorded where the work happens, with counts that match
batches and fired windows, children inside their parents, and a bounded
number of spans per batch and per fired window (the overhead budget, as a
count — a timing belongs to the chip)."""

from collections import Counter, defaultdict

import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.native import slotmap_available
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.aggregates import CountAggregate
from flink_tpu.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.windowing.fire_projectors import TopKFireProjector

BATCH = 4096
BATCHES = 10
KEYS = 500
TOP_K = 4


def run_q5(layout):
    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": BATCH,
        "state.window-layout": layout}))
    sink = CollectSink()
    (env.add_source(
        DataGenSource(total_records=BATCH * BATCHES, num_keys=KEYS,
                      events_per_second_of_eventtime=10_000),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(SlidingEventTimeWindows.of(1000, 200))
        .aggregate(CountAggregate(),
                   fire_projector=TopKFireProjector("count", k=TOP_K))
        .sink_to(sink))
    rec = flight.recorder()
    rec.clear()
    env.execute("q5-" + layout)
    rows = sink.rows()
    windows = len({r["window_end"] for r in rows})
    return rec.kind_totals(), rec.snapshot(), rows, windows


@pytest.fixture(scope="module", params=["slots", "panes"])
def q5(request):
    return (request.param,) + run_q5(request.param)


def test_every_kind_is_recorded_with_the_count_of_its_boundary(q5):
    layout, kt, _, rows, windows = q5
    assert windows > 10 and len(rows) == windows * TOP_K
    # ingest: one batch.ingest per micro-batch, stating its events
    assert kt["batch.ingest"]["count"] == BATCHES
    assert kt["batch.ingest"]["work"] == BATCH * BATCHES
    # one resolve per batch: the slots layout's native sweep over keys
    # and timestamps (every batch here is in order, so every batch takes
    # it and says so), the panes layout's fused index build. Without the
    # native index the slots layout plans slices in the windower and
    # looks slots up in the table: two
    swept = kt.get("resolve.sweep", {"count": 0, "work": 0})
    if layout == "slots" and slotmap_available():
        assert swept["count"] == BATCHES
        assert swept["work"] == BATCH * BATCHES
    else:
        assert swept["count"] == 0
    per_batch = 2 if layout == "slots" and not swept["count"] else 1
    assert kt["prep.resolve"]["count"] == per_batch * BATCHES
    assert kt["prep.stage"]["count"] == 2 * BATCHES
    assert kt["device.dispatch"]["count"] == BATCHES
    # bytes handed over: one padded int32 index per event, COUNT sends
    # no value column (at least the batch, at most the 4x sticky bucket;
    # panes fold each event into its pane and its 5 windows' partials)
    copies = 1 if layout == "slots" else 6
    assert BATCH * BATCHES * 4 <= kt["prep.stage"]["work"] \
        <= copies * 4 * BATCH * BATCHES * 4
    assert 0 < kt["device.fence_wait"]["count"] <= BATCHES
    # fire: one fire.dispatch per watermark advance of the window
    # operator, one harvest and one sink write per fired window
    assert windows / 5 <= kt["fire.dispatch"]["count"] \
        <= kt["op.watermark"]["count"]
    assert kt["fire.harvest"]["count"] == windows
    assert kt["fire.harvest"]["work"] > 0
    assert kt["sink.write"]["count"] == windows
    assert kt["sink.write"]["work"] == len(rows)
    assert 0 < kt["slice.retire"]["count"] <= kt["fire.dispatch"]["count"]
    if layout == "slots":
        assert kt["fire.shard"]["count"] == windows
        # every (key, slice) pair given a slot is erased again by the
        # end-of-input flush, and each is resolved into the fire's slot
        # matrix exactly once: by the first of the 5 windows its slice
        # belongs to, which hands the matrix on to the next
        pairs = kt["prep.resolve"]["work"]
        assert 0 < pairs == kt["slice.retire"]["work"]
        assert kt["fire.shard"]["work"] == pairs
        # the padded slot matrices: 5 int32 slots per row, >= 64 rows
        assert kt["fire.dispatch"]["work"] >= windows * 64 * 5 * 4
    else:
        assert "fire.shard" not in kt
        assert kt["slice.retire"]["work"] > 0      # ring rows freed


def test_children_lie_inside_their_parents(q5):
    _, kt, records, _, _ = q5
    parents = Counter((r.kind, r.parent) for r in records if not r.instant)
    want = {"batch.ingest": "op.process", "prep.resolve": "batch.ingest",
            "prep.stage": "batch.ingest", "device.dispatch": "batch.ingest",
            "device.fence_wait": "op.process", "sink.write": "op.process",
            "fire.dispatch": "op.watermark", "fire.shard": "fire.dispatch",
            "slice.retire": "fire.dispatch"}
    for (kind, parent), _ in parents.items():
        if kind in want:
            assert parent == want[kind], (kind, parent)
    for kind, t in kt.items():
        assert 0.0 <= t["self_s"] <= t["total_s"] + 1e-12, kind
    # batch by batch: what the children cover fits in the ingest span
    covered = defaultdict(float)
    for r in records:
        if r.parent == "batch.ingest":
            covered[r.batch_id] += r.duration_s
    ingests = [r for r in records if r.kind == "batch.ingest"]
    assert sorted(r.batch_id for r in ingests) == list(
        range(1, BATCHES + 1))
    for r in ingests:
        assert 0.0 < covered[r.batch_id] <= r.duration_s
    # and over the run: the parts and the remainder make the whole
    parts = sum(kt[k]["total_s"] for k in
                ("prep.resolve", "prep.stage", "device.dispatch")) \
        + sum(r.duration_s for r in records
              if r.kind == "xla.compile" and r.parent == "batch.ingest")
    assert parts + kt["batch.ingest"]["self_s"] == pytest.approx(
        kt["batch.ingest"]["total_s"], rel=1e-6)
    fire_children = sum(
        r.duration_s for r in records if r.parent == "fire.dispatch"
        and not r.instant)
    assert fire_children + kt["fire.dispatch"]["self_s"] == pytest.approx(
        kt["fire.dispatch"]["total_s"], rel=1e-6)


def test_the_span_budget_per_batch_and_per_fired_window(q5):
    _, kt, records, _, windows = q5
    ingest_side = ("batch.ingest", "prep.resolve", "prep.stage",
                   "device.dispatch", "device.fence_wait")
    fire_side = ("fire.dispatch", "fire.shard", "slice.retire",
                 "fire.harvest", "sink.write")
    assert sum(kt[k]["count"] for k in ingest_side) <= 8 * BATCHES
    assert sum(kt[k]["count"] for k in fire_side if k in kt) \
        <= 4 * windows + 2 * BATCHES
    # the waits (PR 37): per batch one queue wait and at most one wait of
    # the pump, per harvested fire its time in flight, its poll gap and
    # its emission. The loop's waits are bounded by the time it stood
    # idle: one that ends with an entry (a batch or the end of input)
    # comes at most once per entry, every other one lasted its whole 2 ms
    assert kt["source.queue_wait"]["count"] == BATCHES
    assert kt.get("source.wait_loop", {"count": 0})["count"] <= BATCHES + 1
    for kind in ("fire.in_flight", "fire.poll_gap", "window.emit"):
        assert kt[kind]["count"] == windows, kind
    idle = kt.get("loop.wait_source", {"count": 0, "total_s": 0.0})
    assert idle["count"] <= BATCHES + 1 + idle["total_s"] / 0.002
    # everything the job's threads time (compiles aside: a warm process
    # has none): at most 12 spans per batch and 10 per fire of work, and
    # of waiting 3 records more per batch and 3 more per fire
    waits = ("loop.wait_source", "source.wait_loop", "source.queue_wait",
             "fire.in_flight", "fire.poll_gap", "window.emit")
    spans = sum(1 for r in records if not r.instant
                and r.kind != "xla.compile" and r.kind not in waits)
    assert spans <= 12 * BATCHES + 10 * windows
    waited = sum(1 for r in records if r.kind in waits)
    assert waited - idle["count"] <= 2 * BATCHES + 1 + 3 * windows


# ------------------------------------------------- the retire's drop, stated


def test_the_retire_says_which_pairs_left_with_their_whole_table(q5):
    """On the native index a slice's pairs live in one table and leave
    with it: every ``slice.retire`` of the slots layout holds one
    ``retire.drop`` instant, and their work is the retire's — all of it,
    no pair was erased one by one. The panes layout frees ring rows, not
    namespaces, and says nothing."""
    layout, kt, records, _, _ = q5
    drops = [r for r in records if r.kind == "retire.drop"]
    if layout != "slots" or not slotmap_available():
        assert not drops and "retire.drop" not in kt
        return
    assert all(r.instant and r.parent == "slice.retire" for r in drops)
    assert kt["retire.drop"]["count"] == kt["slice.retire"]["count"] \
        == len(drops)
    assert kt["retire.drop"]["work"] == kt["slice.retire"]["work"] \
        == kt["prep.resolve"]["work"] > 0
    # instant by instant inside its retire, with the retire's own count
    retires = sorted((r for r in records if r.kind == "slice.retire"),
                     key=lambda r: r.t0)
    drops.sort(key=lambda r: r.t0)
    for retire, drop in zip(retires, drops):
        assert retire.t0 <= drop.t0 <= retire.t0 + retire.duration_s
        assert drop.work == retire.work


def test_no_drop_is_stated_where_pairs_are_erased_one_by_one(monkeypatch):
    """The Python index erases pair by pair, and a session job frees by
    slot from the flat table: neither records the kind."""
    import flink_tpu.state.slot_table as slot_table_mod
    from flink_tpu.state.slot_table import HostSlotIndex
    from flink_tpu.windowing.assigners import EventTimeSessionWindows

    with monkeypatch.context() as m:
        m.setattr(slot_table_mod, "make_slot_index",
                  lambda capacity, **kw: HostSlotIndex(capacity, **kw))
        kt, _, rows, windows = run_q5("slots")
    assert windows > 10 and len(rows) == windows * TOP_K
    assert kt["slice.retire"]["work"] == kt["prep.resolve"]["work"] > 0
    assert "retire.drop" not in kt and "resolve.sweep" not in kt

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": BATCH}))
    sink = CollectSink()
    (env.add_source(
        DataGenSource(total_records=BATCH * 4, num_keys=KEYS,
                      events_per_second_of_eventtime=1_000),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(EventTimeSessionWindows.with_gap(300))
        .aggregate(CountAggregate())
        .sink_to(sink))
    rec = flight.recorder()
    rec.clear()
    env.execute("sessions")
    kt = rec.kind_totals()
    assert len(sink.rows()) > 100
    assert kt["slice.retire"]["work"] > 0       # sessions fired and freed
    assert "retire.drop" not in kt
