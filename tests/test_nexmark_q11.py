"""NEXmark Query 11 (user sessions) on the CPU at a small size: the job
through ``env.execute()`` against its plain reference, the generator's
bidders against a loop-written transcription of the source's rule, and the
session path's flight-recorder spans."""

import copy

import numpy as np
import pytest

from benchmark.harness import manifest, runner
from benchmark.harness.traffic import TimedSource
from benchmark.jobs import q11
from benchmark.jobs._hash import splitmix64

MAN = manifest.manifest()
CONFIG = manifest.config(MAN, "nexmark-q11-sessions")


def tiny_config():
    cfg = copy.deepcopy(CONFIG)
    cfg["options"].update(q11.TINY["options"])
    cfg["job_options"].update(q11.TINY["job_options"])
    return cfg


def run_job(cfg, seed, events):
    source = TimedSource(q11.make_generator(seed, cfg["job_options"]),
                         {"mode": "backlog"},
                         q11.boundary_events(cfg["job_options"]),
                         min_events=events)
    sink, tap, *_ = runner.execute_job(q11, cfg, source)
    return sink, tap, source.log


# ------------------------------------------------ (a) job == reference


@pytest.mark.parametrize("seed", [3, 1_000_003, 2_147_483_659])
def test_the_jobs_rows_equal_the_reference(seed):
    # 3,000-bid batches: 1.3 s of event time each, so every session of
    # more than one bid is cut by a batch boundary somewhere and the 10 s
    # gap passes every eighth batch
    cfg = tiny_config()
    cfg["options"]["execution.micro-batch.size"] = 3000
    o = cfg["job_options"]
    sink, tap, log = run_job(cfg, seed, 60_000)
    assert len(log.count) > 20 and max(log.count) <= 3000
    want = q11.reference_rows(seed, log.events, o)
    got = sink.result()
    verdict = q11.compare(got, want, o)
    assert verdict["numbers"]["rows_wrong"]["value"] == 0
    assert verdict["failed"] == 0
    assert verdict["attempted"] == len(want["bidder"]) > 1000
    assert int(np.sum(want["count"])) == log.events
    # a gap passed between two bids of one bidder: two sessions, not one
    bidders, sessions = np.unique(want["bidder"], return_counts=True)
    assert sessions.max() >= 2
    again = bidders[sessions >= 2][0]
    rows = np.flatnonzero(got["bidder"] == again)
    assert len(rows) == sessions[bidders == again][0]
    starts = np.sort(got["window_start"][rows])
    ends = np.sort(got["window_end"][rows])
    assert (starts[1:] > ends[:-1]).all()
    # sessions that straddle a batch boundary: first and last bid fall in
    # different batches for most sessions of more than one bid
    edges = np.cumsum(log.count)
    gen_ts = q11.make_generator(seed, o)(0, log.events)[1]
    batch_of = np.searchsorted(gen_ts[edges - 1], want["window_start"])
    last_bid = want["window_end"] - int(o["gap_ms"])
    assert (np.searchsorted(gen_ts[edges - 1], last_bid) > batch_of).any()
    (op,) = tap["ops"]
    assert type(op.windower).__name__ == CONFIG["expect"]["engine"]


def test_a_gap_past_the_gap_opens_a_second_session():
    # the source's engine's rule (Flink's TimeWindow.intersects: windows
    # that touch merge): bidder 7's bids 10,000 ms apart share a session,
    # 10,001 ms apart do not; 2-bid batches put one cut inside a batch and
    # one between batches
    bidder = np.array([7, 9, 7, 7, 9, 9, 8, 7], dtype=np.int64)
    ts = np.array([0, 5, 10_000, 20_001, 20_004, 20_005, 30_000, 30_002],
                  dtype=np.int64)
    cfg = tiny_config()
    cfg["options"]["execution.micro-batch.size"] = 2
    source = TimedSource(
        lambda first, n: ({"bidder": bidder[first:first + n]},
                          ts[first:first + n]),
        {"mode": "backlog"}, boundary=len(ts), min_events=len(ts))
    sink, *_ = runner.execute_job(q11, cfg, source)
    want = q11.sessions_of(bidder, ts, 10_000)
    rows = sorted(zip(*(want[c].tolist() for c in q11.SINK_COLUMNS)))
    assert rows == [(10_005, 5, 9, 1), (20_000, 0, 7, 2),
                    (30_001, 20_001, 7, 1), (30_005, 20_004, 9, 2),
                    (40_000, 30_000, 8, 1), (40_002, 30_002, 7, 1)]
    verdict = q11.compare(sink.result(), want, cfg["job_options"])
    assert verdict["failed"] == 0 and verdict["attempted"] == 6
    assert verdict["numbers"]["rows_wrong"]["value"] == 0


# ------------------------------------------- (b) the generator's bidders


def last_base0_person_id(event, o):
    """``GeneratorConfig``/``PersonGenerator.lastBase0PersonId``."""
    total = (o["person_proportion"] + o["auction_proportion"]
             + o["bid_proportion"])
    epoch, offset = divmod(event, total)
    if offset >= o["person_proportion"]:
        offset = o["person_proportion"] - 1
    return epoch * o["person_proportion"] + offset


def next_bidder(event, hot_draw, cold_draw_of, o):
    """``BidGenerator.nextBid``'s bidder, the random draws handed in."""
    if hot_draw > 0:
        stride = o["hot_bidder_stride"]
        bidder = (last_base0_person_id(event, o) // stride) * stride + 1
    else:
        num_people = last_base0_person_id(event, o) + 1
        active = min(num_people, o["num_active_people"])
        bidder = num_people - active + cold_draw_of(
            active + o["person_id_lead"])
    return bidder + o["first_person_id"]


@pytest.mark.parametrize("first", [0, 5_000_000])
def test_the_generators_bidders_follow_the_sources_rule(first):
    o = CONFIG["job_options"]
    n, seed = 10_000, 11
    cols, ts = q11.make_generator(seed, o)(first, n)
    u64 = splitmix64(np.arange(first, first + n, dtype=np.int64),
                     seed * 4 + 3).tolist()
    bids = o["bid_proportion"]
    before = o["person_proportion"] + o["auction_proportion"]
    hot = 0
    for j, i in enumerate(range(first, first + n)):
        event = (i // bids) * (before + bids) + before + i % bids
        hot_draw = ((u64[j] & 0xFFFF) * o["hot_bidders_ratio"]) >> 16
        bidder = next_bidder(
            event, hot_draw,
            lambda k, u=u64[j]: (((u >> 16) & 0xFFFFFFFF) * k) >> 32, o)
        assert cols["bidder"][j] == bidder, (i, event)
        assert ts[j] == event * 1000 // o["event_rate"]
        p = event // (before + bids)
        if hot_draw > 0:
            hot += 1
        else:
            active = min(p + 1, o["num_active_people"])
            assert p + 1 - active <= bidder - o["first_person_id"] \
                < p + 1 + o["person_id_lead"]
    assert abs(hot / n - 0.75) <= 0.02
    assert (np.diff(ts) >= 0).all()


def test_every_bidder_holds_one_session_at_the_configurations_size():
    """What PERF.md states of the deployment: a cold bidder receives about
    11 bids over the 50,000 events it stays active, a hot one about 3,450
    more, every bidder has one session, ids never repeat."""
    o = CONFIG["job_options"]
    n = 2_300_000        # 10 s of event time
    want = q11.reference_rows(5, n, o)
    bidders, sessions = np.unique(want["bidder"], return_counts=True)
    assert sessions.max() == 1
    assert abs(len(bidders) / (n / 46) - 1) < 0.03
    done = want["window_end"] < 8_000 + o["gap_ms"]     # left long ago
    counts = want["count"][done]
    hot = (want["bidder"][done] - o["first_person_id"]) % 100 == 1
    assert abs(np.median(counts[~hot]) - 11) <= 1
    assert abs(counts[hot].mean() - counts[~hot].mean() - 3450) < 100
    assert q11.first_index_with_ts(int(want["window_start"].max()), o) < n
    for ts in (0, 1, 17, 9_999, 10_000):
        i = q11.first_index_with_ts(ts, o)
        times = q11.make_generator(5, o)(max(i - 1, 0), 2)[1]
        assert times[-1] >= ts and (i == 0 or times[0] < ts)


# ------------------------------------------------ (c) the session spans


def test_the_session_spans_add_up_and_count_what_the_sink_received():
    from flink_tpu.observe import flight_recorder as flight

    cfg = tiny_config()
    run_job(cfg, 13, 40_000)                # compiles land here
    flight.recorder().clear()
    sink, _, log = run_job(cfg, 13, 40_000)
    totals = flight.recorder().kind_totals()
    assert "xla.compile" not in totals
    ingest = totals["batch.ingest"]
    assert ingest["count"] == len(log.count)
    assert ingest["work"] == log.events
    parts = ("prep.meta_sweep", "session.merge", "prep.resolve",
             "prep.stage", "device.dispatch")
    assert all(totals[k]["count"] >= ingest["count"] for k in parts)
    assert ingest["self_s"] + sum(totals[k]["self_s"] for k in parts) \
        == pytest.approx(ingest["total_s"], rel=1e-6)
    rows = len(sink.result()["bidder"])
    assert totals["fire.shard"]["work"] == rows
    assert totals["slice.retire"]["work"] == rows
    assert totals["prep.meta_sweep"]["work"] == rows    # opened == closed
    assert totals["prep.resolve"]["work"] == rows       # each given a slot
    assert totals["session.merge"]["work"] == 0         # in-order stream
    assert totals["fire.harvest"]["work"] >= 4 * rows   # int32 counts
    fire = totals["fire.dispatch"]
    assert fire["total_s"] >= totals["fire.shard"]["total_s"] \
        + totals["slice.retire"]["total_s"]
