"""NEXmark Query 5 over the generator's own auctions on the CPU at a small
size: the job through ``env.execute()`` against its plain reference, the
generator's auctions against a loop-written transcription of the source's
rule, the comparison's rule for auctions tied at the last place, and the
fire path's spans under keys that never come back."""

import copy

import numpy as np
import pytest

from benchmark.harness import manifest, runner
from benchmark.harness.traffic import TimedSource
from benchmark.jobs import q5_generator as q5g
from benchmark.jobs._hash import splitmix64
from tests.test_fire_width import assert_gathers

MAN = manifest.manifest()
CONFIG = manifest.config(MAN, "nexmark-q5-generator")


def tiny_config(batch=None):
    cfg = copy.deepcopy(CONFIG)
    cfg["options"].update(q5g.TINY["options"])
    cfg["job_options"].update(q5g.TINY["job_options"])
    if batch:
        cfg["options"]["execution.micro-batch.size"] = batch
    return cfg


def run_job(cfg, seed, events):
    o = cfg["job_options"]
    source = TimedSource(q5g.make_generator(seed, o), {"mode": "backlog"},
                         q5g.boundary_events(o), min_events=events)
    sink, tap, *_ = runner.execute_job(q5g, cfg, source)
    return sink, tap, source.log


def auctions_of_window(seed, o, j, n_events):
    """The auctions of the bids of window ``j`` (slices ``j-k+1 .. j``),
    the plain way."""
    slide = int(o["slide_ms"])
    k = int(o["size_ms"]) // slide
    lo = q5g.first_index_with_ts(max(j - k + 1, 0) * slide, o)
    hi = min(q5g.first_index_with_ts((j + 1) * slide, o), n_events)
    return q5g.make_generator(seed, o)(lo, hi - lo)[0]["auction"]


# ------------------------------------------------ (a) job == reference


@pytest.mark.parametrize("seed, batch", [(3, 1000), (1_000_003, 3000),
                                         (2_147_483_659, 7000)])
def test_the_jobs_rows_equal_the_reference(seed, batch):
    # a hot auction draws its bids over 1,533 consecutive ones: every
    # batch size here cuts each hot auction's run in the middle
    from flink_tpu import native

    cfg = tiny_config(batch)
    o = cfg["job_options"]
    sink, tap, log = run_job(cfg, seed, 180_000)
    assert max(log.count) <= batch and log.events % 23_000 == 0
    want = q5g.reference_rows(seed, log.events, o)
    got = sink.result()
    verdict = q5g.compare(got, want, o)
    assert verdict["numbers"]["rows_wrong"]["value"] == 0
    assert verdict["failed"] == 0
    slices = log.events // 23_000
    assert verdict["attempted"] == slices + 4      # the flush's windows too
    # what was sunk is the fire's candidates: 16 rows a window, of which
    # Q5's own are the top; the reference may hold more where the 16th
    # count is tied
    ends, rows = np.unique(got["window_end"], return_counts=True)
    assert (rows == 16).all() and len(ends) == slices + 4
    assert len(want["count"]) >= len(got["count"])
    for end in ends[[0, len(ends) // 2, -1]].tolist():
        j = end // int(o["slide_ms"]) - 1
        ids, counts = np.unique(auctions_of_window(seed, o, j, log.events),
                                return_counts=True)
        sunk = got["window_end"] == end
        assert got["count"][sunk].max() == counts.max()
        winner = got["auction"][sunk][np.argmax(got["count"][sunk])]
        assert counts[ids == winner] == counts.max()
    (op,) = tap["ops"]
    assert type(op.windower).__name__ == CONFIG["expect"]["engine"]
    assert native.native_fallbacks() == 0


def test_results_wait_for_the_watermark_four_seconds_behind():
    # the DDL's delay: a window fires once a bid 4 s past its end has
    # come, and the end-of-input flush fires the 7 windows still open
    # (delay/slide + 1 whole ones and size/slide - 1 past the last slice)
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import Sink

    cfg = tiny_config()
    o = cfg["job_options"]
    gen = q5g.make_generator(5, o)
    source = TimedSource(gen, {"mode": "backlog"}, q5g.boundary_events(o),
                         min_events=200_000)
    fired_at = []       # (window end, bids handed over by then)

    class Noting(Sink):
        def write(self, batch):
            if len(batch):
                fired_at.append((int(batch["window_end"][0]),
                                 source.log.events))

    env = StreamExecutionEnvironment(Configuration(dict(cfg["options"])))
    results, _ = q5g.build(env, source, o)
    results.sink_to(Noting())
    env.execute("delay")
    total = source.log.events
    before_flush = [(end, seen) for end, seen in fired_at if seen < total]
    assert len(before_flush) >= 3
    for end, seen in before_flush:
        newest = gen(seen - 1, 1)[1][0]
        assert newest >= end + int(o["watermark_delay_ms"])
    assert len(fired_at) - len(before_flush) >= 7
    assert len(fired_at) == total // 23_000 + 4


# ----------------------------------------- (b) the generator's auctions


def last_base0_auction_id(event, o):
    """``AuctionGenerator.lastBase0AuctionId``."""
    total = (o["person_proportion"] + o["auction_proportion"]
             + o["bid_proportion"])
    epoch, offset = divmod(event, total)
    if offset < o["person_proportion"]:
        epoch -= 1
        offset = o["auction_proportion"] - 1
    elif offset >= o["person_proportion"] + o["auction_proportion"]:
        offset = o["auction_proportion"] - 1
    else:
        offset -= o["person_proportion"]
    return epoch * o["auction_proportion"] + offset


def next_base0_auction_id(event, cold_draw_of, o):
    """``AuctionGenerator.nextBase0AuctionId``."""
    last = last_base0_auction_id(event, o)
    oldest = max(last - o["num_in_flight_auctions"], 0)
    return oldest + cold_draw_of(last - oldest + 1 + o["auction_id_lead"])


def next_bid_auction(event, hot_draw, cold_draw_of, o):
    """``BidGenerator.nextBid``'s auction, the random draws handed in."""
    if hot_draw > 0:
        stride = o["hot_auction_stride"]
        auction = (last_base0_auction_id(event, o) // stride) * stride
    else:
        auction = next_base0_auction_id(event, cold_draw_of, o)
    return auction + o["first_auction_id"]


@pytest.mark.parametrize("first", [0, 5_000_000])
def test_the_generators_auctions_follow_the_sources_rule(first):
    o = CONFIG["job_options"]
    n, seed = 10_000, 11
    cols, ts = q5g.make_generator(seed, o)(first, n)
    u64 = splitmix64(np.arange(first, first + n, dtype=np.int64),
                     seed * 4 + 2).tolist()
    bids = o["bid_proportion"]
    before = o["person_proportion"] + o["auction_proportion"]
    hot, hot_of_stride = 0, {}
    for j, i in enumerate(range(first, first + n)):
        event = (i // bids) * (before + bids) + before + i % bids
        hot_draw = ((u64[j] & 0xFFFF) * o["hot_auction_ratio"]) >> 16
        auction = next_bid_auction(
            event, hot_draw,
            lambda k, u=u64[j]: (((u >> 16) & 0xFFFFFFFF) * k) >> 32, o)
        assert cols["auction"][j] == auction, (i, event)
        assert ts[j] == event * 1000 // o["event_rate"]
        last = last_base0_auction_id(event, o)
        assert last == (event // 50) * 3 + 2
        base0 = auction - o["first_auction_id"]
        # no bid names an auction more than 100 below the last one made
        # or more than 10 above it: an id left behind never returns
        assert max(last - o["num_in_flight_auctions"], 0) <= base0 \
            <= last + o["auction_id_lead"]
        if hot_draw > 0:
            hot += 1
            # one hot auction for every 100 auctions made
            assert hot_of_stride.setdefault(last // 100, base0) == base0
            assert base0 % 100 == 0
    assert abs(hot / n - 0.5) <= 0.02
    assert len(hot_of_stride) >= 6
    assert (np.diff(ts) >= 0).all()


def test_the_streams_shape_at_the_configurations_size():
    """What the configuration states of the deployment: a slide holds
    460,000 bids and about 30,110 live auctions, a hot auction draws about
    767 bids more than a cold one's 7, and an id is bid on within two
    slices and never again."""
    o = CONFIG["job_options"]
    assert q5g.boundary_events(o) == 460_000
    assert q5g.live_cells_per_slice(o) == 30_110
    gen = q5g.make_generator(5, o)
    auction, ts = gen(0, 3 * 460_000)
    auction = auction["auction"]
    ids, counts = np.unique(auction[460_000:920_000], return_counts=True)
    assert 0 <= 30_110 - len(ids) <= 60      # a few ids draw no bid
    done = ids[(ids > ids.min() + 200) & (ids < ids.max() - 200)]
    whole = np.isin(auction, done)
    per_id = np.bincount(auction[whole] - done.min())[done - done.min()]
    hot = (done - o["first_auction_id"]) % 100 == 0
    assert abs(np.median(per_id[~hot]) - 7) <= 1
    assert abs(per_id[hot].mean() - per_id[~hot].mean() - 767) < 15
    # an id is in reach while 111 auctions are made: 37 epochs, 1,702 bids
    order = np.argsort(auction[whole], kind="stable")
    at = np.flatnonzero(whole)[order]
    sorted_ids = auction[at]
    head = np.flatnonzero(np.r_[True, sorted_ids[1:] != sorted_ids[:-1]])
    tail = np.r_[head[1:], len(at)] - 1
    assert 1_500 < (at[tail] - at[head]).max() <= 37 * 46
    for t in (0, 1, 17, 1_999, 2_000, 4_001):
        i = q5g.first_index_with_ts(t, o)
        times = gen(max(i - 1, 0), 2)[1]
        assert times[-1] >= t and (i == 0 or times[0] < t)
    assert ts[-1] == 5_999


# ------------------------------------- (c) the comparison's rule for ties


def window(rows, end=2000):
    return {"window_end": np.full(len(rows), end, dtype=np.int64),
            "auction": np.array([a for a, _ in rows], dtype=np.int64),
            "count": np.array([c for _, c in rows], dtype=np.int64)}


def verdict(got, want):
    v = q5g.compare(window(got), window(want), {"device_top_k": 16})
    assert v["attempted"] == 1
    return v["numbers"]["rows_wrong"]["value"], v["failed"]


def test_any_of_the_auctions_tied_at_the_last_place_is_right():
    # 14 rows above the 16th count, then auctions 50, 51, 52 and 53 tied
    # at it: two of the four fill the last places
    above = [(100 + i, 900 - i) for i in range(14)]
    tied = [(50, 700), (51, 700), (52, 700), (53, 700)]
    want = tied + above[::-1]
    assert verdict(want, want) == (0, 0)        # all 18, as the reference
    for pair in ([0, 1], [1, 3], [2, 3]):
        assert verdict(above + [tied[i] for i in pair], want) == (0, 0)
    # an auction that is not among the tied ones, at their count
    assert verdict(above + [tied[0], (54, 700)], want)[1] == 1
    # a 17th row that the reference does not hold
    assert verdict(above + tied[:2] + [(60, 650)], want)[1] == 1
    # a tied auction at another count, a missing place, a row twice
    assert verdict(above + [tied[0], (51, 699)], want)[1] == 1
    assert verdict(above + [tied[0]], want)[1] == 1
    assert verdict(above + [tied[0], tied[0]], want)[1] == 1
    # Q5's own row (the maximum) wrong, with the 16 places filled
    assert verdict([(100, 899)] + above[1:] + tied[:2], want)[1] == 1
    assert verdict(above[1:] + tied[:3], want)[1] == 1
    # fewer auctions than places: every one of them, and no more
    few = [(7, 3), (8, 3), (9, 1)]
    assert verdict(few, few) == (0, 0)
    assert verdict(few[:2], few)[1] == 1
    # every place tied at the maximum: Q5's rows are all of them
    flat = [(i, 5) for i in range(16)]
    assert verdict(flat, flat) == (0, 0)
    assert verdict(flat[:15] + [(99, 5)], flat)[1] == 1


def test_a_window_one_side_lacks_fails():
    rows = [(1, 5), (2, 4)]
    two = {k: np.concatenate([window(rows)[k], window(rows, 4000)[k]])
           for k in q5g.SINK_COLUMNS}
    v = q5g.compare(window(rows), two, {"device_top_k": 16})
    assert v["attempted"] == 2 and v["failed"] == 1
    v = q5g.compare(two, window(rows), {"device_top_k": 16})
    assert v["attempted"] == 1 and v["failed"] == 1


def test_a_lost_batch_shows_only_where_it_holds_a_candidate():
    """What the comparison does not see, as PERF.md section 2 counts it:
    ``rows_after`` over a range of windows, with a run of bids left out.
    Losing the bids of a window's winner shows in that window; losing as
    many bids that hold none of its candidates does not."""
    cfg = tiny_config()
    o = cfg["job_options"]
    seed, n = 17, 10 * 23_000
    whole = q5g.reference_rows(seed, n, o)
    some = q5g.rows_after(seed, n, o, windows=(5, 8))
    ends = np.unique(some["window_end"]).tolist()
    assert ends == [12_000, 14_000, 16_000]
    for name in q5g.SINK_COLUMNS:
        kept = np.isin(whole["window_end"], ends)
        assert (whole[name][kept] == some[name]).all()
    # window 5 = slices 1..5; its winner's bids, and a cold stretch's
    in_window = some["window_end"] == 12_000
    winner = some["auction"][in_window][-1]
    auction = q5g.make_generator(seed, o)(0, n)[0]["auction"]
    at = np.flatnonzero(auction == winner)

    def lose(first, count):
        def drop(lo, m):
            idx = np.arange(lo, lo + m)
            return (idx >= first) & (idx < first + count)
        return q5g.rows_after(seed, n, o, drop, windows=(5, 6))

    want = {k: v[in_window] for k, v in some.items()}
    hit = q5g.compare(lose(int(at[0]), 400), want, o)
    assert hit["failed"] == 1 and hit["attempted"] == 1
    candidates = set(want["auction"].tolist())
    quiet = next(i for i in range(23_000, 6 * 23_000, 50)
                 if not candidates & set(auction[i:i + 50].tolist()))
    assert q5g.compare(lose(quiet, 50), want, o)["failed"] == 0


# ---------------------------------------------------- (d) the fire's spans


def test_the_fire_spans_count_cells_rows_and_removals():
    """``fire.shard``'s work is the cells that entered (every pair the
    batches gave a slot), ``carry.rows`` the distinct auctions of each
    window, and ``carry.removed``, summed over the job, the rows that
    entered less those held at its end: under ids that never come back a
    row enters once, so that is the distinct auctions of the stream less
    those of the last window."""
    from flink_tpu.observe import flight_recorder as flight

    cfg = tiny_config()
    o = cfg["job_options"]
    seed = 13
    run_job(cfg, seed, 180_000)                 # compiles land here
    rec = flight.recorder()
    rec.clear()
    sink, _, log = run_job(cfg, seed, 180_000)
    totals = rec.kind_totals()
    records = [r for r in rec.snapshot() if r.kind == "carry.rows"]
    gathered = [r for r in rec.snapshot() if r.kind == "fire.gather"]
    rec.clear()
    assert "xla.compile" not in totals
    fires = len(np.unique(sink.result()["window_end"]))
    shard = totals["fire.shard"]
    assert shard["count"] == fires
    assert totals["carry.rows"]["count"] == fires
    assert totals["carry.removed"]["count"] == fires
    assert totals["carry.rows"]["total_s"] == 0.0
    assert all(r.instant and r.parent == "fire.shard" for r in records)
    # every pair a batch gave a slot entered one fire's matrix and left
    # with its slice's whole table
    assert shard["work"] == totals["prep.resolve"]["work"] \
        == totals["retire.drop"]["work"] > 0
    per_window = [len(np.unique(auctions_of_window(seed, o, j, log.events)))
                  for j in range(fires)]
    assert [r.work for r in records] == per_window
    assert totals["carry.rows"]["work"] == sum(per_window)
    stream = q5g.make_generator(seed, o)(0, log.events)[0]["auction"]
    assert totals["carry.removed"]["work"] \
        == len(np.unique(stream)) - per_window[-1]
    assert fires == log.events // 23_000 + 4
    # an auction lives in one slice, or two where it straddles a boundary:
    # every fire is handed two columns of the window's five
    assert totals["fire.gather"]["count"] == fires
    for r, n in zip(gathered, per_window):
        assert_gathers(r.work, n, 2)
        assert r.instant and r.parent == "fire.dispatch"
