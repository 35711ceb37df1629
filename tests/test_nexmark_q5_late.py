"""NEXmark Query 5 under late events on the CPU at a small size: the job
through ``env.execute()`` against its plain reference with the late fires
and late records counted by hand, the two pairings that show why the
lateness is the guarantee, the stream against a loop-written
transcription of the source's rule, and the comparison's rule for a
window emitted more than once."""

import copy

import numpy as np
import pytest

from benchmark.harness import manifest, runner
from benchmark.harness.traffic import TimedSource
from benchmark.jobs import q5_generator as q5g
from benchmark.jobs import q5_late
from benchmark.jobs._hash import splitmix64
from flink_tpu.observe import flight_recorder as flight
from tests.test_fire_width import assert_gathers

MAN = manifest.manifest()
CONFIG = manifest.config(MAN, "nexmark-q5-late")
SLIDE_BIDS = 23_000         # bids per slide at the tiny size


def tiny_config(batch=None, **job_options):
    cfg = copy.deepcopy(CONFIG)
    cfg["options"].update(q5_late.TINY["options"])
    cfg["job_options"].update(q5_late.TINY["job_options"])
    cfg["job_options"].update(job_options)
    if batch:
        cfg["options"]["execution.micro-batch.size"] = batch
    return cfg


def run_job(cfg, seed, events):
    """The job to the end of its input; ``(sink, window operator, batch
    log, the recorder's work per span kind)``."""
    o = cfg["job_options"]
    source = TimedSource(q5_late.make_generator(seed, o),
                         {"mode": "backlog"}, q5_late.boundary_events(o),
                         min_events=events)
    flight.recorder().clear()
    sink, tap, *_ = runner.execute_job(q5_late, cfg, source)
    work = {kind: t["work"]
            for kind, t in flight.recorder().kind_totals().items()}
    (op,) = tap["ops"]
    return sink, op, source.log, work


def late_records_by_hand(seed, o, log):
    """Bids that arrive behind a window that has fired: after each batch
    the watermark is the newest ``dateTime`` so far less one (less the
    delay), and every window it has passed has fired."""
    gen = q5_late.make_generator(seed, o)
    slide, delay = int(o["slide_ms"]), int(o["watermark_delay_ms"])
    newest, late = -1, 0
    for first, n in zip(log.first, log.count):
        ts = gen(first, n)[1]
        if newest >= 0:
            fired_end = (newest - 1 - delay + 1) // slide * slide
            late += int((ts < fired_end).sum())
        newest = max(newest, int(ts.max()))
    return late


# ------------------------------------------------ (a) job == reference


@pytest.mark.parametrize("seed, batch", [(3, 1000), (1_000_003, 3000),
                                         (2_147_483_659, 7000)])
def test_the_last_emission_of_every_window_equals_the_reference(seed, batch):
    from flink_tpu import native

    cfg = tiny_config(batch)
    o = cfg["job_options"]
    sink, op, log, work = run_job(cfg, seed, 180_000)
    assert max(log.count) <= batch and log.events % SLIDE_BIDS == 0
    got = sink.result()
    verdict = q5_late.check(got, seed, log.events, o)
    assert verdict["numbers"]["rows_wrong"]["value"] == 0
    assert verdict["failed"] == 0
    windows = len(np.unique(got["window_end"]))
    assert verdict["attempted"] == windows >= log.events // SLIDE_BIDS + 4
    # no bid is past retention: 3,000 ms is the least lateness that holds
    assert op.windower.late_records_dropped == 0
    assert type(op.windower).__name__ == CONFIG["expect"]["engine"]
    assert native.native_fallbacks() == 0
    # every write is one fire of one window: those past a window's first
    # are the late fires, and the bids behind a fired window the late
    # records
    fires = len(sink.stamps)
    assert len(np.unique(got["emission"])) == fires > 2 * windows
    assert work["fire.late"] == fires - windows
    assert work["late.records"] == late_records_by_hand(seed, o, log)
    assert 0.03 < work["late.records"] / log.events < 0.07
    assert work["resolve.sweep"] == log.events      # every batch swept
    # an auction is bid on for 1,702 bids, a late bid keeps its own
    # dateTime: no key lives in more than two of a window's five slices,
    # so every fire, late ones too, is handed two columns
    records = flight.recorder().snapshot()
    gathered = [r.work for r in records if r.kind == "fire.gather"]
    rows = [r.work for r in records if r.kind == "carry.rows"]
    assert len(gathered) == len(rows) == fires
    for cells, n in zip(gathered, rows):
        assert_gathers(cells, n, 2)


def test_without_its_allowed_lateness_the_job_is_not_correct():
    # the control a user would recognise: a bid behind a window that has
    # fired is missing from it for good
    cfg = tiny_config(allowed_lateness_ms=0)
    o = cfg["job_options"]
    sink, op, log, work = run_job(cfg, 7, 460_000)
    verdict = q5_late.check(sink.result(), 7, log.events, o)
    assert verdict["numbers"]["rows_wrong"]["value"] > 100
    assert verdict["failed"] > verdict["attempted"] // 2
    assert len(sink.stamps) == verdict["attempted"]     # no window twice
    assert "fire.late" not in work and "late.records" not in work


def test_under_the_ddls_watermark_nothing_is_late():
    # PERF.md section 7 row 5 as it was written paired this disorder with
    # the DDL's 4 s watermark delay: a bid held back by at most 3 s is
    # never behind it, so every window fires once and is exact
    cfg = tiny_config(watermark_delay_ms=4000, allowed_lateness_ms=0)
    o = cfg["job_options"]
    sink, op, log, work = run_job(cfg, 7, 460_000)
    verdict = q5_late.check(sink.result(), 7, log.events, o)
    assert verdict["failed"] == 0 and verdict["attempted"] > 20
    assert len(sink.stamps) == verdict["attempted"]
    assert op.windower.late_records_dropped == 0
    assert "fire.late" not in work and "late.records" not in work


# ------------------------------------------------ (b) the stream


def transcription(seed, o, bids):
    """The source's rule as a loop over the in-order bids ``0 ..
    bids-1``: ``(the indices in the order they are handed over while
    every index under ``bids`` that could still arrive has, held flags
    by index)``."""
    places = q5_late.delay_places(o)
    u64 = splitmix64(np.arange(bids, dtype=np.int64), int(seed) * 4 + 1)
    below = round(float(o["prob_delayed_event"]) * 65536)
    waiting, order, held = {}, [], []
    for i, u in enumerate(u64.tolist()):
        order.extend(waiting.pop(i, ()))        # those due now, oldest first
        is_held = u % 65536 < below
        held.append(is_held)
        if is_held:
            waiting.setdefault(i + 1 + (u >> 16) % places, []).append(i)
        else:
            order.append(i)
    return order, np.array(held)


@pytest.mark.parametrize("seed", [5, 2_147_483_659])
def test_the_stream_is_the_sources_rule(seed):
    o = tiny_config()["job_options"]
    bids = 120_000
    order, held = transcription(seed, o, bids)
    (auction_of, ts_of) = q5g.make_generator(seed, o)(0, bids)
    auction_of = auction_of["auction"]
    order = np.array(order)
    cols, ts = q5_late.make_generator(seed, o)(0, len(order))
    assert (cols["auction"] == auction_of[order]).all()
    assert (ts == ts_of[order]).all()
    # every bid of the in-order stream exactly once: those handed over and
    # those still held are all of them
    assert len(np.unique(order)) == len(order)
    assert len(order) + int(held[np.setdiff1d(np.arange(bids),
                                              order)].sum()) == bids
    assert 0.095 < held.mean() < 0.105
    # no dateTime more than 3,000 ms behind the newest before it, and a
    # bid that was held does trail
    trail = np.maximum.accumulate(ts) - ts
    limit = int(o["occasional_delay_sec"]) * 1000
    assert limit - 10 < trail.max() <= limit
    assert 0.05 < (trail > 0).mean() < 0.10
    assert not (trail[~held[order]] > 0).any()


def test_the_generator_is_a_function_of_first_and_n():
    o = tiny_config()["job_options"]
    whole = q5_late.make_generator(9, o)(0, 200_000)
    gen = q5_late.make_generator(9, o)
    rng = np.random.default_rng(1)
    for first in [0, 150_000, 150_007, 40_000, 199_999,
                  *rng.integers(0, 190_000, 6).tolist()]:
        n = min(int(rng.integers(1, 9000)), 200_000 - first)
        cols, ts = gen(first, n)                # anywhere ...
        more, ts_more = gen(first + n, 100)     # ... and on from there
        for got, want in ((cols["auction"], whole[0]["auction"]),
                          (ts, whole[1])):
            assert (got == want[first:first + n]).all()
        stop = min(first + n + 100, 200_000)
        assert (ts_more[:stop - first - n] == whole[1][first + n:stop]).all()
    assert len(gen(77, 0)[1]) == 0


# ------------------------------------------------ (c) the comparison


def earlier(rows, less):
    """An earlier emission of a window: each count ``less`` lower."""
    return [(a, c - less) for a, c in rows]


def with_emissions(want, end, emissions):
    """``want`` with window ``end``'s rows replaced by ``emissions``
    (each a list of ``(auction, count)``), written one after another in
    the window's place."""
    keep = want["window_end"] != end
    at = int(np.argmax(~keep))
    number = int(want["emission"].max()) + 1
    new = {name: [] for name in q5_late.SINK_COLUMNS}
    for k, rows in enumerate(emissions):
        for auction, count in rows:
            for name, v in zip(q5_late.SINK_COLUMNS,
                               (end, auction, count, number + k)):
                new[name].append(v)
    return {name: np.concatenate([
        want[name][:at], np.array(new[name], dtype=np.int64),
        want[name][keep][at:]]) for name in q5_late.SINK_COLUMNS}


@pytest.mark.parametrize("case, failed", [
    ("two adjacent emissions of one window", 0),
    ("three, the first two alike", 0),
    ("a count that falls between emissions", 1),
    ("a wrong last emission after right earlier ones", 1),
    ("a window never emitted", 1),
    ("a window the reference lacks", 1)])
def test_a_window_emitted_more_than_once(case, failed):
    o = tiny_config()["job_options"]
    want = q5_late.reference_rows(11, 4 * SLIDE_BIDS, o)
    ends = np.unique(want["window_end"])
    end = int(ends[len(ends) // 2])
    rows = q5_late.emissions(want)[end][-1][-16:]   # no tie at the 16th
    assert q5_late.compare(want, want, o)["failed"] == 0
    got = {
        "two adjacent emissions of one window":
            lambda: with_emissions(want, end, [earlier(rows, 3), rows]),
        "three, the first two alike":
            lambda: with_emissions(want, end, [earlier(rows, 1),
                                               earlier(rows, 1), rows]),
        "a count that falls between emissions":
            lambda: with_emissions(want, end, [
                [(rows[0][0], rows[0][1] + 1)] + rows[1:], rows]),
        "a wrong last emission after right earlier ones":
            lambda: with_emissions(want, end, [rows, earlier(rows, -1)]),
        "a window never emitted":
            lambda: with_emissions(want, end, []),
        "a window the reference lacks":
            lambda: with_emissions(want, int(ends[-1]) + 2000, [rows]),
    }[case]()
    verdict = q5_late.compare(got, want, o)
    assert verdict["failed"] == failed
    assert (verdict["numbers"]["rows_wrong"]["value"] > 0) == bool(failed)
    assert verdict["attempted"] == len(ends)


def test_the_control_discards_every_held_bid():
    o = tiny_config()["job_options"]
    n = 6 * SLIDE_BIDS
    want = q5_late.reference_rows(5, n, o)
    shed = q5_late.reference_rows(5, n, o, control=True)
    verdict = q5_late.compare(shed, want, o)
    assert verdict["failed"] == verdict["attempted"]    # every window
    # what is left out is what was held: a tenth of the bids handed over
    held = q5_late._Stream(5, o).take(0, n)[2]
    whole, _ = q5_late.slice_counts(5, n, o)
    rest, _ = q5_late.slice_counts(5, n, o, leave_out_held=True)
    bids = [sum(int(c.sum()) for _, c in slices.values())
            for slices in (whole, rest)]
    assert bids == [n, n - int(held.sum())]
