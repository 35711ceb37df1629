"""NEXmark Query 11 (user sessions): the job, its traffic and its plain
reference.

``SELECT bidder, count(*), SESSION_START, SESSION_END FROM bid GROUP BY
bidder, SESSION(dateTime, gap)``: how many bids a user made in each session
they were active. The job is built through the public API; the generator
and the reference are the benchmark's own (NumPy only, nothing of the
program beyond the ``RecordBatch`` the source interface hands over).

The bidders are the NEXmark generator's own (Beam ``nexmark/sources/
generator`` ``BidGenerator.nextBid`` / ``PersonGenerator.nextBase0PersonId``,
kept by ``nexmark/nexmark``). Event numbers run in epochs of
``person + auction + bid`` proportions (1 + 3 + 46 = 50): one Person, three
Auctions, then 46 Bids. Only the bids are made here, but numbered as the
full stream numbers them: bid ``i`` is event
``e = (i // 46) * 50 + 4 + i % 46`` and the last person made before it is
``p = e // 50``. With probability ``1 - 1/hot_bidders_ratio`` the bidder is
the hot one of its stride, ``(p // hot_bidder_stride) * hot_bidder_stride +
1``; otherwise uniform over the last ``active = min(p + 1,
num_active_people)`` people plus ``person_id_lead`` ids not yet made:
``p + 1 - active + uniform[0, active + lead)``. Ids are offset by
``first_person_id``. ``dateTime`` is a function of the event number:
``e * 1000 // event_rate`` ms.

Options (a configuration's ``job_options``): ``gap_ms``,
``hot_bidders_ratio``, ``num_active_people``, ``person_id_lead``,
``hot_bidder_stride``, ``first_person_id``, ``person_proportion``,
``auction_proportion``, ``bid_proportion``, ``event_rate`` (NEXmark events —
of all three kinds — per second of event time), ``warmup_events``,
``control_lost_events``.
"""

import numpy as np

from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = ("window_end", "window_start", "bidder", "count")

#: the job at a size a CPU test can hold: laid over a configuration's
#: ``options`` and ``job_options`` by the harness's tests, scale cut only
#: (the gap, the ratios and the proportions stay). At 2,500 events per
#: second of event time a bidder stays active for 20 s of it, twice the
#: gap, so some bidders hold two or three sessions; the gap passes every
#: third micro-batch.
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 16},
    "job_options": {"event_rate": 2500, "warmup_events": 163_840,
                    "control_lost_events": 8192},
}


#: bids generated at a time
PIECE = 16384
#: bids the reference cuts into runs at a time
STRETCH = 1 << 18


def _epoch(o):
    """``(bids per epoch, events per epoch, events before the bids)``."""
    before = int(o["person_proportion"]) + int(o["auction_proportion"])
    bids = int(o["bid_proportion"])
    return bids, before + bids, before


def make_generator(seed, o):
    """``gen(first, n)`` -> the bids with global bid indices
    ``first .. first+n-1``. One hash per bid; the hot draw is cut from its
    low 16 bits and the cold bidder from the next 32."""
    salt = int(seed) * 4 + 3
    bids, total, before = _epoch(o)
    person = int(o["person_proportion"])
    ratio = int(o["hot_bidders_ratio"])
    stride = int(o["hot_bidder_stride"])
    active_max = int(o["num_active_people"])
    lead = int(o["person_id_lead"])
    first_id = int(o["first_person_id"])
    rate = int(o["event_rate"])

    def part(first, n, bidder, ts):
        """Fills ``bidder`` and ``ts`` for the ``n`` bids from ``first``."""
        idx = np.arange(first, first + n, dtype=np.int64)
        # the indices are consecutive, so what depends on the epoch alone
        # is worked out once per epoch and repeated over its bids
        e0 = first // bids
        epochs = np.arange(e0, (first + n - 1) // bids + 1, dtype=np.int64)
        lo = first - e0 * bids
        # lastBase0PersonId: the last person of this epoch (a bid's offset
        # in its epoch is past the people)
        last_person = epochs * person + (person - 1)
        active = np.minimum(last_person + 1, active_max)
        per_epoch = np.stack([
            epochs * (total - bids) + before,     # event number - bid index
            (last_person // stride) * stride + (1 + first_id),  # hot one
            last_person + 1 - active + first_id,  # oldest active person
            active + lead])                       # ids a cold draw spans
        shift, hot_id, oldest, span = np.repeat(
            per_epoch, bids, axis=1)[:, lo:lo + n]
        u64 = splitmix64(idx, salt)
        # random.nextInt(hotBiddersRatio) > 0
        hot = (u64 & np.uint64(0xFFFF)).astype(np.int64) * ratio >= 1 << 16
        draw = (((u64 >> np.uint64(16)) & np.uint64(0xFFFFFFFF)
                 ).astype(np.int64) * span) >> 32
        np.add(oldest, draw, out=bidder)
        np.copyto(bidder, hot_id, where=hot)
        idx += shift
        idx *= 1000
        np.floor_divide(idx, rate, out=ts)

    def gen(first, n):
        # in pieces that stay in the cache: a 1 MB temporary per operation
        # costs more in page faults than the arithmetic on it
        bidder = np.empty(n, dtype=np.int64)
        ts = np.empty(n, dtype=np.int64)
        for a in range(0, n, PIECE):
            b = min(a + PIECE, n)
            part(first + a, b - a, bidder[a:b], ts[a:b])
        return {"bidder": bidder}, ts

    return gen


def boundary_events(o):
    """Bids per second of event time: the offered stream ends on a
    multiple of it. (A session flush needs no boundary; the tail only has
    to be a known number of batches of at least half a batch.)"""
    bids, total, _ = _epoch(o)
    return int(o["event_rate"]) * bids // total


def warmup_events(o):
    return int(o["warmup_events"])


def first_index_with_ts(ts_ms, o):
    """Global bid index of the first bid whose event time is at least
    ``ts_ms`` (event time is a function of the index)."""
    bids, total, before = _epoch(o)
    event = -(-int(ts_ms) * int(o["event_rate"]) // 1000)
    epoch, offset = divmod(event, total)
    return epoch * bids + max(offset - before, 0)


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, window
    transformation)``: the stream to sink and the transformation whose
    operator holds the session state."""
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.aggregates import CountAggregate
    from flink_tpu.windowing.assigners import EventTimeSessionWindows

    sessions = (
        env.from_source(source,
                        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("bidder")
        .window(EventTimeSessionWindows.with_gap(int(o["gap_ms"])))
        .aggregate(CountAggregate()))
    return sessions, sessions.transformation


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink must hold for the first ``n_events`` bids, as
    columns: a bidder's bids in event-time order, cut where the gap to the
    previous one is more than ``gap_ms``; a session runs from its first bid
    to ``gap_ms`` past its last. Sessions still open at the end of the
    input are rows too (the final watermark closes them).
    ``control=True`` computes them under at-most-once delivery: one
    micro-batch of bids, drawn from the seed, is lost — the guarantee
    "every event counted exactly once" broken.

    Streamed: each stretch of the stream is cut into runs of bids, and
    the runs of all stretches are cut again as if each were one bid —
    the same rule twice, so a session that spans two stretches is whole."""
    lose = (0, 0)
    if control:
        batch = int(o["control_lost_events"])
        slots = max(n_events // batch, 1)
        lose = (int(splitmix64(np.array([n_events]), int(seed))[0]
                    % np.uint64(slots)) * batch, batch)
    gen, gap = make_generator(seed, o), int(o["gap_ms"])

    def runs_of(first):
        n = min(STRETCH, n_events - first)
        cols, ts = gen(first, n)
        bidder = cols["bidder"]
        lo, hi = lose[0] - first, lose[0] + lose[1] - first
        if lo < n and hi > 0:
            keep = np.ones(n, dtype=bool)
            keep[max(lo, 0):max(hi, 0)] = False
            bidder, ts = bidder[keep], ts[keep]
        return sessions_of(bidder, ts, gap)

    runs = [runs_of(first) for first in range(0, n_events, STRETCH)]
    whole = {c: np.concatenate([r[c] for r in runs]) for c in SINK_COLUMNS}
    return sessions_of(whole["bidder"], whole["window_start"], gap,
                       last=whole["window_end"] - gap, count=whole["count"])


def sessions_of(bidder, ts, gap, last=None, count=None):
    """The session rows of a stream of bids whose event time never falls:
    one row per run of a bidder's bids at most ``gap`` apart. The rule is
    the source's engine's: ``queries/q11.sql`` runs on Flink, where each
    bid opens ``[ts, ts + gap)`` and windows that overlap OR TOUCH merge
    (``TimeWindow.intersects``: ``start <= other.end && end >=
    other.start``), so two bids exactly ``gap`` apart share a session and
    ``gap + 1`` ms apart do not. (Beam's ``Sessions`` cuts at exactly
    ``gap``; the two agree on every stream without such a pair.)

    With ``last`` and ``count`` each item is a run of bids from ``ts`` to
    ``last`` and no longer a single bid; the items of one bidder come in
    time order and do not overlap."""
    if not len(bidder):
        return {c: np.zeros(0, dtype=np.int64) for c in SINK_COLUMNS}
    # a stable sort by bidder keeps each bidder's items in time order; the
    # ids of a stretch lie close together, and NumPy sorts narrow integers
    # by radix
    base = bidder.min()
    key = (bidder - base).astype(np.min_scalar_type(int(bidder.max() - base)))
    order = np.argsort(key, kind="stable")
    bidder, ts = bidder[order], ts[order]
    last = ts if last is None else last[order]
    opens = np.ones(len(bidder), dtype=bool)
    opens[1:] = (bidder[1:] != bidder[:-1]) | (ts[1:] - last[:-1] > gap)
    head = np.flatnonzero(opens)
    tail = np.append(head[1:], len(bidder)) - 1
    return {"window_end": last[tail] + gap, "window_start": ts[head],
            "bidder": bidder[head],
            "count": (tail - head + 1 if count is None
                      else np.add.reduceat(count[order], head))}


def _distinct_rows(cols):
    """The distinct rows of ``cols`` in sorted order, and how often each
    occurs."""
    rows = np.stack([np.asarray(cols[n], dtype=np.int64)
                     for n in SINK_COLUMNS], axis=1)
    if not len(rows):
        return rows, np.zeros(0, dtype=np.int64)
    rows = rows[np.lexsort(rows.T[::-1])]
    new = np.ones(len(rows), dtype=bool)
    new[1:] = (rows[1:] != rows[:-1]).any(axis=1)
    at = np.flatnonzero(new)
    return rows[at], np.diff(np.append(at, len(rows)))


def compare(got, want, o):
    """Numbers compared, each beside its limit, and the sessions that
    failed. Exact: starts, ends and counts are integers. A session is
    (bidder, end); one that has a row on one side only, or a row twice,
    failed."""
    g, g_counts = _distinct_rows(got)
    w, _ = _distinct_rows(want)
    if g.shape == w.shape and (g == w).all():
        wrong = g[:0]
    else:
        both, sides = _distinct_rows(
            {n: np.concatenate([g[:, i], w[:, i]])
             for i, n in enumerate(SINK_COLUMNS)})
        wrong = both[sides == 1]
    doubled = g[g_counts > 1]
    end, bidder = SINK_COLUMNS.index("window_end"), \
        SINK_COLUMNS.index("bidder")
    failed = {(r[bidder], r[end])
              for r in np.concatenate([wrong, doubled]).tolist()}
    return {"numbers": {"rows_wrong": {
                "value": len(wrong) + int((g_counts - 1).sum()),
                "limit": 0}},
            "attempted": len(w),
            "failed": len(failed)}


def check(got, seed, n_events, o):
    """``compare`` against the reference of the first ``n_events`` bids."""
    return compare(got, reference_rows(seed, n_events, o), o)


def sessions_per_window_end(o):
    """Least sessions per distinct ``window_end`` millisecond. Every
    person bids (about 11 cold bids each) and holds one session where its
    active time is under the gap, so one second of event time closes
    ``event_rate / 50`` sessions on at most 1,000 distinct milliseconds;
    and never under one."""
    _, total, _ = _epoch(o)
    people_per_ms = int(o["event_rate"]) * int(o["person_proportion"]) \
        / total / 1000.0
    return max(people_per_ms, 1.0)


def work(n_events, fired_windows, o):
    """Bytes the job's device work needs, from the traffic alone (terms in
    ``benchmark/harness/work.py``): COUNT has one int32 accumulator leaf
    and no value column; a fired session reads its one accumulator and
    writes one int32 count. ``fired_windows`` is what the sink's stamps
    count — distinct ``window_end`` milliseconds, not sessions — so it is
    scaled by the least sessions that share one: a floor."""
    from benchmark.harness.work import window_state_bytes

    sessions = fired_windows * sessions_per_window_end(o)
    return window_state_bytes(
        events=n_events, value_bytes_per_event=0, leaf_bytes=(4,),
        fired_cells=sessions, emitted_rows=sessions, row_bytes=4)
