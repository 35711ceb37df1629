"""NEXmark Query 5 (hot items) under late events: the job, its traffic and
its plain reference.

The query, the auctions and the 16 candidate rows per fire are
``q5_generator.py``'s. What is new is the order in which the bids arrive
and what the job does about it.

**The stream** is the one Apache Beam's NEXmark suite offers in streaming
mode by default (``sdks/java/testing/nexmark`` ``NexmarkConfiguration``:
``probDelayedEvent`` 0.1, ``occasionalDelaySec`` 3, ``watermarkHoldbackSec``
0; ``sources/UnboundedEventSource.EventReader.advance``: with that
probability an event is held back ``nextLong(occasionalDelaySec * 1000) +
1`` ms and handed out later with its event time unchanged, while the
source's watermark follows the generator's clock). Here, so that the
stream is a function of the seed: bid ``i`` is ``q5_generator``'s bid
``i`` (same auction, same ``dateTime``). With probability
``prob_delayed_event`` (one hash of ``(seed, i)`` under a salt of its own,
its low 16 bits) it is held back by ``1 + (hash >> 16) % D`` bid places,
``D`` = the bids in ``occasional_delay_sec`` of event time. Its arrival
key is ``a(i) = i + delay(i)`` (``delay`` 0 when not held), and
**position ``p`` of the stream holds the bid with the ``p``-th smallest
``(a(i), i)``**. So for ``A = 0, 1, 2, ...`` the stream hands over the
held bids arriving at ``A``, oldest first, and then bid ``A`` itself
unless it is held. A held bid's ``dateTime`` trails the newest handed
over before it by at most ``occasional_delay_sec`` seconds (every bid
before it has an index under ``i + D``), which is why an allowed lateness
of that many seconds drops nothing. ``gen(first, n)`` returns positions
``first .. first+n-1`` and is a pure function of ``(seed, first, n)``: a
cursor (``_Stream``) caches the held bids in flight between consecutive
calls and seeks anywhere else — the position of the first bid with ``a >=
A`` is ``A - #{i < A : a(i) >= A}``, which the ``D`` bids before ``A``
decide.

**The job** reads it under a watermark with no holdback (the newest
``dateTime`` - 1) and ``allowed_lateness_ms``: a window is first emitted
when the watermark passes its end and emitted again by every micro-batch
that brings a late bid for it. What is sunk is each fire's 16 candidate
rows, every emission, in write order, with a fourth column ``emission``
that a map in ``build`` sets to the number of the fired batch (1, 2, ...
in write order): a window's emissions are its rows grouped by that
number, in rising order. Two emissions of one window with nothing
between them do happen (a window alone inside the last 3 s is re-fired
by consecutive batches); the column keeps them apart where the rows
alone could not.

**The reference** regenerates the first ``n`` positions (what a run of
``n`` events handed over) and bins each bid by ``dateTime // slide``: the
order of arrival plays no part in it. **The comparison** holds the LAST
emission of every window to the reference by ``q5_generator.
window_faults``' rule, exactly, and across a window's emissions no
auction's count may fall. What it does NOT see: a fault confined to an
emission that is later superseded, beyond that monotone rule. **The
control** leaves every held-back bid out of the reference — a pipeline
that discards late data: every hot auction loses a tenth of its bids, so
every window shows it.

``first_index_with_ts(ts)`` is ``q5_generator``'s: a position from which
no bid that was NOT held back is older than ``ts`` (position ``p`` holds
an arrival key of at least ``p``, and a bid not held arrives at its own
index). It is not the first such position — that one lies the held bids
in flight (about ``prob * D / 2``) earlier and depends on the seed; only
a paced cell reads it.

Options (a configuration's ``job_options``): ``q5_generator``'s, and
``prob_delayed_event``, ``occasional_delay_sec``, ``allowed_lateness_ms``.
"""

import itertools

import numpy as np

from benchmark.jobs import q5_generator as q5g
from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = ("window_end", "auction", "count", "emission")

#: the job at a size a CPU test can hold (``q5_generator.TINY``'s cut): a
#: slide holds 23,000 bids, the 3 s of delay 34,500 places, an 8,192-bid
#: batch 0.36 s of event time
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 16},
    "job_options": {"event_rate": 12_500, "warmup_events": 345_000},
}

#: arrival keys worked through at a time: arrays that stay in the cache
#: (a 1 MB temporary per operation costs more in page faults than the
#: arithmetic on it)
PIECE = 16384
#: positions the reference counts at a time
STRETCH = 1 << 20

first_index_with_ts = q5g.first_index_with_ts
boundary_events = q5g.boundary_events
warmup_events = q5g.warmup_events
work = q5g.work


def delay_places(o):
    """``D``: the bids in ``occasional_delay_sec`` of event time."""
    return q5g.first_index_with_ts(
        int(o["occasional_delay_sec"]) * 1000, o)


def held_back(seed, o):
    """``held(first, n)`` -> ``(which, places)``: of the bids ``first ..
    first+n-1`` those held back, counted from ``first``, and the bid
    places each is held back by."""
    salt = int(seed) * 4 + 1
    places = np.uint64(delay_places(o))
    below = np.uint16(round(float(o["prob_delayed_event"]) * (1 << 16)))

    def held(first, n):
        u64 = splitmix64(np.arange(first, first + n, dtype=np.int64), salt)
        which = np.flatnonzero(u64.astype(np.uint16) < below)
        return which, ((u64[which] >> np.uint64(16)) % places
                       ).astype(np.int64) + 1

    return held


class _Stream:
    """The stream's positions in order from wherever it was last asked
    for: ``take(first, n)`` -> ``(auction, dateTime, held)`` of positions
    ``first .. first+n-1``. It works through the arrival keys a ``PIECE``
    at a time and keeps, between consecutive calls, the held bids in
    flight under the piece they arrive in (``flying``: order key,
    auction, dateTime) and what it made beyond the call; any other
    ``first`` seeks.

    The order key of a held bid is ``arrival * (D + 1) + D - delay``:
    ascending in ``(arrival, index)``, since of two bids arriving at one
    key the older was held longer."""

    def __init__(self, seed, o):
        self.bids = q5g.make_generator(seed, o)
        self.held = held_back(seed, o)
        self.places = delay_places(o)
        self._seek(0)

    def _bids(self, first, n):
        cols, ts = self.bids(first, n)
        return cols["auction"], ts

    def _hold(self, first, auction, ts, arriving_from=0):
        """Puts the held bids among ``first .. first+len(auction)-1``
        that arrive at ``arriving_from`` or later in flight, under the
        piece each arrives in; returns all the held ones, counted from
        ``first``."""
        held, places = self.held(first, len(auction))
        key = (first + held + places) * (self.places + 1) \
            + (self.places - places)
        order = np.argsort(key)         # no two keys are equal
        order = order[np.searchsorted(
            key[order], arriving_from * (self.places + 1)):]
        columns = (key[order], auction[held[order]], ts[held[order]])
        piece = columns[0] // ((self.places + 1) * PIECE)
        cuts = [0, *(np.flatnonzero(np.diff(piece)) + 1).tolist(),
                len(piece)]
        for lo, hi in zip(cuts, cuts[1:]):
            if hi > lo:
                self.flying.setdefault(int(piece[lo]), []).append(
                    tuple(c[lo:hi] for c in columns))
        return held

    def _land(self, piece):
        """Takes the held bids arriving in ``piece`` out of flight:
        ``(arrival key, auction, dateTime)`` in the order they arrive."""
        parts = self.flying.pop(piece, None)
        if parts is None:
            return (np.zeros(0, dtype=np.int64),) * 3
        key, auction, ts = (np.concatenate(c) for c in zip(*parts))
        order = np.argsort(key)
        return key[order] // (self.places + 1), auction[order], ts[order]

    def _seek(self, first):
        """Stand at the start of the piece of arrival keys that holds
        ``first``. Position ``first`` holds an arrival key of at least
        ``first``; the bids in flight at a key are the held ones of the
        ``D`` before it that arrive at it or later, and as many positions
        under the key are still to come."""
        self.key = first // PIECE * PIECE   # next arrival key to work on
        self.flying = {}
        lo = max(self.key - self.places, 0)
        self._hold(lo, *self._bids(lo, self.key - lo),
                   arriving_from=self.key)
        in_flight = sum(len(part[0]) for parts in self.flying.values()
                        for part in parts)
        self.at = first                 # next position to hand over
        self.skip = first - (self.key - in_flight)  # made before it
        # made and not yet handed over
        self.made = (np.zeros(0, dtype=np.int64),) * 3

    def _piece(self):
        """Work through arrival keys ``key .. key+PIECE-1``: at each the
        held bids arriving at it, oldest first, then the bid of that
        index unless it is held itself."""
        key, end = self.key, self.key + PIECE
        auction, ts = self._bids(key, PIECE)
        held = self._hold(key, auction, ts)
        arrival, auction_due, ts_due = self._land(key // PIECE)
        arrival = arrival - key
        on_time = np.ones(PIECE, dtype=bool)
        on_time[held] = False
        on_time = np.flatnonzero(on_time)
        # a held bid arriving at `a` stands behind every bid not held
        # with an index under `a` and the held bids due before it; a bid
        # not held behind those under it and the held bids due by then
        place_due = arrival - np.searchsorted(held, arrival) \
            + np.arange(len(arrival))
        place = np.cumsum(np.bincount(arrival, minlength=PIECE))[on_time] \
            + np.arange(len(on_time))
        total = len(on_time) + len(arrival)
        is_held = np.zeros(total, dtype=bool)
        is_held[place_due] = True
        out_auction = np.empty(total, dtype=np.int64)
        out_ts = np.empty(total, dtype=np.int64)
        out_auction[place_due] = auction_due
        out_ts[place_due] = ts_due
        out_auction[place] = auction[on_time]
        out_ts[place] = ts[on_time]
        self.key = end
        return out_auction, out_ts, is_held

    def take(self, first, n):
        if first != self.at:
            self._seek(first)
        out = (np.empty(n, dtype=np.int64), np.empty(n, dtype=np.int64),
               np.empty(n, dtype=bool))
        filled = 0
        while filled < n:
            if not len(self.made[0]):
                self.made = self._piece()
            drop = min(self.skip, len(self.made[0]))
            take = min(len(self.made[0]) - drop, n - filled)
            for column, made in zip(out, self.made):
                column[filled:filled + take] = made[drop:drop + take]
            self.made = tuple(c[drop + take:] for c in self.made)
            self.skip -= drop
            filled += take
        self.at = first + n
        return out


def make_generator(seed, o):
    """``gen(first, n)`` -> the bids at positions ``first .. first+n-1``
    of the stream, as ``({"auction": ...}, dateTime)``."""
    stream = _Stream(seed, o)

    def gen(first, n):
        auction, ts, _ = stream.take(first, n)
        return {"auction": auction}, ts

    return gen


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, window
    transformation)``: the stream to sink — each fire's candidate rows as
    they left the device, numbered by fired batch — and the transformation
    whose operator holds the window state."""
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.aggregates import CountAggregate
    from flink_tpu.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.windowing.fire_projectors import TopKFireProjector

    numbers = itertools.count(1)

    def number_the_emission(batch):
        # a fired batch is one emission of one window
        return batch.with_column(
            "emission", np.full(len(batch), next(numbers), dtype=np.int64))

    counts = (
        env.from_source(source,
                        WatermarkStrategy.for_bounded_out_of_orderness(
                            int(o["watermark_delay_ms"])))
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(int(o["size_ms"]),
                                           int(o["slide_ms"])))
        .allowed_lateness(int(o["allowed_lateness_ms"]))
        .aggregate(CountAggregate(), fire_projector=TopKFireProjector(
            "count", k=int(o["device_top_k"]))))
    return counts.map(number_the_emission, name="emission_number"), \
        counts.transformation


def slice_counts(seed, n_events, o, leave_out_held=False):
    """``({slice number: (lowest auction id, bids per id from it on)},
    newest dateTime)`` of the first ``n_events`` positions of the stream,
    each bid binned by its own ``dateTime``."""
    stream = _Stream(seed, o)
    slide = int(o["slide_ms"])
    slices, newest = {}, -1
    for first in range(0, n_events, STRETCH):
        auction, ts, held = stream.take(first, min(STRETCH,
                                                   n_events - first))
        if leave_out_held:
            auction, ts = auction[~held], ts[~held]
        if not len(ts):
            continue
        newest = max(newest, int(ts.max()))
        # a stretch names few slices and a short range of ids: one count
        # over (slice, id), then each slice's row cut to what it names
        number = ts // slide
        s0, low = int(number.min()), int(auction.min())
        width = int(auction.max()) - low + 1
        table = np.bincount((number - s0) * width + (auction - low),
                            minlength=(int(number.max()) - s0 + 1) * width
                            ).reshape(-1, width)
        for j, row in enumerate(table, start=s0):
            named = np.flatnonzero(row)
            if len(named):
                part = (low + int(named[0]), row[named[0]:named[-1] + 1])
                slices[j] = q5g._added(
                    slices.get(j, (0, np.zeros(0, dtype=np.int64))), part)
    return slices, newest


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink's last emission of every window must hold for
    the first ``n_events`` positions: per window the ``device_top_k``
    largest counts with every auction tied at the last of them, smallest
    count first, ``emission`` the window's number. ``control=True``
    leaves every held-back bid out: a pipeline that discards late data —
    the guarantee "a bid up to 3 s late is counted" broken."""
    slices, newest = slice_counts(seed, n_events, o, leave_out_held=control)
    slide = int(o["slide_ms"])
    k = int(o["size_ms"]) // slide
    top = int(o["device_top_k"])
    n_slices = newest // slide + 1
    cols = {name: [] for name in SINK_COLUMNS}
    for j in range(n_slices + k - 1):   # window = slices j-k+1 .. j
        base, c = 0, np.zeros(0, dtype=np.int64)
        for s in range(max(j - k + 1, 0), min(j, n_slices - 1) + 1):
            if s in slices:
                base, c = q5g._added((base, c), slices[s])
        slices.pop(j - k + 1, None)     # the next window starts past it
        live = np.flatnonzero(c)
        if not len(live):
            continue
        if len(live) > top:
            least = np.partition(c[live], len(live) - top)[len(live) - top]
            live = live[c[live] >= least]
        live = live[np.argsort(c[live], kind="stable")]
        cols["window_end"].append(
            np.full(len(live), (j + 1) * slide, dtype=np.int64))
        cols["auction"].append(live + base)
        cols["count"].append(c[live])
        cols["emission"].append(np.full(len(live), j, dtype=np.int64))
    return {name: (np.concatenate(parts) if parts
                   else np.zeros(0, dtype=np.int64))
            for name, parts in cols.items()}


def emissions(cols):
    """``{window_end: [[(auction, count), ...] of each emission, in
    rising order of its number]}``."""
    columns = (np.asarray(cols[n], dtype=np.int64).tolist()
               for n in SINK_COLUMNS)
    by_window = {}
    for end, auction, count, number in zip(*columns):
        by_window.setdefault(end, {}).setdefault(number, []).append(
            (auction, count))
    return {end: [rows for _, rows in sorted(by_number.items())]
            for end, by_number in by_window.items()}


def counts_that_fell(window):
    """Auctions whose count in one emission is under what an earlier
    emission of the same window gave them, counted once per fall."""
    seen, falls = {}, 0
    for rows in window:
        for auction, count in rows:
            falls += count < seen.get(auction, count)
            seen[auction] = count
    return falls


def compare(got, want, o):
    """Numbers compared, each beside its limit, and the windows that
    failed. Exact: auctions and counts are integers. A window's last
    emission is held to the reference; its earlier ones only to the rule
    that no count falls."""
    top = int(o["device_top_k"])
    g, w = emissions(got), emissions(want)
    wrong = failed = 0
    for end in g.keys() | w.keys():
        if end not in w:
            faults = sum(len(rows) for rows in g[end])
        elif end not in g:
            faults = min(top, len(w[end][-1]))
        else:
            faults = q5g.window_faults(g[end][-1], w[end][-1], top) \
                + counts_that_fell(g[end])
        wrong += faults
        failed += faults > 0
    return {"numbers": {"rows_wrong": {"value": wrong, "limit": 0}},
            "attempted": len(w),
            "failed": failed}


def check(got, seed, n_events, o):
    """``compare`` against the reference of the first ``n_events``
    positions."""
    return compare(got, reference_rows(seed, n_events, o), o)
