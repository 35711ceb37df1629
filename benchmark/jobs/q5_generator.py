"""NEXmark Query 5 (hot items) over the NEXmark generator's own auctions:
the job, its traffic and its plain reference.

``HOP(dateTime, slide, size)`` ``COUNT(*)`` per auction, the rows with the
maximum count per window (``nexmark/nexmark`` ``queries/q5.sql``), over the
``bid`` view of ``ddl_gen.sql``, whose watermark trails ``dateTime`` by
4 s. The job is ``q5.py``'s but for that delay and for what is sunk: the
fire's ``device_top_k`` candidate rows per window leave the device, and all
of them go to the sink and are compared, not only their maximum. Q5's own
rows are the candidates with the maximum count; ``compare`` derives them
and holds them to the reference too.

The auctions are the generator's own (Beam ``nexmark/sources/generator``
``BidGenerator.nextBid`` / ``AuctionGenerator``, kept by
``nexmark/nexmark``). Event numbers run in epochs of ``person + auction +
bid`` proportions (1 + 3 + 46 = 50): one Person, three Auctions, then 46
Bids. Only the bids are made here, but numbered as the full stream numbers
them: bid ``i`` is event ``e = (i // 46) * 50 + 4 + i % 46`` and the last
auction made before it (``lastBase0AuctionId``) is ``last = (e // 50) * 3 +
2``. With probability ``1 - 1/hot_auction_ratio`` the auction is the hot
one of its stride, ``(last // hot_auction_stride) * hot_auction_stride``;
otherwise (``nextBase0AuctionId``) uniform over the auctions in flight plus
``auction_id_lead`` ids not yet made: ``min + uniform[0, last - min + 1 +
lead)`` with ``min = max(last - num_in_flight_auctions, 0)``. Ids are
offset by ``first_auction_id``. ``dateTime`` is a function of the event
number: ``e * 1000 // event_rate`` ms. An id is bid on while ``last`` is
within ``[id - lead, id + in flight]`` and never again.

The control sheds one bid in ``control_shed_one_in``, drawn from the seed:
at-most-once delivery, which this stream shows in every window (a hot
auction loses about 12 of its 767 bids). What the comparison does NOT
see: a fault that touches none of a window's candidates. One lost
16,384-bid micro-batch holds about 11 of a window's 1,500 hot auctions, so
it changes the 16 candidates of about one window in nine of the five that
hold it (``rows_after`` with ``drop`` counts it; PERF.md section 2 has the
count at the cell's size).

Options (a configuration's ``job_options``): ``hot_auction_ratio``,
``hot_auction_stride``, ``num_in_flight_auctions``, ``auction_id_lead``,
``first_auction_id``, ``person_proportion``, ``auction_proportion``,
``bid_proportion``, ``size_ms``, ``slide_ms``, ``watermark_delay_ms``,
``device_top_k``, ``event_rate`` (NEXmark events — of all three kinds —
per second of event time), ``warmup_events``, ``control_shed_one_in``.
"""

import numpy as np

from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = ("window_end", "auction", "count")

#: the job at a size a CPU test can hold: laid over a configuration's
#: ``options`` and ``job_options`` by the harness's tests, scale cut only
#: (the ratios, the stride, the proportions, the window and the delay
#: stay). At 12,500 events per second of event time a slide holds 23,000
#: bids and 15 hot auctions, a window 75 of them, an 8,192-bid batch 5.
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 16},
    "job_options": {"event_rate": 12_500, "warmup_events": 230_000},
}

#: bids generated at a time
PIECE = 16384
#: bids the reference counts at a time
STRETCH = 1 << 20


def _epoch(o):
    """``(bids per epoch, events per epoch, events before the bids)``."""
    before = int(o["person_proportion"]) + int(o["auction_proportion"])
    bids = int(o["bid_proportion"])
    return bids, before + bids, before


def first_index_with_ts(ts_ms, o):
    """Global bid index of the first bid whose event time is at least
    ``ts_ms`` (event time is a function of the index); ``ts_ms`` may be
    an array."""
    bids, total, before = _epoch(o)
    event = -(-np.asarray(ts_ms, dtype=np.int64) * int(o["event_rate"])
              // 1000)
    epoch, offset = np.divmod(event, total)
    index = epoch * bids + np.maximum(offset - before, 0)
    return index if index.ndim else int(index)


def make_generator(seed, o):
    """``gen(first, n)`` -> the bids with global bid indices
    ``first .. first+n-1``. One hash per bid; the hot draw is cut from its
    low 16 bits and the cold auction from the next 32."""
    salt = int(seed) * 4 + 2
    bids, total, before = _epoch(o)
    per_epoch = int(o["auction_proportion"])
    ratio = int(o["hot_auction_ratio"])
    stride = int(o["hot_auction_stride"])
    in_flight = int(o["num_in_flight_auctions"])
    lead = int(o["auction_id_lead"])
    first_id = int(o["first_auction_id"])
    rate = int(o["event_rate"])
    # random.nextInt(hotAuctionRatio) > 0 on 16 bits is x * ratio >= 1 << 16
    cold_below = np.uint16(-(-(1 << 16) // ratio))

    def part(first, n, auction, ts):
        """Fills ``auction`` and ``ts`` for the ``n`` bids from ``first``."""
        # the indices are consecutive, so what depends on the epoch alone
        # is worked out once per epoch and repeated over its bids
        e0, e1 = first // bids, (first + n - 1) // bids
        epochs = np.arange(e0, e1 + 1, dtype=np.int64)
        lo = first - e0 * bids
        # lastBase0AuctionId: the last auction of this epoch (a bid's
        # offset in its epoch is past the auctions)
        last = epochs * per_epoch + (per_epoch - 1)
        oldest = np.maximum(last - in_flight, 0)
        hot_one = (last // stride) * stride
        hot_id, from_hot, span = np.repeat(np.stack([
            hot_one + first_id,             # the hot auction
            oldest - hot_one,               # oldest in flight, from it
            last - oldest + (1 + lead)]),   # ids a cold draw spans
            bids, axis=1)[:, lo:lo + n]
        u64 = splitmix64(np.arange(first, first + n, dtype=np.int64), salt)
        # hot or cold is chosen by arithmetic, not by a mask: a branch on
        # a fair coin costs more than the rest of the piece
        cold = u64.astype(np.uint16) < cold_below
        np.right_shift(u64, np.uint64(16), out=u64)
        np.multiply(u64.astype(np.uint32), span, out=auction)
        np.right_shift(auction, 32, out=auction)
        auction += from_hot
        auction *= cold
        auction += hot_id
        # dateTime = e * 1000 // rate is a step function of the index: a
        # piece holds few of its steps, each repeated over its bids (a
        # division per bid costs more than the rest of the piece)
        t0 = (e0 * total + before + lo) * 1000 // rate
        t1 = (e1 * total + before + (first + n - 1) % bids) * 1000 // rate
        steps = np.arange(t0, t1 + 2, dtype=np.int64)
        at = np.clip(first_index_with_ts(steps, o), first, first + n)
        ts[:] = np.repeat(steps[:-1], np.diff(at))

    def gen(first, n):
        # in pieces that stay in the cache: a 1 MB temporary per operation
        # costs more in page faults than the arithmetic on it
        auction = np.empty(n, dtype=np.int64)
        ts = np.empty(n, dtype=np.int64)
        for a in range(0, n, PIECE):
            b = min(a + PIECE, n)
            part(first + a, b - a, auction[a:b], ts[a:b])
        return {"auction": auction}, ts

    return gen


def boundary_events(o):
    """Bids per slide: the offered stream ends on a multiple of it, so
    the end-of-input flush closes whole slices only."""
    return first_index_with_ts(int(o["slide_ms"]), o)


def warmup_events(o):
    return int(o["warmup_events"])


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, window
    transformation)``: the stream to sink — each fired window's candidate
    rows as they left the device — and the transformation whose operator
    holds the window state."""
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.aggregates import CountAggregate
    from flink_tpu.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.windowing.fire_projectors import TopKFireProjector

    counts = (
        env.from_source(source,
                        WatermarkStrategy.for_bounded_out_of_orderness(
                            int(o["watermark_delay_ms"])))
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(int(o["size_ms"]),
                                           int(o["slide_ms"])))
        .aggregate(CountAggregate(), fire_projector=TopKFireProjector(
            "count", k=int(o["device_top_k"]))))
    return counts, counts.transformation


def _shed(seed, one_in):
    """``drop(first, n)`` -> the bids at-most-once delivery loses: one in
    ``one_in``, drawn from the seed by a hash of its own."""
    salt = int(seed) * 4 + 3

    def drop(first, n):
        u64 = splitmix64(np.arange(first, first + n, dtype=np.int64), salt)
        return u64 % np.uint64(one_in) == 0

    return drop


def _slice_counts(gen, lo, hi, drop):
    """``(lowest auction id, bids per id from it on)`` of the bids
    ``lo .. hi-1``. Sparse by the stream's nature: the ids a stretch names
    lie within the auctions made during it plus those in flight and the
    lead, so the counts are kept over that range and no further."""
    base, counts = 0, np.zeros(0, dtype=np.int64)
    for first in range(lo, hi, STRETCH):
        n = min(STRETCH, hi - first)
        auction = gen(first, n)[0]["auction"]
        if drop is not None:
            auction = auction[~drop(first, n)]
        if not len(auction):
            continue
        low = int(auction.min())
        got = np.bincount(auction - low)
        base, counts = _added((base, counts), (low, got))
    return base, counts


def _added(a, b):
    """Two ``(lowest id, counts from it on)`` summed."""
    if not len(a[1]):
        return b
    if not len(b[1]):
        return a
    base = min(a[0], b[0])
    out = np.zeros(max(a[0] + len(a[1]), b[0] + len(b[1])) - base,
                   dtype=np.int64)
    for low, counts in (a, b):
        out[low - base:low - base + len(counts)] += counts
    return base, out


def rows_after(seed, n_events, o, drop=None, windows=None):
    """The candidate rows of the first ``n_events`` bids, as columns: per
    window the ``device_top_k`` largest counts with every auction tied at
    the last of them, smallest count first (a window's last row is one of
    Q5's own). ``drop(first, n)`` marks bids left out; ``windows`` limits
    the result to a range of window numbers (window ``j`` ends with slice
    ``j``)."""
    gen = make_generator(seed, o)
    slide = int(o["slide_ms"])
    k = int(o["size_ms"]) // slide
    top = int(o["device_top_k"])
    n_slices = int(gen(n_events - 1, 1)[1][0]) // slide + 1
    first_w, last_w = windows or (0, n_slices + k - 1)
    edges = np.minimum(first_index_with_ts(
        np.arange(n_slices + 1, dtype=np.int64) * slide, o), n_events)
    slices = {}
    ends, auctions, counts = [], [], []
    for j in range(first_w, last_w):  # window = slices j-k+1 .. j
        base, c = 0, np.zeros(0, dtype=np.int64)
        for s in range(max(j - k + 1, 0), min(j, n_slices - 1) + 1):
            if s not in slices:
                slices[s] = _slice_counts(gen, int(edges[s]),
                                          int(edges[s + 1]), drop)
            base, c = _added((base, c), slices[s])
        slices.pop(j - k + 1, None)     # the next window starts past it
        live = np.flatnonzero(c)
        if not len(live):
            continue
        if len(live) > top:
            least = np.partition(c[live], len(live) - top)[len(live) - top]
            live = live[c[live] >= least]
        live = live[np.argsort(c[live], kind="stable")]
        ends.append(np.full(len(live), (j + 1) * slide, dtype=np.int64))
        auctions.append(live + base)
        counts.append(c[live])
    if not ends:
        return {name: np.zeros(0, dtype=np.int64) for name in SINK_COLUMNS}
    return {"window_end": np.concatenate(ends),
            "auction": np.concatenate(auctions),
            "count": np.concatenate(counts)}


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink may hold for the first ``n_events`` bids
    (``rows_after``). ``control=True`` computes them under at-most-once
    delivery: one bid in ``control_shed_one_in`` is lost — the guarantee
    "every event counted exactly once" broken."""
    drop = _shed(seed, int(o["control_shed_one_in"])) if control else None
    return rows_after(seed, n_events, o, drop)


def _by_window(cols):
    """``{window_end: [(auction, count), ...]}``."""
    end, auction, count = (np.asarray(cols[n], dtype=np.int64).tolist()
                           for n in SINK_COLUMNS)
    out = {}
    for e, a, c in zip(end, auction, count):
        out.setdefault(e, []).append((a, c))
    return out


def window_faults(got, want, top):
    """Rows of one window's ``got`` that the reference's ``want`` does not
    bear out, counted. ``want`` holds the ``top`` largest counts and every
    auction tied at the last of them; which of those tied auctions fill
    the ``top`` places is the program's own choice, everything else is
    fixed: the rows above that count, Q5's rows (every auction at the
    maximum count), how many rows there are, each auction once."""
    least = min(c for _, c in want)
    most = max(c for _, c in want)
    above = {r for r in want if r[1] > least}
    tied = {a for a, c in want if c == least}
    rows = set(got)
    faults = len(got) - len(rows)                       # a row twice
    faults += len({r for r in rows if r[1] > least} ^ above)
    faults += sum(1 for a, c in rows
                  if c < least or (c == least and a not in tied))
    faults += len({a for a, _ in rows}) < len(rows)     # an auction twice
    faults += max(min(top, len(want)) - len(got), 0)    # places left empty
    faults += max(len(got) - len(want), 0)
    if most == least:                                   # Q5's rows, tied
        faults += len({a for a, c in rows if c == most} ^ tied)
    return faults


def compare(got, want, o):
    """Numbers compared, each beside its limit, and the windows that
    failed. Exact: auctions and counts are integers."""
    top = int(o["device_top_k"])
    g, w = _by_window(got), _by_window(want)
    wrong = failed = 0
    for end in g.keys() | w.keys():
        if end not in w:
            faults = len(g[end])
        elif end not in g:
            faults = min(top, len(w[end]))
        else:
            faults = window_faults(g[end], w[end], top)
        wrong += faults
        failed += faults > 0
    return {"numbers": {"rows_wrong": {"value": wrong, "limit": 0}},
            "attempted": len(w),
            "failed": failed}


def check(got, seed, n_events, o):
    """``compare`` against the reference of the first ``n_events`` bids."""
    return compare(got, reference_rows(seed, n_events, o), o)


def live_cells_per_slice(o):
    """(auction, slice) cells that hold a bid, per slide slice, from the
    generator's parameters: the auctions made during the slide, those in
    flight at its start and the lead (every one of them is bid on: an id
    takes about 7 cold bids while it is in reach)."""
    bids, _, _ = _epoch(o)
    made = boundary_events(o) // bids * int(o["auction_proportion"])
    return made + int(o["num_in_flight_auctions"]) \
        + int(o["auction_id_lead"])


def work(n_events, fired_windows, o):
    """Bytes the job's device work needs, from the traffic alone (terms in
    ``benchmark/harness/work.py``): COUNT has one int32 accumulator leaf
    and no value column; a fired window reads the live cells of its
    ``size/slide`` slices and writes ``device_top_k`` rows."""
    from benchmark.harness.work import window_state_bytes

    k = int(o["size_ms"]) // int(o["slide_ms"])
    return window_state_bytes(
        events=n_events, value_bytes_per_event=0, leaf_bytes=(4,),
        fired_cells=fired_windows * k * live_cells_per_slice(o),
        emitted_rows=fired_windows * int(o["device_top_k"]),
        row_bytes=4 + 4)
