"""NEXmark Query 5 (hot items): the job, its traffic and its plain reference.

HOP(size, slide) COUNT per auction, arg-max per window. The job is built
through the public API exactly as ``chip_smoke.run_q5`` builds it; the
generator and the reference are the benchmark's own (NumPy only, nothing
of the program beyond the ``RecordBatch`` the source interface hands over).

Options (a configuration's ``job_options``): ``num_auctions``,
``num_bidders``, ``hot_ratio``, ``event_rate`` (events per second of event
time), ``size_ms``, ``slide_ms``, ``device_top_k``, ``warmup_events``.
"""

import numpy as np

from benchmark.jobs._hash import first_index_at, splitmix64

SINK_COLUMNS = ("window_end", "auction", "count")

#: the job at a size a CPU test can hold: laid over a configuration's
#: ``options`` and ``job_options`` by the harness's tests, scale cut only
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 16},
    "job_options": {"num_auctions": 1000, "event_rate": 10_000,
                    "warmup_events": 250_000, "control_lost_events": 8192},
}


def make_generator(seed, o):
    """``gen(first, n, columns=None)`` -> the bids with global indices
    ``first .. first+n-1``. One hash per record; the fields are cut from
    its 64 bits (hot flag 10, auction 22, bidder 16, price 16) as the
    program's ``BidSource`` cuts them."""
    salt = int(seed) * 4 + 1
    num_auctions = int(o["num_auctions"])
    num_bidders = int(o["num_bidders"])
    hot_below = int(float(o["hot_ratio"]) * 1024)
    hot_auctions = max(num_auctions // 100, 1)
    rate = int(o["event_rate"])

    def gen(first, n, columns=None):
        idx = np.arange(first, first + n, dtype=np.int64)
        u64 = splitmix64(idx, salt)
        hot = (u64 & np.uint64(0x3FF)).astype(np.int64) < hot_below
        u_auction = ((u64 >> np.uint64(10)) & np.uint64(0x3FFFFF)
                     ).astype(np.float64) / (1 << 22)
        cols = {"auction": np.where(
            hot, u_auction * hot_auctions,
            u_auction * num_auctions).astype(np.int64)}
        if columns is None or "bidder" in columns:
            cols["bidder"] = (((u64 >> np.uint64(32)) & np.uint64(0xFFFF)
                               ).astype(np.int64) * num_bidders) >> 16
        if columns is None or "price" in columns:
            u_price = np.maximum(
                (u64 >> np.uint64(48)).astype(np.float64) / (1 << 16), 1e-12)
            cols["price"] = ((np.power(u_price, -1.0 / 3.0) - 1.0) * 100 + 1
                             ).astype(np.float32)
        return cols, (idx * 1000) // rate

    return gen


def boundary_events(o):
    """Events per slide: the offered stream ends on a multiple of it, so
    the end-of-input flush closes whole slices only."""
    return int(o["slide_ms"]) * int(o["event_rate"]) // 1000


def warmup_events(o):
    return int(o["warmup_events"])


def first_index_with_ts(ts_ms, o):
    """Global index of the first event whose event time is at least
    ``ts_ms`` (event time is a function of the index)."""
    return first_index_at(ts_ms, int(o["event_rate"]))


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, window
    transformation)``: the stream to sink and the transformation whose
    operator holds the window state."""
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.aggregates import CountAggregate
    from flink_tpu.windowing.assigners import SlidingEventTimeWindows
    from flink_tpu.windowing.fire_projectors import TopKFireProjector

    def window_argmax(batch):
        # a fired batch holds one whole window (or its top-k candidates)
        counts = batch["count"]
        return batch.filter(counts == counts.max())

    counts = (
        env.from_source(source,
                        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("auction")
        .window(SlidingEventTimeWindows.of(int(o["size_ms"]),
                                           int(o["slide_ms"])))
        .aggregate(CountAggregate(), fire_projector=TopKFireProjector(
            "count", k=int(o["device_top_k"]))))
    return counts.map(window_argmax, name="hot_items_argmax"), \
        counts.transformation


def _slice_counts(seed, n_events, o, lose=None):
    """[n_slices, num_auctions] bid counts per slide slice of the first
    ``n_events`` events. ``lose=(first, count)`` leaves that index range
    out: the control's lost micro-batch."""
    gen = make_generator(seed, o)
    slide = int(o["slide_ms"])
    num_auctions = int(o["num_auctions"])
    n_slices = ((n_events - 1) * 1000 // int(o["event_rate"])) // slide + 1
    per_slice = np.zeros(n_slices * num_auctions, dtype=np.int64)
    chunk = 1 << 21
    for first in range(0, n_events, chunk):
        n = min(chunk, n_events - first)
        cols, ts = gen(first, n, columns=())
        cell = (ts // slide) * num_auctions + cols["auction"]
        if lose is not None:
            lo, hi = lose[0] - first, lose[0] + lose[1] - first
            keep = np.ones(n, dtype=bool)
            keep[max(lo, 0):max(hi, 0)] = False
            cell = cell[keep]
        # a chunk spans few slices: count only the rows it touches
        base = (int(ts[0]) // slide) * num_auctions
        got = np.bincount(cell - base)
        per_slice[base:base + len(got)] += got
    return per_slice.reshape(n_slices, num_auctions)


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink must hold for the first ``n_events`` events, as
    columns. ``control=True`` computes them under at-most-once delivery:
    one micro-batch of events, drawn from the seed, is lost — the
    guarantee "every event counted exactly once" broken."""
    lose = None
    if control:
        batch = int(o["control_lost_events"])
        slots = max(n_events // batch, 1)
        lose = (int(splitmix64(np.array([n_events]), int(seed))[0]
                    % np.uint64(slots)) * batch, batch)
    per_slice = _slice_counts(seed, n_events, o, lose)
    slide = int(o["slide_ms"])
    k = int(o["size_ms"]) // slide
    n_slices = len(per_slice)
    cum = np.cumsum(per_slice, axis=0)
    ends, auctions, counts = [], [], []
    for j in range(n_slices + k - 1):  # window = slices j-k+1 .. j
        top = cum[min(j, n_slices - 1)]
        c = top - cum[j - k] if j >= k else top
        best = c.max()
        if best > 0:
            a = np.flatnonzero(c == best)
            ends.append(np.full(len(a), (j + 1) * slide, dtype=np.int64))
            auctions.append(a.astype(np.int64))
            counts.append(np.full(len(a), best, dtype=np.int64))
    return {"window_end": np.concatenate(ends),
            "auction": np.concatenate(auctions),
            "count": np.concatenate(counts)}


def compare(got, want, o):
    """Numbers compared, each beside its limit, and the windows that
    failed. Exact: winners, ties and counts are integers."""
    def rows(c):
        return np.stack([np.asarray(c[n], dtype=np.int64)
                         for n in SINK_COLUMNS], axis=1)

    g, w = rows(got), rows(want)
    gu, g_counts = np.unique(g, axis=0, return_counts=True) \
        if len(g) else (g, np.zeros(0, dtype=np.int64))
    gset = set(map(tuple, gu.tolist()))
    wset = set(map(tuple, w.tolist()))
    wrong = gset ^ wset
    duplicates = int((g_counts - 1).sum())
    failed = {r[0] for r in wrong} | {
        int(r[0]) for r, c in zip(gu, g_counts) if c > 1}
    return {"numbers": {"rows_wrong": {"value": len(wrong) + duplicates,
                                       "limit": 0}},
            "attempted": len({r[0] for r in wset}),
            "failed": len(failed)}


def check(got, seed, n_events, o):
    """``compare`` against the reference of the first ``n_events`` events."""
    return compare(got, reference_rows(seed, n_events, o), o)


def live_cells_per_slice(o):
    """Expected (auction, slice) cells that hold a bid, per slide slice, from
    the generator's parameters: an auction is live where at least one of
    the slice's bids fell on it (Poisson share ``1 - exp(-bids on it)``)."""
    num_auctions = int(o["num_auctions"])
    hot_auctions = max(num_auctions // 100, 1)
    hot_bids = boundary_events(o) * (int(float(o["hot_ratio"]) * 1024) / 1024)
    cold_each = (boundary_events(o) - hot_bids) / num_auctions
    hot_each = cold_each + hot_bids / hot_auctions
    return (hot_auctions * -np.expm1(-hot_each)
            + (num_auctions - hot_auctions) * -np.expm1(-cold_each))


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
