"""NEXmark Query 4 (average price for a category) through the SQL path:
the job, its traffic and its plain reference.

``nexmark/nexmark`` ``nexmark-flink/src/main/resources/queries/q4.sql``::

    INSERT INTO nexmark_q4
    SELECT Q.category, AVG(Q.final)
    FROM (SELECT MAX(B.price) AS final, A.category
          FROM auction A, bid B
          WHERE A.id = B.auction AND B.dateTime BETWEEN A.dateTime AND A.expires
          GROUP BY A.id, A.category) Q
    GROUP BY Q.category;

A regular join (no window, no state TTL: both inputs held for the job's
life), ``MAX`` per ``(id, category)`` over its pairs — an updating stream
of UPDATE_BEFORE / UPDATE_AFTER pairs whenever an auction's best bid
rises — and ``AVG`` per category over that changelog, retracting the old
maximum and adding the new one. Every column is a BIGINT; ``AVG(BIGINT)``
is the 64-bit sum divided by the count, truncated toward zero.

The auctions and bids are the NEXmark generator's own (Beam
``nexmark/sources/generator``, kept by ``nexmark/nexmark``), numbered as
the full stream numbers them: epochs of ``person + auction + bid``
proportions (1 + 3 + 46 = 50), persons dropped as Q4's views drop them.
Row ``r`` is event ``e = (r // 49) * 50 + 1 + r % 49``: an auction where
``r % 49 < 3``, else a bid; ``dateTime`` is ``e * 1000 // event_rate``.

- An auction's ``id`` is ``first_auction_id + (r // 49) * 3 + r % 49``
  (``lastBase0AuctionId``), its ``category`` ``first_category_id +
  nextInt(num_categories)``, its ``expires`` ``dateTime +
  nextAuctionLengthMs``: ``1 + nextLong(max(2 * horizon, 1))`` where
  ``horizon`` is the time from its event to event ``e + num_in_flight *
  50 // 3`` (when ``num_in_flight_auctions`` more have been made).
- A bid's auction is ``q5_generator``'s (``BidGenerator.nextBid``): the
  hot one of its stride with probability ``1 - 1/hot_auction_ratio``,
  else uniform over the auctions in flight plus ``auction_id_lead`` not
  yet made (a bid may come before its auction); its ``price`` is
  ``nextPrice = round(10 ** (6 u) * 100)``.

``TimedSource`` offers the rows as one stream and the job splits it by
event type into the two views, as ``ddl_gen.sql``'s ``auction`` and
``bid`` views split NEXmark's one datagen table; the SELECT of
``q4.sql`` runs through ``StreamTableEnvironment.execute_sql`` verbatim
(``INSERT INTO`` would run the job at once: the harness sinks the
query's changelog instead of the blackhole). A map before the split
counts the rows that entered the job, and a map after the query stamps
each changelog row with that count as ``window_end``: every operator of
the job emits a batch's rows before the next batch enters, so a row
stamped ``s`` is the output of the first ``s`` rows.

The comparison replays the sink's changelog in order. Every
UPDATE_BEFORE or DELETE must retract the row last emitted for its key,
every INSERT must add an absent key, and after every micro-batch of the
stream (``boundary_events`` rows), and at every other stamp the sink
wrote, the table its rows leave — the one it last wrote, where it wrote
none at that stamp — must equal the reference's over the same rows,
exact integers: an emission that is missing, or merged into a later
one, leaves the table behind. The control loses one micro-batch
(``control_lost_events`` rows, drawn from the seed in the first half of
the stream): at-most-once delivery.

Options (a configuration's ``job_options``): ``person_proportion``,
``auction_proportion``, ``bid_proportion``, ``hot_auction_ratio``,
``hot_auction_stride``, ``num_in_flight_auctions``, ``auction_id_lead``,
``first_auction_id``, ``first_category_id``, ``num_categories``,
``watermark_delay_ms``, ``event_rate`` (NEXmark events — of all three
kinds — per second of event time), ``boundary_events`` (the stream ends
on a multiple of it), ``warmup_events`` (rows),
``control_lost_events`` (rows).
"""

import numpy as np

from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = ("window_end", "kind", "id", "final")

#: ``q4.sql`` as ``nexmark/nexmark`` ships it; the job runs its SELECT
Q4_SQL = """INSERT INTO nexmark_q4
SELECT
    Q.category,
    AVG(Q.final)
FROM (
    SELECT MAX(B.price) AS final, A.category
    FROM auction A, bid B
    WHERE A.id = B.auction AND B.dateTime BETWEEN A.dateTime AND A.expires
    GROUP BY A.id, A.category
) Q
GROUP BY Q.category"""

#: the job at a size a CPU test can hold: laid over a configuration's
#: ``options`` and ``job_options`` by the harness's tests, scale cut only
#: (the proportions, the ratios, the categories and the delay stay). At
#: 50,000 events per second of event time an auction lives about 33 ms,
#: so the residual drops bids on both sides of it.
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 16,
                "state.join.capacity": 1 << 21},
    "job_options": {"event_rate": 50_000, "boundary_events": 8192,
                    "warmup_events": 200_000,
                    "control_lost_events": 8192},
}

#: rows generated at a time
PIECE = 16384
#: bids the reference joins at a time
STRETCH = 1 << 22
#: changelog row kinds (``flink_tpu.core.records``'s RowKind codes)
INSERT, UPDATE_BEFORE, UPDATE_AFTER, DELETE = 0, 1, 2, 3


def _epoch(o):
    """``(rows per epoch, events per epoch, auctions per epoch)``."""
    persons = int(o["person_proportion"])
    auctions = int(o["auction_proportion"])
    total = persons + auctions + int(o["bid_proportion"])
    if persons != 1:
        raise ValueError("the row numbering assumes one person an epoch")
    return total - persons, total, auctions


def first_index_with_ts(ts_ms, o):
    """Global row index of the first auction or bid whose event time is
    at least ``ts_ms`` (event time is a function of the index)."""
    rows, total, _ = _epoch(o)
    event = -(-int(ts_ms) * int(o["event_rate"]) // 1000)
    epoch, offset = divmod(event, total)
    return epoch * rows + max(offset - 1, 0)


def _columns(r, o, salt):
    """Every column of the rows with global indices ``r`` (int64): the
    auction columns hold 0 on a bid and the bid columns 0 on an
    auction."""
    rows, total, per = _epoch(o)
    rate = int(o["event_rate"])
    epoch, k = np.divmod(r, rows)
    event = epoch * total + 1 + k
    ts = event * 1000 // rate
    lo, hi = splitmix64(r, salt).view(np.uint32).reshape(-1, 2).T.astype(
        np.int64)
    is_auction = k < per
    # an auction: lastBase0AuctionId is its own id; a bid: the last made
    last = epoch * per + np.where(is_auction, k, per - 1)
    category = int(o["first_category_id"]) + (
        (lo * int(o["num_categories"])) >> 32)
    ahead = int(o["num_in_flight_auctions"]) * total // per
    horizon = (event + ahead) * 1000 // rate - ts
    expires = ts + 1 + ((hi * np.maximum(2 * horizon, 1)) >> 32)
    ratio = int(o["hot_auction_ratio"])
    stride = int(o["hot_auction_stride"])
    oldest = np.maximum(last - int(o["num_in_flight_auctions"]), 0)
    hot = (lo & 0xFFFF) * ratio >= 1 << 16
    auction = np.where(hot, (last // stride) * stride,
                       oldest + ((hi * (last - oldest + 1
                                        + int(o["auction_id_lead"]))) >> 32))
    # nextPrice = round(10 ** (6 u) * 100), u from a second hash
    frac = splitmix64(r, salt + 2).view(np.uint32)[0::2]
    price = np.floor(np.exp(frac * (6 * np.log(10.0) / 2 ** 32)) * 100
                     + 0.5).astype(np.int64)
    first_id = int(o["first_auction_id"])
    return {"kind": (~is_auction).astype(np.int8),
            "id": np.where(is_auction, last + first_id, 0),
            "category": np.where(is_auction, category, 0),
            "expires": np.where(is_auction, expires, 0),
            "auction": np.where(is_auction, 0, auction + first_id),
            "price": np.where(is_auction, 0, price),
            "dateTime": ts}


def make_generator(seed, o):
    """``gen(first, n)`` -> the rows with global indices
    ``first .. first+n-1``, every column on every row (``kind`` 0 for an
    auction, 1 for a bid)."""
    salt = int(seed) * 4 + 1

    def gen(first, n):
        parts = [_columns(np.arange(a, min(a + PIECE, first + n),
                                    dtype=np.int64), o, salt)
                 for a in range(first, first + n, PIECE)]
        if not parts:
            parts = [_columns(np.zeros(0, dtype=np.int64), o, salt)]
        cols = {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
        return cols, cols["dateTime"].copy()

    return gen


def boundary_events(o):
    """The offered stream ends on a multiple of it: one micro-batch (the
    query has no window to close)."""
    return int(o["boundary_events"])


def warmup_events(o):
    return int(o["warmup_events"])


def _view(batch, kind, names):
    from flink_tpu.core.records import TIMESTAMP_FIELD, RecordBatch

    rows = batch["kind"] == kind
    cols = {n: batch[n][rows] for n in names}
    cols[TIMESTAMP_FIELD] = batch.timestamps[rows]
    return RecordBatch(cols)


def _device_join(t):
    """The join's transformation, found by walking the query's inputs."""
    seen, todo = set(), [t]
    while todo:
        t = todo.pop()
        if id(t) in seen:
            continue
        seen.add(id(t))
        if t.kind == "two_input":
            return t
        todo.extend(t.inputs)
    raise LookupError("the query holds no join")


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, join
    transformation)``: the query's changelog stamped with the rows that
    had entered the job, and the transformation whose operator holds the
    join's state."""
    from flink_tpu.core.records import ROWKIND_FIELD, RecordBatch
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.table.environment import StreamTableEnvironment

    entered = [0]

    def count(batch):
        entered[0] += len(batch)
        return batch

    events = env.from_source(
        source, WatermarkStrategy.for_bounded_out_of_orderness(
            int(o["watermark_delay_ms"]))).map(count, name="q4_entered")
    auction = ("id", "category", "dateTime", "expires")
    bid = ("auction", "price", "dateTime")
    t_env = StreamTableEnvironment(env)
    t_env.create_temporary_view(
        "auction", events.map(lambda b: _view(b, 0, auction),
                              name="auction_view"),
        columns=list(auction), time_field="dateTime")
    t_env.create_temporary_view(
        "bid", events.map(lambda b: _view(b, 1, bid), name="bid_view"),
        columns=list(bid), time_field="dateTime")
    table = t_env.execute_sql(Q4_SQL.split("\n", 1)[1]).table
    category, final = table.columns

    def stamp(batch):
        return RecordBatch({
            "window_end": np.full(len(batch), entered[0], dtype=np.int64),
            "kind": np.asarray(batch[ROWKIND_FIELD], dtype=np.int8),
            "id": np.asarray(batch[category], dtype=np.int64),
            "final": np.asarray(batch[final], dtype=np.int64)})

    results = table.to_data_stream().map(stamp, name="q4_stamp")
    return results, _device_join(results.transformation)


# ------------------------------------------------------------ reference


def _lost(seed, n_events, o):
    """``(first, count)`` of the rows at-most-once delivery loses: one
    micro-batch of ``control_lost_events``, drawn from the seed in the
    first half of the stream (so that the tables after it show the
    loss)."""
    batch = int(o["control_lost_events"])
    slots = max(n_events // batch // 2, 1)
    at = int(splitmix64(np.array([n_events]), int(seed))[0]
             % np.uint64(slots)) * batch
    return at, batch


def _rows_of(kind_index, o):
    """Global row index of the ``i``-th auction (kind 0) or bid (1)."""
    rows, _, per = _epoch(o)

    def of(i):
        if kind_index == 0:
            return (i // per) * rows + i % per
        return (i // (rows - per)) * rows + per + i % (rows - per)

    return of


def _count_of(kind_index, n_rows, o):
    """Auctions (0) or bids (1) among the first ``n_rows`` rows."""
    rows, _, per = _epoch(o)
    epochs, rest = divmod(int(n_rows), rows)
    if kind_index == 0:
        return epochs * per + min(rest, per)
    return epochs * (rows - per) + max(rest - per, 0)


def tables(seed, n_events, o, stamps, lost=(0, 0), acc="int64"):
    """The query's table after each prefix ``stamps`` (row counts,
    ascending) of the generated stream, a ``{category: average}`` each
    (``tables_of``). Rows ``lost`` (``(first, count)``) are left out."""
    salt = int(seed) * 4 + 1
    auction_row, bid_row = _rows_of(0, o), _rows_of(1, o)

    def kept(r):
        return r[(r < lost[0]) | (r >= lost[0] + lost[1])]

    a_rows = kept(auction_row(np.arange(_count_of(0, n_events, o),
                                        dtype=np.int64)))
    n_bids = _count_of(1, n_events, o)
    bids = ((lambda r: (_columns(r, o, salt), r))(kept(bid_row(np.arange(
        first, min(first + STRETCH, n_bids), dtype=np.int64))))
        for first in range(0, n_bids, STRETCH))
    return tables_of(_columns(a_rows, o, salt), a_rows, bids, stamps, acc)


def tables_of(auctions, auction_rows, bids, stamps, acc="int64"):
    """The query's table after each prefix ``stamps`` (row counts,
    ascending): a ``{category: average}`` per stamp, from the auctions'
    columns and their rows' places in the stream, and the bids as
    ``(columns, places)`` blocks. SQL read literally: every (auction,
    bid) pair with ``id = auction`` and ``dateTime <= B.dateTime <=
    expires`` (no uniqueness of ids assumed), the maximum price per
    ``(id, category)``, per category the sum of those maxima over their
    count, truncated toward zero. ``acc`` is what sums them: ``int64``,
    the query's; ``int32`` (wrapping) and ``float32`` (its average in
    float32 too) are the precisions below it."""
    stamps = np.asarray(stamps, dtype=np.int64)
    order = np.lexsort((auctions["category"], auctions["id"]))
    a_id, a_cat = auctions["id"][order], auctions["category"][order]
    a_ts, a_exp = auctions["dateTime"][order], auctions["expires"][order]
    a_row = np.asarray(auction_rows, dtype=np.int64)[order]
    new_group = np.r_[True, (a_id[1:] != a_id[:-1])
                      | (a_cat[1:] != a_cat[:-1])] if len(a_id) else \
        np.zeros(0, dtype=bool)
    group = np.cumsum(new_group) - 1
    group_cat = a_cat[new_group]
    # every qualifying pair: (group, stamp index it is visible from, price)
    parts = []
    for b, b_rows in bids:
        lo = np.searchsorted(a_id, b["auction"], "left")
        hi = np.searchsorted(a_id, b["auction"], "right")
        cnt = hi - lo
        bi = np.repeat(np.arange(len(b_rows)), cnt)
        ai = lo[bi] + (np.arange(len(bi)) - np.repeat(np.cumsum(cnt) - cnt,
                                                      cnt))
        bt = b["dateTime"][bi]
        ok = (a_ts[ai] <= bt) & (bt <= a_exp[ai])
        bi, ai = bi[ok], ai[ok]
        visible = np.searchsorted(stamps, np.maximum(a_row[ai],
                                                     np.asarray(b_rows)[bi]),
                                  "right")
        inside = visible < len(stamps)
        parts.append((group[ai][inside], visible[inside],
                      np.asarray(b["price"], dtype=np.int64)[bi][inside]))
    empty = np.zeros(0, dtype=np.int64)
    g, v, p = (np.concatenate([q[i] for q in parts]) if parts else empty
               for i in range(3))
    g, v, p = _sorted(g, v, p)
    run_max = _running_max(g, p)
    last = np.r_[(g[1:] != g[:-1]) | (v[1:] != v[:-1]), True] if len(g) \
        else np.zeros(0, dtype=bool)
    g, v, m = g[last], v[last], run_max[last]
    first_of_group = np.r_[True, g[1:] != g[:-1]] if len(g) else last
    prev = np.r_[0, m[:-1]] if len(m) else m
    delta = np.where(first_of_group, m, m - prev)
    cats, cat_of = np.unique(group_cat[g], return_inverse=True)
    sums = np.zeros((len(cats), len(stamps)), dtype=np.int64)
    counts = np.zeros((len(cats), len(stamps)), dtype=np.int64)
    np.add.at(sums, (cat_of, v), delta)
    np.add.at(counts, (cat_of, v), first_of_group.astype(np.int64))
    counts = np.cumsum(counts, axis=1)
    if acc == "float32":
        sums = np.cumsum(sums.astype(np.float32), axis=1)
    elif acc == "int32":
        sums = np.cumsum(sums.astype(np.int32), axis=1, dtype=np.int32)
    else:
        sums = np.cumsum(sums, axis=1)
    out = []
    for j in range(len(stamps)):
        live = counts[:, j] > 0
        s, c = sums[live, j], counts[live, j]
        if acc == "float32":
            avg = np.trunc(s / c.astype(np.float32)).astype(np.int64)
        else:
            s = s.astype(np.int64)
            q = np.abs(s) // c
            avg = np.where(s < 0, -q, q)
        out.append(dict(zip(cats[live].tolist(), avg.tolist())))
    return out


def _bits(x):
    return int(x.max()).bit_length() if len(x) else 0


def _sorted(g, v, p):
    """The pairs in ``(group, stamp, price)`` order: one sort of the three
    packed in an int64 where their ranges fit, else a sort by each."""
    low = int(p.min()) if len(p) else 0
    if _bits(g) + _bits(v) + _bits(p - low) <= 63:
        bv, bp = _bits(v), _bits(p - low)
        packed = np.sort((g << (bv + bp)) | (v << bp) | (p - low))
        return (packed >> (bv + bp), (packed >> bp) & ((1 << bv) - 1),
                (packed & ((1 << bp) - 1)) + low)
    order = np.lexsort((p, v, g))
    return g[order], v[order], p[order]


def _running_max(g, p):
    """Each group's running maximum of ``p`` (the pairs sorted by
    group): one running maximum of the group and the price packed, where
    they fit an int64."""
    if not len(p):
        return p
    low = int(p.min())
    span = int(p.max()) - low + 1
    if _bits(g) + span.bit_length() <= 63:
        packed = np.maximum.accumulate(g * span + (p - low))
        return packed - g * span + low
    out = np.empty_like(p)
    starts = np.flatnonzero(np.r_[True, g[1:] != g[:-1]])
    for a, b in zip(starts.tolist(), np.r_[starts[1:], len(g)].tolist()):
        out[a:b] = np.maximum.accumulate(p[a:b])
    return out


def _changelog(stamps, tabs):
    """The changelog that takes the table from one stamp to the next:
    per category whose average changed an UPDATE_BEFORE / UPDATE_AFTER
    pair, an INSERT for a new one."""
    out = {c: [] for c in SINK_COLUMNS}
    before = {}
    for s, table in zip(stamps, tabs):
        for cat in sorted(table):
            new = table[cat]
            if cat in before and before[cat] == new:
                continue
            rows = [(UPDATE_BEFORE, before[cat]), (UPDATE_AFTER, new)] \
                if cat in before else [(INSERT, new)]
            for kind, value in rows:
                out["window_end"].append(s)
                out["kind"].append(kind)
                out["id"].append(cat)
                out["final"].append(value)
        before = dict(table)
    return {c: np.asarray(v, dtype=np.int8 if c == "kind" else np.int64)
            for c, v in out.items()}


def _stamps(n_events, o):
    step = boundary_events(o)
    return np.r_[np.arange(step, n_events, step), n_events].astype(np.int64)


def reference_rows(seed, n_events, o, control=False):
    """The changelog a sink must hold for the first ``n_events`` rows,
    the table emitted once every ``boundary_events`` rows. ``control=True``
    computes it under at-most-once delivery: one micro-batch of rows,
    drawn from the seed, is lost."""
    stamps = _stamps(n_events, o)
    lost = _lost(seed, n_events, o) if control else (0, 0)
    return _changelog(stamps, tables(seed, n_events, o, stamps, lost))


def _replay(cols):
    """``(stamps, table after each stamp's last row, faults per stamp)``
    of a changelog replayed in order: a fault is a row that retracts what
    its key does not hold, adds a key that is there, or comes after a
    later stamp."""
    stamps, tabs, faults = [], [], []
    table = {}
    end = np.asarray(cols["window_end"], dtype=np.int64)
    kinds = np.asarray(cols["kind"]).astype(np.int64)
    keys = np.asarray(cols["id"]).astype(np.int64).tolist()
    values = np.asarray(cols["final"]).astype(np.int64).tolist()
    for i, s in enumerate(end.tolist()):
        if not stamps or s != stamps[-1]:
            if stamps and s < stamps[-1]:
                faults[-1] += 1     # a row after a later stamp's
                s = stamps[-1]
            else:
                if stamps:
                    tabs.append(dict(table))
                stamps.append(s)
                faults.append(0)
        k, v, kind = keys[i], values[i], int(kinds[i])
        if kind in (UPDATE_BEFORE, DELETE):
            if table.get(k) != v:
                faults[-1] += 1
            table.pop(k, None)
        elif kind in (INSERT, UPDATE_AFTER):
            if k in table:
                faults[-1] += 1
            table[k] = v
        else:
            faults[-1] += 1
    if stamps:
        tabs.append(dict(table))
    return stamps, tabs, faults


def _carried(stamps, at, tabs):
    """The table after each of ``stamps`` of a changelog replayed to
    ``tabs`` at its own stamps ``at`` (ascending): at a stamp where it
    wrote no row, the table it last wrote (empty before its first)."""
    idx = np.searchsorted(np.asarray(at, dtype=np.int64),
                          np.asarray(stamps, dtype=np.int64), "right") - 1
    return [tabs[i] if i >= 0 else {} for i in idx.tolist()]


def _verdict(got, stamps, want_tables):
    """Numbers with limits for ``got``'s changelog against the reference
    tables at ``stamps`` (every micro-batch's, and every stamp ``got``
    wrote): at each, the table ``got``'s rows up to it leave — the one it
    last wrote, where it wrote none there."""
    g_stamps, g_tabs, faults = _replay(got)
    stamps = np.asarray(stamps, dtype=np.int64)
    have = _carried(stamps, g_stamps, g_tabs)
    bad = np.array([h != w for h, w in zip(have, want_tables)], dtype=bool)
    # a stamp the reference does not know (past the stream's end) is wrong
    stray = np.setdiff1d(np.asarray(g_stamps, dtype=np.int64), stamps)
    faulted = np.zeros(len(stamps), dtype=bool)
    at = np.searchsorted(stamps, np.asarray(g_stamps, dtype=np.int64))
    for i, f in zip(at.tolist(), faults):
        if f and i < len(stamps):
            faulted[i] = True
    return {"numbers": {
        "tables_wrong": {"value": int(bad.sum()) + len(stray), "limit": 0},
        "changelog_faults": {"value": int(sum(faults)), "limit": 0}},
        "attempted": len(stamps) + len(stray),
        "failed": int((bad | faulted).sum()) + len(stray)}


def compare(got, want, o):
    """Numbers compared, each beside its limit: ``tables_wrong`` counts
    the stamps — every one either changelog wrote — at which the table
    ``got``'s rows leave differs from the one ``want``'s leave,
    ``changelog_faults`` the rows of ``got`` that retract what their key
    does not hold, add a key that is there or come after a later stamp.
    Exact: every value is an integer. ``attempted`` counts the stamps;
    ``failed`` those with a fault or a wrong table."""
    w_stamps, w_tabs, w_faults = _replay(want)
    if sum(w_faults):
        raise ValueError("the reference changelog does not replay")
    g_ends = np.asarray(got["window_end"], dtype=np.int64)
    stamps = np.union1d(np.asarray(w_stamps, dtype=np.int64), g_ends)
    return _verdict(got, stamps, _carried(stamps, w_stamps, w_tabs))


def check(got, seed, n_events, o):
    """The sink's changelog against the reference tables after every
    micro-batch of the ``n_events`` rows (``_stamps``) and at every other
    stamp the sink wrote inside them: a micro-batch whose emission is
    missing, or merged into a later one, leaves the table behind at its
    stamp."""
    ends = np.asarray(got["window_end"], dtype=np.int64)
    stamps = np.union1d(_stamps(n_events, o),
                        ends[(ends > 0) & (ends <= n_events)])
    return _verdict(got, stamps, tables(seed, n_events, o, stamps))


# ----------------------------------------------------------- device work


def _shares(o):
    rows, _, per = _epoch(o)
    return per / rows, (rows - per) / rows


#: int32 words a held row takes: an auction's id, category, dateTime and
#: expires (two each), a bid's price and dateTime, and each row's link
AUCTION_WORDS, BID_WORDS = 9, 5


def join_probe_work(n_events, o):
    """Bytes the join's insert and probe programs need for ``n_events``
    rows, at least: each row's words written once; each bid, which finds
    its auction, reads that auction's link, the four words the residual
    compares and the four it carries out, and writes its pair (its probe
    row and those four words). Pairs that fail the residual, and the
    auctions' probes of earlier bids, are left out: a floor."""
    auctions, bids = _shares(o)
    per_event = 4 * (auctions * AUCTION_WORDS + bids * BID_WORDS)
    per_bid = 4 * (1 + 4 + 4) + 4 * (1 + 4)
    return n_events * (per_event + bids * per_bid)


def agg_fold_work(n_events, o):
    """Bytes the MAX fold needs for ``n_events`` rows, at least: one
    group per auction, whose slot, fresh flag, partial (two words) and
    stored words are read and whose new words are written and sent back
    once (each auction's group is new once)."""
    auctions, _ = _shares(o)
    return n_events * auctions * 4 * (1 + 1 + 2 + 2 + 2 + 2)


def work(n_events, fired_windows, o):
    """Bytes the job's device work needs, from the traffic alone: the
    join's (``join_probe_work``) and the MAX fold's (``agg_fold_work``)
    per row, and per stamp the AVG fold over the categories (every
    category's two 64-bit parts read, written and sent back)."""
    per_stamp = int(o["num_categories"]) * 4 * (1 + 1 + 3 * 4)
    return (join_probe_work(n_events, o) + agg_fold_work(n_events, o)
            + fired_windows * per_stamp)
