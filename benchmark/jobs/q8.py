"""NEXmark Query 8 (monitor new users) as a window join: the job, its
traffic and its plain reference.

``SELECT Rstream(P.id, P.name, A.reserve) FROM Person [RANGE 12 HOUR] P,
Auction [RANGE 12 HOUR] A WHERE P.id = A.seller`` (Tucker et al.), as
Apache Beam's ``nexmark/queries/Query8.java`` runs it: persons keyed by
``id`` and auctions keyed by ``seller`` in ``FixedWindows`` of
``size_ms``, one ``IdNameReserve(id, name, reserve)`` per (person,
auction) pair of a window. In Flink's DataStream terms
``persons.join(auctions).where("id").equal_to("seller").window(
TumblingEventTimeWindows.of(size_ms)).apply()``, an INNER equi-join per
window, emitted when the watermark — ``watermark_delay_ms`` behind the
newest event, ``ddl_gen.sql``'s 4 s — passes the window's end.

The persons and auctions are the NEXmark generator's own (Beam
``nexmark/sources/generator`` ``PersonGenerator`` / ``AuctionGenerator``,
kept by ``nexmark/nexmark``). Event numbers run in epochs of ``person +
auction + bid`` proportions (1 + 3 + 46 = 50): one Person, three
Auctions, then 46 Bids. Only the persons and auctions are made here
(Bids never reach Q8), numbered as the full stream numbers them: row
``r`` is event ``e = (r // 4) * 50 + r % 4`` — a person where ``r % 4 ==
0``, else an auction — so the epoch of row ``r`` is ``r // 4`` and a
person's id is ``first_person_id + epoch``. An auction's seller is, with
probability ``1 - 1/hot_sellers_ratio``, the hot one of its stride,
``(epoch // hot_seller_stride) * hot_seller_stride``; otherwise
(``nextBase0PersonId``) uniform over the last ``active = min(epoch + 1,
num_active_people)`` people plus ``person_id_lead`` ids not yet made:
``epoch + 1 - active + uniform[0, active + lead)``; offset by
``first_person_id``. Its ``reserve`` is ``initialBid + nextPrice`` with
``nextPrice = round(10 ** (6 u) * 100)``: under 2 * 10**8, exact in
int32. A person's ``name`` is ``FIRST_NAMES[i] + " " + LAST_NAMES[j]``,
carried as the code ``i * 9 + j`` (exact: the name is drawn from those
two lists and nothing else). ``dateTime`` is ``e * 1000 // event_rate``
ms. ``TimedSource`` offers the stream as one source, and the job splits
it by event type into the join's two inputs, as Beam's
``JUST_NEW_PERSONS`` / ``JUST_NEW_AUCTIONS`` split one event stream.

The control loses one micro-batch of ``control_lost_events`` rows, drawn
from the seed: at-most-once delivery, which takes the pairs of the lost
auctions and of every auction of a lost person out of its window.

Options (a configuration's ``job_options``): ``person_proportion``,
``auction_proportion``, ``bid_proportion``, ``hot_sellers_ratio``,
``hot_seller_stride``, ``num_active_people``, ``person_id_lead``,
``first_person_id``, ``size_ms``, ``watermark_delay_ms``, ``event_rate``
(NEXmark events — of all three kinds — per second of event time),
``warmup_events`` (rows), ``control_lost_events`` (rows).
"""

import numpy as np

from benchmark.jobs import q11
from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = ("window_end", "id", "name", "reserve")

#: Beam's ``PersonGenerator`` name lists: a name is one of each
FIRST_NAMES = ("Peter", "Paul", "Luke", "John", "Saul", "Vicky", "Kate",
               "Julie", "Sarah", "Deiter", "Walter")
LAST_NAMES = ("Shultz", "Abrams", "Spencer", "White", "Bartels", "Walton",
              "Smith", "Jones", "Noris")

#: the job at a size a CPU test can hold: laid over a configuration's
#: ``options`` and ``job_options`` by the harness's tests, scale cut only
#: (the proportions, the ratios, the window and the delay stay). At
#: 50,000 events per second of event time a window holds 10,000 persons
#: and 30,000 auctions, five 8,192-row batches; a side's capacity holds
#: a window, the next one's first 4 s and a fired window whose pages
#: wait for its harvest.
TINY = {
    "options": {"execution.micro-batch.size": 8192,
                "state.slot-table.capacity": 1 << 17},
    "job_options": {"event_rate": 50_000, "warmup_events": 100_000,
                    "control_lost_events": 8192},
}

#: rows generated at a time
PIECE = 16384
#: rows the reference joins at a time
STRETCH = 1 << 21
#: the fields of a sunk row, packed in one int64 by the comparison:
#: ``(id - the window's lowest) << 35 | name << 28 | reserve``
_ID_BITS, _NAME_BITS, _RESERVE_BITS = 28, 7, 28


def _epoch(o):
    """``(rows per epoch, events per epoch)``: persons and auctions of an
    epoch (q11's events before the bids), and all its events."""
    _, total, rows = q11._epoch(o)
    return rows, total


def first_index_with_ts(ts_ms, o):
    """Global row index of the first person or auction whose event time is
    at least ``ts_ms`` (event time is a function of the index; a row is a
    person or an auction)."""
    rows, total = _epoch(o)
    event = -(-int(ts_ms) * int(o["event_rate"]) // 1000)
    epoch, offset = divmod(event, total)
    return epoch * rows + min(offset, rows)


def make_generator(seed, o):
    """``gen(first, n)`` -> the rows with global indices
    ``first .. first+n-1``, every column on every row (``kind`` 0 for a
    person, 1 for an auction; the other kind's columns hold 0). One hash
    per row draws the seller (hot from its low 16 bits, the cold offset
    from its high 32) or the name (one index from each half); a second
    draws the two prices, one from each half."""
    salt = int(seed) * 4 + 1
    rows_per, total = _epoch(o)
    persons = int(o["person_proportion"])
    ratio = int(o["hot_sellers_ratio"])
    stride = int(o["hot_seller_stride"])
    active_max = int(o["num_active_people"])
    lead = int(o["person_id_lead"])
    first_id = int(o["first_person_id"])
    rate = int(o["event_rate"])
    if persons != 1 or rows_per != 4:
        raise ValueError("the row numbering assumes one person and three "
                         "auctions an epoch")

    def part(first, n, out):
        r = np.arange(first, first + n, dtype=np.int64)
        epoch, k = r >> 2, r & 3            # rows_per is 4: checked above
        u = splitmix64(r, salt)
        # the hash's two 32-bit halves: the low one draws the hot seller
        # (its low 16 bits: nextInt(hotSellersRatio) > 0) and the first
        # name, the high one the cold seller's offset and the last name
        lo, hi = u.view(np.uint32).reshape(-1, 2).T.astype(np.int64)
        hot = (lo & 0xFFFF) * ratio >= 1 << 16
        active = np.minimum(epoch + 1, active_max)
        seller = np.where(hot, (epoch // stride) * stride,
                          epoch + 1 - active + ((hi * (active + lead)) >> 32))
        # nextPrice = round(10 ** (6 u) * 100), u from a 32-bit half, twice
        frac = splitmix64(r, salt + 2).view(np.uint32).reshape(-1, 2)
        price = np.floor(np.exp(frac * (6 * np.log(10.0) / 2 ** 32))
                         * 100 + 0.5)
        is_person = k < persons
        out["kind"][:] = ~is_person
        np.multiply(is_person, first_id + epoch, out=out["id"])
        np.multiply(is_person, (lo * len(FIRST_NAMES) >> 32)
                    * len(LAST_NAMES) + (hi * len(LAST_NAMES) >> 32),
                    out=out["name"], casting="unsafe")
        np.multiply(~is_person, seller + first_id, out=out["seller"])
        np.multiply(~is_person, price[:, 0] + price[:, 1],
                    out=out["reserve"], casting="unsafe")
        out["ts"][:] = (epoch * total + k) * 1000 // rate

    def gen(first, n):
        # in pieces that stay in the cache: a 1 MB temporary per operation
        # costs more in page faults than the arithmetic on it
        out = {"kind": np.empty(n, dtype=np.int8),
               "id": np.empty(n, dtype=np.int64),
               "name": np.empty(n, dtype=np.int32),
               "seller": np.empty(n, dtype=np.int64),
               "reserve": np.empty(n, dtype=np.int32),
               "ts": np.empty(n, dtype=np.int64)}
        for a in range(0, n, PIECE):
            b = min(a + PIECE, n)
            part(first + a, b - a, {c: v[a:b] for c, v in out.items()})
        ts = out.pop("ts")
        return out, ts

    return gen


def boundary_events(o):
    """Rows per window: the offered stream ends on a multiple of it, so
    the end-of-input flush fires whole windows only."""
    return first_index_with_ts(int(o["size_ms"]), o)


def warmup_events(o):
    return int(o["warmup_events"])


def _persons(batch):
    """``JUST_NEW_PERSONS``: the persons of a batch, as Q8 reads them."""
    from flink_tpu.core.records import TIMESTAMP_FIELD, RecordBatch

    is_person = batch["kind"] == 0
    return RecordBatch({"id": batch["id"][is_person],
                        "name": batch["name"][is_person],
                        TIMESTAMP_FIELD: batch.timestamps[is_person]})


def _auctions(batch):
    """``JUST_NEW_AUCTIONS``: the auctions of a batch, as Q8 reads them."""
    from flink_tpu.core.records import TIMESTAMP_FIELD, RecordBatch

    is_auction = batch["kind"] == 1
    return RecordBatch({"seller": batch["seller"][is_auction],
                        "reserve": batch["reserve"][is_auction],
                        TIMESTAMP_FIELD: batch.timestamps[is_auction]})


def build(env, source, o):
    """The job on ``env`` reading ``source``. Returns ``(results, join
    transformation)``: the pairs to sink and the transformation whose
    operator holds the join's state."""
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    events = env.from_source(
        source, WatermarkStrategy.for_bounded_out_of_orderness(
            int(o["watermark_delay_ms"])))
    persons = events.map(_persons, name="just_new_persons")
    auctions = events.map(_auctions, name="just_new_auctions")
    pairs = (persons.join(auctions).where("id").equal_to("seller")
             .window(TumblingEventTimeWindows.of(int(o["size_ms"])))
             .apply(name="q8_new_users"))
    return pairs, pairs.transformation


def _lost(seed, n_events, o):
    """``(first, count)`` of the rows at-most-once delivery loses: one
    micro-batch of ``control_lost_events``, drawn from the seed."""
    batch = int(o["control_lost_events"])
    slots = max(n_events // batch, 1)
    at = int(splitmix64(np.array([n_events]), int(seed))[0]
             % np.uint64(slots)) * batch
    return at, batch


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink must hold for the first ``n_events`` rows, as
    columns: per window, one row per auction whose seller's person is in
    the same window. A window's persons are consecutive epochs, so their
    ids are a range and the join is a range test and an offset; a
    person's name is drawn from its own row. Window by window, in
    stretches of rows. ``control=True`` computes them under at-most-once
    delivery: one micro-batch of rows, drawn from the seed, is lost."""
    gen = make_generator(seed, o)
    size = int(o["size_ms"])
    rows_per, _ = _epoch(o)
    lose = _lost(seed, n_events, o) if control else (0, 0)
    last_ts = int(gen(n_events - 1, 1)[1][0]) if n_events else -1
    out = {c: [] for c in SINK_COLUMNS}
    for j in range(last_ts // size + 1 if n_events else 0):
        lo = first_index_with_ts(j * size, o)
        hi = min(first_index_with_ts((j + 1) * size, o), n_events)
        if lo >= hi:
            continue
        # every person row of the window: ids first_id + epoch, in order
        first_person = -(-lo // rows_per) * rows_per
        ids, names = [], []
        sellers, reserves = [], []
        for a in range(lo, hi, STRETCH):
            b = min(a + STRETCH, hi)
            cols, _ = gen(a, b - a)
            keep = np.ones(b - a, dtype=bool)
            keep[max(lose[0] - a, 0):max(lose[0] + lose[1] - a, 0)] = False
            person = cols["kind"] == 0
            ids.append(cols["id"][person & keep])
            names.append(cols["name"][person & keep])
            auction = ~person & keep
            sellers.append(cols["seller"][auction])
            reserves.append(cols["reserve"][auction])
        ids, names = np.concatenate(ids), np.concatenate(names)
        seller, reserve = np.concatenate(sellers), np.concatenate(reserves)
        base = int(o["first_person_id"]) + first_person // rows_per
        # the window's person with id ``base + i`` is at ``i`` unless rows
        # were lost: look its name up through the ids that are there
        slot = np.full(max(hi - first_person, 0) // rows_per + 1, -1,
                       dtype=np.int64)
        slot[ids - base] = np.arange(len(ids))
        at = seller - base
        inside = (at >= 0) & (at < len(slot))
        found = np.full(len(seller), -1, dtype=np.int64)
        found[inside] = slot[at[inside]]
        hit = found >= 0
        out["window_end"].append(np.full(int(hit.sum()), (j + 1) * size,
                                         dtype=np.int64))
        out["id"].append(seller[hit])
        out["name"].append(names[found[hit]])
        out["reserve"].append(reserve[hit])
    return {c: (np.concatenate(v) if v else np.zeros(0, dtype=np.int64))
            for c, v in out.items()}


def _windows(cols):
    """``{window_end: rows of it}`` — slices where each window's rows are
    consecutive, as a fire writes them; index arrays otherwise."""
    end = np.asarray(cols["window_end"], dtype=np.int64)
    if not len(end):
        return {}
    cuts = np.flatnonzero(end[1:] != end[:-1]) + 1
    starts, stops = np.r_[0, cuts], np.r_[cuts, len(end)]
    ends = end[starts]
    if len(np.unique(ends)) == len(ends):
        return {int(e): slice(int(a), int(b))
                for e, a, b in zip(ends, starts, stops)}
    order = np.argsort(end, kind="stable")
    uniq, first = np.unique(end[order], return_index=True)
    bounds = np.r_[first, len(end)]
    return {int(e): order[bounds[i]:bounds[i + 1]]
            for i, e in enumerate(uniq)}


def _packed(cols, rows, base):
    """Each row of ``rows`` as one int64, and how many rows no packing
    holds (fields out of range: never a reference row)."""
    ident = np.asarray(cols["id"])[rows].astype(np.int64) - base
    name = np.asarray(cols["name"])[rows].astype(np.int64)
    reserve = np.asarray(cols["reserve"])[rows].astype(np.int64)
    fits = ((ident >= 0) & (ident < 1 << _ID_BITS)
            & (name >= 0) & (name < 1 << _NAME_BITS)
            & (reserve >= 0) & (reserve < 1 << _RESERVE_BITS))
    code = (ident << (_NAME_BITS + _RESERVE_BITS)) \
        | (name << _RESERVE_BITS) | reserve
    return code[fits], int((~fits).sum())


def _multiset_distance(a, b):
    """Rows one sorted array has and the other lacks, each copy
    counted: ``sum |count in a - count in b|`` over distinct values."""
    if np.array_equal(a, b):
        return 0
    both = np.concatenate([a, b])
    side = np.r_[np.ones(len(a), np.int64), -np.ones(len(b), np.int64)]
    order = np.argsort(both, kind="stable")
    both, side = both[order], side[order]
    starts = np.r_[0, np.flatnonzero(both[1:] != both[:-1]) + 1]
    return int(np.abs(np.add.reduceat(side, starts)).sum()) \
        if len(both) else 0


def compare(got, want, o):
    """Numbers compared, each beside its limit, and the windows that
    failed. Exact: ids, names and reserves are integers. Per window the
    two multisets of (id, name, reserve): ``rows_wrong`` counts each row
    one side holds more often than the other — missing, extra and
    doubled rows alike. ``attempted`` counts the reference's windows."""
    g, w = _windows(got), _windows(want)
    wrong = failed = 0
    for end in g.keys() | w.keys():
        w_rows = w.get(end, slice(0, 0))
        g_rows = g.get(end, slice(0, 0))
        ids = np.asarray(want["id"])[w_rows]
        base = int(ids.min()) if len(ids) else 0
        w_code, w_out = _packed(want, w_rows, base)
        g_code, g_out = _packed(got, g_rows, base)
        if w_out:
            raise ValueError(f"window {end}: a reference row does not pack")
        faults = g_out + _multiset_distance(np.sort(g_code),
                                            np.sort(w_code))
        wrong += faults
        failed += faults > 0
    return {"numbers": {"rows_wrong": {"value": wrong, "limit": 0}},
            "attempted": len(w),
            "failed": failed}


def check(got, seed, n_events, o):
    """``compare`` against the reference of the first ``n_events`` rows."""
    return compare(got, reference_rows(seed, n_events, o), o)


def pairs_floor_per_window(o):
    """Pairs a window emits at least: every auction of an epoch at least
    ``num_active_people`` + ``person_id_lead`` epochs from both of its
    window's ends finds its seller in it (the hot seller is at most
    ``hot_seller_stride`` people back, a cold one at most the active
    people back or the lead ahead), and every person is one row."""
    rows_per, _ = _epoch(o)
    epochs = boundary_events(o) // rows_per
    margin = int(o["num_active_people"]) + int(o["person_id_lead"])
    return int(o["auction_proportion"]) * max(epochs - margin, 0)


def work(n_events, fired_windows, o):
    """Bytes the join's device work needs, from the traffic alone (its
    own terms, ``harness/work.py`` counts an aggregation's). A row's
    device columns — the key's two int32 words and its one value word,
    the name or the reserve (the id and the seller are the key) — are
    written once at ingest, 12 bytes; a fired window reads every row's
    12 bytes once and writes each pair's output row, the key's two
    words, the name and the reserve, 16 bytes. Pairs are counted by
    their floor (``pairs_floor_per_window``)."""
    row_bytes, pair_bytes = 4 * 3, 4 * 4
    return (n_events * row_bytes
            + fired_windows * (boundary_events(o) * row_bytes
                               + pairs_floor_per_window(o) * pair_bytes))
