"""One statistic of the program's flight recorder, summed over the span
``kinds``, as the recorder holds it at the end of the run: the per-kind
aggregates of ``kind_totals()`` (the set-up clears the recorder before the
window, and nothing but the window runs between that and the read), never
the ring of records, which wraps.

``stat``: ``self_s`` (a span's time minus what its children on the same
thread covered), ``total_s``, ``max_s`` (the longest single span; over
several kinds the longest of them) or ``work`` (what the spans said they
did: events, pairs, bytes). ``per``: ``window_seconds``, ``result_windows``
(those the sink received), ``events`` or ``one``. A time comes out in
milliseconds, except per window second, where it is a share in percent;
work comes out as counted. ``None`` where no listed kind was recorded, or
where the program's recorder does not keep the statistic."""

STATS = ("self_s", "total_s", "max_s", "work")
PERS = ("window_seconds", "result_windows", "events", "one")


def value(kind_totals, run, kinds, stat, per):
    if stat not in STATS:
        raise ValueError(
            f"unknown statistic {stat!r}; known: {', '.join(STATS)}")
    if per not in PERS:
        raise ValueError(
            f"unknown denominator {per!r}; known: {', '.join(PERS)}")
    found = [kind_totals[k][stat] for k in kinds
             if k in kind_totals and stat in kind_totals[k]]
    if not found:
        return None
    x = max(found) if stat == "max_s" else sum(found)
    time = stat != "work"
    if per == "window_seconds":
        return (100.0 if time else 1.0) * x / run.window_s
    if per == "result_windows":
        over = run.sink.windows_written_between()
    elif per == "events":
        over = run.events
    else:
        over = 1
    return (1e3 if time else 1.0) * x / over if over else None


def read(run, kinds, stat, per):
    from flink_tpu.observe import flight_recorder as flight

    return value(flight.recorder().kind_totals(), run, kinds, stat, per)
