"""One span kind of the program's flight recorder per record of its own:
what ``kind_totals()`` holds of ``kind`` at the end of the run, divided by
nothing but the kind's own ``count`` (the set-up clears the recorder before
the window, and nothing but the window runs between that and the read).
Where ``flight_stat`` divides a kind by result windows, events or seconds,
this reads what ONE record of the kind took: per fire where a result window
is fired more than once, per batch, per harvest.

``stat``: ``mean_ms`` (``total_s`` over ``count``: every record of the
window, those of no duration included), ``p50_ms`` / ``p99_ms`` (over the
recorder's bounded reservoir: the kind's most recent records that have a
duration) or ``max_ms`` (the longest single record). Milliseconds. ``None``
where the kind was not recorded — a program that lacks the kind, a run in
which it never happened — or where the program's recorder does not keep the
statistic."""

STATS = ("mean_ms", "p50_ms", "p99_ms", "max_ms")


def value(kind_totals, kind, stat):
    if stat not in STATS:
        raise ValueError(
            f"unknown statistic {stat!r}; known: {', '.join(STATS)}")
    kept = kind_totals.get(kind)
    if not kept or not kept.get("count"):
        return None
    if stat == "mean_ms":
        if "total_s" not in kept:
            return None
        return 1e3 * kept["total_s"] / kept["count"]
    if stat == "max_ms":
        return 1e3 * kept["max_s"] if "max_s" in kept else None
    return kept.get(stat)


def read(run, kind, stat):
    from flink_tpu.observe import flight_recorder as flight

    return value(flight.recorder().kind_totals(), kind, stat)
