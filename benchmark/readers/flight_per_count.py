"""Span kinds of the program's flight recorder summed, per record of ONE
kind: what ``kind_totals()`` holds of ``kinds`` at the end of the run (the
set-up clears the recorder before the window, and nothing but the window
runs between that and the read) over the ``count`` of the kind ``of``.
Where ``flight_per_record`` divides one kind's seconds by its own count,
this divides what several kinds did by how often the thing they are the
parts of happened — the three spans of a checkpoint per checkpoint written
— and it divides ``work``, which that reader does not read: bytes per
checkpoint, rows per snapshot.

``stat``: ``total_s`` or ``self_s`` (milliseconds per record of ``of``) or
``work`` (as counted, per record of ``of``). ``None`` where ``of`` was not
recorded, where none of ``kinds`` was, where the program's recorder does
not keep the statistic, and where the kinds state no work at all (a program
whose span of that name says nothing of what it did)."""

STATS = ("total_s", "self_s", "work")


def value(kind_totals, kinds, stat, of):
    if stat not in STATS:
        raise ValueError(
            f"unknown statistic {stat!r}; known: {', '.join(STATS)}")
    records = (kind_totals.get(of) or {}).get("count")
    found = [kind_totals[k][stat] for k in kinds
             if k in kind_totals and stat in kind_totals[k]]
    if not records or not found:
        return None
    if stat == "work":
        return sum(found) / records or None
    return 1e3 * sum(found) / records


def read(run, kinds, stat, of):
    from flink_tpu.observe import flight_recorder as flight

    return value(flight.recorder().kind_totals(), kinds, stat, of)
