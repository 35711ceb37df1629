"""Share of the traced range in which no operation ran on the busiest
chip: 1 - union of its device-op intervals, clipped to the range the
capture's host rows cover, over that range's seconds."""


def read(run):
    return run.trace["idle_pct"] if run.trace else None
