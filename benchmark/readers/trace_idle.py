"""Share of the traced slice in which no operation ran on the busiest
chip: 1 - union of its device-op intervals over the slice's seconds."""


def read(run):
    return run.trace["idle_pct"] if run.trace else None
