"""From the ``TimedSource``'s batch log. ``stat``:

- ``busy_pct``: seconds spent generating batches over window seconds;
- ``late_ms``: hand-over time minus due time per batch (paced traffic
  only), at ``percentile``: how late the generator ran.
"""

import statistics


def read(run, stat, percentile=None):
    log = run.log
    if stat == "busy_pct":
        return 100.0 * sum(log.generate_s) / run.window_s
    if stat == "late_ms":
        late = sorted(1e3 * (h - d) for h, d in zip(log.handed, log.due)
                      if d is not None)
        if len(late) < 2:
            return None
        cuts = statistics.quantiles(late, n=100, method="inclusive")
        return cuts[int(percentile) - 1]
    raise ValueError(f"unknown stat {stat!r}; known: busy_pct, late_ms")
