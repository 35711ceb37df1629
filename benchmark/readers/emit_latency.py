"""Latency of result windows under paced traffic, at ``percentile``.

Latency of one result window ``W`` = (time of the LAST sink write that
carries a row with ``window_end == W``) - (time the last event with
``ts < W`` was due). Events are in order and event time is a function of
the global index, so that event's index — and its due time
``t0 + i / rate`` — is arithmetic: no per-event stamp is needed. A window
closed only by the end-of-input flush (no later event was ever handed
over) is left out of the sample. The sample is every other result window
of the run.
"""

import statistics

from benchmark.harness.traffic import last_write_per_window


def latencies_ms(stamps, t0, rate, events, first_index_with_ts):
    """``{window_end: latency in ms}`` of the windows a later event closed."""
    out = {}
    for window_end, written in last_write_per_window(stamps).items():
        after = first_index_with_ts(window_end)
        if after >= events or after == 0:
            continue            # closed by the flush, or holds no event
        out[window_end] = 1e3 * (written - (t0 + (after - 1) / rate))
    return out


def read(run, percentile):
    if run.mix["mode"] != "paced":
        return None
    sample = sorted(latencies_ms(
        run.sink.stamps, run.t0, float(run.mix["rate"]), run.events,
        lambda ts: run.job.first_index_with_ts(ts, run.job_options)).values())
    if len(sample) < 2:
        return None
    cuts = statistics.quantiles(sample, n=100, method="inclusive")
    return cuts[int(percentile) - 1]
