"""Least time the chip could take for the traced range's work over the
time its busiest chip was busy inside that range. The work is counted from
the traffic (``job.work``: events handed over and result windows written
inside ``run.trace_span``, the range ``harness/trace.py`` clipped the
device rows to, on the host's clock), the bound is the peak bytes/s of
``peaks.json`` for this device kind times the chips that ran something,
and the denominator is ALL device-busy time of the range: whatever kernel
does the work, the same traffic reads the same. The parts are kept beside
the reduction (``trace_reduced.roofline`` of the run's details)."""

from benchmark.harness.work import roofline_share


def read(run):
    if not run.trace or run.peak is None:
        return None
    lo, hi = run.trace_span
    events = run.log.events_between(lo, hi)
    windows = run.sink.windows_written_between(lo, hi)
    needed = run.job.work(events, windows, run.job_options)
    chips = len(run.trace["busy_s_per_plane"])  # the work is spread on all
    peak = chips * run.peak["hbm_bytes_per_s"]
    busy_s = run.trace["busy_s_busiest"]
    run.trace["roofline"] = {
        "range_s": hi - lo, "events": events, "result_windows": windows,
        "bytes_needed": needed, "peak_bytes_per_s": peak, "busy_s": busy_s}
    return roofline_share(needed, peak, busy_s)
