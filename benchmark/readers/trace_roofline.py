"""Least time the chip could take for the slice's work over the time its
busiest chip was busy in the slice. The work is counted from the traffic
(``job.work``: events handed over and result windows written inside the
slice), the bound is the peak bytes/s of ``peaks.json`` for this device
kind times the chips that ran something, and the denominator is ALL device-busy time: whatever kernel does
the work, the same traffic reads the same."""

from benchmark.harness.work import roofline_share


def read(run):
    if not run.trace or run.peak is None:
        return None
    lo, hi = run.trace_span
    needed = run.job.work(run.log.events_between(lo, hi),
                          run.sink.windows_written_between(lo, hi),
                          run.job_options)
    chips = len(run.trace["busy_s_per_plane"])  # the work is spread on all
    return roofline_share(needed, chips * run.peak["hbm_bytes_per_s"],
                          run.trace["busy_s_busiest"])
