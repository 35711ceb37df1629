"""One kernel's share of its roofline in the traced range: the least time
the chip could take for the bytes the kernel needs (the job module's
function ``work`` of the events handed over inside ``run.trace_span``)
over the device time of the programs whose names hold one of
``modules`` in that range (``device_ops`` of the reduction: the XLA
modules clipped to it, the ten that took longest). The bound is the peak
bytes/s of ``peaks.json`` for this device kind. ``None`` where the run was
not traced, where no such program ran in the range (a program that lacks
the kernel), or where the job module counts no such work."""

from benchmark.harness.work import roofline_share


def read(run, modules, work):
    if not run.trace or run.peak is None:
        return None
    count = getattr(run.job, work, None)
    busy_s = sum(s for name, s in run.trace["device_ops"]
                 if any(m in name for m in modules))
    if count is None or busy_s <= 0:
        return None
    lo, hi = run.trace_span
    needed = count(run.log.events_between(lo, hi), run.job_options)
    run.trace.setdefault("kernel_roofline", {})[work] = {
        "bytes_needed": needed, "busy_s": busy_s,
        "peak_bytes_per_s": run.peak["hbm_bytes_per_s"]}
    return roofline_share(needed, run.peak["hbm_bytes_per_s"], busy_s)
