"""One run of one cell: set-up, the measured window, and what follows it.

Nothing here names a cell, a configuration or a metric: the cell's files
say what runs (``manifest.py`` finds them) and each metric's file names
the reader that takes it from what the run collected (``Collected``).
"""

import gc
import json
import os
import shutil
import time

from benchmark.harness import manifest, probe, trace as tracing
from benchmark.harness.traffic import (StampingSink, TimedSource,
                                       longest_gaps)


class Collected:
    """What one run collected, as the readers see it."""

    def __init__(self, **fields):
        self.__dict__.update(fields)


def execute_job(job, cfg, source, span=None):
    """Build the configuration's job on a fresh environment, feed it from
    ``source`` into a ``StampingSink`` and run it to the end of its input.
    Returns ``(sink, tap, t0, t1, host)``: the window is ``t0`` -> the
    return of ``env.execute()``, drain and last sink writes inside it;
    ``host`` is the CPU seconds this thread and the process had over it
    (``probe.host_usage``)."""
    from flink_tpu import Configuration, StreamExecutionEnvironment

    env = StreamExecutionEnvironment(Configuration(dict(cfg["options"])))
    results, window_transformation = job.build(
        env, source, cfg["job_options"])
    tap = probe.tap_window_operator(window_transformation, span)
    sink = StampingSink(job.SINK_COLUMNS, **({"span": span} if span else {}))
    results.sink_to(sink)
    before = probe.host_usage()
    t0 = time.perf_counter()
    source.arm(t0)
    env.execute("benchmark-" + cfg["job"])
    t1 = time.perf_counter()
    host = {k: v - before[k] for k, v in probe.host_usage().items()}
    return sink, tap, t0, t1, host


def flight_seconds():
    """Cumulative seconds per span kind of the program's flight recorder."""
    from flink_tpu.observe import flight_recorder as flight

    return {kind: t["total_s"]
            for kind, t in flight.recorder().kind_totals().items()}


def set_up(job, cfg, mix, seed):
    """Compile cache, native planes, and a warm-up job of the cell's own
    shape — the same builder, options and batch sizes over a bounded
    input that ends as the window's input ends — run to its end and
    discarded."""
    from flink_tpu.observe import flight_recorder as flight
    from flink_tpu.platform import enable_compilation_cache

    enable_compilation_cache()
    probe.check_native()
    o = cfg["job_options"]
    before = probe.compile_count()
    source = TimedSource(job.make_generator(seed, o),
                         dict(mix, mode="backlog"), job.boundary_events(o),
                         min_events=job.warmup_events(o))
    execute_job(job, cfg, source)
    gc.collect()
    flight.recorder().clear()
    return {"warmup_events": source.log.events,
            "warmup_compiles": probe.compile_count() - before}


def measure(job, cfg, mix, seed, seconds, trace_dir):
    """The measured window. Nothing is compared, reduced or printed
    inside it."""
    o = cfg["job_options"]
    tracer = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        slice_ = mix["trace"]
        tracer = tracing.SliceTracer(
            trace_dir, min(slice_["after_s"], seconds * 0.4),
            min(slice_["seconds"], seconds * 0.4))
    span = {"span": tracing.annotate} if tracer else {}
    source = TimedSource(job.make_generator(seed, o), mix,
                         job.boundary_events(o), seconds=seconds, **span)
    flight_before = flight_seconds()
    compiles_before = probe.compile_count()
    if tracer is not None:
        tracer.start()
    try:
        sink, tap, t0, t1, host = execute_job(job, cfg, source, **span)
    finally:
        if tracer is not None:
            tracer.close()
    return Collected(
        log=source.log, sink=sink, tap=tap, t0=t0, t1=t1, host=host,
        window_s=t1 - t0, events=source.log.events,
        compiles=probe.compile_count() - compiles_before,
        flight_s={kind: s - flight_before.get(kind, 0.0)
                  for kind, s in flight_seconds().items()},
        trace_span=tracer.span if tracer else None,
        trace_asked_s=tracer.seconds if tracer else None, trace=None)


def reduce_slice(run, trace_dir):
    """Reads the slice's trace into ``run.trace`` and removes the files.
    ``run.trace_span`` becomes the range the reduction was taken over, on
    the host's clock: what a reader counts beside the trace, it counts
    over the same range."""
    if run.trace_span is None:
        raise RuntimeError("the window ended before the traced slice began")
    rows = tracing.read_events(tracing.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    run.trace = tracing.reduce_trace(rows, run.trace_asked_s)
    if run.trace is None:
        raise RuntimeError("no operation ran on the device in the slice")
    clock = tracing.host_clock(rows, run.trace_span)
    run.trace_span = tuple(clock(ns) for ns in run.trace["range_ns"])
    run.trace["range_in_window_s"] = [t - run.t0 for t in run.trace_span]


def check(job, cfg, run, seed):
    """The comparison that decides ``correct``: the rows the timed job's
    own sink received against the plain reference on the same events,
    regenerated from the seed."""
    o = cfg["job_options"]
    verdict = job.check(run.sink.result(), seed, run.events, o)
    compared = {name: {"value": c["value"], "limit": c["limit"],
                       "ok": bool(c["value"] <= c["limit"])}
                for name, c in verdict["numbers"].items()}
    correct = all(c["ok"] for c in compared.values()) \
        and verdict["failed"] == 0 and verdict["attempted"] > 0
    return correct, verdict["attempted"], verdict["failed"], compared


def read_metrics(entries, run):
    """Each metric's reader on what the run collected; a reader that
    finds nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for entry in entries:
        spec = manifest.metric_spec(entry["name"])
        value = manifest.reader(spec["reader"]).read(
            run, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def resolve(man, cell_name):
    """The cell's own files: ``(cell, configuration, traffic mix)``."""
    cell = manifest.cell(man, cell_name)
    return (cell, manifest.config(man, cell["config"]),
            manifest.traffic(cell["traffic"]))


class NoAccelerator(RuntimeError):
    """JAX shows no accelerator, or fewer chips than the cell asks for."""


def look_for_chips(chips, platform="tpu"):
    """The device as JAX reports it, or ``NoAccelerator`` where it is not
    ``chips`` devices of ``platform``: there is no CPU fallback."""
    device = probe.device_info()
    if device["platform"] != platform:
        raise NoAccelerator(f"needs a {platform}, JAX gave {device}")
    if device["count"] != chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), JAX shows "
                            f"{device['count']}")
    return device


def run_cell(man, cell, cfg, mix, device, seed, seconds, trace, t_process,
             out_dir):
    """The whole run on ``device``. Returns the result line's fields."""
    cell_name = cell["name"]
    job = manifest.job(cfg["job"])
    chips = int(cell["chips"])
    platform = device["platform"]
    device = dict(device)
    peak = manifest.peak(device["kind"]) if trace else None

    warm = set_up(job, cfg, mix, seed)
    trace_dir = os.path.join(out_dir, f"trace-{cell_name}") if trace else None
    setup_s = time.perf_counter() - t_process
    run = measure(job, cfg, mix, seed, seconds, trace_dir)

    run.setup_s = setup_s
    run.job, run.job_options, run.mix = job, cfg["job_options"], mix
    run.peak = peak
    device["memory_peak_bytes"] = probe.peak_bytes(chips)
    placed = probe.check_placement(run.tap, cfg["expect"], platform, chips)
    fires = run.tap["fires"]
    run.tap = None      # the program's state is freed before the reference
    gc.collect()
    if trace:
        reduce_slice(run, trace_dir)
        device["busy_s"] = run.trace["busy_s_mean"]
        device["window_s"] = run.trace["window_s"]

    t_check = time.perf_counter()
    correct, attempted, failed, compared = check(job, cfg, run, seed)
    check_s = time.perf_counter() - t_check
    metrics = read_metrics(
        manifest.metrics_of(man, cell_name,
                            "per_layer" if trace else "end_to_end"), run)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, "compared": compared}
    if trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    details = {"workload": cell_name, "seed": seed, "seconds": seconds,
               "trace": trace, "events": run.events,
               "window_s": run.window_s, "set_up_seconds": setup_s,
               "check_s": check_s, "fires": fires,
               "compiles": run.compiles, **warm, **placed,
               "generate_s": sum(run.log.generate_s),
               "generate_s_longest": max(run.log.generate_s, default=0.0),
               "flight_s": run.flight_s, "host": run.host,
               "stood_still": {
                   "hand_overs": longest_gaps(run.log.handed, run.t0),
                   "sink_writes": longest_gaps(
                       [t for t, _ in run.sink.stamps], run.t0)},
               "trace_reduced": run.trace, "result": result}
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell_name}-{seed}-t{trace}.json"),
              "w", encoding="utf-8") as f:
        json.dump(details, f, indent=1, default=str)
    return result
