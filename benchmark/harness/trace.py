"""Profiler trace of a slice of the window, and its reduction.

The main thread is inside ``env.execute()`` for the whole window, so a
timer thread starts and stops ``jax.profiler``. The reduction works on a
plain table of events (``read_events``) so that it can be checked on a
small recorded table without a chip.

Table row: ``(plane, line, name, start_ns, duration_ns)``. Device planes
are those named ``/device:...``; of a device plane the line that lists
single operations (``XLA Ops``) is the busy/idle source, and the line
that lists whole programs (``XLA Modules``) names them for the
breakdown. Host rows are kept only where their name starts with
``HOST_PREFIX``: the benchmark's own annotations.
"""

import glob
import os
import threading
import time

import numpy as np

HOST_PREFIX = "bench."
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


class SliceTracer:
    """Traces ``seconds`` of the window, starting ``after`` seconds into
    it, into ``directory``. ``close()`` waits for the thread; ``span`` is
    the traced interval on the host's clock, or ``None``."""

    def __init__(self, directory, after, seconds):
        self.directory = directory
        self.after, self.seconds = after, seconds
        self.span = None
        self.error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="bench-slice-tracer", daemon=True)

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        if self._stop.wait(self.after):
            return
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            t_lo = time.perf_counter()
            self._stop.wait(self.seconds)
            t_hi = time.perf_counter()
            jax.profiler.stop_trace()
            self.span = (t_lo, t_hi)
        except Exception as e:  # noqa: BLE001 - reported by the run
            self.error = e

    def close(self):
        self._stop.set()
        self._thread.join(timeout=120)
        if self.error is not None:
            raise self.error
        if self._thread.is_alive():
            raise RuntimeError("the slice tracer did not stop")


def annotate(name):
    """A host span in the profiler's own trace (cheap when no trace is
    being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def find_xplane(directory):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def read_events(xplane_path):
    """The table of device rows and of the benchmark's host rows."""
    import jax

    rows = []
    data = jax.profiler.ProfileData.from_file(xplane_path)
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIX):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return rows


def union_seconds(intervals):
    """Seconds covered by ``[(start_ns, end_ns), ...]``, overlaps once."""
    covered, reach = 0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            covered += hi - lo
            reach = hi
        elif hi > reach:
            covered += hi - reach
            reach = hi
    return covered / 1e9


def gaps(intervals, lo=None, hi=None):
    """Idle ``(start_ns, end_ns)`` stretches between the merged busy
    intervals, within ``[lo, hi]`` where given."""
    out, reach = [], lo
    for a, b in sorted(intervals):
        if reach is not None and a > reach:
            out.append((reach, a))
        reach = b if reach is None else max(reach, b)
    if hi is not None and reach is not None and hi > reach:
        out.append((reach, hi))
    return out


def _device_lines(rows):
    """``{device plane: {"ops": [...], "modules": [...]}}`` of rows. A
    device plane that shows rows and none on the line of single
    operations, or none on the line of whole programs, is an error that
    lists the lines it has: busy time is never read off another line."""
    planes, lines = {}, {}
    for plane, line, name, start, dur in rows:
        if not plane.startswith("/device:") or dur <= 0:
            continue
        lines.setdefault(plane, set()).add(line)
        p = planes.setdefault(plane, {"ops": [], "modules": []})
        if line in OPS_LINES:
            p["ops"].append((name, start, start + dur))
        elif line in MODULE_LINES:
            p["modules"].append((name, start, start + dur))
    for plane, p in planes.items():
        if not p["ops"] or not p["modules"]:
            raise LookupError(
                f"{plane} has no rows on {OPS_LINES} or on {MODULE_LINES}; "
                f"lines with rows: {', '.join(sorted(lines[plane]))}")
    return planes


def reduce_trace(rows, window_s, top=10):
    """Busy seconds per device plane, the idle share, the programs that
    took most device time and the longest idle gaps, each gap named by the
    benchmark's host span that covers most of it."""
    planes = _device_lines(rows)
    if not planes:
        return None
    busy = {k: union_seconds([(a, b) for _, a, b in p["ops"]])
            for k, p in planes.items()}
    busiest = max(busy, key=busy.get)
    by_name = {}
    for p in planes.values():
        for name, a, b in p["modules"]:
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    host = [(name, start, start + dur)
            for plane, _, name, start, dur in rows
            if not plane.startswith("/device:")]
    names = sorted({name for name, _, _ in host})
    spans = {n: (np.array([a for m, a, _ in host if m == n]),
                 np.array([b for m, _, b in host if m == n])) for n in names}
    idle = []
    for a, b in gaps([(a, b) for _, a, b in planes[busiest]["ops"]]):
        cover = {n: float(np.clip(np.minimum(b, hb) - np.maximum(a, ha),
                                  0, None).sum())
                 for n, (ha, hb) in spans.items()}
        who = max(cover, key=cover.get) if cover else None
        idle.append((who if who and cover[who] > 0 else "unattributed",
                     (b - a) / 1e9))
    by_span = {}
    for who, s in idle:
        by_span[who] = by_span.get(who, 0.0) + s
    return {
        "busy_s_per_plane": busy,
        "busy_s_mean": sum(busy.values()) / len(busy),
        "busy_s_busiest": busy[busiest],
        "idle_pct": 100.0 * (1.0 - busy[busiest] / window_s),
        "idle_by_host_span_s": by_span,
        "device_ops": [[n, s] for n, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[w, s] for w, s in sorted(
            idle, key=lambda g: -g[1])[:top]],
    }
