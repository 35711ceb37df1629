"""Profiler trace of a slice of the window, and its reduction.

The main thread is inside ``env.execute()`` for the whole window, so a
timer thread starts and stops ``jax.profiler``. The reduction works on a
plain table of events (``read_events``) so that it can be checked on a
small recorded table without a chip.

Table row: ``(plane, line, name, start_ns, duration_ns)``. Device planes
are those named ``/device:...``; of a device plane the line that lists
single operations (``XLA Ops``) is the busy/idle source, and the line
that lists whole programs (``XLA Modules``) names them for the
breakdown. Host rows are kept where their name starts with one of
``HOST_PREFIXES``: the benchmark's own annotations and the rows the
program's flight recorder mirrors into a profiler session. A host plane
has one line per thread and the lines share names, so a host row's line
is ``<name>#<index of the line in its plane>``.

The device rows of a capture run on after its host rows end (by 0.6 to
1.7 s on the v5e), so everything is taken over the range the kept host
rows cover, first start to last end: device rows are
clipped to it and the shares are of its length, never of the seconds the
host asked for.
"""

import glob
import os
import threading
import time

BENCH_PREFIX = "bench."
HOST_PREFIXES = (BENCH_PREFIX, "flink.")
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: the rows ``probe.tap_window_operator`` opens around the window
#: operator's calls: the host line that holds them is the operator thread's
OPERATOR_ROWS = (BENCH_PREFIX + "process_batch", BENCH_PREFIX + "on_watermark")
#: what ``SliceTracer`` opens first and last inside a capture, each beside a
#: reading of the host's clock: they bound the range and tie the two clocks
MARK_ROW = BENCH_PREFIX + "trace_mark"
NO_SPAN = "no span open"


class SliceTracer:
    """Traces ``seconds`` of the window, starting ``after`` seconds into
    it, into ``directory``. ``close()`` waits for the thread; ``span`` is
    the host clock's reading at the capture's first and last row
    (``MARK_ROW``), or ``None``."""

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
            t_lo = self._mark()
            self._stop.wait(self.seconds)
            t_hi = self._mark()
            jax.profiler.stop_trace()
            self.span = (t_lo, t_hi)
        except Exception as e:  # noqa: BLE001 - reported by the run
            self.error = e

    @staticmethod
    def _mark():
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(MARK_ROW):
            return t

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

    return jax.profiler.TraceAnnotation(BENCH_PREFIX + name)


def find_xplane(directory):
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def read_events(xplane_path):
    """The table of device rows and of the kept host rows."""
    import jax

    rows = []
    data = jax.profiler.ProfileData.from_file(xplane_path)
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for i, line in enumerate(plane.lines):
            where = line.name if device else f"{line.name}#{i}"
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIXES):
                    rows.append((plane.name, where, ev.name,
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


def clip(intervals, lo, hi):
    """The parts of ``[(name, start_ns, end_ns), ...]`` inside
    ``[lo, hi]``."""
    return [(name, max(a, lo), min(b, hi)) for name, a, b in intervals
            if b > lo and a < hi]


def innermost(spans):
    """``[(start_ns, end_ns, name), ...]`` in time order: the stretches of
    one thread's line under each of ``[(name, start_ns, end_ns), ...]``
    while it is the span that opened last among those open — of nested
    spans the innermost. Where none is open there is no stretch."""
    edges = sorted({t for _, a, b in spans for t in (a, b)})
    opening = sorted(spans, key=lambda s: (s[1], -s[2]))
    out, stack, i = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while i < len(opening) and opening[i][1] <= lo:
            stack.append(opening[i])
            i += 1
        while stack and stack[-1][2] <= lo:
            stack.pop()
        if stack:
            if out and out[-1][2] == stack[-1][0] and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, out[-1][2])
            else:
                out.append((lo, hi, stack[-1][0]))
    return out


def idle_by_span(idle, stretches):
    """For each idle ``(start_ns, end_ns)``: ``{name: ns}`` of the
    stretches of ``innermost`` it lies under, ``NO_SPAN`` for the rest.
    Both lists in time order."""
    out, i = [], 0
    for a, b in idle:
        under = {}
        while i < len(stretches) and stretches[i][1] <= a:
            i += 1
        j = i
        while j < len(stretches) and stretches[j][0] < b:
            lo, hi, name = stretches[j]
            under[name] = under.get(name, 0) + min(b, hi) - max(a, lo)
            j += 1
        bare = (b - a) - sum(under.values())
        if bare > 0:
            under[NO_SPAN] = bare
        out.append(under)
    return out


def host_clock(rows, marks):
    """``f(trace ns) -> seconds on the host's clock``, from the
    ``MARK_ROW`` rows of the table and the readings ``marks`` that
    ``SliceTracer`` took as it opened each."""
    starts = sorted(start for plane, _, name, start, _ in rows
                    if name == MARK_ROW and not plane.startswith("/device:"))
    if len(starts) != len(marks) or not marks:
        raise LookupError(f"{len(starts)} {MARK_ROW} row(s) in the capture "
                          f"for {len(marks)} reading(s) of the host's clock")
    offsets = [t - ns / 1e9 for t, ns in zip(marks, starts)]
    if max(offsets) - min(offsets) > 1e-3:
        raise ValueError(f"the host's clock and the capture's drift apart: "
                         f"offsets {offsets}")
    offset = sum(offsets) / len(offsets)
    return lambda ns: offset + ns / 1e9


def reduce_trace(rows, asked_s, top=10):
    """Over the range the kept host rows cover: busy seconds per device
    plane, the idle share of the busiest, the programs that took most
    device time, every idle second of the busiest chip by the innermost
    span open on the operator thread's line, and the longest idle gaps,
    each named by the span that way open over most of it. ``None`` where
    no device plane has rows; an error where the host rows cover under
    half of the ``asked_s`` seconds the capture was to last."""
    planes = _device_lines(rows)
    if not planes:
        return None
    host = {}
    for plane, line, name, start, dur in rows:
        if not plane.startswith("/device:"):
            host.setdefault((plane, line), []).append(
                (name, start, start + dur))
    kept = [span for spans in host.values() for span in spans]
    if not kept:
        raise LookupError("the capture holds no host row to take its "
                          f"range from (kept: {', '.join(HOST_PREFIXES)}*)")
    lo = min(a for _, a, _ in kept)
    hi = max(b for _, _, b in kept)
    window_s = (hi - lo) / 1e9
    if window_s < 0.5 * asked_s:
        raise ValueError(f"the host rows cover {window_s:.3f} s of the "
                         f"{asked_s:.3f} s the capture was to last")
    ops = {k: clip(p["ops"], lo, hi) for k, p in planes.items()}
    busy = {k: union_seconds([(a, b) for _, a, b in v])
            for k, v in ops.items()}
    busiest = max(busy, key=busy.get)
    whole = union_seconds([(a, b) for _, a, b in planes[busiest]["ops"]])
    by_name = {}
    for p in planes.values():
        for name, a, b in clip(p["modules"], lo, hi):
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    tapped = {k: sum(b - a for name, a, b in spans if name in OPERATOR_ROWS)
              for k, spans in host.items()}
    operator = max(tapped, key=tapped.get)
    if not tapped[operator]:
        raise LookupError("no host line holds the window operator's rows "
                          f"({', '.join(OPERATOR_ROWS)})")
    stretches = innermost(host[operator])
    idle = gaps([(a, b) for _, a, b in ops[busiest]], lo, hi)
    under = idle_by_span(idle, stretches)
    by_span = {}
    for u in under:
        for name, ns in u.items():
            by_span[name] = by_span.get(name, 0.0) + ns / 1e9
    named = [(max(u, key=u.get), (b - a) / 1e9)
             for (a, b), u in zip(idle, under)]
    return {
        "asked_s": asked_s,
        "window_s": window_s,
        "range_ns": [lo, hi],
        "overrun_s": max(0, max(
            b - hi for p in planes.values() for _, _, b in p["ops"])) / 1e9,
        "busy_s_outside": whole - busy[busiest],
        "operator_line": list(operator),
        "busy_s_per_plane": busy,
        "busy_s_mean": sum(busy.values()) / len(busy),
        "busy_s_busiest": busy[busiest],
        "idle_pct": 100.0 * (1.0 - busy[busiest] / window_s),
        "idle_by_host_span_s": dict(sorted(
            by_span.items(), key=lambda kv: -kv[1])),
        "device_ops": [[n, s] for n, s in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[w, s] for w, s in sorted(
            named, key=lambda g: -g[1])[:top]],
    }
