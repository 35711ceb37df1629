"""Flight recorder: always-on, bounded-overhead per-batch pipeline
tracing.

WHERE the time went used to be rediscovered through ad-hoc counters;
the reference dedicates a whole layer to making that a standing
capability (SURVEY/PAPER §5 — spans, flame graphs, latency markers, the
webmonitor). This module is that layer for the micro-batch engines: a
process-global recorder the hot paths write into unconditionally, cheap
enough to leave on (a span costs 3.96 us on the chip's host, PERF.md
§6, PR 26; benchmark/ reads its per-kind totals for every per-layer
metric).

Design constraints, in order:

- **No allocation on the hot path.** Each thread owns preallocated
  parallel numpy arrays (a ring: drop-oldest by cursor wraparound) and
  a reusable stack of span context managers — recording one span is a
  handful of scalar stores, no objects, no locks (per-thread rings;
  the registry lock is taken once per thread lifetime).
- **Monotonic clock.** Spans time with ``time.perf_counter``; one
  ``(wall, perf)`` anchor pair taken at recorder creation maps records
  onto the wall clock for export.
- **Correlated attribution.** Every record carries ``(job, shard,
  batch_id, watermark)``. Call sites pass what they know; the rest is
  inherited from an ambient per-thread context (``set_job`` /
  ``set_batch`` / ``set_watermark``) so the executor names the job
  once, the engine names the batch once, and a harvest three layers
  down still lands attributed. The context also holds the *origin* of
  the work in hand (``set_origin``: the clock reading at which the
  source handed over the batch the task loop is processing); a
  dispatched fire takes ``(watermark, origin)`` with it
  (:func:`fire_context`) and its harvest, turns later, puts both back,
  so every record of one result window — harvest, downstream
  operators, sink, ``window.emit`` — carries that window's watermark.
- **Waiting is recorded where it is waited.** Each hand-over between
  two threads or between host and device has a kind of its own, timed
  on the waiting side: ``loop.wait_source`` / ``source.wait_loop`` /
  ``source.queue_wait`` at the source queue, ``fire.in_flight`` /
  ``fire.poll_gap`` between a fire's dispatch and its harvest,
  ``window.emit`` from the closing batch's origin to the sink's return.
- **One timeline.** Durations (batch lifecycle, fires, harvests,
  checkpoints, serving lookups) and instants (XLA backend compiles,
  D2H materializations, watchdog deadline misses, armed chaos
  injections) interleave in the same ring, so a mystery fire-p99 spike
  reads directly as "compile under fire span on shard 3" in Perfetto.

- **A span says what it is.** Each thread keeps the chain of its open
  spans, so a record carries the kind of the span that enclosed it
  (``parent``) and the per-kind aggregates carry *self time* — a
  span's duration minus what its children on the same thread covered
  (an externally timed ``instant(..., duration_s=dt)`` counts as a
  child of the span open on that thread, clipped to it; a child on
  another thread is not subtracted). A span may state how much work it
  did (``s.work = n``: events, pairs, bytes — whatever the kind
  counts), summed per kind at the same boundary that times it, and
  ``faults=True`` adds the thread's page faults over the span.
- **One clock with the device.** While a profiler session is active, a
  span of the batch / fire lifecycle is also open as a
  ``jax.profiler.TraceAnnotation`` named ``flink.<kind>``, so the
  session holds the program's spans on the profiler's own clock beside
  the device rows. Without a session that costs one check per span.

Span kinds are a closed registry (:data:`flink_tpu.observe.
KNOWN_SPAN_KINDS`): an unregistered kind raises at the call site, and
flint's REG03 cross-checks every literal producer statically — the
recorder and the exporter schema cannot drift.

Usage::

    from flink_tpu.observe import flight_recorder as flight

    flight.set_job("pipeline-a")
    with flight.span("batch.ingest", shard=-1, batch=seq) as s:
        ...
        s.work = len(batch)
    flight.instant("watchdog.miss", shard=3)

Disable with ``FLINK_TPU_FLIGHT_RECORDER=0`` (spans become no-ops that
cost one module-global check), or per-region with :func:`disabled`.
"""

from __future__ import annotations

import os
import resource
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import numpy as np

#: sentinel for "no watermark attribution" (int64 min would render as
#: a plausible timestamp; this is unmistakably absent)
WM_NONE = -(1 << 62)

#: per-thread ring capacity (records); power of two so the drop-oldest
#: wraparound is a mask, not a modulo
_CAPACITY = 1 << int(os.environ.get(
    "FLINK_TPU_FLIGHT_RECORDER_CAPACITY_POW2", "16"))
#: per-kind duration reservoir depth (overwritten modulo — a cheap
#: recent-window sample, not a full history)
_RESERVOIR = 256

_enabled = os.environ.get("FLINK_TPU_FLIGHT_RECORDER", "1") != "0"

#: first components of the kinds mirrored into a profiler session as
#: ``flink.<kind>`` rows: the batch / fire lifecycle on the task loop
#: and the checkpoint that stops it (the other control-plane spans and
#: the instants stay in the recorder only)
_MIRRORED = frozenset(
    ("op", "batch", "prep", "session", "device", "exchange", "fire", "slice",
     "sink", "loop", "source", "checkpoint"))
#: ``resource.RUSAGE_THREAD`` (Linux); absent elsewhere, where
#: ``faults=True`` then counts nothing
_RUSAGE_THREAD = getattr(resource, "RUSAGE_THREAD", None)
#: ``jax.profiler.TraceAnnotation``, looked up at the first span (the
#: recorder itself never imports jax at module import)
_trace_annotation = None


def _annotation_class():
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation


class SpanRecord(NamedTuple):
    """One decoded record (``snapshot()`` output)."""

    kind: str
    instant: bool
    t0: float          # perf_counter seconds
    t1: float
    job: Optional[str]
    shard: int
    batch_id: int
    watermark: Optional[int]
    thread: str
    #: kind of the span that enclosed this record on its thread
    parent: Optional[str] = None
    #: what the span said it did (0 where it said nothing)
    work: int = 0

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0


class _SpanCtx:
    """Reusable span context manager (pooled per thread — entering a
    span allocates nothing once the pool is warm). ``work`` is the
    caller's to set inside the ``with``; ``duration_s`` is readable
    right after it."""

    __slots__ = ("_ring", "_kind", "_shard", "_batch", "_wm", "_job",
                 "_t0", "_outer", "_child", "_mirror", "_tm", "_faults",
                 "_flt", "work", "duration_s")

    def __init__(self, ring: "_ThreadRing") -> None:
        self._ring = ring
        self._tm = None

    def __enter__(self) -> "_SpanCtx":
        r = self._ring
        self._outer = r.open
        r.open = self
        self._child = 0.0
        self.work = 0
        name = self._mirror
        if name is not None:
            cls = _annotation_class()
            if cls.is_enabled():
                # the TraceMe starts at construction: opened before and
                # closed after the span's own clock reads, so the
                # session's row encloses the record
                self._tm = cls(name)
        if self._faults:
            ru = resource.getrusage(_RUSAGE_THREAD)
            self._flt = (ru.ru_minflt, ru.ru_majflt)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        r = self._ring
        d = t1 - self._t0
        self.duration_s = d
        outer = self._outer
        r.open = outer
        self._outer = None
        parent = -1
        if outer is not None:
            outer._child += d
            parent = outer._kind
        kind = self._kind
        r.write(kind, 0, self._t0, t1, self._job, self._shard,
                self._batch, self._wm, parent, d - self._child,
                self.work)
        if self._faults:
            ru = resource.getrusage(_RUSAGE_THREAD)
            r.k_minflt[kind] += ru.ru_minflt - self._flt[0]
            r.k_majflt[kind] += ru.ru_majflt - self._flt[1]
        tm = self._tm
        if tm is not None:
            self._tm = None
            tm.__exit__(exc_type, exc, tb)
        r.pool.append(self)


class _NullSpan:
    """What ``span()`` returns with the recorder off: nothing is timed,
    and ``work`` swallows what the call site states."""

    __slots__ = ()
    duration_s = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    @property
    def work(self) -> int:
        return 0

    @work.setter
    def work(self, n) -> None:
        pass


class _TimerSpan(_NullSpan):
    """``span(..., timed=True)`` with the recorder off: records
    nothing, but still times — for the call sites whose own accounting
    reads the span's ``duration_s`` (the executor's ``busy_s``)."""

    __slots__ = ("_t0", "duration_s")

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.duration_s = time.perf_counter() - self._t0
        return None


_NULL_SPAN = _NullSpan()


class _ThreadRing:
    """One thread's preallocated record ring + per-kind aggregates +
    span-context pool. Single-writer (the owning thread); snapshot
    readers copy the arrays, which is safe because writes are
    monotonic scalar stores and a torn read costs at most one
    half-written record at the cursor."""

    def __init__(self, n_kinds: int, name: str) -> None:
        self.name = name
        cap = _CAPACITY
        self.mask = cap - 1
        self.cursor = 0
        self.kind = np.zeros(cap, dtype=np.int16)
        self.flags = np.zeros(cap, dtype=np.int8)
        self.t0 = np.zeros(cap, dtype=np.float64)
        self.t1 = np.zeros(cap, dtype=np.float64)
        self.job = np.full(cap, -1, dtype=np.int32)
        self.shard = np.full(cap, -1, dtype=np.int32)
        self.batch = np.full(cap, -1, dtype=np.int64)
        self.wm = np.full(cap, WM_NONE, dtype=np.int64)
        self.parent = np.full(cap, -1, dtype=np.int16)
        self.work = np.zeros(cap, dtype=np.int64)
        # per-kind duration aggregates (merged across threads on read)
        self.k_count = np.zeros(n_kinds, dtype=np.int64)
        self.k_total = np.zeros(n_kinds, dtype=np.float64)
        self.k_self = np.zeros(n_kinds, dtype=np.float64)
        self.k_max = np.zeros(n_kinds, dtype=np.float64)
        self.k_work = np.zeros(n_kinds, dtype=np.int64)
        # the thread's page faults over spans opened with faults=True
        self.k_minflt = np.zeros(n_kinds, dtype=np.int64)
        self.k_majflt = np.zeros(n_kinds, dtype=np.int64)
        self.k_res = np.zeros((n_kinds, _RESERVOIR), dtype=np.float32)
        self.k_cursor = np.zeros(n_kinds, dtype=np.int64)
        # ambient attribution context (set by the layer that knows)
        self.ctx_job = -1
        self.ctx_batch = -1
        self.ctx_wm = WM_NONE
        #: perf_counter reading at which the source handed over the
        #: batch in hand (0.0: none)
        self.ctx_origin = 0.0
        #: innermost span open on this thread (the spans chain through
        #: ``_outer``: the stack is the contexts themselves)
        self.open: Optional[_SpanCtx] = None
        self.pool: List[_SpanCtx] = [_SpanCtx(self) for _ in range(8)]

    def write(self, kind_id: int, flags: int, t0: float, t1: float,
              job: int, shard: int, batch: int, wm: int,
              parent: int = -1, self_s: float = 0.0,
              work: int = 0) -> None:
        i = self.cursor & self.mask
        self.cursor += 1
        self.kind[i] = kind_id
        self.flags[i] = flags
        self.t0[i] = t0
        self.t1[i] = t1
        self.job[i] = job
        self.shard[i] = shard
        self.batch[i] = batch
        self.wm[i] = wm
        self.parent[i] = parent
        self.work[i] = work
        # counts aggregate for EVERY record (an operator reading
        # flight.chaos_inject_count must see armed injections);
        # durations only for spans — instants' quantiles stay 0
        self.k_count[kind_id] += 1
        if work:
            self.k_work[kind_id] += work
        if not flags:
            d = t1 - t0
            self.k_total[kind_id] += d
            if self_s > 0.0:
                self.k_self[kind_id] += self_s
            if d > self.k_max[kind_id]:
                self.k_max[kind_id] = d
            self.k_res[kind_id, self.k_cursor[kind_id] % _RESERVOIR] = d
            self.k_cursor[kind_id] += 1


class FlightRecorder:
    """The process-global span plane (see module docstring). Normally
    used through the module-level :func:`span` / :func:`instant`;
    constructing private instances is for tests."""

    def __init__(self, kinds) -> None:
        self.kinds = tuple(kinds)
        self._kind_id = {k: i for i, k in enumerate(self.kinds)}
        if len(self._kind_id) != len(self.kinds):
            raise ValueError("duplicate span kinds")
        #: per kind id, the name of its row in a profiler session
        self._mirror = tuple(
            "flink." + k if k.split(".", 1)[0] in _MIRRORED else None
            for k in self.kinds)
        self._lock = threading.Lock()
        self._rings: List[_ThreadRing] = []
        self._tl = threading.local()
        self._jobs: List[str] = []
        self._job_id: Dict[str, int] = {}
        #: (wall, perf) anchor: wall = anchor[0] + (t - anchor[1])
        self.anchor = (time.time(), time.perf_counter())

    # ------------------------------------------------------------ hot path

    def _ring(self) -> _ThreadRing:
        ring = getattr(self._tl, "ring", None)
        if ring is None:
            ring = _ThreadRing(len(self.kinds),
                               threading.current_thread().name)
            with self._lock:
                self._rings.append(ring)
            self._tl.ring = ring
        return ring

    def span(self, kind: str, shard: int = -1, batch: int = -1,
             watermark: int = WM_NONE, job: Optional[str] = None,
             faults: bool = False, timed: bool = False):
        """Context manager timing one lifecycle section. Unspecified
        attribution falls back to the thread's ambient context.
        ``faults`` also sums the thread's minor / major page faults
        over the span per kind; ``timed`` keeps ``duration_s`` readable
        after the ``with`` even when the recorder is off."""
        if not _enabled:
            return _TimerSpan() if timed else _NULL_SPAN
        ring = self._ring()
        pool = ring.pool
        ctx = pool.pop() if pool else _SpanCtx(ring)
        kid = ctx._kind = self._kind_id[kind]
        ctx._mirror = self._mirror[kid]
        ctx._faults = faults and _RUSAGE_THREAD is not None
        ctx._shard = shard
        ctx._batch = batch if batch >= 0 else ring.ctx_batch
        ctx._wm = watermark if watermark != WM_NONE else ring.ctx_wm
        ctx._job = self.job_id(job) if job is not None else ring.ctx_job
        return ctx

    def instant(self, kind: str, shard: int = -1, batch: int = -1,
                watermark: int = WM_NONE, job: Optional[str] = None,
                t0: Optional[float] = None,
                duration_s: float = 0.0, work: int = 0,
                timed: bool = False) -> None:
        """Record an instant event (or a short externally-timed span,
        e.g. an XLA compile whose duration arrives via monitoring:
        pass ``duration_s`` and it lands as ``[now - d, now]``, a child
        of the span open on this thread, clipped to it). ``timed``
        says the record is a measurement whatever it reads: a
        ``duration_s`` of 0 then is a sample of 0 in the kind's
        aggregates and quantiles, not an instant."""
        if not _enabled:
            return
        ring = self._ring()
        now = time.perf_counter() if t0 is None else t0
        parent = -1
        outer = ring.open
        if outer is not None:
            parent = outer._kind
            if duration_s > 0.0:
                outer._child += max(
                    min(duration_s, now - outer._t0), 0.0)
        ring.write(
            self._kind_id[kind], 0 if duration_s > 0.0 or timed else 1,
            now - duration_s, now,
            self.job_id(job) if job is not None else ring.ctx_job,
            shard,
            batch if batch >= 0 else ring.ctx_batch,
            watermark if watermark != WM_NONE else ring.ctx_wm,
            parent, duration_s, work)

    # ------------------------------------------------------ ambient context

    def job_id(self, name: str) -> int:
        # flint: disable=LCK01 -- deliberate double-checked fast path
        # on the per-span hot path: entries are insert-only and the
        # slow path re-checks under the lock before assigning
        jid = self._job_id.get(name)
        if jid is None:
            with self._lock:
                jid = self._job_id.get(name)
                if jid is None:
                    jid = len(self._jobs)
                    self._jobs.append(name)
                    self._job_id[name] = jid
        return jid

    def set_job(self, name: Optional[str]) -> None:
        self._ring().ctx_job = -1 if name is None else self.job_id(name)

    def set_batch(self, batch_id: int) -> None:
        self._ring().ctx_batch = int(batch_id)

    def set_watermark(self, wm: int) -> None:
        self._ring().ctx_wm = int(wm)

    def set_origin(self, t: float) -> None:
        self._ring().ctx_origin = t

    # ------------------------------------------------------------- reading

    def _iter_rings(self) -> Iterator[_ThreadRing]:
        with self._lock:
            rings = list(self._rings)
        return iter(rings)

    def snapshot(self) -> List[SpanRecord]:
        """Decode every thread's ring, merged and sorted by start time.
        Half-open rings decode their written prefix; full rings decode
        all records (oldest first is not guaranteed across the wrap —
        the sort restores global time order)."""
        out: List[SpanRecord] = []
        with self._lock:
            jobs = list(self._jobs)
        for ring in self._iter_rings():
            n = min(ring.cursor, ring.mask + 1)
            if n == 0:
                continue
            for i in range(n):
                jid = int(ring.job[i])
                wm = int(ring.wm[i])
                par = int(ring.parent[i])
                out.append(SpanRecord(
                    kind=self.kinds[int(ring.kind[i])],
                    instant=bool(ring.flags[i]),
                    t0=float(ring.t0[i]), t1=float(ring.t1[i]),
                    job=jobs[jid] if 0 <= jid < len(jobs) else None,
                    shard=int(ring.shard[i]),
                    batch_id=int(ring.batch[i]),
                    watermark=None if wm == WM_NONE else wm,
                    thread=ring.name,
                    parent=self.kinds[par] if par >= 0 else None,
                    work=int(ring.work[i])))
        out.sort(key=lambda r: r.t0)
        return out

    def dropped(self) -> int:
        """Records overwritten by the drop-oldest policy so far."""
        return sum(max(0, r.cursor - (r.mask + 1))
                   for r in self._iter_rings())

    def kind_totals(self) -> Dict[str, Dict[str, float]]:
        """Per-kind aggregates merged across threads: ``{kind: {count,
        total_s, self_s, max_s, work, minor_faults, major_faults,
        p50_ms, p99_ms}}`` (``self_s``: total minus what children on
        the same thread covered; ``work``: what the spans said they
        did; quantiles over the bounded recent-window reservoirs;
        instants contribute counts and work only).
        Memoized on the rings' cursors: a metrics scrape reading many
        gauges pays ONE merge, not one per gauge."""
        from flink_tpu.metrics.core import quantile_sorted

        version = tuple(r.cursor for r in self._iter_rings())
        cached = getattr(self, "_kt_cache", None)
        if cached is not None and cached[0] == version:
            return cached[1]
        n = len(self.kinds)
        count = np.zeros(n, dtype=np.int64)
        total = np.zeros(n, dtype=np.float64)
        self_s = np.zeros(n, dtype=np.float64)
        kmax = np.zeros(n, dtype=np.float64)
        work = np.zeros(n, dtype=np.int64)
        minflt = np.zeros(n, dtype=np.int64)
        majflt = np.zeros(n, dtype=np.int64)
        samples: List[List[float]] = [[] for _ in range(n)]
        for ring in self._iter_rings():
            count += ring.k_count
            total += ring.k_total
            self_s += ring.k_self
            kmax = np.maximum(kmax, ring.k_max)
            work += ring.k_work
            minflt += ring.k_minflt
            majflt += ring.k_majflt
            for k in range(n):
                m = int(min(ring.k_cursor[k], _RESERVOIR))
                if m:
                    samples[k].extend(ring.k_res[k, :m].tolist())
        out: Dict[str, Dict[str, float]] = {}
        for k, kind in enumerate(self.kinds):
            if not count[k]:
                continue
            data = sorted(samples[k])
            out[kind] = {
                "count": int(count[k]),
                "total_s": float(total[k]),
                "self_s": float(self_s[k]),
                "max_s": float(kmax[k]),
                "work": int(work[k]),
                "minor_faults": int(minflt[k]),
                "major_faults": int(majflt[k]),
                "p50_ms": quantile_sorted(data, 0.5) * 1e3,
                "p99_ms": quantile_sorted(data, 0.99) * 1e3,
            }
        self._kt_cache = (version, out)
        return out

    def clear(self) -> None:
        """Reset every ring and aggregate (keeps thread registrations
        and job interning — cheap, called between bench reps)."""
        # cursors reset below, and a later refill can land on the same
        # cursor tuple a cached merge was keyed on — drop it explicitly
        self._kt_cache = None
        for ring in self._iter_rings():
            ring.cursor = 0
            ring.k_count[:] = 0
            ring.k_total[:] = 0.0
            ring.k_self[:] = 0.0
            ring.k_max[:] = 0.0
            ring.k_work[:] = 0
            ring.k_minflt[:] = 0
            ring.k_majflt[:] = 0
            ring.k_cursor[:] = 0


# ------------------------------------------------------------- module API

_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def recorder() -> FlightRecorder:
    """The process-global recorder (created on first use)."""
    global _recorder
    # flint: disable=LCK01 -- double-checked publish of an immutable
    # singleton slot; the slow path re-checks under the lock
    if _recorder is None:
        with _recorder_lock:
            if _recorder is None:
                from flink_tpu.observe import KNOWN_SPAN_KINDS

                _recorder = FlightRecorder(KNOWN_SPAN_KINDS)
    # flint: disable=LCK01 -- read of the published immutable singleton
    return _recorder


def span(kind: str, shard: int = -1, batch: int = -1,
         watermark: int = WM_NONE, job: Optional[str] = None,
         faults: bool = False, timed: bool = False):
    if not _enabled:
        return _TimerSpan() if timed else _NULL_SPAN
    return recorder().span(kind, shard=shard, batch=batch,
                           watermark=watermark, job=job, faults=faults,
                           timed=timed)


def instant(kind: str, shard: int = -1, batch: int = -1,
            watermark: int = WM_NONE, job: Optional[str] = None,
            t0: Optional[float] = None, duration_s: float = 0.0,
            work: int = 0, timed: bool = False) -> None:
    if not _enabled:
        return
    recorder().instant(kind, shard=shard, batch=batch,
                       watermark=watermark, job=job, t0=t0,
                       duration_s=duration_s, work=work, timed=timed)


def add_work(kind: str, n: int) -> None:
    """Add ``n`` to the work of the nearest span of ``kind`` open on
    this thread; nothing where none is. For a callee that knows what
    was done (bytes a snapshot fetched) under a span its caller owns."""
    if not _enabled:
        return
    rec = recorder()
    kid = rec._kind_id[kind]
    ctx = rec._ring().open
    while ctx is not None:
        if ctx._kind == kid:
            ctx.work += n
            return
        ctx = ctx._outer


def set_job(name: Optional[str]) -> None:
    if _enabled:
        recorder().set_job(name)


def set_batch(batch_id: int) -> None:
    if _enabled:
        recorder().set_batch(batch_id)


def set_watermark(wm: int) -> None:
    if _enabled:
        recorder().set_watermark(wm)


def set_origin(t: float) -> None:
    """Note the origin of the work in hand: the ``perf_counter``
    reading at which the source handed over the batch the thread is
    about to process."""
    if _enabled:
        recorder().set_origin(t)


def fire_context():
    """``(watermark, origin)`` of the thread's ambient context: what a
    fire takes with it at dispatch, for its harvest to put back."""
    if not _enabled:
        return WM_NONE, 0.0
    ring = recorder()._ring()
    return ring.ctx_wm, ring.ctx_origin


def set_fire_context(watermark: int, origin: float) -> None:
    """Put a fire's ``(watermark, origin)`` back into the thread's
    ambient context: its harvest and what that forwards carry them."""
    if _enabled:
        ring = recorder()._ring()
        ring.ctx_wm = watermark
        ring.ctx_origin = origin


def window_emit(watermark: int = WM_NONE) -> None:
    """THE emission contract: rows a watermark released have left
    through the chain — record ``window.emit``, the ambient origin ->
    now, under ``watermark`` (default: the ambient one, which a harvest
    set to its fire's). Nothing where no origin is known."""
    if not _enabled:
        return
    origin = recorder()._ring().ctx_origin
    if origin > 0.0:
        now = time.perf_counter()
        instant("window.emit", watermark=watermark, t0=now,
                duration_s=now - origin)


def ingest_span(seq: int):
    """THE ingest-span contract, in one place for every engine base
    (mesh window/session, joins): name the batch in the ambient
    context, then open ``batch.ingest`` carrying it."""
    set_batch(seq)
    return span("batch.ingest", batch=seq)


def fire_span(watermark: int):
    """THE fire-span contract: note the watermark in the ambient
    context, then open ``fire.dispatch`` carrying it."""
    set_watermark(int(watermark))
    return span("fire.dispatch", watermark=int(watermark))


def enabled() -> bool:
    return _enabled


class disabled:
    """Context manager suppressing recording (to exclude a noisy
    region, or for a recorder-off control run)."""

    def __enter__(self):
        global _enabled
        self._prev = _enabled
        _enabled = False
        return self

    def __exit__(self, *exc):
        global _enabled
        _enabled = self._prev
        return None


def _probe_compile(duration_s: float) -> None:
    """recompile-sentinel subscriber: one real XLA backend compile
    lands as a duration span ending now (jax.monitoring reports the
    compile's length, not its start)."""
    if _enabled:
        recorder().instant("xla.compile", duration_s=duration_s)


def _probe_transfer() -> None:
    """recompile-sentinel subscriber: one device->host materialization
    (``ArrayImpl.__array__``) lands as an instant."""
    if _enabled:
        recorder().instant("d2h.transfer")


def install_probes() -> None:
    """Wire the jax-level probes (backend compiles, D2H
    materializations) into the flight recorder — idempotent, shares
    the recompile sentinel's one-time ``jax.monitoring`` +
    ``__array__`` hook installation. Safe to call before jax is
    otherwise touched; costs nothing after the first call. A
    recorder disabled at process level (FLINK_TPU_FLIGHT_RECORDER=0)
    skips the installation entirely — opting out must not
    monkey-patch ``__array__`` (the sentinel still installs its own
    hooks when explicitly used)."""
    if not _enabled:
        return
    from flink_tpu.observe import recompile_sentinel as rs

    rs.add_compile_listener(_probe_compile)
    rs.add_transfer_listener(_probe_transfer)
    rs.install()
