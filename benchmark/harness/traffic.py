"""The benchmark's own source and sink.

``TimedSource`` offers a job module's generated stream to the job behind
the program's ``Source`` interface, on the benchmark's clock, and logs
every batch it hands over. ``StampingSink`` stamps every write and keeps
the rows as columns for the comparison. Both only observe: the job runs
as the program runs it.
"""

import contextlib
import time

import numpy as np

from flink_tpu.connectors.sinks import Sink
from flink_tpu.connectors.sources import Source
from flink_tpu.core.records import RecordBatch


def no_span(name):
    """Where no trace is taken, a span is nothing."""
    return contextlib.nullcontext()


class BatchLog:
    """One row per batch handed over: first global index, event count,
    hand-over time, seconds spent generating it, and (paced) the time its
    last event was due."""

    def __init__(self):
        self.first, self.count, self.handed = [], [], []
        self.generate_s, self.due = [], []

    def add(self, first, count, handed, generate_s, due):
        self.first.append(first)
        self.count.append(count)
        self.handed.append(handed)
        self.generate_s.append(generate_s)
        self.due.append(due)

    @property
    def events(self):
        return int(sum(self.count))

    def events_between(self, t_lo, t_hi):
        """Events of the batches handed over in ``[t_lo, t_hi)``."""
        return int(sum(c for c, t in zip(self.count, self.handed)
                       if t_lo <= t < t_hi))


class TimedSource(Source):
    """A generated stream offered for a time, or up to a count.

    ``generate(first, n)`` gives the columns and event times of the global
    indices ``first .. first+n-1``. Traffic parameters (``traffic``):

    - ``mode`` ``"backlog"``: every poll returns the next ``max_records``
      events at once (closed loop: the replay of a backlog).
    - ``mode`` ``"paced"``: event ``i`` is due at ``t0 + i / rate``; a poll
      returns exactly ``batch_events`` events once the last of them is
      due, and before that an empty batch after a sleep of at most 1 ms.
      The schedule never slows when the job does.

    Offering stops at ``t0 + seconds`` (or, for a warm-up, once
    ``min_events`` are out). The stream then runs on to the next multiple
    of ``boundary`` events that is at least half a batch away, in equal
    batches of at least half a batch: the end-of-input flush closes whole
    windows, and no batch is small enough to meet a pad tier of its own.
    After that every poll returns ``None`` and the job drains.
    """

    bounded = True

    def __init__(self, generate, traffic, boundary, seconds=None,
                 min_events=None, clock=time.perf_counter, sleep=time.sleep,
                 span=no_span):
        if (seconds is None) == (min_events is None):
            raise ValueError("give seconds or min_events, not both")
        self.generate = generate
        self.mode = traffic["mode"]
        if self.mode not in ("backlog", "paced"):
            raise ValueError(f"unknown traffic mode {self.mode!r}; "
                             "known: backlog, paced")
        self.rate = float(traffic["rate"]) if self.mode == "paced" else None
        self.batch_events = int(traffic.get("batch_events", 0)) or None
        self.boundary = int(boundary)
        self.seconds = seconds
        self.min_events = min_events
        self.clock, self.sleep, self.span = clock, sleep, span
        self.log = BatchLog()
        self.t0 = None
        self._next = 0
        self._tail = None     # sizes of the batches that close the stream

    def arm(self, t0):
        """Start of the window: the schedule and the deadline count from
        here."""
        self.t0 = t0

    def open(self, subtask_index=0, parallelism=1):
        if parallelism != 1:
            raise ValueError("TimedSource is one split; the job shards the "
                             "stream after the source")
        if self.t0 is None:
            self.arm(self.clock())

    def _stop_offering(self, now):
        if self.seconds is not None:
            return now >= self.t0 + self.seconds
        return self._next >= self.min_events

    def _plan_tail(self, batch):
        end = -(-self._next // self.boundary) * self.boundary
        if end - self._next < batch // 2:
            end += self.boundary
        rest = end - self._next
        parts = -(-rest // batch)
        self._tail = [rest // parts + (i < rest % parts)
                      for i in range(parts)]

    def _hand_over(self, n, due):
        t = self.clock()
        with self.span("source_generate"):
            cols, ts = self.generate(self._next, n)
            batch = RecordBatch.from_pydict(cols, timestamps=ts)
        handed = self.clock()
        self.log.add(self._next, n, handed, handed - t, due)
        self._next += n
        return batch

    def poll_batch(self, max_records):
        batch = self.batch_events or int(max_records)
        if self._tail is None:
            now = self.clock()
            if self._stop_offering(now):
                self._plan_tail(batch)
            elif self.mode == "paced":
                due = self.t0 + (self._next + batch - 1) / self.rate
                if now < due:
                    self.sleep(min(due - now, 0.001))
                    return RecordBatch.from_pydict(
                        *self.generate(self._next, 0))
                return self._hand_over(batch, due)
            else:
                return self._hand_over(batch, None)
        if not self._tail:
            return None
        n = self._tail.pop(0)
        due = None
        if self.mode == "paced":
            # the tail keeps the schedule: it is late only if the job is
            due = self.t0 + (self._next + n - 1) / self.rate
            while (wait := due - self.clock()) > 0:
                self.sleep(min(wait, 0.001))
        return self._hand_over(n, due)

    def snapshot_position(self):
        return {"next": self._next}

    def restore_position(self, pos):
        self._next = int(pos["next"])


class StampingSink(Sink):
    """Keeps what the job wrote as column arrays, with the time of each
    write and the ``window_end`` values it carried."""

    def __init__(self, columns, clock=time.perf_counter, span=no_span):
        self.columns = tuple(columns)
        self.clock, self.span = clock, span
        self.parts = {c: [] for c in self.columns}
        self.stamps = []      # (time, distinct window_end values)

    def write(self, batch):
        now = self.clock()
        if len(batch) == 0:
            return
        with self.span("sink_write"):
            for c in self.columns:
                self.parts[c].append(np.asarray(batch[c]))
            ends = self.parts["window_end"][-1]
            lo, hi = ends.min(), ends.max()   # a fired batch is one window
            self.stamps.append(
                (now, np.array([lo]) if lo == hi else np.unique(ends)))

    def result(self):
        """All rows, as ``{column: array}``."""
        return {c: (np.concatenate(p) if p else np.zeros(0, dtype=np.int64))
                for c, p in self.parts.items()}

    def windows_written_between(self, t_lo=float("-inf"),
                                t_hi=float("inf")):
        """Distinct result windows whose (last) write fell in
        ``[t_lo, t_hi)``; all of them by default."""
        last = last_write_per_window(self.stamps)
        return sum(1 for t in last.values() if t_lo <= t < t_hi)


def longest_gaps(times, t0, top=5):
    """The ``top`` longest stretches between consecutive ``times``, each as
    ``[seconds into the window where it began, its length]``: where a run
    stood still, for the look into a run that reads far off."""
    gaps = sorted(((b - a, a - t0) for a, b in zip(times, times[1:])),
                  reverse=True)[:top]
    return [[at, length] for length, at in gaps]


def last_write_per_window(stamps):
    """``{window_end: time of the last write that carried it}``."""
    last = {}
    for t, ends in stamps:
        for w in ends.tolist():
            last[w] = max(t, last.get(w, t))
    return last
