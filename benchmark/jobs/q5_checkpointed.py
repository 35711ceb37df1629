"""NEXmark Query 5 (hot items) with checkpointing on: the job, and a
comparison that restores what was written.

The query, the auctions, the 4 s watermark, the 16 candidate rows per fire,
the generator, the plain reference and the window rule are
``q5_generator.py``'s, delegated and not copied: this is that job as
``nexmark-flink`` deploys it — exactly-once, incremental checkpoints of all
operator state and the source position to a directory on the local disk.
``build`` is ``q5_generator.build`` — and before the sink a map that notes
when each fired window passed — on an environment whose
``state.checkpoints.dir`` is a fresh temporary directory (made per job,
removed by the comparison); checkpointing itself is switched on by the
configuration's own ``options`` (``execution.checkpointing.interval-ms``,
``.incremental``, ``.mode``), nothing here.

**The warm-up job** (the harness's set-up runs ``build`` over a bounded
input: its source has ``min_events``) ends long before a 2 s interval has
passed, so the first checkpoints, and the one program a delta compiles —
the gather of its dirty rows, per sticky pad tier — would fall into the
window. That job alone is therefore cut on
``execution.checkpointing.every-n-source-batches``
(``warmup_checkpoint_batches``): a full checkpoint and a delta whose dirty
rows pad to the tier the window's deltas pad to. The timed job cuts on the
interval.

**The comparison** (``check``, what decides ``correct``), each number
beside its limit:

(a) ``rows_wrong``: the timed job's sink rows against the plain reference
    by ``q5_generator.compare``'s rule: a checkpoint changes no answer.
(b) ``checkpoints_missing``: completed checkpoints short of what the run's
    length owes (``seconds // checkpoint_period_ms`` on the time trigger —
    the period is the interval plus the stall; every ``n``-th batch on the
    batch trigger); ``checkpoints_unreadable``: retained checkpoints whose
    files fail their manifest's CRCs or whose delta chain does not read
    back (``read_manifest``, ``verify_snapshot_files``,
    ``read_checkpoint_chain``); ``checkpoint_state_wrong``: rows of keyed
    state (auction, slice, count) that a retained checkpoint, its chain
    materialized, and the reference's state at the same source position
    do not share (``state_faults``). A job that skips its checkpoints is
    faster and wrong.
(c) ``restored_rows_wrong`` / ``restored_windows_missing``: a fresh job of
    the same builder, restored through the executor's own path
    (``env.execute(restore_from=<checkpoint root>)``: the newest completed
    checkpoint, a delta at the end of a chain) and fed the same stream from
    the checkpointed source position to the timed run's last bid, must emit
    every window that had not fired at the cut — those whose end lies
    beyond the cut's watermark — and nothing else, each held to the
    reference by rule (a); and every window of the reference must either
    come from it or have reached the first job's sink before the
    checkpoint's manifest was stamped (a result still in flight at the cut
    is lost by a crash, and is missing here). It runs after the window
    with the flight recorder off, so that no metric reads it.

What it does NOT see: a fault in a checkpoint that retention has removed
by the end of the run (three are kept; the last is restored, all three are
read back and their state compared), state outside the keyed table and
the source position that the restored job's answers do not depend on, and
the time a recovery takes.

**The control** (``reference_rows(control=True)``): the reference under
at-least-once delivery — one micro-batch of ``control_replayed_events``
bids, drawn from the seed, counted twice: what a source position saved one
batch behind the state gives after a restore. Every hot auction of that
batch doubles its count, so the five windows that hold it change all 16
candidates.

Options (a configuration's ``job_options``): ``q5_generator``'s but for
its own control's, and ``checkpoint_period_ms``,
``warmup_checkpoint_batches``, ``control_replayed_events``.
"""

import os
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark.jobs import q5_generator as q5g
from benchmark.jobs._hash import splitmix64

SINK_COLUMNS = q5g.SINK_COLUMNS

CHECKPOINT_DIR = "state.checkpoints.dir"
EVERY_N_BATCHES = "execution.checkpointing.every-n-source-batches"
BATCH_SIZE = "execution.micro-batch.size"

#: the job at a size a CPU test can hold (``q5_generator.TINY``'s cut).
#: A one-second run on a CPU never sees the 2 s interval, so the cut adds
#: the batch trigger: every 8 batches (2.85 slides: a delta's dirty rows
#: stay in one pad tier), which the warm-up job then takes too
TINY = {
    "options": {**q5g.TINY["options"], EVERY_N_BATCHES: 8},
    "job_options": {**q5g.TINY["job_options"],
                    "control_replayed_events": 8192},
}

first_index_with_ts = q5g.first_index_with_ts
make_generator = q5g.make_generator
boundary_events = q5g.boundary_events
warmup_events = q5g.warmup_events
compare = q5g.compare
work = q5g.work

#: what ``build`` kept of the job it built last, for ``check``: the
#: checkpoint root it made, the options the job ran under, its source
_built = None


def wire(env, source, o, emitted):
    """``q5_generator``'s job, and before its sink a map that notes in
    ``emitted`` when each fired window passed, ``(time.time(), window
    end)``: the next thing the push does is the sink's write, on the same
    thread, so what passed before a checkpoint's manifest was stamped is
    what the sink held at the cut."""
    def note(batch):
        # a fired batch is one window
        emitted.append((time.time(), int(batch["window_end"][0])))
        return batch

    counts, window = q5g.build(env, source, o)
    return counts.map(note, name="emission_log"), window


def build(env, source, o):
    """The job on ``env``, checkpointing into a fresh temporary directory;
    the directory of the job built before is removed. A warm-up job (a
    source bounded by ``min_events``) cuts on the batch trigger, unless
    the options already name one."""
    global _built
    if _built is not None:
        shutil.rmtree(_built["root"], ignore_errors=True)
    root = tempfile.mkdtemp(prefix="q5-checkpointed-")
    env.config.set(CHECKPOINT_DIR, root)
    if getattr(source, "min_events", None) is not None \
            and not env.config.get_raw(EVERY_N_BATCHES):
        env.config.set(EVERY_N_BATCHES, int(o["warmup_checkpoint_batches"]))
    _built = {"root": root, "options": env.config.to_dict(),
              "source": source, "emitted": []}
    return wire(env, source, o, _built["emitted"])


def replayed(seed, n_events, o):
    """``(first, count)`` of the micro-batch the control counts twice."""
    batch = int(o["control_replayed_events"])
    slots = max(n_events // batch, 1)
    return (int(splitmix64(np.array([n_events]), int(seed))[0]
                % np.uint64(slots)) * batch, batch)


def rows_counted_twice(seed, n_events, o, twice):
    """``q5_generator.rows_after`` with the bids ``twice = (first,
    count)`` counted once more: the candidate rows at-least-once delivery
    gives."""
    gen = make_generator(seed, o)
    slide = int(o["slide_ms"])
    k = int(o["size_ms"]) // slide
    top = int(o["device_top_k"])
    lo, hi = twice[0], min(twice[0] + twice[1], n_events)
    n_slices = int(gen(n_events - 1, 1)[1][0]) // slide + 1
    edges = np.minimum(first_index_with_ts(
        np.arange(n_slices + 1, dtype=np.int64) * slide, o), n_events)
    slices = {}
    cols = {name: [] for name in SINK_COLUMNS}
    for j in range(n_slices + k - 1):   # window = slices j-k+1 .. j
        base, c = 0, np.zeros(0, dtype=np.int64)
        for s in range(max(j - k + 1, 0), min(j, n_slices - 1) + 1):
            if s not in slices:
                a, b = int(edges[s]), int(edges[s + 1])
                slices[s] = q5g._slice_counts(gen, a, b, None)
                if max(a, lo) < min(b, hi):
                    slices[s] = q5g._added(slices[s], q5g._slice_counts(
                        gen, max(a, lo), min(b, hi), None))
            base, c = q5g._added((base, c), slices[s])
        slices.pop(j - k + 1, None)     # the next window starts past it
        live = np.flatnonzero(c)
        if not len(live):
            continue
        if len(live) > top:
            least = np.partition(c[live], len(live) - top)[len(live) - top]
            live = live[c[live] >= least]
        live = live[np.argsort(c[live], kind="stable")]
        cols["window_end"].append(
            np.full(len(live), (j + 1) * slide, dtype=np.int64))
        cols["auction"].append(live + base)
        cols["count"].append(c[live])
    return {name: (np.concatenate(parts) if parts
                   else np.zeros(0, dtype=np.int64))
            for name, parts in cols.items()}


def reference_rows(seed, n_events, o, control=False):
    """The rows the sink may hold for the first ``n_events`` bids
    (``q5_generator.rows_after``). ``control=True`` computes them under
    at-least-once delivery: one micro-batch counted twice — the guarantee
    "every event counted exactly once" broken the way a restore breaks
    it."""
    if control:
        return rows_counted_twice(seed, n_events, o,
                                  replayed(seed, n_events, o))
    return q5g.rows_after(seed, n_events, o)


def checkpoints_owed(built, o):
    """Completed checkpoints a run of the job ``build`` kept owes: one
    per ``n`` batches handed over on the batch trigger, one per
    ``checkpoint_period_ms`` of the seconds offered on the time trigger
    (the window is at least that long)."""
    every_n = int(built["options"].get(EVERY_N_BATCHES) or 0)
    source = built["source"]
    if every_n:
        return len(source.log.count) // every_n
    return int(source.seconds * 1000 // int(o["checkpoint_period_ms"]))


def read_back(root):
    """``({id: operator states} of the retained checkpoints that read
    back, how many do not)``, ids ascending. A checkpoint reads back when
    its files match its manifest's CRCs and its delta chain, down to the
    full checkpoint under it, materializes."""
    from flink_tpu.checkpoint.storage import (read_checkpoint_chain,
                                              read_manifest,
                                              verify_snapshot_files)

    states, unreadable = {}, 0
    for i in sorted(int(name[4:]) for name in os.listdir(root)
                    if name.startswith("chk-") and name[4:].isdigit()):
        path = os.path.join(root, f"chk-{i}")
        try:
            verify_snapshot_files(path, read_manifest(path)["file_crcs"])
            states[i] = read_checkpoint_chain(path)
        except Exception as e:  # noqa: BLE001 - whatever stops a restore
            print(f"checkpoint {path} does not read back: {e!r}",
                  file=sys.stderr)
            unreadable += 1
    return states, unreadable


def disk_bytes(root):
    """Bytes of every file under ``root``."""
    return sum(os.path.getsize(os.path.join(folder, name))
               for folder, _, names in os.walk(root) for name in names)


def replay_source(generate, end, batch, stands_for):
    """The stream from wherever a restore puts it to bid ``end``, in equal
    batches of at most ``batch`` bids: behind the program's ``Source``
    interface, with the position format of the source it ``stands_for``
    — and that source's class name, because a restore maps state to
    operators by place and name, and a source the builder does not name is
    named after its class."""
    from flink_tpu.connectors.sources import Source
    from flink_tpu.core.records import RecordBatch

    class Replay(Source):
        bounded = True

        def __init__(self):
            self.next, self.sizes = 0, None

        def snapshot_position(self):
            return {"next": self.next}

        def restore_position(self, pos):
            self.next = int(pos["next"])

        def poll_batch(self, max_records):
            if self.sizes is None:
                rest = max(end - self.next, 0)
                parts = -(-rest // batch)
                self.sizes = [rest // parts + (i < rest % parts)
                              for i in range(parts)]
            if not self.sizes:
                return None
            n = self.sizes.pop(0)
            cols, ts = generate(self.next, n)
            self.next += n
            return RecordBatch.from_pydict(cols, timestamps=ts)

    Replay.__name__ = type(stands_for).__name__
    return Replay()


def cut_of(states, seed, o):
    """``(source position, end up to which windows had fired)`` at the
    cut whose operator ``states`` these are: the watermark trails the
    newest bid by the delay, and a window has fired once its end is at or
    under it."""
    (cut,) = (int(s["source"]["next"]) for s in states.values()
              if "source" in s)
    return cut, int(make_generator(seed, o)(cut - 1, 1)[1][0]) \
        - int(o["watermark_delay_ms"])


def state_faults(states, seed, o):
    """Rows of keyed state (auction, slice end, count) that a
    checkpoint's materialized ``states`` and the reference's state at the
    same cut do not share, either way. The reference's: the bids before
    the cut, counted per auction in every slice whose last window had not
    fired. A delta without its tombstones brings back the rows of retired
    slices; no later answer would show them."""
    cut, fired_to = cut_of(states, seed, o)
    gen = make_generator(seed, o)
    slide, size = int(o["slide_ms"]), int(o["size_ms"])
    oldest = max((fired_to - size + slide) // slide, 0)  # first live slice
    newest = int(gen(cut - 1, 1)[1][0]) // slide
    edges = np.minimum(first_index_with_ts(
        np.arange(oldest, newest + 2, dtype=np.int64) * slide, o), cut)
    want = []
    for s, a, b in zip(range(oldest, newest + 1), edges, edges[1:]):
        base, counts = q5g._slice_counts(gen, int(a), int(b), None)
        live = np.flatnonzero(counts)
        want.append((live + base, np.full(len(live), (s + 1) * slide),
                     counts[live]))
    want = [np.concatenate(column) for column in zip(*want)]
    (table,) = (s["windower"]["table"] for s in states.values()
                if "windower" in s)
    got = [np.asarray(table[column], dtype=np.int64)
           for column in ("key_id", "namespace", "leaf_0")]
    sides = [np.stack(side, axis=1)[np.lexsort((side[0], side[1]))]
             for side in (want, got)]
    if np.array_equal(*sides):
        return 0
    want, got = (list(map(tuple, side.tolist())) for side in sides)
    return len(set(want) ^ set(got)) + len(got) - len(set(got))


def restored_rows(built, path, seed, n_events, o):
    """The sink rows of a fresh job of the same builder and options,
    restored from ``path`` (a checkpoint root: its newest; or one
    checkpoint) through ``env.execute(restore_from=...)`` and fed the
    stream from the restored position to bid ``n_events``. It takes no
    checkpoint of its own (no directory) and records no span: the run's
    metrics are read after it."""
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.observe import flight_recorder as flight

    from benchmark.harness.traffic import StampingSink

    options = {key: value for key, value in built["options"].items()
               if key != CHECKPOINT_DIR}
    env = StreamExecutionEnvironment(Configuration(options))
    source = replay_source(make_generator(seed, o), n_events,
                           int(options[BATCH_SIZE]), built["source"])
    results, _ = wire(env, source, o, [])
    sink = StampingSink(SINK_COLUMNS)
    results.sink_to(sink)
    with flight.disabled():
        env.execute("benchmark-q5_checkpointed-restored", restore_from=path)
    return sink.result()


def after_restore(built, path, states, seed, n_events, o, want):
    """A job restored from ``path`` (whose operator ``states`` these are)
    and replayed to bid ``n_events``, against the reference ``want``:
    ``(q5_generator.compare's verdict over the windows beyond the cut,
    windows lost, the cut's position)``. Beyond the cut lie the windows
    that had not fired there (``cut_of``). The restored job must emit
    those and no other, each by the window rule.
    Lost is a window of the reference that neither the restored job
    emits nor the first job's sink held when the checkpoint's manifest
    was stamped: a result in flight at a cut taken without the drain."""
    from flink_tpu.checkpoint.storage import (read_manifest,
                                              resolve_snapshot_dir)

    cut, fired_to = cut_of(states, seed, o)
    keep = np.asarray(want["window_end"]) > fired_to
    want_after = {name: np.asarray(column)[keep]
                  for name, column in want.items()}
    got_after = restored_rows(built, path, seed, n_events, o)
    stamped = read_manifest(resolve_snapshot_dir(path))["timestamp_ms"]
    held = {end for at, end in built["emitted"] if at * 1000 < stamped}
    lost = set(np.asarray(want["window_end"]).tolist()) - held \
        - set(np.asarray(got_after["window_end"]).tolist())
    return compare(got_after, want_after, o), len(lost), cut


def check(got, seed, n_events, o):
    """(a) the timed job's rows against the reference; (b) the
    checkpoints it completed, and whether the retained ones read back;
    (c) a job restored from the last one, replayed to the run's last bid,
    against the reference's windows beyond the cut. Removes the
    checkpoint directory."""
    built = _built
    want = reference_rows(seed, n_events, o)
    verdict = compare(got, want, o)
    try:
        states, unreadable = read_back(built["root"])
        completed = max(states, default=0)
        numbers = {
            "checkpoints_missing": max(
                checkpoints_owed(built, o) - completed, 0),
            "checkpoints_unreadable": unreadable,
            "checkpoint_state_wrong": sum(
                state_faults(s, seed, o) for s in states.values())}
        if not states:
            # nothing to restore from: every window is lost
            numbers["restored_rows_wrong"] = 0
            numbers["restored_windows_missing"] = verdict["attempted"]
        else:
            after, lost, cut = after_restore(
                built, built["root"], states[completed], seed, n_events,
                o, want)
            verdict["failed"] += after["failed"]    # result windows too
            numbers["restored_rows_wrong"] = \
                after["numbers"]["rows_wrong"]["value"]
            numbers["restored_windows_missing"] = \
                lost if after["attempted"] else 1
            print(f"checkpoints: {completed} completed, retained "
                  f"{sorted(states)}, {disk_bytes(built['root'])} bytes on "
                  f"disk; restored from the last at bid {cut} of "
                  f"{n_events}: {after['attempted']} windows beyond the "
                  "cut", file=sys.stderr)
    finally:
        shutil.rmtree(built["root"], ignore_errors=True)
    verdict["numbers"].update(
        {name: {"value": value, "limit": 0}
         for name, value in numbers.items()})
    return verdict
