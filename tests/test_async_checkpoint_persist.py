"""A checkpoint persisted off the task loop (``LocalExecutor``'s
``_CheckpointWriter``): the loop takes the drain and the snapshot and goes
on taking batches while one writer thread compresses, writes, renames and
retires; the loop completes the checkpoint — the next delta's base,
``notify_checkpoint_complete`` — only after the rename, on its own thread.
Each test slows the persist down so that the loop visibly runs beside it.
"""

import os
import queue
import threading
import time

import numpy as np
import pytest

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.checkpoint import storage as storage_mod
from flink_tpu.checkpoint.storage import (
    CheckpointStorage,
    read_manifest,
    read_snapshot_dir,
)
from flink_tpu.cluster.local_executor import LocalExecutor, SavepointRequest
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.datastream.stream import DataStream
from flink_tpu.graph.transformations import Transformation
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.runtime.operators import Operator
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.assigners import TumblingEventTimeWindows

BATCH = 256
#: 32 batches, a cut every 4: 8 checkpoints
TOTAL = 8_192
EVERY_N = 4
#: how long each persist is held up before it writes
SLOW_S = 0.15


class Tally(Operator):
    """Passes batches on and counts them in host state it changes in place
    on every batch: a dict its snapshot hands over as it is (metadata) and
    an array its snapshot copies. ``log`` records each cut and each
    completion."""

    name = "tally"

    def __init__(self, log):
        self.log = log
        self.seen = {"records": 0, "batches": 0}
        self.per_key = np.zeros(8, dtype=np.int64)

    def process_batch(self, batch, input_index=0):
        self.seen["records"] += len(batch)
        self.seen["batches"] += 1
        np.add.at(self.per_key, np.asarray(batch["key"]) % 8, 1)
        return [batch]

    def snapshot_state(self):
        self.log["cuts"].append(dict(self.seen))
        return {"seen": self.seen, "per_key": self.per_key.copy()}

    def restore_state(self, state):
        self.seen = dict(state["seen"])
        self.per_key = np.array(state["per_key"])

    def notify_checkpoint_complete(self, checkpoint_id):
        self.log["notified"].append(
            (checkpoint_id, threading.get_ident(), time.perf_counter(),
             self.seen["batches"]))


def new_log():
    return {"cuts": [], "notified": [], "started": [], "renamed": {},
            "writer_threads": set(), "in_flight": 0, "max_in_flight": 0}


def slow_persist(monkeypatch, log, fail_at=None, on_start=None):
    """Hold every persist up by ``SLOW_S`` before it writes; note which
    ids started, when each was renamed into place and on which thread;
    raise ``OSError`` instead of writing checkpoint ``fail_at``."""
    write = CheckpointStorage.write_checkpoint
    lock = threading.Lock()

    def slowed(self, checkpoint_id, *args, **kwargs):
        with lock:
            log["in_flight"] += 1
            log["max_in_flight"] = max(log["max_in_flight"],
                                       log["in_flight"])
        try:
            log["started"].append(checkpoint_id)
            log["writer_threads"].add(threading.get_ident())
            if on_start is not None:
                on_start(checkpoint_id)
            time.sleep(SLOW_S)
            if checkpoint_id == fail_at:
                raise OSError(f"disk full writing checkpoint {checkpoint_id}")
            out = write(self, checkpoint_id, *args, **kwargs)
            log["renamed"][checkpoint_id] = time.perf_counter()
            return out
        finally:
            with lock:
                log["in_flight"] -= 1

    monkeypatch.setattr(CheckpointStorage, "write_checkpoint", slowed)


def options(tmp_path, **extra):
    return {"execution.micro-batch.size": BATCH,
            "state.checkpoints.dir": str(tmp_path / "ck"),
            "execution.checkpointing.every-n-source-batches": EVERY_N,
            "execution.checkpointing.incremental": True,
            "state.checkpoints.num-retained": 100, **extra}


def build(config, log):
    """source -> tally -> keyBy -> 1 s tumbling count -> sink."""
    env = StreamExecutionEnvironment(Configuration(config))
    sink = CollectSink()
    ds = env.add_source(
        DataGenSource(total_records=TOTAL, num_keys=20,
                      events_per_second_of_eventtime=4_000),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
    tally = DataStream(env, Transformation(
        name="tally", kind="one_input",
        operator_factory=lambda: Tally(log), inputs=[ds.transformation]))
    (tally.key_by("key").window(TumblingEventTimeWindows.of(1000)).count()
        .sink_to(sink))
    return env, sink


def on_disk(root):
    """Ids of the complete checkpoints (a manifest) under ``root``."""
    return sorted(int(n[4:]) for n in os.listdir(root)
                  if n.startswith("chk-")
                  and os.path.exists(os.path.join(root, n, "manifest.json")))


def tally_state(path):
    (state,) = (s for s in read_snapshot_dir(path).values() if "seen" in s)
    return state


def test_the_loop_takes_batches_while_a_checkpoint_is_persisted(
        tmp_path, monkeypatch):
    """(a) Between a cut and its completion the loop takes batches;
    ``notify_checkpoint_complete`` arrives on the loop's thread, after the
    checkpoint's rename."""
    log = new_log()
    slow_persist(monkeypatch, log)
    env, _ = build(options(tmp_path), log)
    loop = threading.get_ident()
    result = env.execute("async-persist")
    n = result.metrics["checkpoints"]
    assert n == TOTAL // BATCH // EVERY_N
    assert [c for c, *_ in log["notified"]] == list(range(1, n + 1))
    assert loop not in log["writer_threads"]
    for (cid, thread, at, batches), cut in zip(log["notified"],
                                               log["cuts"]):
        assert thread == loop
        assert at > log["renamed"][cid]
        # taken after the cut, before the completion (the last cut is at
        # the last batch)
        assert batches > cut["batches"] or cid == n, cid
    # the writer thread ends with the job
    assert not [t for t in threading.enumerate()
                if t.name.startswith("checkpoint-writer")]


def test_a_persist_that_raises_fails_the_job_and_no_delta_builds_on_it(
        tmp_path, monkeypatch):
    """(b) The job fails with the persist's own exception; nothing later
    names the unwritten checkpoint as its base."""
    log = new_log()
    slow_persist(monkeypatch, log, fail_at=3)
    env, _ = build(options(tmp_path), log)
    with pytest.raises(OSError, match="disk full writing checkpoint 3"):
        env.execute("async-persist-fails")
    root = str(tmp_path / "ck")
    assert on_disk(root) == [1, 2]
    assert [c for c, *_ in log["notified"]] == [1, 2]
    assert log["started"] == [1, 2, 3]
    bases = [read_manifest(os.path.join(root, f"chk-{i}"))["extra"]
             .get("base") for i in on_disk(root)]
    assert bases == [None, 1]
    assert not [n for n in os.listdir(root) if n.startswith(".snap-")]
    assert not [t for t in threading.enumerate()
                if t.name.startswith("checkpoint-writer")]


def test_a_cut_due_while_a_persist_is_unfinished_waits_for_it(
        tmp_path, monkeypatch):
    """(c) At most one persist in flight, never two ``.snap-*`` temporary
    directories at once, and the wait is recorded."""
    log = new_log()
    slow_persist(monkeypatch, log)
    crc, snaps = storage_mod._file_crc32, []
    root = str(tmp_path / "ck")

    def counting(path, *args, **kwargs):
        if ".snap-" in path:    # the persist's, before its rename
            snaps.append(len([n for n in os.listdir(root)
                              if n.startswith(".snap-")]))
        return crc(path, *args, **kwargs)

    monkeypatch.setattr(storage_mod, "_file_crc32", counting)
    rec = flight.recorder()
    rec.clear()
    env, _ = build(options(tmp_path), log)
    n = env.execute("async-persist-waits").metrics["checkpoints"]
    kt = rec.kind_totals()
    rec.clear()
    assert log["max_in_flight"] == 1
    assert snaps and set(snaps) == {1}
    # a cut comes due every 4 batches, long before a persist held up for
    # SLOW_S is done: every cut but the first waits for the one before
    assert kt["checkpoint.persist_wait"]["count"] >= n - 1
    assert kt["checkpoint.persist_wait"]["work"] == \
        kt["checkpoint.persist_wait"]["count"]
    for kind in ("checkpoint.drain", "checkpoint.snapshot",
                 "checkpoint.persist", "checkpoint.write"):
        assert kt[kind]["count"] == n, kind
    assert kt["checkpoint.offloop"]["count"] == n
    assert kt["checkpoint.offloop"]["work"] == n
    assert kt["checkpoint.persist"]["work"] == kt["checkpoint.write"]["work"]
    assert kt["checkpoint.persist"]["total_s"] >= n * SLOW_S


def _run_with_control(tmp_path, monkeypatch, log, request_at, **request):
    """The job under ``LocalExecutor.run`` with a control queue; a
    savepoint request is put on it as checkpoint ``request_at``'s persist
    starts, so the loop serves it with that persist in flight. At the
    savepoint: the ids started, on disk and notified."""
    from flink_tpu.checkpoint import savepoint as savepoint_mod

    control = queue.Queue()
    root = str(tmp_path / "ck")
    seen_at_savepoint = []
    write_savepoint = savepoint_mod.write_savepoint

    def noting(*args, **kwargs):
        seen_at_savepoint.append((list(log["started"]), on_disk(root),
                                  [c for c, *_ in log["notified"]]))
        return write_savepoint(*args, **kwargs)

    monkeypatch.setattr(savepoint_mod, "write_savepoint", noting)
    req = SavepointRequest(str(tmp_path / "sp"), **request)
    slow_persist(monkeypatch, log, on_start=lambda cid: (
        control.put(req) if cid == request_at else None))
    config = options(tmp_path)
    env, sink = build(config, log)
    result = LocalExecutor(Configuration(config)).run(
        env.get_stream_graph(), job_name="async-persist-savepoint",
        control_queue=control)
    return result, req, seen_at_savepoint


@pytest.mark.parametrize("stop", [False, True])
def test_a_savepoint_finds_every_checkpoint_complete_on_disk(
        tmp_path, monkeypatch, stop):
    """(d) A savepoint, and stop-with-savepoint, served while a persist is
    in flight: the persist completes first."""
    log = new_log()
    result, req, seen = _run_with_control(tmp_path, monkeypatch, log,
                                          request_at=2, stop=stop)
    assert req.wait(timeout=0) == str(tmp_path / "sp")
    ((started, written, notified),) = seen
    assert started == written == notified and 2 in started
    root = str(tmp_path / "ck")
    if stop:
        assert result.metrics["savepoint"] == str(tmp_path / "sp")
        assert on_disk(root) == [1, 2]
    else:
        n = result.metrics["checkpoints"]
        assert on_disk(root) == list(range(1, n + 1)) and n > 2


def test_the_end_of_input_finds_every_checkpoint_complete_on_disk(
        tmp_path, monkeypatch):
    """(d) ``execute`` returns with every checkpoint it cut on disk,
    completed and notified, the last one included."""
    log = new_log()
    slow_persist(monkeypatch, log)
    env, _ = build(options(tmp_path), log)
    n = env.execute("async-persist-end").metrics["checkpoints"]
    ids = list(range(1, n + 1))
    assert log["started"] == on_disk(str(tmp_path / "ck")) == ids
    assert [c for c, *_ in log["notified"]] == ids


def test_what_is_persisted_is_the_state_at_the_cut(tmp_path, monkeypatch):
    """(e) The loop goes on changing the operator's host state in place
    while the persist is held up; what lands on disk is the cut's."""
    import sys

    log = new_log()
    slow_persist(monkeypatch, log)
    env, _ = build(options(tmp_path), log)
    # the two threads trade the interpreter lock as often as it allows
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        n = env.execute("async-persist-private").metrics["checkpoints"]
    finally:
        sys.setswitchinterval(interval)
    root = str(tmp_path / "ck")
    for i, cut in zip(range(1, n + 1), log["cuts"]):
        state = tally_state(os.path.join(root, f"chk-{i}"))
        assert state["seen"] == cut
        assert int(state["per_key"].sum()) == cut["records"]
        assert cut["batches"] == i * EVERY_N


def test_the_keyed_state_persisted_is_the_cut_s(monkeypatch):
    """(e) The same for the slot table's arrays: NEXmark Q5 with
    checkpoints, each persist held up while the loop ingests into the live
    table, every checkpoint's keyed state the reference's at its cut."""
    import shutil

    from tests.test_nexmark_q5_checkpointed import CUTS, SEED, run, tiny

    from benchmark.jobs import q5_checkpointed as job

    log = new_log()
    slow_persist(monkeypatch, log)
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    o = cfg["job_options"]
    try:
        run(cfg)
        states, unreadable = job.read_back(job._built["root"])
        assert sorted(states) == list(range(1, 8)) and unreadable == 0
        for i, state in states.items():
            assert job.state_faults(state, SEED, o) == 0, i
    finally:
        shutil.rmtree(job._built["root"], ignore_errors=True)


def test_a_job_that_takes_no_checkpoint_starts_no_writer(tmp_path):
    log = new_log()
    threads = []
    config = options(tmp_path, **{
        "execution.checkpointing.every-n-source-batches": 10_000})
    env, _ = build(config, log)

    class Looking(Tally):
        def process_batch(self, batch, input_index=0):
            threads.extend(t.name for t in threading.enumerate())
            return super().process_batch(batch, input_index)

    tally = next(t for t in env.get_stream_graph().nodes
                 if t.name == "tally")
    tally.operator_factory = lambda: Looking(log)
    assert env.execute("no-checkpoint").metrics["checkpoints"] == 0
    assert threads and not [t for t in threads
                            if t.startswith("checkpoint-writer")]
