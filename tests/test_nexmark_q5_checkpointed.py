"""NEXmark Query 5 with checkpointing on (``benchmark/jobs/q5_checkpointed``)
at the tiny size on the CPU: the job through ``env.execute()`` with the
batch trigger, every completed checkpoint restored in turn and replayed,
the faults the restore comparison has to catch, the checkpoint's spans,
and the tombstones a table keeps only while a delta can be asked for.
"""

import copy
import os
import shutil

import numpy as np
import pytest

from benchmark.harness import manifest, runner
from benchmark.harness.traffic import TimedSource
from benchmark.jobs import q5_checkpointed as job
from benchmark.jobs import q5_generator as q5g
from flink_tpu.observe import KNOWN_SPAN_KINDS
from flink_tpu.observe import flight_recorder as flight

MAN = manifest.manifest()
SEED = 2_147_483_659
EVERY_N = "execution.checkpointing.every-n-source-batches"
FULL_EVERY = "execution.checkpointing.incremental.full-every"
RETAINED = "state.checkpoints.num-retained"
#: ten slides of 23,000 bids: 31 batches of at most 8,192
EVENTS = 230_000
#: a cut every 4 batches (1.4 slides), every third checkpoint full, all
#: kept: 7 checkpoints — full, delta, delta, full, delta, delta, full
CUTS = {EVERY_N: 4, FULL_EVERY: 3, RETAINED: 100}


def tiny(config, module, **options):
    cfg = copy.deepcopy(manifest.config(MAN, config))
    cfg["options"].update(module.TINY["options"])
    cfg["job_options"].update(module.TINY["job_options"])
    cfg["options"].update(options)
    return cfg


def run(cfg, module=job, seed=SEED, events=EVENTS):
    """The job over a bounded stream; ``(sink rows, bids handed over)``."""
    o = cfg["job_options"]
    source = TimedSource(module.make_generator(seed, o), {"mode": "backlog"},
                         module.boundary_events(o), min_events=events)
    sink, *_ = runner.execute_job(module, cfg, source)
    return sink.result(), source.log.events


@pytest.fixture
def built():
    """What the job module kept of the last job; its directory removed."""
    yield lambda: job._built
    if job._built is not None:
        shutil.rmtree(job._built["root"], ignore_errors=True)


def rows(cols):
    return sorted(zip(*(np.asarray(cols[c]).tolist()
                        for c in job.SINK_COLUMNS)))


def checkpoint_dirs(root):
    return {int(name[4:]): os.path.join(root, name)
            for name in os.listdir(root) if name.startswith("chk-")}


def python_index(monkeypatch):
    import flink_tpu.state.slot_table as slot_table

    monkeypatch.setattr(
        slot_table, "make_slot_index",
        lambda capacity, **kw: slot_table.HostSlotIndex(capacity, **kw))


# ------------------------------------------- (1) checkpoints change no answer


def test_a_checkpointed_run_is_exact_and_equals_the_same_job_without(built):
    from flink_tpu.checkpoint.storage import read_manifest

    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    got, n = run(cfg)
    dirs = checkpoint_dirs(built()["root"])
    assert sorted(dirs) == [1, 2, 3, 4, 5, 6, 7]
    deltas = [bool(read_manifest(dirs[i])["extra"].get("incremental"))
              for i in sorted(dirs)]
    # a chain of deltas, a consolidation, a chain on the new full one
    assert deltas == [False, True, True, False, True, True, False]
    assert read_manifest(dirs[6])["extra"]["base"] == 5
    plain, n_plain = run(tiny("nexmark-q5-generator", q5g), module=q5g)
    assert n == n_plain and rows(got) == rows(plain)
    verdict = job.check(got, SEED, n, cfg["job_options"])
    assert verdict["failed"] == 0 and verdict["attempted"] == 15
    assert {name: c["value"] for name, c in verdict["numbers"].items()} == {
        "rows_wrong": 0, "checkpoints_missing": 0,
        "checkpoints_unreadable": 0, "checkpoint_state_wrong": 0,
        "restored_rows_wrong": 0, "restored_windows_missing": 0}
    assert not os.path.exists(built()["root"])      # the check removed it


def test_a_run_that_skips_its_checkpoints_is_not_correct(built, monkeypatch):
    """Faster and wrong: the trigger never comes due."""
    from flink_tpu.checkpoint.storage import CheckpointStorage

    written = []
    write = CheckpointStorage.write_checkpoint

    def every_other(self, checkpoint_id, *args, **kwargs):
        written.append(checkpoint_id)
        return write(self, checkpoint_id, *args, **kwargs)

    monkeypatch.setattr(CheckpointStorage, "write_checkpoint", every_other)
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    got, n = run(cfg)
    assert written == [1, 2, 3, 4, 5, 6, 7]
    for i in (6, 7):    # as if the last two had never been taken
        shutil.rmtree(checkpoint_dirs(built()["root"])[i])
    numbers = job.check(got, SEED, n, cfg["job_options"])["numbers"]
    assert numbers["checkpoints_missing"]["value"] == 2
    assert numbers["rows_wrong"]["value"] == 0


def test_a_torn_checkpoint_is_counted_unreadable(built):
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    got, n = run(cfg)
    path = checkpoint_dirs(built()["root"])[5]
    victim = next(os.path.join(path, f) for f in sorted(os.listdir(path))
                  if f.endswith(".npz"))
    with open(victim, "r+b") as f:
        f.truncate(os.path.getsize(victim) // 2)
    numbers = job.check(got, SEED, n, cfg["job_options"])["numbers"]
    # chk-5 itself and chk-6, the delta chained on it
    assert numbers["checkpoints_unreadable"]["value"] == 2
    assert numbers["restored_rows_wrong"]["value"] == 0     # chk-7 is full


# --------------------- (2) every checkpoint restored in turn and replayed


@pytest.mark.parametrize("index", ["native", "python"])
def test_a_restore_from_each_checkpoint_replays_exactly(
        index, built, monkeypatch):
    if index == "python":
        python_index(monkeypatch)
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    o = cfg["job_options"]
    got, n = run(cfg)
    want = job.reference_rows(SEED, n, o)
    assert job.compare(got, want, o)["failed"] == 0
    states, unreadable = job.read_back(built()["root"])
    assert sorted(states) == [1, 2, 3, 4, 5, 6, 7] and unreadable == 0
    dirs = checkpoint_dirs(built()["root"])
    slide, delay = int(o["slide_ms"]), int(o["watermark_delay_ms"])
    withheld, carried = 0, 0
    for i, state in states.items():
        assert job.state_faults(state, SEED, o) == 0, i
        after, lost, cut = job.after_restore(
            built(), dirs[i], state, SEED, n, o, want)
        assert cut == i * 4 * 8192
        assert after["failed"] == 0 and lost == 0, (i, after, lost)
        assert after["numbers"]["rows_wrong"]["value"] == 0
        _, fired_to = job.cut_of(state, SEED, o)
        # windows whose end the newest bid has passed and the 4 s
        # holdback still withholds: the restored job owes them
        withheld += (fired_to + delay) // slide - max(fired_to, 0) // slide
        # a window had fired, so a matrix was being carried: rows of the
        # slices still live had entered it and not yet left
        carried += fired_to >= slide
        assert after["attempted"] == len(
            {e for e in want["window_end"].tolist() if e > fired_to}) >= 7
    assert withheld >= 7 and carried >= 4


# ------------------------------- (3) planted faults the restore must catch


def test_a_cut_without_the_drain_loses_a_window(built, monkeypatch):
    """The fires in flight at the cut are marked fired in the snapshot;
    their rows reach the sink only after it. A crash would lose them."""
    from flink_tpu.cluster.local_executor import LocalExecutor
    from flink_tpu.runtime.operators import WindowAggOperator

    drain, skipped = LocalExecutor._drain_pending, []

    def no_drain_at_a_cut(self, nodes, wait=False):
        # no look at the fires in flight, blocking or in passing, until
        # the 7 cuts are behind: what is pending at a cut is then every
        # window fired so far, whatever the fires' timing
        if len(skipped) < 7:
            skipped.extend([1] * wait)
            return None
        return drain(self, nodes, wait=wait)

    monkeypatch.setattr(LocalExecutor, "_drain_pending", no_drain_at_a_cut)
    monkeypatch.setattr(WindowAggOperator, "_check_no_pending",
                        lambda self: None)
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    o = cfg["job_options"]
    got, n = run(cfg)
    assert len(skipped) == 7
    want = job.reference_rows(SEED, n, o)
    assert job.compare(got, want, o)["failed"] == 0     # nothing crashed
    states, _ = job.read_back(built()["root"])
    dirs = checkpoint_dirs(built()["root"])
    lost = {i: job.after_restore(built(), dirs[i], states[i], SEED, n, o,
                                 want)[1] for i in states}
    # every window that had fired by a cut is lost with it: ends 2000
    # and 4000 by the third cut, one more per 1.4 slides after it
    assert lost == {1: 0, 2: 0, 3: 2, 4: 3, 5: 5, 6: 6, 7: 7}
    verdict = job.check(got, SEED, n, o)
    assert verdict["numbers"]["restored_windows_missing"]["value"] == 7
    assert verdict["numbers"]["restored_rows_wrong"]["value"] == 0
    assert verdict["numbers"]["rows_wrong"]["value"] == 0


def test_a_delta_without_its_tombstones_brings_retired_slices_back(
        built, monkeypatch):
    from flink_tpu.state.slot_table import SlotTable

    delta = SlotTable.snapshot_delta

    def no_tombstones(self):
        out = delta(self)
        out["freed_namespaces"] = out["freed_namespaces"][:0]
        return out

    monkeypatch.setattr(SlotTable, "snapshot_delta", no_tombstones)
    cfg = tiny("nexmark-q5-checkpointed", job,
               **{**CUTS, FULL_EVERY: 10})    # 1 full, a chain of 6 deltas
    got, n = run(cfg)
    numbers = job.check(got, SEED, n, cfg["job_options"])["numbers"]
    assert numbers["checkpoint_state_wrong"]["value"] > 1000
    assert numbers["rows_wrong"]["value"] == 0


def test_a_source_position_one_batch_behind_counts_a_batch_twice(
        built, monkeypatch):
    from flink_tpu.cluster.local_executor import LocalExecutor

    snapshot_all = LocalExecutor.snapshot_all

    def one_batch_behind(*args, **kwargs):
        snap = snapshot_all(*args, **kwargs)
        for state in snap.values():
            if "source" in state:
                state["source"] = {"next": state["source"]["next"] - 8192}
        return snap

    monkeypatch.setattr(LocalExecutor, "snapshot_all",
                        staticmethod(one_batch_behind))
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    got, n = run(cfg)
    verdict = job.check(got, SEED, n, cfg["job_options"])
    numbers = verdict["numbers"]
    assert numbers["restored_rows_wrong"]["value"] >= 16
    assert verdict["failed"] >= 1 and numbers["rows_wrong"]["value"] == 0


@pytest.mark.parametrize("seed", [7, 1_000_003, SEED])
def test_the_control_doubles_a_batch_and_every_window_that_holds_it_shows(
        seed):
    o = tiny("nexmark-q5-checkpointed", job)["job_options"]
    n = 12 * job.boundary_events(o)
    first, count = job.replayed(seed, n, o)
    assert first % 8192 == 0 and count == 8192 and first + count <= n
    want = job.reference_rows(seed, n, o)
    twice = job.reference_rows(seed, n, o, control=True)
    verdict = job.compare(twice, want, o)
    # the batch lies in one or two slices, so in five or six windows
    assert 5 <= verdict["failed"] <= 6
    assert verdict["numbers"]["rows_wrong"]["value"] >= 5 * 4
    assert job.compare(job.rows_counted_twice(seed, n, o, (0, 0)), want,
                       o)["failed"] == 0


# ------------------------------------ (4) what a checkpoint says of itself


def test_each_checkpoint_records_three_spans_and_its_rows(built):
    from flink_tpu.checkpoint.storage import read_snapshot_dir
    from flink_tpu.observe.export import chrome_trace, validate_trace_schema

    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    rec = flight.recorder()
    rec.clear()
    run(cfg)
    kt = rec.kind_totals()
    records = rec.snapshot()
    for kind in ("checkpoint.drain", "checkpoint.snapshot",
                 "checkpoint.write", "checkpoint.rows"):
        assert kt[kind]["count"] == 7, kind
    assert kt["checkpoint.tombstones"]["count"] == 4    # the deltas
    dirs = checkpoint_dirs(built()["root"])
    tables = [read_snapshot_dir(dirs[i])["2:window_agg(CountAggregate)"]
              ["windower"]["table"] for i in sorted(dirs)]
    assert kt["checkpoint.rows"]["work"] == sum(
        len(t["key_id"]) for t in tables)
    assert kt["checkpoint.tombstones"]["work"] == sum(
        len(t["freed_namespaces"]) for t in tables
        if "freed_namespaces" in t) > 0
    assert kt["checkpoint.write"]["work"] == job.disk_bytes(built()["root"])
    # a full snapshot fetches the whole accumulator array (int32 counts),
    # a delta its dirty rows padded to their bucket
    capacity = cfg["options"]["state.slot-table.capacity"]
    fetched = sorted(r.work for r in records
                     if r.kind == "checkpoint.snapshot")
    assert fetched[-3:] == [4 * capacity] * 3
    assert all(0 < w < 4 * capacity and w % 4096 == 0 for w in fetched[:4])
    assert kt["checkpoint.drain"]["work"] >= 1      # fires it waited for
    # the instants lie inside their snapshot span; the three spans follow
    # each other and nothing else runs on the loop between them
    spans = {kind: sorted((r for r in records if r.kind == kind),
                          key=lambda r: r.t0)
             for kind in ("checkpoint.drain", "checkpoint.snapshot",
                          "checkpoint.write", "checkpoint.rows")}
    for drain, snap, write, counted in zip(*spans.values()):
        assert drain.t0 + drain.duration_s <= snap.t0
        assert snap.t0 <= counted.t0 <= snap.t0 + snap.duration_s
        assert snap.t0 + snap.duration_s <= write.t0
        assert write.t0 - drain.t0 - drain.duration_s - snap.duration_s \
            < 0.05
    assert validate_trace_schema(
        chrome_trace(records, anchor=rec.anchor), KNOWN_SPAN_KINDS) == []
    rec.clear()


def test_the_restore_after_the_window_leaves_the_recorder_as_it_was(built):
    cfg = tiny("nexmark-q5-checkpointed", job, **CUTS)
    rec = flight.recorder()
    rec.clear()
    got, n = run(cfg)
    before = {k: v["count"] for k, v in rec.kind_totals().items()}
    assert job.check(got, SEED, n, cfg["job_options"])["failed"] == 0
    assert {k: v["count"] for k, v in rec.kind_totals().items()} == before
    rec.clear()


# ---------------------------------------------- the tombstones, bounded


def _table():
    from flink_tpu.state.slot_table import SlotTable
    from flink_tpu.windowing.aggregates import CountAggregate

    return SlotTable(CountAggregate(), capacity=1 << 12)


def _fill_and_free(table, rounds):
    """``rounds`` namespaces of three keys each, each freed whole."""
    keys = np.arange(3, dtype=np.int64)
    for ns in range(1, rounds + 1):
        table.lookup_or_insert(keys, np.full(3, ns, dtype=np.int64))
        table.free_namespaces([ns])


def test_a_table_that_takes_no_delta_keeps_no_tombstones():
    table = _table()
    table.keep_tombstones(False)
    _fill_and_free(table, 1000)
    slots = table.lookup_or_insert(np.arange(5, dtype=np.int64),
                                   np.full(5, 2000, dtype=np.int64))
    table.free_rows(slots[:2], [2000, 2000])
    table.free_slots(slots[2:3])
    assert table._freed_ns == [] and table._freed_pairs == []
    assert len(table.snapshot()["key_id"]) == 2     # a full one is fine
    with pytest.raises(RuntimeError, match="keeps no tombstones"):
        table.snapshot_delta()


def test_a_table_that_takes_deltas_keeps_them_as_chunks_until_the_next():
    table = _table()
    _fill_and_free(table, 1000)
    assert len(table._freed_ns) == 1000
    assert all(c.dtype == np.int64 for c in table._freed_ns)
    table.free_namespaces([7, 7, 1000])     # freed twice: named once
    delta = table.snapshot_delta()
    assert delta["freed_namespaces"].tolist() == list(range(1, 1001))
    assert table._freed_ns == []
    assert len(table.snapshot_delta()["freed_namespaces"]) == 0


@pytest.mark.parametrize("incremental", [False, True])
def test_the_operator_tells_its_table_whether_a_delta_can_be_asked_for(
        incremental):
    """Through ``env.execute()``: with incremental checkpoints off — every
    other cell of the benchmark — a job that retires a slice per slide
    leaves no tombstone behind; with them on and no checkpoint taken, one
    chunk per retire."""
    from flink_tpu import Configuration, StreamExecutionEnvironment
    from flink_tpu.connectors.sinks import CollectSink

    cfg = tiny("nexmark-q5-generator", q5g)
    cfg["options"]["execution.checkpointing.incremental"] = incremental
    o = cfg["job_options"]
    env = StreamExecutionEnvironment(Configuration(cfg["options"]))
    source = TimedSource(q5g.make_generator(SEED, o), {"mode": "backlog"},
                         q5g.boundary_events(o), min_events=EVENTS)
    results, window = q5g.build(env, source, o)
    tap = runner.probe.tap_window_operator(window)
    results.sink_to(CollectSink())
    env.execute("tombstones")
    (op,) = tap["ops"]
    table = op.windower.table
    assert table._keep_tombstones == incremental
    assert sum(map(len, table._freed_ns)) == (11 if incremental else 0)
