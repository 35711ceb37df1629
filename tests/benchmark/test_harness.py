"""The benchmark's harness held to its contract on the CPU: functions
only, no child process, no chip. What needs a chip (times, rates, the
trace itself) is not tested here; the reduction is, on a recorded table.
"""

import copy
import importlib
import io
import json
import os
import re
import time

import numpy as np
import pytest

from benchmark.harness import lastline, manifest, runner, trace, work
from benchmark.harness.traffic import (StampingSink, TimedSource,
                                       last_write_per_window, longest_gaps)

ROOT = manifest.ROOT
MAN = manifest.manifest()
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

#: the program's option that sizes the keyed state: where a job's cut
#: names it, the state shape a configuration expects is cut with it
CAPACITY = "state.slot-table.capacity"
#: where an answer is born: the program's one harvest class, whatever the
#: engine; a configuration's ``expect.harvest`` may name another
HARVEST = "flink_tpu.runtime.pending:PendingFire.harvest"
CELLS = [w["name"] for w in MAN["workloads"]]


def tiny(cell_name):
    """The cell's own files with its job's ``TINY`` cut laid over them:
    each job at a size a test can hold, scale cut only."""
    cell, cfg, mix = runner.resolve(MAN, cell_name)
    cfg = copy.deepcopy(cfg)
    cut = manifest.job(cfg["job"]).TINY
    cfg["options"].update(cut["options"])       # parallelism stays
    cfg["job_options"].update(cut["job_options"])
    if "state_shape" in cfg["expect"] and CAPACITY in cut["options"]:
        cfg["expect"]["state_shape"][-1] = cut["options"][CAPACITY]
    return cell, cfg, mix


def engine_class(expect):
    """The class a configuration's ``expect`` names, from the module of
    the program that ``expect`` says it lives in."""
    return getattr(importlib.import_module(expect["engine_module"]),
                   expect["engine"])


def harvest_method(expect):
    module, _, path = expect.get("harvest", HARVEST).partition(":")
    cls, method = path.split(".")
    return getattr(importlib.import_module(module), cls), method


def drive(cell_name, tmp_path, seed=2_147_483_659, seconds=1.0):
    """The rest of a run, the look for a chip skipped (the virtual CPU
    devices of ``tests/conftest.py`` stand in for the cell's chips)."""
    cell, cfg, mix = tiny(cell_name)
    return runner.run_cell(MAN, cell, cfg, mix,
                           dict(CPU, count=cell["chips"]), seed, seconds, 0,
                           time.perf_counter(), str(tmp_path))


# ------------------------------------------- (i) the manifest and its files


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in MAN["configs"]]
             + [w[k] for w in MAN["workloads"]
                for k in ("name", "config", "traffic")]
             + [m["name"] for k in ("end_to_end", "per_layer")
                for m in MAN[k]]
             + [r for c in MAN["configs"] for r in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer")
               for m in MAN[k])
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in MAN[group]}) == len(MAN[group])
    metrics = [m["name"] for k in ("end_to_end", "per_layer") for m in MAN[k]]
    assert len(set(metrics)) == len(metrics)


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_name_of_a_cell_resolves_to_a_file(cell_name):
    cell, cfg, mix = runner.resolve(MAN, cell_name)
    assert cfg["chips"] == cell["chips"]
    assert mix["mode"] in ("backlog", "paced")
    job = manifest.job(cfg["job"])
    for fn in ("make_generator", "build", "reference_rows", "compare",
               "work", "boundary_events", "warmup_events"):
        assert callable(getattr(job, fn))
    cut = getattr(job, "TINY", None)
    assert cut is not None and set(cut) == {"options", "job_options"}, \
        f"jobs/{cfg['job']}.py exports no CPU-size cut TINY = " \
        "{'options': ..., 'job_options': ...}"
    assert set(cut["job_options"]) <= set(cfg["job_options"])
    assert isinstance(engine_class(cfg["expect"]), type)
    assert isinstance(harvest_method(cfg["expect"])[0], type)
    entry = next(c for c in MAN["configs"] if c["name"] == cell["config"])
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"].startswith(tuple(MAN["paths"]))
    for which in ("end_to_end", "per_layer"):
        reported = manifest.metrics_of(MAN, cell_name, which)
        assert reported, f"{cell_name} reports no {which} metric"
        for m in reported:
            spec = manifest.metric_spec(m["name"])
            assert callable(manifest.reader(spec["reader"]).read)
    assert "setup_s" in {m["name"] for m in manifest.metrics_of(
        MAN, cell_name, "end_to_end")}


def test_a_per_layer_metrics_cells_report_what_it_moves():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for m in MAN["per_layer"]:
        moved = e2e[m["moves"]]
        for cell_name in m.get("workloads", CELLS):
            assert cell_name in moved.get("workloads", CELLS), (m, cell_name)


@pytest.mark.parametrize("finder, kind", [
    (lambda n: manifest.cell(MAN, n), "workload"),
    (manifest.traffic, "traffic mix"), (manifest.metric_spec, "metric"),
    (manifest.job, "job module"), (manifest.reader, "reader"),
    (manifest.peak, "device kind")])
def test_an_unknown_name_is_an_error_that_lists_what_exists(finder, kind):
    with pytest.raises(manifest.UnknownName, match="known: [a-zA-Z]"):
        finder("no-such-name")


def _code(path):
    """A Python file's text without its docstrings and comments."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    return re.sub(r"#.*", "", re.sub(r'""".*?"""', "", text, flags=re.S))


def _quoted(names, code):
    return [n for n in names
            if re.search(rf"[\"']{re.escape(n)}[\"']", code)]


def test_the_harness_holds_no_name_and_imports_no_other_harness():
    names = ([c["name"] for c in MAN["configs"]] + CELLS
             + [m["name"] for k in ("end_to_end", "per_layer")
                for m in MAN[k]]
             + list(manifest._files("readers", ".py"))
             + list(manifest._files("jobs", ".py")))
    here = os.path.join(ROOT, "benchmark")
    for folder, _, files in os.walk(here):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(folder, f), encoding="utf-8") as fh:
                text = fh.read()
            assert not re.search(
                r"^\s*(import|from)\s+(chip_smoke|bench|tools)\b", text,
                re.M), f
            if f == "run.py" or os.path.basename(folder) == "harness":
                hits = _quoted(names, _code(os.path.join(folder, f)))
                assert not hits, (f, hits)


def test_the_tests_hold_no_name_of_a_job_configuration_cell_or_engine():
    """A later PR adds a job, a configuration, a cell or an engine as
    files and manifest entries; a test keyed by what exists today would
    need an edit it may not make. (Metric and span-kind names as sample
    data of a reader's test are fine.)"""
    names = ([c["name"] for c in MAN["configs"]] + CELLS
             + list(manifest._files("jobs", ".py"))
             + [manifest.config(MAN, c["name"])["expect"]["engine"]
                for c in MAN["configs"]])
    here = os.path.dirname(os.path.abspath(__file__))
    for f in (f for f in os.listdir(here) if f.endswith(".py")):
        hits = _quoted(names, _code(os.path.join(here, f)))
        assert not hits, (f, hits)


# ------------------------------------------------- (ii') the timed source


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _generate(first, n):
    idx = np.arange(first, first + n, dtype=np.int64)
    return {"key": idx % 7}, idx // 10


def test_backlog_source_offers_until_the_deadline_then_closes_on_a_boundary():
    clock = FakeClock()
    src = TimedSource(_generate, {"mode": "backlog"}, boundary=1000,
                      seconds=5.0, clock=clock)
    src.arm(clock())
    src.open(0, 1)
    sizes = []
    for _ in range(7):                      # 7 full batches before it
        sizes.append(len(src.poll_batch(256)))
        clock.now += 0.5
    clock.now = 105.0                       # the deadline
    while True:
        b = src.poll_batch(256)
        if b is None:
            break
        sizes.append(len(b))
    assert sizes[:7] == [256] * 7
    # 1792 out at the deadline -> runs on to 2000 in one batch of 208
    assert sizes[7:] == [208] and src.log.events == 2000
    assert src.poll_batch(256) is None      # nothing after the end
    assert src.log.first == [0, 256, 512, 768, 1024, 1280, 1536, 1792]
    assert src.log.events_between(100.0, 101.0) == 512


@pytest.mark.parametrize("out, tail", [
    (1792, [208]),              # 208 >= half a batch: one batch
    (1920, [216, 216, 216, 216, 216]),  # 80 < 128: a boundary further
    (1000, [250, 250, 250, 250])])      # on a boundary: a whole one more
def test_the_tail_has_no_batch_under_half_a_batch(out, tail):
    src = TimedSource(_generate, {"mode": "backlog"}, boundary=1000,
                      min_events=out)
    src._next = out
    src._plan_tail(256)
    assert src._tail == tail
    assert (out + sum(tail)) % 1000 == 0 and min(tail) >= 128


class FakeSleep:
    """Sleeping moves the fake clock."""

    def __init__(self, clock):
        self.clock, self.naps = clock, []

    def __call__(self, seconds):
        self.naps.append(seconds)
        self.clock.now += seconds


def test_paced_source_keeps_its_schedule_on_a_fake_clock():
    clock = FakeClock()
    sleep = FakeSleep(clock)
    src = TimedSource(_generate,
                      {"mode": "paced", "rate": 1000.0, "batch_events": 100},
                      boundary=250, seconds=1.0, clock=clock, sleep=sleep)
    src.arm(clock())
    src.open(0, 1)
    # event i is due at t0 + i/1000: the first batch (last event 99) at
    # +0.099; before that every poll sleeps at most 1 ms and comes back empty
    empties = 0
    while True:
        b = src.poll_batch(4096)            # max_records does not matter
        if len(b):
            break
        empties += 1
    assert len(b) == 100 and empties == 99 and max(sleep.naps) <= 0.001
    assert src.log.due[0] == pytest.approx(100.0 + 0.099)
    assert src.log.handed[0] == pytest.approx(100.099)
    clock.now = 100.5                       # the job stalled: 4 batches due
    sizes = [len(src.poll_batch(4096)) for _ in range(4)]
    assert sizes == [100] * 4 and len(src.poll_batch(4096)) == 0
    late = [h - d for h, d in zip(src.log.handed, src.log.due)]
    assert late[1] == pytest.approx(0.5 - 0.199)    # timed from when due
    assert late[4] == pytest.approx(0.5 - 0.499)
    clock.now = 101.0                       # the deadline, 500 events out
    out = []
    while True:
        b = src.poll_batch(4096)
        if b is None:
            break
        if len(b):
            out.append(len(b))
    # 500 is a boundary: one whole boundary more, in equal batches >= 50
    assert out == [84, 83, 83] and src.log.events == 750
    assert src.log.due[-1] == pytest.approx(100.0 + 0.749)
    assert src.poll_batch(4096) is None


def test_paced_tail_never_sleeps_a_negative_time():
    """The clock may pass the due time between two reads of it."""
    class TickingClock(FakeClock):
        def __call__(self):
            self.now += 0.0007
            return self.now

    clock = TickingClock()
    naps = []

    def sleep(seconds):
        assert seconds >= 0
        naps.append(seconds)

    src = TimedSource(_generate,
                      {"mode": "paced", "rate": 1000.0, "batch_events": 10},
                      boundary=25, min_events=10, clock=clock, sleep=sleep)
    src.arm(clock())
    src.open(0, 1)
    while src.poll_batch(64) is not None:
        pass
    assert src.log.events == 25 and naps


def test_window_latency_from_a_hand_made_batch_log_and_sink_log():
    from benchmark.readers.emit_latency import latencies_ms

    # event time = index // 10 ms; offered at 1000 events/s from t0 = 50;
    # 95 events handed over in all
    stamps = [(50.030, np.array([2])),      # window [.., 2): events 0..19
              (50.045, np.array([2, 4])),   # 2 written again, and 4
              (50.100, np.array([8])),      # events up to 79; 80.. exist
              (50.200, np.array([10]))]     # first ts >= 10 is event 100: flush
    got = latencies_ms(stamps, t0=50.0, rate=1000.0, events=95,
                       first_index_with_ts=lambda ts: ts * 10)
    # window 2: last event with ts < 2 is index 19, due at 50.019; the LAST
    # write that carried it was at 50.045
    assert got[2] == pytest.approx(26.0)
    assert got[4] == pytest.approx((50.045 - 50.039) * 1e3)
    assert got[8] == pytest.approx((50.100 - 50.079) * 1e3)
    assert 10 not in got                    # closed only by the flush


def test_the_longest_gaps_say_where_a_run_stood_still():
    times = [10.0, 10.1, 10.2, 13.2, 13.3, 14.3]
    assert longest_gaps(times, t0=10.0, top=2) == [
        [pytest.approx(0.2), pytest.approx(3.0)],
        [pytest.approx(3.3), pytest.approx(1.0)]]
    assert longest_gaps([], t0=0.0) == [] == longest_gaps([1.0], t0=0.0)


def test_a_source_needs_exactly_one_stop_rule_and_a_known_mode():
    with pytest.raises(ValueError):
        TimedSource(_generate, {"mode": "backlog"}, 10)
    with pytest.raises(ValueError, match="known: backlog"):
        TimedSource(_generate, {"mode": "bursty"}, 10, seconds=1)


def test_the_sink_stamps_every_write_and_keeps_columns():
    from flink_tpu.core.records import RecordBatch

    clock = FakeClock()
    sink = StampingSink(("window_end", "key"), clock=clock)
    for t, ends in ((1.0, [10, 10]), (2.0, [10, 20]), (3.0, [20])):
        clock.now = t
        sink.write(RecordBatch.from_pydict(
            {"window_end": np.array(ends), "key": np.arange(len(ends))}))
    assert sink.result()["window_end"].tolist() == [10, 10, 10, 20, 20]
    assert last_write_per_window(sink.stamps) == {10: 2.0, 20: 3.0}
    assert sink.windows_written_between(0.0, 2.5) == 1


# ------------------------- (iv) each job through env.execute(), and faults


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_tiny_run_equals_its_reference(cell_name, tmp_path):
    r = drive(cell_name, tmp_path)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    assert all(c["ok"] for c in r["compared"].values())
    assert set(r["metrics"]) == {m["name"] for m in manifest.metrics_of(
        MAN, cell_name, "end_to_end")}
    line = lastline.result_line(**r)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    (details,) = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    with open(tmp_path / details, encoding="utf-8") as f:
        assert json.load(f)["compiles"] == 0


def _skip_a_batch(original, counter):
    def process_batch(self, batch):
        counter[0] += 1
        if counter[0] % 9 == 5:
            return None         # the step returns its state unchanged
        return original(self, batch)
    return process_batch


def _half_a_batch(original, counter):
    def process_batch(self, batch):
        counter[0] += 1
        if counter[0] % 9 == 5:
            batch = batch.slice(0, len(batch) // 2)
        return original(self, batch)
    return process_batch


def _alter_an_answer(original, counter):
    def harvest(self):
        build = self.build

        def altered(host):
            host = list(host)
            host[-1] = host[-1] + 1     # the last result column, at its birth
            return build(host)

        counter[0] += 1
        if counter[0] % 4 == 3:
            self.build = altered
        return original(self)
    return harvest


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("target, fault", [
    ("process_batch", _skip_a_batch),
    ("process_batch", _half_a_batch),
    ("harvest", _alter_an_answer)],
    ids=["state-unchanged", "half-a-batch", "answer-altered"])
def test_a_run_on_a_broken_timed_path_is_not_correct(
        cell_name, target, fault, tmp_path, monkeypatch):
    expect = tiny(cell_name)[1]["expect"]
    if target == "harvest":
        owner, method = harvest_method(expect)
    else:
        owner, method = engine_class(expect), target
    counter = [0]
    monkeypatch.setattr(owner, method, fault(getattr(owner, method), counter))
    r = drive(cell_name, tmp_path)
    assert counter[0] > 5, "the fault was never reached"
    assert not r["correct"] and r["failed"] > 0
    assert not all(c["ok"] for c in r["compared"].values())


@pytest.mark.parametrize("cell_name", [
    w["name"] for w in MAN["workloads"] if w["chips"] > 1])
def test_a_run_without_the_exchange_between_chips_is_not_correct(
        cell_name, tmp_path, monkeypatch):
    import jax

    from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

    calls = [0]

    def no_exchange(block, *args, **kwargs):
        calls[0] += 1
        return block            # every shard keeps what it meant to send

    PROGRAM_CACHE.programs.clear()      # the next build traces the fault
    monkeypatch.setattr(jax.lax, "all_to_all", no_exchange)
    try:
        r = drive(cell_name, tmp_path)
    finally:
        PROGRAM_CACHE.programs.clear()  # and no later test inherits it
    assert calls[0] > 0, "the exchange was never traced"
    assert not r["correct"] and r["failed"] > 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_corrupted_row_is_caught(cell_name):
    _, cfg, _ = tiny(cell_name)
    job, o = manifest.job(cfg["job"]), cfg["job_options"]
    n = 3 * job.boundary_events(o)
    want = job.reference_rows(11, n, o)
    assert job.compare(want, want, o)["failed"] == 0
    for column in job.SINK_COLUMNS[1:]:
        got = {k: v.copy() for k, v in want.items()}
        got[column][len(got[column]) // 2] += 1
        verdict = job.compare(got, want, o)
        assert verdict["failed"] >= 1
        assert any(c["value"] > c["limit"]
                   for c in verdict["numbers"].values())
    short = {k: v[:-1] for k, v in want.items()}
    assert job.compare(short, want, o)["failed"] == 1
    doubled = {k: np.concatenate([v, v[:1]]) for k, v in want.items()}
    assert job.compare(doubled, want, o)["failed"] == 1


@pytest.mark.parametrize("seed", [7, 1_000_003, 2_147_483_659])
@pytest.mark.parametrize("cell_name", CELLS)
def test_the_control_comes_out_not_correct(cell_name, seed):
    """The reference put in the program's place, with one stated guarantee
    broken (a lost micro-batch): it has to fail, on every seed."""
    _, cfg, _ = tiny(cell_name)
    job, o = manifest.job(cfg["job"]), cfg["job_options"]
    n = 12 * job.boundary_events(o)
    want = job.reference_rows(seed, n, o)
    verdict = job.compare(job.reference_rows(seed, n, o, control=True),
                          want, o)
    assert verdict["failed"] >= 1
    over = [c["value"] / c["limit"] if c["limit"] else c["value"]
            for c in verdict["numbers"].values() if c["value"] > c["limit"]]
    assert over and max(over) >= 3


# ------------------------------------------------ (v) the trace reduction


def test_busy_union_and_gaps_on_a_synthetic_interval_set():
    spans = [(0, 10), (5, 20), (30, 40), (32, 35), (40, 41)]
    assert trace.union_seconds(spans) == pytest.approx(31e-9)
    assert trace.gaps(spans) == [(20, 30)]
    assert trace.gaps(spans, lo=-5, hi=50) == [(-5, 0), (20, 30), (41, 50)]
    assert trace.union_seconds([]) == 0


MS = 1_000_000
NO_SPAN = trace.NO_SPAN
DEV, HOST = "/device:TPU:0", "/host:CPU"
OPERATOR, PUMP, TRACER = "python3#8", "python3#9", "python3#10"


def _table(ops, modules, host):
    """Rows from ``(name, start_ms, end_ms)`` lists; ``host`` by line."""
    return ([(DEV, "XLA Ops", n, a * MS, (b - a) * MS) for n, a, b in ops]
            + [(DEV, "XLA Modules", n, a * MS, (b - a) * MS)
               for n, a, b in modules]
            + [(DEV, "Steps", "0", 0, 2000 * MS)]
            + [(HOST, line, n, a * MS, (b - a) * MS)
               for line, spans in host.items() for n, a, b in spans])


#: one second of host rows (the tracer's two marks bound it); the last
#: device program straddles its end and one more runs wholly after it
SYNTHETIC = _table(
    ops=[("fusion.1", 0, 100), ("scatter.2", 400, 600),
         ("fusion.1", 600, 650), ("copy.3", 800, 810),
         ("fusion.1", 900, 1400), ("fusion.1", 1500, 1600)],
    modules=[("jit_scatter(1)", 0, 100), ("jit_scatter(1)", 400, 650),
             ("jit_fire(2)", 800, 810), ("jit_fire(2)", 900, 1400),
             ("jit_fire(2)", 1500, 1600)],
    host={TRACER: [("bench.trace_mark", 0, 0.001),
                   ("bench.trace_mark", 999.999, 1000)],
          PUMP: [("bench.source_generate", 0.5, 999)],
          OPERATOR: [("flink.op.process", 100, 500),
                     ("bench.process_batch", 110, 490),
                     ("flink.batch.ingest", 120, 480),
                     ("flink.prep.resolve", 130, 400),
                     ("bench.on_watermark", 650, 800)]})


def test_reduction_of_a_synthetic_table():
    r = trace.reduce_trace(SYNTHETIC, asked_s=1.0)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["range_ns"] == [0, 1000 * MS]
    assert r["operator_line"] == [HOST, OPERATOR]
    # the straddling program counts up to the end of the host rows only
    # (100 of its 500 ms), the one after it not at all
    assert r["busy_s_busiest"] == pytest.approx(0.46)
    assert r["busy_s_mean"] == pytest.approx(0.46)
    assert r["idle_pct"] == pytest.approx(54.0)
    assert r["overrun_s"] == pytest.approx(0.6)
    assert r["busy_s_outside"] == pytest.approx(0.5)
    assert r["device_ops"] == [["jit_scatter(1)", pytest.approx(0.35)],
                               ["jit_fire(2)", pytest.approx(0.11)]]
    # every idle nanosecond under the innermost span open on the OPERATOR
    # thread's line: the pump thread's row covers all and names nothing
    assert r["idle_by_host_span_s"] == {
        "flink.prep.resolve": pytest.approx(0.27),
        "bench.on_watermark": pytest.approx(0.15),
        NO_SPAN: pytest.approx(0.09),
        "flink.op.process": pytest.approx(0.01),
        "bench.process_batch": pytest.approx(0.01),
        "flink.batch.ingest": pytest.approx(0.01)}
    assert sum(r["idle_by_host_span_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s_busiest"])
    # a gap under four nested spans is named by the inner one, a gap under
    # none says so
    assert r["idle_gaps"] == [["flink.prep.resolve", pytest.approx(0.3)],
                              ["bench.on_watermark", pytest.approx(0.15)],
                              [NO_SPAN, pytest.approx(0.09)]]
    host_only = [row for row in SYNTHETIC if row[0] == HOST]
    assert trace.reduce_trace(host_only, asked_s=1.0) is None



def test_a_capture_with_too_few_host_rows_is_an_error_not_a_reading():
    with pytest.raises(ValueError, match="cover 1.000 s of the 3.000 s"):
        trace.reduce_trace(SYNTHETIC, asked_s=3.0)
    device_only = [row for row in SYNTHETIC if row[0] == DEV]
    with pytest.raises(LookupError, match="no host row"):
        trace.reduce_trace(device_only, asked_s=1.0)
    no_operator = [row for row in SYNTHETIC if row[1] != OPERATOR]
    with pytest.raises(LookupError, match="window operator's rows"):
        trace.reduce_trace(no_operator, asked_s=1.0)


def test_spans_that_only_overlap_go_to_the_one_that_opened_last():
    # two threads merged onto one line (the first recorded table): no
    # nesting to lean on
    spans = [("a", 0, 10), ("b", 5, 20), ("c", 6, 8), ("d", 30, 40)]
    assert trace.innermost(spans) == [
        (0, 5, "a"), (5, 6, "b"), (6, 8, "c"), (8, 20, "b"), (30, 40, "d")]
    assert trace.idle_by_span([(4, 7), (20, 35)],
                              trace.innermost(spans)) == [
        {"a": 1, "b": 1, "c": 1}, {"d": 5, NO_SPAN: 10}]


def test_the_marks_tie_the_captures_clock_to_the_hosts():
    clock = trace.host_clock(SYNTHETIC, (50.0, 50.999999))
    assert clock(0) == pytest.approx(50.0)
    assert clock(1000 * MS) == pytest.approx(51.0)
    with pytest.raises(LookupError, match="2 bench.trace_mark"):
        trace.host_clock(SYNTHETIC, (50.0,))
    with pytest.raises(ValueError, match="drift"):
        trace.host_clock(SYNTHETIC, (50.0, 51.5))


@pytest.mark.parametrize("lost", ["XLA Ops", "XLA Modules"])
def test_a_device_plane_without_its_ops_or_modules_line_is_an_error(lost):
    dev = "/device:TPU:0"
    rows = [(dev, "XLA Ops", "fusion.1", 0, 400),
            (dev, "XLA Modules", "jit_scatter(1)", 0, 500),
            (dev, "Async XLA Ops", "copy-start", 0, 100)]
    with pytest.raises(LookupError, match="Async XLA Ops"):
        trace.reduce_trace([r for r in rows if r[1] != lost], asked_s=1.0)


FIXTURES = os.path.join(ROOT, "benchmark", "fixtures")


@pytest.mark.parametrize("table", sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".json")))
def test_reduction_of_a_recorded_chip_trace(table):
    with open(os.path.join(FIXTURES, table), encoding="utf-8") as f:
        fixture = json.load(f)
    r = trace.reduce_trace([tuple(x) for x in fixture["rows"]],
                           fixture["asked_s"])
    want = fixture["expect"]
    for number in ("window_s", "busy_s_busiest", "idle_pct", "overrun_s"):
        assert r[number] == pytest.approx(want[number]), number
    assert [n for n, _ in r["device_ops"]] == want["device_ops"]
    assert list(r["idle_by_host_span_s"])[:3] == want["idle_top3"]
    assert 0 < r["idle_pct"] < 100
    assert all(s > 0 for _, s in r["device_ops"] + r["idle_gaps"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(r["idle_by_host_span_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s_busiest"])


# ----------------------------------------------------- (vi) work counting


def test_work_bytes_for_a_hand_countable_case():
    # 10 events, one float32 value column, one float32 leaf; one fire over
    # 3 live cells emitting 3 float32 rows:
    # 10 * (4 + 4 + 2*4) + 3*4 + 3*4 = 184
    assert work.window_state_bytes(10, 4, (4,), 3, 3, 4) == 184
    # a COUNT: no value column, int32 leaf; 2 fires over 5 cells, 2 rows of 8
    assert work.window_state_bytes(10, 0, (4,), 10, 4, 8) == 120 + 40 + 32
    assert work.roofline_share(819e9, 819e9, 2.0) == pytest.approx(50.0)
    assert work.roofline_share(100, 819e9, 0.0) is None
    assert work.roofline_share(0, 819e9, 1.0) is None


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_jobs_work_grows_with_events_and_fires(cell_name):
    _, cfg, _ = runner.resolve(MAN, cell_name)
    job, o = manifest.job(cfg["job"]), cfg["job_options"]
    base = job.work(1_000_000, 1, o)
    assert job.work(2_000_000, 1, o) - base == pytest.approx(
        job.work(3_000_000, 1, o) - job.work(2_000_000, 1, o))
    assert job.work(1_000_000, 2, o) > base > 0
    # no event can need less than its slot index and one accumulator update
    assert job.work(1_000_000, 0, o) >= 1_000_000 * 12


# ------------------------------------------------------ (vii) the last line


def test_the_last_line_has_the_contracts_keys_and_compared_last():
    metrics = {"events_per_s": {"value": 5e6, "unit": "events/s", "x": 1}}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    compared = {"rows_wrong": {"value": 0, "limit": 0, "ok": True}}
    line = lastline.result_line(True, 5, 0, metrics, device, compared)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["metrics"]["events_per_s"] == {"value": 5e6,
                                               "unit": "events/s"}
    traced = lastline.result_line(False, 5, 1, metrics, device, compared,
                                  breakdown={"device_ops": [],
                                             "idle_gaps": []})
    assert list(traced)[-2:] == ["breakdown", "compared"]
    assert json.loads(json.dumps(traced)) == traced
    text = lastline.compared_text(compared)
    assert "rows_wrong" in text and "limit=0" in text and "ok" in text


def test_finish_writes_the_line_last_and_once(monkeypatch):
    left = []
    monkeypatch.setattr(lastline.os, "_exit", left.append)
    monkeypatch.setattr(lastline, "_once", lastline.threading.Lock())
    out = io.StringIO()
    line = lastline.result_line(True, 1, 0, {}, {"platform": "tpu"}, {})
    lastline.finish(out, line)
    assert left == [0]
    assert json.loads(out.getvalue().splitlines()[-1]) == line


def test_no_chip_means_no_result():
    with pytest.raises(runner.NoAccelerator):
        runner.look_for_chips(1)       # the tests run on the CPU
    with pytest.raises(runner.NoAccelerator):
        runner.look_for_chips(3, platform="cpu")
