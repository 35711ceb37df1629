"""The ``flight_per_record`` reader on hand-built per-kind aggregates (CPU;
no chip, no run): a kind divided by its own count, its quantiles and its
longest record, and what it reads where there is nothing to read."""

import pytest

from benchmark.harness import manifest
from benchmark.readers import flight_per_record

KIND_TOTALS = {
    # 6 fires of one result window, two of them found ready at once
    "fire.poll_gap": {"count": 6, "total_s": 0.012, "self_s": 0.012,
                      "max_s": 0.005, "work": 0, "p50_ms": 2.5,
                      "p99_ms": 4.9},
    "fire.in_flight": {"count": 4, "total_s": 0.1, "self_s": 0.1,
                       "max_s": 0.04, "work": 0, "p50_ms": 24.0,
                       "p99_ms": 39.0},
    # an instant: counted, never timed
    "fire.late": {"count": 5, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0,
                  "work": 5, "p50_ms": 0.0, "p99_ms": 0.0},
}


@pytest.mark.parametrize("kind, stat, want", [
    ("fire.poll_gap", "mean_ms", 2.0),      # 12 ms over its own 6 records
    ("fire.poll_gap", "p50_ms", 2.5),
    ("fire.poll_gap", "p99_ms", 4.9),
    ("fire.poll_gap", "max_ms", 5.0),
    ("fire.in_flight", "mean_ms", 25.0),
    ("fire.in_flight", "max_ms", 40.0),
    ("fire.late", "mean_ms", 0.0)])
def test_each_statistic_of_a_kind_over_its_own_records(kind, stat, want):
    assert flight_per_record.value(KIND_TOTALS, kind, stat) \
        == pytest.approx(want)


@pytest.mark.parametrize("stat", flight_per_record.STATS)
def test_an_absent_kind_reads_none_and_never_zero(stat):
    assert flight_per_record.value(KIND_TOTALS, "window.emit", stat) is None
    assert flight_per_record.value({}, "fire.poll_gap", stat) is None
    assert flight_per_record.value(
        {"fire.poll_gap": {"count": 0, "total_s": 0.0}}, "fire.poll_gap",
        stat) is None


def test_a_recorder_that_does_not_keep_the_statistic_reads_none():
    older = {"fire.in_flight": {"count": 4, "total_s": 0.1}}
    assert flight_per_record.value(older, "fire.in_flight", "mean_ms") \
        == pytest.approx(25.0)
    for stat in ("p50_ms", "p99_ms", "max_ms"):
        assert flight_per_record.value(older, "fire.in_flight", stat) is None
    assert flight_per_record.value(
        {"fire.in_flight": {"count": 4}}, "fire.in_flight", "mean_ms") is None


def test_an_unknown_statistic_lists_the_known():
    with pytest.raises(ValueError,
                       match=", ".join(flight_per_record.STATS)):
        flight_per_record.value(KIND_TOTALS, "fire.poll_gap", "mean_s")


def test_read_takes_the_programs_recorder_as_it_stands():
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    rec.clear()
    flight.instant("sink.write", duration_s=0.004)
    flight.instant("sink.write", duration_s=0.002)
    flight.instant("sink.write")            # of no duration: counted
    run = object()                          # the reader asks it nothing
    assert flight_per_record.read(run, "sink.write", "mean_ms") \
        == pytest.approx(2.0)
    assert flight_per_record.read(run, "sink.write", "max_ms") \
        == pytest.approx(4.0)
    assert flight_per_record.read(run, "sink.write", "p99_ms") \
        == pytest.approx(4.0, rel=1e-3)
    assert flight_per_record.read(run, "fire.shard", "mean_ms") is None
    rec.clear()


READER = "flight_per_record"


def _entries_of_this_reader():
    return [m for m in manifest.manifest()["per_layer"]
            if manifest.metric_spec(m["name"])["reader"] == READER]


def test_every_metric_of_this_reader_names_a_kind_the_program_registers():
    from flink_tpu.observe import KNOWN_SPAN_KINDS

    files = {name for name in manifest._files("metrics", ".json")
             if manifest.metric_spec(name)["reader"] == READER}
    entries = _entries_of_this_reader()
    assert {m["name"] for m in entries} == files and files
    for m in entries:
        args = manifest.metric_spec(m["name"])["args"]
        assert set(args) == {"kind", "stat"}, m["name"]
        assert args["kind"] in KNOWN_SPAN_KINDS, m["name"]
        assert args["stat"] in flight_per_record.STATS, m["name"]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert callable(manifest.reader(READER).read)


def test_a_per_record_metric_reads_one_kind_under_every_name_it_has():
    """A quantity whose cells report different end-to-end metrics is one
    measurement under two names (`.backlog` / `.paced`): same arguments."""
    by_stem = {}
    for m in _entries_of_this_reader():
        stem = m["name"].split(".", 1)[0]
        by_stem.setdefault(stem, []).append(
            manifest.metric_spec(m["name"])["args"])
    assert by_stem
    for stem, specs in by_stem.items():
        assert all(s == specs[0] for s in specs), stem
