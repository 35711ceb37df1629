"""The ``flight_stat`` reader on hand-built per-kind aggregates (CPU; no
chip, no run): every statistic over every denominator, and its errors."""

import types

import pytest

from benchmark.harness import manifest
from benchmark.readers import flight_stat

KIND_TOTALS = {
    "fire.shard": {"count": 4, "total_s": 0.5, "self_s": 0.5,
                   "max_s": 0.2, "work": 4000},
    "fire.dispatch": {"count": 2, "total_s": 2.0, "self_s": 1.0,
                      "max_s": 1.5, "work": 8000},
    "op.process": {"count": 9, "total_s": 6.0, "self_s": 0.25,
                   "max_s": 0.75, "work": 0},
}
KINDS = ["fire.shard", "fire.dispatch", "slice.retire"]   # last: absent


def a_run(windows=5, events=2000, window_s=10.0):
    sink = types.SimpleNamespace(windows_written_between=lambda: windows)
    return types.SimpleNamespace(sink=sink, events=events,
                                 window_s=window_s)


@pytest.mark.parametrize("stat, summed", [
    ("self_s", 1.5), ("total_s", 2.5), ("max_s", 1.5), ("work", 12000)])
@pytest.mark.parametrize("per", flight_stat.PERS)
def test_each_statistic_over_each_denominator(stat, summed, per):
    over = {"window_seconds": 10.0, "result_windows": 5, "events": 2000,
            "one": 1}[per]
    if stat == "work":
        want = summed / over
    elif per == "window_seconds":
        want = 100.0 * summed / over      # a share of the window, in %
    else:
        want = 1e3 * summed / over        # a time, in ms
    got = flight_stat.value(KIND_TOTALS, a_run(), KINDS, stat, per)
    assert got == pytest.approx(want)


def test_an_absent_kind_reads_none_and_never_zero():
    assert flight_stat.value(KIND_TOTALS, a_run(), ["slice.retire"],
                             "total_s", "one") is None
    assert flight_stat.value({}, a_run(), KINDS, "work", "events") is None


def test_a_recorder_that_does_not_keep_the_statistic_reads_none():
    # the parent commit's aggregates have durations and counts only
    older = {"fire.shard": {"count": 4, "total_s": 0.5, "max_s": 0.2}}
    run = a_run()
    assert flight_stat.value(older, run, KINDS, "self_s", "one") is None
    assert flight_stat.value(older, run, KINDS, "work", "events") is None
    assert flight_stat.value(older, run, KINDS, "total_s", "one") == 500.0


def test_no_result_window_or_event_reads_none():
    assert flight_stat.value(KIND_TOTALS, a_run(windows=0), KINDS,
                             "total_s", "result_windows") is None
    assert flight_stat.value(KIND_TOTALS, a_run(events=0), KINDS,
                             "work", "events") is None


@pytest.mark.parametrize("stat, per, known", [
    ("mean_s", "one", flight_stat.STATS),
    ("self_s", "fires", flight_stat.PERS)])
def test_an_unknown_statistic_or_denominator_lists_the_known(
        stat, per, known):
    with pytest.raises(ValueError, match=", ".join(known)):
        flight_stat.value(KIND_TOTALS, a_run(), KINDS, stat, per)


def test_read_takes_the_programs_recorder_as_it_stands():
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    rec.clear()
    with flight.span("sink.write") as s:
        s.work = 6
    with flight.span("sink.write") as s:
        s.work = 4
    assert flight_stat.read(a_run(), ["sink.write"], "work",
                            "result_windows") == 2.0
    assert flight_stat.read(a_run(), ["fire.shard"], "work", "one") is None
    rec.clear()


READER = "flight_stat"


def _files_of_this_reader():
    """Names of the ``benchmark/metrics/*.json`` files whose reader is
    this one: counted from the files, so a later PR's metric of this
    reader is one more file and no edit here."""
    return {name for name in manifest._files("metrics", ".json")
            if manifest.metric_spec(name)["reader"] == READER}


def test_every_metric_of_this_reader_names_kinds_the_program_registers():
    from flink_tpu.observe import KNOWN_SPAN_KINDS

    man = manifest.manifest()
    seen = 0
    for m in man["per_layer"]:
        spec = manifest.metric_spec(m["name"])
        if spec["reader"] != READER:
            continue
        seen += 1
        assert m["source"] == "program_span", m["name"]
        assert set(spec["args"]["kinds"]) <= set(KNOWN_SPAN_KINDS), m
        assert spec["args"]["stat"] in flight_stat.STATS
        assert spec["args"]["per"] in flight_stat.PERS
    assert seen == len(_files_of_this_reader()) > 0


def test_every_metric_file_has_its_entry_and_every_entry_its_file():
    man = manifest.manifest()
    entries = {m["name"] for k in ("end_to_end", "per_layer")
               for m in man[k]}
    files = set(manifest._files("metrics", ".json"))
    assert files - entries == set(), "metric files with no manifest entry"
    assert entries - files == set(), "manifest entries with no metric file"
    assert _files_of_this_reader() <= {m["name"] for m in man["per_layer"]}
