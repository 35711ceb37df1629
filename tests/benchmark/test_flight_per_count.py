"""The ``flight_per_count`` reader on hand-built per-kind aggregates (CPU;
no chip, no run): several kinds summed over the count of one, seconds and
work, and what it reads where there is nothing to read."""

import pytest

from benchmark.harness import manifest
from benchmark.readers import flight_per_count

KIND_TOTALS = {
    # three parts of one thing that happened 4 times
    "checkpoint.drain": {"count": 4, "total_s": 0.02, "self_s": 0.02,
                         "work": 6},
    "checkpoint.snapshot": {"count": 4, "total_s": 0.06, "self_s": 0.05,
                            "work": 4_000_000},
    "checkpoint.write": {"count": 4, "total_s": 1.0, "self_s": 1.0,
                         "work": 2_800_000},
    # an instant: counted, never timed
    "checkpoint.rows": {"count": 8, "total_s": 0.0, "self_s": 0.0,
                        "work": 1_600_000},
}
PARTS = ["checkpoint.drain", "checkpoint.snapshot", "checkpoint.write"]


@pytest.mark.parametrize("kinds, stat, of, want", [
    (PARTS, "total_s", "checkpoint.write", 270.0),   # 1.08 s over 4, in ms
    (PARTS, "self_s", "checkpoint.write", 267.5),
    (["checkpoint.write"], "work", "checkpoint.write", 700_000),
    (["checkpoint.rows"], "work", "checkpoint.rows", 200_000),
    (["checkpoint.rows"], "work", "checkpoint.write", 400_000)])
def test_kinds_summed_over_the_count_of_one(kinds, stat, of, want):
    assert flight_per_count.value(KIND_TOTALS, kinds, stat, of) \
        == pytest.approx(want)


@pytest.mark.parametrize("stat", flight_per_count.STATS)
def test_nothing_to_read_reads_none_and_never_zero(stat):
    value = flight_per_count.value
    assert value({}, PARTS, stat, "checkpoint.write") is None
    assert value(KIND_TOTALS, PARTS, stat, "checkpoint.restore") is None
    assert value(KIND_TOTALS, ["fire.shard"], stat,
                 "checkpoint.write") is None
    assert value({"checkpoint.write": {"count": 0, "total_s": 0.0}},
                 ["checkpoint.write"], stat, "checkpoint.write") is None


def test_a_program_whose_span_states_no_work_reads_none():
    # the parent commit's one span of that name: timed, no work stated,
    # and none of the newer kinds beside it
    older = {"checkpoint.write": {"count": 4, "total_s": 1.2, "self_s": 1.2,
                                  "work": 0}}
    assert flight_per_count.value(older, ["checkpoint.write"], "work",
                                  "checkpoint.write") is None
    assert flight_per_count.value(older, PARTS, "total_s",
                                  "checkpoint.write") == pytest.approx(300.0)
    assert flight_per_count.value({"checkpoint.write": {"count": 4}},
                                  PARTS, "total_s",
                                  "checkpoint.write") is None


def test_an_unknown_statistic_lists_the_known():
    with pytest.raises(ValueError,
                       match=", ".join(flight_per_count.STATS)):
        flight_per_count.value(KIND_TOTALS, PARTS, "max_s",
                               "checkpoint.write")


def test_read_takes_the_programs_recorder_as_it_stands():
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    rec.clear()
    for rows in (10, 30):
        with flight.span("checkpoint.snapshot"):
            flight.instant("checkpoint.rows", work=rows)
        with flight.span("checkpoint.write") as write:
            write.work = 100 * rows
    run = object()                          # the reader asks it nothing
    assert flight_per_count.read(run, ["checkpoint.rows"], "work",
                                 "checkpoint.rows") == 20
    assert flight_per_count.read(run, ["checkpoint.write"], "work",
                                 "checkpoint.write") == 2000
    assert flight_per_count.read(
        run, ["checkpoint.snapshot", "checkpoint.write"], "total_s",
        "checkpoint.write") > 0
    assert flight_per_count.read(run, ["checkpoint.drain"], "total_s",
                                 "checkpoint.write") is None
    rec.clear()


def test_every_metric_of_this_reader_names_kinds_the_program_registers():
    from flink_tpu.observe import KNOWN_SPAN_KINDS

    files = {name for name in manifest._files("metrics", ".json")
             if manifest.metric_spec(name)["reader"] == "flight_per_count"}
    entries = {m["name"]: m for m in manifest.manifest()["per_layer"]}
    assert files and files <= set(entries)
    for name in files:
        args = manifest.metric_spec(name)["args"]
        assert set(args["kinds"]) | {args["of"]} <= set(KNOWN_SPAN_KINDS)
        assert args["stat"] in flight_per_count.STATS
        assert entries[name]["source"] == "program_span", name
