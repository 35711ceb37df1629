"""Filesystem table connector — SQL write + read of bucketed
exactly-once files.

reference: the filesystem table connector (readable + writable,
partitioned directories, 'format' through the schema seams).
"""

import json
import os

import numpy as np

from flink_tpu import Configuration, StreamExecutionEnvironment
from flink_tpu.connectors.filesystem import FileSource, read_committed_rows
from flink_tpu.connectors.formats import resolve_format
from flink_tpu.connectors.kafka import FakeBroker
from flink_tpu.core.records import RecordBatch
from flink_tpu.table.environment import StreamTableEnvironment


def _seed_topic(topic, n=3000, keys=20):
    broker = FakeBroker.get("default")
    broker.create_topic(topic, 1)
    rng = np.random.default_rng(8)
    ks = rng.integers(0, keys, n).astype(np.int64)
    vs = np.round(rng.random(n), 6)
    ts = np.arange(n, dtype=np.int64) * 4
    broker.append(topic, 0, RecordBatch.from_pydict(
        {"key": ks, "value": vs, "ts": ts}, timestamps=ts))
    return ks, vs, ts


def test_insert_into_filesystem_then_select_back(tmp_path):
    """SQL aggregate -> INSERT INTO a bucketed filesystem table ->
    a second job SELECTs the committed files back."""
    out = str(tmp_path / "warehouse")
    ks, vs, ts = _seed_topic("fs_in")

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 500}))
    tenv = StreamTableEnvironment(env)
    tenv.execute_sql(
        "CREATE TABLE fs_in (key BIGINT, value DOUBLE, ts BIGINT, "
        "WATERMARK FOR ts AS ts) "
        "WITH ('connector'='kafka', 'topic'='fs_in')")
    tenv.execute_sql(
        "CREATE TABLE warehouse (key BIGINT, window_end BIGINT, "
        "total DOUBLE) "
        f"WITH ('connector'='filesystem', 'path'='{out}', "
        "'format'='json', 'sink.bucket-by'='key')")
    tenv.execute_sql("""
        INSERT INTO warehouse
        SELECT key, window_end, SUM(value) AS total
        FROM TABLE(TUMBLE(TABLE fs_in, DESCRIPTOR(ts),
                          INTERVAL '1' SECOND))
        GROUP BY key, window_start, window_end
    """)

    # bucket directories by key; only committed parts
    assert sorted(os.listdir(out)) == sorted(
        str(k) for k in set(ks.tolist()))
    rows = [json.loads(r) for r in read_committed_rows(out)]

    import collections

    oracle = collections.defaultdict(float)
    for k, v, t in zip(ks.tolist(), vs.tolist(), ts.tolist()):
        oracle[(k, (t // 1000 + 1) * 1000)] += v
    got = {(r["key"], r["window_end"]): r["total"] for r in rows}
    assert set(got) == set(oracle)
    for k in oracle:
        assert abs(got[k] - oracle[k]) < 1e-4  # f32 agg

    # a SECOND job reads the committed files back through SQL
    env2 = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 500}))
    tenv2 = StreamTableEnvironment(env2)
    tenv2.execute_sql(
        "CREATE TABLE warehouse (key BIGINT, window_end BIGINT, "
        "total DOUBLE) "
        f"WITH ('connector'='filesystem', 'path'='{out}', "
        "'format'='json')")
    back = tenv2.execute_sql(
        "SELECT key, SUM(CAST(total AS FLOAT)) AS s FROM warehouse "
        "GROUP BY key"
    ).collect()
    per_key = collections.defaultdict(float)
    for (k, _), v in oracle.items():
        per_key[k] += v
    got_back = {r["key"]: r["s"] for r in back}
    assert set(got_back) == set(per_key)
    for k, v in per_key.items():
        assert abs(got_back[k] - v) < 1e-3  # f32 agg, two passes


def test_file_source_restore_survives_directory_growth(tmp_path):
    """The checkpoint carries remaining file PATHS, so files committed
    after the snapshot neither shift the cursor (skips) nor re-emit
    consumed files (duplicates)."""
    from flink_tpu.connectors.filesystem import FileSink

    d = str(tmp_path / "out")
    sink = FileSink(d, ["v"], fmt="json")
    sink.open(0)
    for v in (1, 2):
        sink.write(RecordBatch({"v": np.array([v])}))
        sink.commit(sink.prepare_commit())  # one committed file per v

    deser, _ = resolve_format("json", ["v"], ["BIGINT"])
    src = FileSource(d, deser)
    src.open(0, 1)
    first = src.poll_batch(10)["v"].tolist()
    pos = src.snapshot_position()

    # a new file lands between snapshot and restore
    sink.write(RecordBatch({"v": np.array([99])}))
    sink.commit(sink.prepare_commit())

    src2 = FileSource(d, deser)
    src2.open(0, 1)
    src2.restore_position(pos)
    rest = []
    while (b := src2.poll_batch(10)) is not None:
        rest.extend(b["v"].tolist())
    # exactly the pre-snapshot remainder: no skip, no re-read, and the
    # post-snapshot file is NOT part of this run's split
    assert sorted(first + rest) == [1, 2]


def test_file_source_honors_max_records_and_midfile_restore(tmp_path):
    from flink_tpu.connectors.filesystem import FileSink

    d = str(tmp_path / "out")
    sink = FileSink(d, ["v"], fmt="json")
    sink.open(0)
    sink.write(RecordBatch({"v": np.arange(10)}))
    sink.commit(sink.prepare_commit())
    deser, _ = resolve_format("json", ["v"], ["BIGINT"])
    src = FileSource(d, deser)
    src.open(0, 1)
    assert src.poll_batch(4)["v"].tolist() == [0, 1, 2, 3]
    pos = src.snapshot_position()
    assert pos["row"] == 4
    src2 = FileSource(d, deser)
    src2.open(0, 1)
    src2.restore_position(pos)
    got = []
    while (b := src2.poll_batch(3)) is not None:
        assert len(b) <= 3
        got.extend(b["v"].tolist())
    assert got == [4, 5, 6, 7, 8, 9]


def test_text_framing_rejects_raw_newlines_loudly(tmp_path):
    import pytest

    from flink_tpu.connectors.filesystem import FileSink

    d = str(tmp_path / "out")
    sink = FileSink(d, ["s"], fmt="csv", types=["STRING"])
    sink.open(0)
    with pytest.raises(ValueError, match="raw newline"):
        sink.write(RecordBatch({"s": np.array(["a\nb"], dtype=object)}))


def test_file_source_reads_buckets_and_restores_position(tmp_path):
    from flink_tpu.connectors.filesystem import (
        ColumnBucketAssigner,
        FileSink,
    )

    d = str(tmp_path / "out")
    sink = FileSink(d, ["k", "v"], fmt="json",
                    bucket_assigner=ColumnBucketAssigner("k"))
    sink.open(0)
    sink.write(RecordBatch({"k": np.array([1, 2, 1]),
                            "v": np.array([10.0, 20.0, 30.0])}))
    sink.commit(sink.prepare_commit())

    deser, _ = resolve_format("json", ["k", "v"], ["BIGINT", "DOUBLE"])
    src = FileSource(d, deser)
    src.open(0, 1)
    got = []
    pos = None
    b = src.poll_batch(1 << 16)
    got.extend(zip(b["k"].tolist(), b["v"].tolist()))
    pos = src.snapshot_position()
    # restore mid-scan: a fresh source resumes at the file boundary
    src2 = FileSource(d, deser)
    src2.open(0, 1)
    src2.restore_position(pos)
    while (b := src2.poll_batch(1 << 16)) is not None:
        got.extend(zip(b["k"].tolist(), b["v"].tolist()))
    assert sorted(got) == [(1, 10.0), (1, 30.0), (2, 20.0)]
