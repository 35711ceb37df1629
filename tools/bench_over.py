"""Bench: host vs device OVER aggregation engines.

Workload: one operator fed B batches of R rows over K keys, ROWS
n-PRECEDING frames with SUM/AVG/MIN/MAX — the shape where the host
engine's per-key-segment Python loop is the bottleneck and the device
engine's fused scans should win as K grows.

Prints one JSON line per (engine, keys) with rows/s, then a summary
speedup line. Runs on JAX's default backend:
``python tools/bench_over.py``.
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

from flink_tpu.core.records import (  # noqa: E402
    KEY_ID_FIELD,
    TIMESTAMP_FIELD,
    RecordBatch,
)


def make_batches(rng, n_batches, rows, keys, ts_step=1, wm=0):
    batches, wms = [], []
    for _ in range(n_batches):
        new_wm = wm + rows * ts_step
        ts = np.sort(rng.integers(wm + 1, new_wm + 1, size=rows))
        batches.append(RecordBatch({
            KEY_ID_FIELD: rng.integers(0, keys, rows).astype(np.int64),
            "x": rng.normal(size=rows),
            TIMESTAMP_FIELD: ts.astype(np.int64)}))
        wms.append(new_wm)
        wm = new_wm
    return batches, wms


def run(engine: str, keys: int, n_batches=20, rows=50_000,
        preceding=16) -> dict:
    from flink_tpu.runtime.over_agg import OverAggOperator
    from flink_tpu.runtime.over_device import DeviceOverAggOperator

    specs = [("SUM", "x", "__s__"), ("AVG", "x", "__a__"),
             ("MIN", "x", "__mn__"), ("MAX", "x", "__mx__")]
    cls = DeviceOverAggOperator if engine == "device" else OverAggOperator
    op = cls("k", specs, mode="ROWS", preceding=preceding)
    op.open(None)
    rng = np.random.default_rng(1)
    # warmup fires (compile) — THREE: the padded kernel size steps up
    # once per-key context fills in (fire 1 has no context), so both
    # compiled shapes must be warm before timing; measured batches
    # follow in event time so none of their rows arrive late
    wb, wwm = make_batches(rng, 3, rows, keys)
    batches, wms = make_batches(rng, n_batches, rows, keys, wm=wwm[-1])
    for b, wm in zip(wb, wwm):
        op.process_batch(b)
        op.process_watermark(wm)

    t0 = time.perf_counter()
    n_out = 0
    for b, wm in zip(batches, wms):
        op.process_batch(b)
        for o in op.process_watermark(wm):
            n_out += len(o)
    dt = time.perf_counter() - t0
    total = n_batches * rows
    assert n_out == total, (n_out, total)
    return {"engine": engine, "keys": keys,
            "rows_per_s": total / dt, "elapsed_s": dt}


def main():
    speedups = {}
    for keys in (100, 2_000, 50_000):
        r_host = run("host", keys)
        r_dev = run("device", keys)
        for r in (r_host, r_dev):
            print(json.dumps({k: round(v, 1)
                              if isinstance(v, float) else v
                              for k, v in r.items()}))
        speedups[keys] = r_dev["rows_per_s"] / r_host["rows_per_s"]
    print(json.dumps({
        "metric": "over_device_speedup_vs_host",
        "value": {str(k): round(v, 3) for k, v in speedups.items()},
        "unit": "x (by key count)"}))


if __name__ == "__main__":
    main()
