"""Streaming-join benchmarks: the Nexmark-style join rows.

Two rows, growing BENCHMARKS.md toward the Nexmark matrix (ROADMAP
item 4 — scenario diversity as a measured table):

- ``nexmark_q8_windowed_join``: person/auction style (Nexmark Q8
  monitors sellers who registered recently): auctions join persons who
  registered within the trailing window — the interval-join
  formulation, run on the device engine (dual keyed slot tables, fused
  device-mode exchange, banded probe program per batch).
- ``interval_join_10m_keys``: the row-5 thrashing shape applied to a
  two-input operator — 10M distinct keys, live rows far above the
  per-shard device budget, so ingest evicts page cohorts and band
  probes serve cold candidates straight from the paged tier.

Methodology matches bench.py: median of post-warm reps (best/all reps
as secondary fields). ``fire_latency_ms`` reports the emit-latency
percentiles — wall time from an arriving batch to its matches
materialized on the host (the two-input analogue of window fire
latency, so the matrix stays comparable). The ``breakdown`` field is
derived from flight-recorder spans — the same spans a captured
Perfetto trace of the run shows, never private driver timers. It
reports span TOTALS (ingest / probe+prune / harvest): the join
engines don't yet emit per-interaction device spans, so no host-prep
split is claimed (the mesh-sessions bench owns that contract).

    BENCH_JOIN_RECORDS=... BENCH_JOIN_REPS=... \
        JAX_PLATFORMS=cpu python tools/bench_joins.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

from flink_tpu.metrics.core import quantile_sorted  # noqa: E402

BATCH = 1 << 15


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _latency(samples_ms):
    if not samples_ms:
        return None
    samples_ms = sorted(samples_ms)
    return {"p50": quantile_sorted(samples_ms, 0.5),
            "p99": quantile_sorted(samples_ms, 0.99),
            "max": samples_ms[-1], "count": len(samples_ms)}


def _mesh(shards=8):
    import jax

    from flink_tpu.parallel.mesh import make_mesh

    return make_mesh(min(len(jax.devices()), shards))


def _drive(engine, total, num_keys, rate, band_ms, seed):
    """Alternate left/right batches at ``rate`` events/s of event
    time; watermark trails by the band so pruning is live. Returns
    (events, matches, emit-latency samples, wall seconds, breakdown)
    with the breakdown derived from this pass's flight-recorder
    spans."""
    rng = np.random.default_rng(seed)
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    flight.set_job("bench_joins")
    rec.clear()
    events = matches = 0
    lat = []
    t0 = time.perf_counter()
    t = 0
    while events < total:
        for side, name in ((0, "price"), (1, "rate")):
            n = min(BATCH, max(total - events, 1))
            keys = rng.integers(0, num_keys, n).astype(np.int64)
            ts = t + (np.arange(n, dtype=np.int64) * 1000) // rate
            b0 = time.perf_counter()
            out = engine.process_batch(RecordBatch({
                KEY_ID_FIELD: keys,
                name: rng.random(n).astype(np.float32),
                TIMESTAMP_FIELD: ts,
            }), side)
            m = sum(len(x) for x in out)
            if m:
                lat.append((time.perf_counter() - b0) * 1e3)
            matches += m
            events += n
        t = int(ts[-1]) + 1
        engine.on_watermark(t - band_ms)
    dt = time.perf_counter() - t0
    # span-derived totals, NOT the mesh engines' host-prep breakdown:
    # the join engines don't (yet) emit device.dispatch/fence spans,
    # so a host_prep_s line here would claim their inline device work
    # as host time — report only what the spans actually attribute
    kt = rec.kind_totals()
    breakdown = {
        name: round(kt.get(kind, {}).get("total_s", 0.0), 3)
        for name, kind in (("ingest_s", "batch.ingest"),
                           ("probe_fire_s", "fire.dispatch"),
                           ("harvest_s", "fire.harvest"))}
    breakdown["total_s"] = round(dt, 3)
    return events, matches, lat, dt, breakdown


def bench_q8(scale=1.0, reps=None):
    """Person/auction windowed join: auctions (seller-keyed) join the
    persons who registered in the trailing 10 s window."""
    from flink_tpu.joins import MeshIntervalJoinEngine

    total = int(int(os.environ.get(
        "BENCH_JOIN_RECORDS", 4_000_000)) * scale)
    reps = reps or int(os.environ.get("BENCH_JOIN_REPS", 3))
    num_keys = 100_000          # active sellers
    window_ms = 10_000
    rate = 200_000              # events/s of event time per side

    def make():
        # auctions at t match persons registered in [t - window, t]:
        # persons are input 0, auctions input 1 -> stored persons are
        # probed with band [t - window, t] from the auction side
        return MeshIntervalJoinEngine(
            0, window_ms, mesh=_mesh(),
            capacity_per_shard=1 << 18)

    _drive(make(), min(total, 1 << 20), num_keys, rate, window_ms,
           seed=1)  # warm
    runs = [_drive(make(), total, num_keys, rate, window_ms, seed=1)
            for _ in range(reps)]
    evps = [ev / dt for ev, _, _, dt, _ in runs]
    ev, matches, lat, dt, breakdown = runs[evps.index(_median(evps))]
    return {
        "metric": "nexmark_q8_windowed_join_events_per_sec",
        "value": round(_median(evps), 1),
        "best": round(max(evps), 1),
        "reps": [round(x, 1) for x in evps],
        "unit": "events/s",
        "matches": int(matches),
        "fire_latency_ms": _latency(lat),
        "breakdown": breakdown,
        "shape": (f"person/auction interval join, {num_keys:,} "
                  f"sellers, 10 s trailing window, "
                  f"{rate:,} ev/s/side event time, device-mode "
                  "exchange + banded probe program"),
    }


def bench_interval_10m(scale=1.0, reps=None):
    """The thrashing shape: 10M keys, live rows >> device budget."""
    from flink_tpu.joins import MeshIntervalJoinEngine

    total = int(int(os.environ.get(
        "BENCH_JOIN_RECORDS", 4_000_000)) * scale)
    reps = reps or int(os.environ.get("BENCH_JOIN_REPS", 3))
    num_keys = 10_000_000
    band_ms = 2_000
    rate = 400_000
    budget = 1 << 16            # slots/shard/side vs ~800k live rows

    def make():
        return MeshIntervalJoinEngine(
            -band_ms, band_ms, mesh=_mesh(),
            capacity_per_shard=budget, max_device_slots=budget)

    _drive(make(), min(total, 1 << 20), num_keys, rate, band_ms,
           seed=2)  # warm
    runs = []
    spills = []
    for _ in range(reps):
        eng = make()
        runs.append(_drive(eng, total, num_keys, rate, band_ms,
                           seed=2))
        spills.append(eng.spill_counters())
    evps = [ev / dt for ev, _, _, dt, _ in runs]
    i = evps.index(_median(evps))
    ev, matches, lat, dt, breakdown = runs[i]
    sp = spills[i]
    if os.environ.get("BENCH_JOIN_REQUIRE_SPILL") == "1" and (
            sp["rows_evicted"] == 0 or sp["cold_rows_served"] == 0):
        raise RuntimeError(
            f"vacuous join bench: spill never engaged ({sp})")
    return {
        "metric": "interval_join_10m_keys_events_per_sec",
        "value": round(_median(evps), 1),
        "best": round(max(evps), 1),
        "reps": [round(x, 1) for x in evps],
        "unit": "events/s",
        "matches": int(matches),
        "fire_latency_ms": _latency(lat),
        "breakdown": breakdown,
        "spill": sp,
        "shape": (f"10M distinct keys, +-2 s band at {rate:,} ev/s "
                  f"of event time (~1.6M live rows vs "
                  f"{budget * 8:,} device slots/side) — forced paged "
                  "eviction, cold band candidates served from the "
                  "page tier"),
    }


def main():
    import warnings

    warnings.filterwarnings("ignore")
    # BENCH_JOIN_RECORDS is the one scale knob here — the suite driver
    # (bench_suite._join_rows) already folds BENCH_SUITE_SCALE into it,
    # so reading the suite scale again would apply it twice (the
    # bench_mesh_sessions contract)
    for fn in (bench_q8, bench_interval_10m):
        r = fn(1.0)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
