"""CEP benchmark: the ``cep_patterns_10m_keys`` row.

The row-5 thrashing shape applied to pattern detection: a 2-stage
within-window sequence over 10M distinct keys at 400k ev/s of event
time, so the live partial-match set (~260k keys holding a stage-a
partial inside the 2 s window) sits far above the per-shard device
budget — ingest evicts page cohorts and due keys reload (with the lazy
within-prune) straight from the paged tier.

The same shape runs on the HOST backend (the per-key ``CepOperator``
NFA — the bit-identity oracle every CEP gate diffs against) at a
reduced record count, and the row reports the device/host events-per-
second ratio. ``BENCH_CEP_REQUIRE_WIN=1`` makes a device loss a hard
error; ``BENCH_CEP_REQUIRE_SPILL=1`` fails a run where the spill tier
never engaged (a vacuous-coverage run must not publish a number).

Methodology matches bench.py: median of post-warm reps (best/all reps
as secondary fields). ``fire_latency_ms`` is the emit-latency
percentile set — wall time from a watermark advance to its matches
materialized on the host (the CEP analogue of window fire latency, so
the matrix stays comparable).

    BENCH_CEP_RECORDS=... BENCH_CEP_REPS=... \
        JAX_PLATFORMS=cpu python tools/bench_cep.py
"""

import json
import os
import sys
import tempfile
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
NUM_KEYS = 10_000_000
RATE = 400_000          # events/s of event time
WITHIN_MS = 2_000
WM_LAG_MS = 500
BUDGET = 1 << 14        # slots/shard vs ~260k live partial keys


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


def _pattern():
    from flink_tpu.cep.pattern import (
        AfterMatchSkipStrategy,
        Pattern,
    )

    return (Pattern.begin(
                "a", skip=AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT)
            .where(lambda b: np.asarray(b["v"]) % 3 == 0)
            .next("b")
            .where(lambda b: np.asarray(b["v"]) % 3 == 1)
            .within(WITHIN_MS))


def _drive(engine, total, seed):
    """Keyed batches at RATE ev/s of event time, a trailing-watermark
    fire after every batch, and a final drain fire. Returns (events,
    matches, emit-latency samples, wall seconds, breakdown) with the
    breakdown read from this pass's flight-recorder span aggregates
    (``kind_totals()`` — as the session and join rows read theirs, so
    the matrix attributes time the same way everywhere)."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    flight.set_job("bench_cep")
    rec.clear()
    rng = np.random.default_rng(seed)
    events = matches = 0
    lat = []
    t0 = time.perf_counter()
    t = 0
    while events < total:
        n = min(BATCH, total - events)
        keys = rng.integers(0, NUM_KEYS, n).astype(np.int64)
        vals = rng.integers(0, 9, n).astype(np.int64)
        ts = t + (np.arange(n, dtype=np.int64) * 1000) // RATE
        engine.process_batch(RecordBatch({
            KEY_ID_FIELD: keys,
            "v": vals,
            TIMESTAMP_FIELD: ts,
        }))
        events += n
        t = int(ts[-1]) + 1
        f0 = time.perf_counter()
        out = engine.on_watermark(t - WM_LAG_MS)
        m = sum(len(b) for b in out)
        if m:
            lat.append((time.perf_counter() - f0) * 1e3)
        matches += m
    # staged drain: every fire must fit its due-key set inside the
    # per-shard slot budget, so the final watermark advances in
    # batch-sized steps instead of one MAX jump over the whole lag
    wm = t - WM_LAG_MS
    step = max(BATCH * 1000 // RATE, 1)
    while wm < t:
        wm = min(wm + step, t)
        matches += sum(len(b) for b in engine.on_watermark(wm))
    dt = time.perf_counter() - t0
    # the CEP engine emits ingest/fire/harvest spans but no
    # device.dispatch/fence pair (yet), so — like the join row — no
    # host_prep_s line: report only what the spans attribute
    kt = rec.kind_totals()
    breakdown = {
        name: round(kt.get(kind, {}).get("total_s", 0.0), 3)
        for name, kind in (("ingest_s", "batch.ingest"),
                           ("advance_fire_s", "fire.dispatch"),
                           ("harvest_s", "fire.harvest"))}
    breakdown["total_s"] = round(dt, 3)
    return events, matches, lat, dt, breakdown


def bench_cep(scale=1.0, reps=None):
    from flink_tpu.cep.mesh_engine import MeshCepEngine

    total = int(int(os.environ.get(
        "BENCH_CEP_RECORDS", 4_000_000)) * scale)
    reps = reps or int(os.environ.get("BENCH_CEP_REPS", 3))

    def _mesh():
        import jax

        from flink_tpu.parallel.mesh import make_mesh

        return make_mesh(min(len(jax.devices()), 8))

    def make(spill_dir):
        return MeshCepEngine(_pattern(), mesh=_mesh(),
                             capacity_per_shard=BUDGET,
                             spill_dir=spill_dir)

    with tempfile.TemporaryDirectory() as td:
        _drive(make(td), min(total, 1 << 19), seed=3)  # warm
        runs = []
        spills = []
        for _ in range(reps):
            eng = make(td)
            runs.append(_drive(eng, total, seed=3))
            spills.append(eng.spill_counters())
    evps = [ev / dt for ev, _, _, dt, _ in runs]
    i = evps.index(_median(evps))
    ev, matches, lat, dt, breakdown = runs[i]
    sp = spills[i]
    if matches == 0:
        raise RuntimeError("vacuous cep bench: zero matches")
    if os.environ.get("BENCH_CEP_REQUIRE_SPILL") == "1" and (
            sp["rows_evicted"] == 0 or sp["rows_reloaded"] == 0):
        raise RuntimeError(
            f"vacuous cep bench: spill never engaged ({sp})")

    # the SAME shape on the host oracle (reduced record count — the
    # per-key python NFA is the thing being beaten, not raced at 4M)
    host_total = min(total, 1 << 18)
    host = MeshCepEngine(_pattern(), backend="host")
    hev, hmatches, _, hdt, _ = _drive(host, host_total, seed=3)
    host_evps = hev / hdt
    if hmatches == 0:
        raise RuntimeError("vacuous cep bench: host oracle emitted "
                           "zero matches")
    speedup = _median(evps) / host_evps
    if os.environ.get("BENCH_CEP_REQUIRE_WIN") == "1" and speedup <= 1:
        raise RuntimeError(
            f"device CEP did not beat the host oracle: "
            f"{_median(evps):,.0f} ev/s vs {host_evps:,.0f} ev/s")

    return {
        "metric": "cep_patterns_10m_keys_events_per_sec",
        "value": round(_median(evps), 1),
        "best": round(max(evps), 1),
        "reps": [round(x, 1) for x in evps],
        "unit": "events/s",
        "matches": int(matches),
        "fire_latency_ms": _latency(lat),
        "breakdown": breakdown,
        "spill": sp,
        "host_events_per_s": round(host_evps, 1),
        "speedup_vs_host": round(speedup, 2),
        "shape": (f"2-stage within-{WITHIN_MS // 1000}s sequence, "
                  f"10M distinct keys at {RATE:,} ev/s of event time "
                  f"(~260k live partials vs {BUDGET * 8:,} device "
                  f"slots) — forced paged eviction with lazy "
                  f"within-prune on reload; device NFA "
                  f"{speedup:.1f}x the host CepOperator oracle "
                  f"({host_evps:,.0f} ev/s) at the same shape"),
    }


def main():
    import warnings

    warnings.filterwarnings("ignore")
    # BENCH_CEP_RECORDS is the one scale knob — the suite driver folds
    # BENCH_SUITE_SCALE into it (the bench_mesh_sessions contract)
    print(json.dumps(bench_cep(1.0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
