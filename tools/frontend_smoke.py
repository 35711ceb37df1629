"""Multi-process serving-tier smoke: 2 shm frontends under live load
(tier-1).

The executable form of the frontend-tier acceptance criteria on a box
of ANY core count — structural claims, not throughput:

1. **Seqlock fuzz phase** — an owner process writes generation after
   generation into a shm-backed hot cache while TWO frontend reader
   processes attach and probe the SAME arena continuously. Every hit
   is verified against the generation-deterministic value scheme
   ``v == g * 1e6 + key`` (both columns written under one seqlock
   stamp cycle). The run FAILS on:
   - ANY torn read surfacing (an inconsistent ``(g, v)`` pair),
   - zero reader hits, or readers observing only one generation
     (vacuity: the writer must really mutate under the probes).
2. **Serving parity phase** — a session cluster ingests a real job
   with the shm serving tier armed (``serving_shm_dir``) while client
   threads hammer ``FrontendPool.lookup_batch`` (hits answered inside
   the frontend processes, misses crossing to the owner's replica
   path). The run FAILS on:
   - owner/frontend parity divergence (a sampled frontend batch must
     equal the owner's own ``lookup_batch`` — repeated mismatch only,
     a publish landing between the two calls moves one boundary),
   - replica staleness p99 over ``FRONTEND_SMOKE_STALENESS_BUDGET_MS``
     (default 2000 — the frontends must not starve the publish loop),
   - zero frontend hits (vacuity: the shm hit path must actually
     serve — hit rate > 0),
   - any client error, or both frontends dying.

    JAX_PLATFORMS=cpu python tools/frontend_smoke.py
    FRONTEND_SMOKE_RECORDS=... to scale the ingest phase.
"""

import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

RECORDS = int(os.environ.get("FRONTEND_SMOKE_RECORDS", 60_000))
KEYS = int(os.environ.get("FRONTEND_SMOKE_KEYS", 2048))
CLIENTS = int(os.environ.get("FRONTEND_SMOKE_CLIENTS", 4))
FRONTENDS = int(os.environ.get("FRONTEND_SMOKE_FRONTENDS", 2))
FUZZ_SECONDS = float(os.environ.get("FRONTEND_SMOKE_FUZZ_S", 2.0))
STALENESS_BUDGET_MS = float(os.environ.get(
    "FRONTEND_SMOKE_STALENESS_BUDGET_MS", 2000))
LOOKUP_BATCH = int(os.environ.get("FRONTEND_SMOKE_LOOKUP_BATCH", 128))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Reader process body for the fuzz phase (same oracle as
# tests/test_serving_frontend.py): probe continuously, verify every
# hit's (g, v) pair against the formula of exactly one generation.
_READER_SRC = r"""
import json, os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from flink_tpu.tenancy.hot_cache_native import FrontendCacheClient

shm_dir, fe_id, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
client = FrontendCacheClient(shm_dir, frontend_id=fe_id)
keys = np.arange(128, dtype=np.int64)
probes = hits = bad = 0
gens = set()
deadline = time.monotonic() + seconds
# under heavy box load the probe window can land after the writer's
# first generations — extend (bounded) until live mutation was seen
hard = deadline + 20.0
while (time.monotonic() < deadline
       or (len(gens) < 2 and time.monotonic() < hard)):
    n, probe, misses = client.probe("fuzz", "op", keys)
    probes += len(keys)
    hits += n
    if probe is None:
        continue
    for i in range(len(keys)):
        if not probe.hit[i]:
            continue
        row = probe.materialize(i)[0]
        if row["v"] != row["g"] * 1_000_000.0 + float(keys[i]):
            bad += 1
        gens.add(row["g"])
client.close()
print(json.dumps({"probes": probes, "hits": hits, "bad": bad,
                  "n_gens": len(gens)}))
"""


def fuzz_phase(tmp: str) -> bool:
    """Owner writes live generations; two attached reader processes
    must see zero torn rows. Returns ok."""
    from flink_tpu.tenancy.hot_cache import make_hot_row_cache

    cache = make_hot_row_cache(max_entries=1 << 12,
                               shm_dir=os.path.join(tmp, "fuzz-shm"))
    ok = True
    try:
        keys = list(range(128))

        def write_gen(gen):
            cache.put_many(
                "fuzz", "op", keys, gen,
                [{0: {"g": float(gen),
                      "v": gen * 1_000_000.0 + float(k)}}
                 for k in keys])

        write_gen(1)
        env = dict(os.environ)
        env["PYTHONPATH"] = (_REPO + os.pathsep
                             + env.get("PYTHONPATH", ""))
        readers = [subprocess.Popen(
            [sys.executable, "-c", _READER_SRC, cache.shm_dir,
             str(fe), str(FUZZ_SECONDS)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=env, text=True) for fe in (1, 2)]
        # write while the READERS are alive (generous hang backstop,
        # not a tight wall budget: a loaded box can spend longer than
        # FUZZ_SECONDS just booting the reader interpreters, and a
        # writer that stops early flakes the multi-generation guard)
        gen = 1
        deadline = time.monotonic() + 60.0
        while (any(r.poll() is None for r in readers)
               and time.monotonic() < deadline):
            gen += 1
            write_gen(gen)
        reports = []
        for r in readers:
            out, err = r.communicate(timeout=60)
            if r.returncode != 0:
                print(f"FAIL: fuzz reader died: {err[-500:]}")
                return False
            reports.append(json.loads(out))
        torn = sum(rep["bad"] for rep in reports)
        hits = sum(rep["hits"] for rep in reports)
        if torn:
            print(f"FAIL: {torn} torn reads surfaced across "
                  f"{hits} hits (seqlock protocol broken over shm)")
            ok = False
        if hits == 0:
            print("FAIL: fuzz readers never hit — vacuous fuzz")
            ok = False
        if not any(rep["n_gens"] > 1 for rep in reports):
            print(f"FAIL: readers saw one generation while the owner "
                  f"wrote {gen} — the probes never overlapped live "
                  "priming (vacuous fuzz)")
            ok = False
        print(f"frontend smoke fuzz: generations={gen} hits={hits} "
              f"torn_surfaced={torn} reader_gens="
              f"{[rep['n_gens'] for rep in reports]}")
    finally:
        cache.close()
    return ok


def serving_phase(tmp: str) -> bool:
    """Real ingest + 2-frontend lookup load: parity, staleness,
    vacuity. Returns ok."""
    import warnings

    warnings.filterwarnings("ignore")
    import numpy as np

    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.core.config import Configuration
    from flink_tpu.datastream.environment import (
        StreamExecutionEnvironment,
    )
    from flink_tpu.metrics.core import quantile_sorted
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.tenancy.frontend import FrontendPool
    from flink_tpu.tenancy.session_cluster import SessionCluster
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 4096,
        "parallelism.default": 4,
        "serving.replica": True,
        "serving.replica.publish-interval-ms": 25,
    }))
    sink = CollectSink()
    (env.add_source(
        DataGenSource(total_records=RECORDS, num_keys=KEYS,
                      events_per_second_of_eventtime=50_000, seed=13),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(TumblingEventTimeWindows.of(60_000))
        .sum("value").sink_to(sink))

    cluster = SessionCluster(
        quantum_records=8192,
        serving_shm_dir=os.path.join(tmp, "serving-shm"))
    cluster.submit(env, "job-1")
    operator = "window_agg(SumAggregate)"
    pool = FrontendPool(cluster.serving, n_frontends=FRONTENDS)
    stop = threading.Event()
    errors = []
    parity = {"checked": 0, "diverged": 0}
    staleness = []

    def sampler():
        while not stop.is_set():
            staleness.append(cluster.serving.replica_staleness_ms())
            time.sleep(0.01)

    def client(i):
        rng = np.random.default_rng(500 + i)
        while not stop.is_set():
            ks = rng.integers(0, KEYS, LOOKUP_BATCH).tolist()
            try:
                got = pool.lookup_batch("job-1", operator, ks)
                if i == 0 and parity["checked"] < 8:
                    # owner/frontend parity: same tables + same miss
                    # path must agree; a publish between the two calls
                    # moves one boundary, so only REPEATED mismatch
                    # counts as divergence
                    for _ in range(5):
                        if got == cluster.lookup_batch(
                                "job-1", operator, ks):
                            break
                        got = pool.lookup_batch("job-1", operator, ks)
                    else:
                        parity["diverged"] += 1
                    parity["checked"] += 1
            except (RuntimeError, TimeoutError) as e:
                msg = str(e)
                if ("is not serving" in msg
                        or "already terminated" in msg
                        or "shut down" in msg
                        or "FrontendPool is closed" in msg):
                    return  # job finished: lookups drain off
                errors.append(f"client {i}: {e!r}")
                return
            time.sleep(0.005)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    threads.append(threading.Thread(target=sampler, daemon=True))
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    try:
        cluster.run(timeout_s=600)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        fe_rows = cluster.serving.hot_cache.fe_stats(FRONTENDS)
        live = len(pool.live_frontends())
        pool.close()
        cluster.serving.hot_cache.close()
    elapsed = time.perf_counter() - t0

    ok = True
    if errors:
        print(f"FAIL: {errors[:3]}")
        ok = False
    if parity["diverged"]:
        print(f"FAIL: {parity['diverged']}/{parity['checked']} "
              "sampled batches diverged between the frontend and the "
              "owner lookup path")
        ok = False
    if parity["checked"] == 0:
        print("FAIL: zero parity samples — vacuous parity gate")
        ok = False
    fe_hits = sum(r["hits"] for r in fe_rows)
    fe_probes = sum(r["probes"] for r in fe_rows)
    fe_crossings = sum(r["miss_crossings"] for r in fe_rows)
    if fe_hits == 0:
        print("FAIL: frontends never served a shm hit — the "
              "multi-process hit path is vacuously off (probes="
              f"{fe_probes})")
        ok = False
    if live == 0:
        print("FAIL: every frontend died during the run")
        ok = False
    staleness_p99 = quantile_sorted(sorted(staleness), 0.99) \
        if staleness else 0.0
    if STALENESS_BUDGET_MS and staleness_p99 > STALENESS_BUDGET_MS:
        print(f"FAIL: replica staleness p99 {staleness_p99:.0f} ms "
              f"over the {STALENESS_BUDGET_MS:.0f} ms budget — the "
              "frontend tier is starving the publish loop")
        ok = False
    if len(sink.result()) == 0:
        print("FAIL: job produced no output")
        ok = False
    print(f"frontend smoke serving: frontends={FRONTENDS} "
          f"live_at_end={live} probes={fe_probes} hits={fe_hits} "
          f"hit_rate={fe_hits / fe_probes if fe_probes else 0.0:.3f} "
          f"miss_crossings={fe_crossings} "
          f"parity_checked={parity['checked']} "
          f"diverged={parity['diverged']} "
          f"staleness_p99={staleness_p99:.1f}ms "
          f"elapsed={elapsed:.1f}s => {'OK' if ok else 'FAIL'}")
    return ok


def main():
    import tempfile

    from flink_tpu.native import hotcache_available

    if not hotcache_available():
        print("FRONTEND SMOKE: native hotcache unavailable — the "
              "multi-process tier cannot exist here")
        return 1
    with tempfile.TemporaryDirectory(prefix="frontend_smoke_") as tmp:
        ok = fuzz_phase(tmp)
        ok = serving_phase(tmp) and ok
    print(f"frontend smoke => {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
