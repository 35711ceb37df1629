"""Multi-tenant serving smoke: 2 jobs + concurrent lookup load (tier-1).

The executable form of the serving-plane acceptance criteria — since
r17 this gates the READ-REPLICA path (boundary-published snapshots +
host hot-row cache + sharded coalescer workers):

1. **Warm phase** — job-1 runs alone on the session cluster and compiles
   the step-program family (incl. the replica publish/gather tiers).
2. **Measured phase** — a FRESH cluster runs TWO fresh jobs (new engine
   instances, same mesh/layout) under the recompile sentinel while
   client threads hammer batched queryable-state lookups. The run FAILS
   on:
   - ANY steady-state XLA compile (shared program cache + warmed
     replica tier lattice must serve both jobs),
   - per-job program-cache misses > 0,
   - lookup p99 over budget (``SERVING_SMOKE_P99_BUDGET_MS``, default
     25 ms — the replica+cache path must hold it under concurrent
     ingest),
   - throughput under the floor (``SERVING_SMOKE_MIN_LOOKUPS_PER_S``,
     default 350,000/s — raised from 216k when the r19 native fast
     path landed: GIL-free hot-row probe table + packed zero-copy
     batch lookups),
   - the serving plane silently on the Python cache while
     ``SERVING_REQUIRE_NATIVE_HOTCACHE=1`` (tier1.sh exports it when
     the up-front native build succeeded — no vacuous green),
   - hot-row cache hit rate == 0 (vacuity: the cache must actually
     serve),
   - replica generations < 2 (vacuity: boundary publishes must
     actually happen),
   - any quota violation, zero served lookups, empty job output, or a
     packed-vs-dict lookup mismatch (one materialized cross-check).
   ``SERVING_SMOKE_PACKED=0`` forces the dict client path (the control
   for the packed lookups, gated at the pre-r19 216k floor).

Prints a JSON line with ``queryable_lookups_per_s``.

    JAX_PLATFORMS=cpu python tools/serving_smoke.py
    SERVING_SMOKE_RECORDS=... SERVING_SMOKE_CLIENTS=... to scale.
    SERVING_SMOKE_REPLICA=0 measures the legacy live-plane path
    (floor/hit-rate/generation gates auto-disable).
"""

import json
import os
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

RECORDS = int(os.environ.get("SERVING_SMOKE_RECORDS", 200_000))
CLIENTS = int(os.environ.get("SERVING_SMOKE_CLIENTS", 16))
KEYS = int(os.environ.get("SERVING_SMOKE_KEYS", 4096))
P99_BUDGET_MS = float(os.environ.get("SERVING_SMOKE_P99_BUDGET_MS", 25))
#: packed (zero-copy) client lever — read early: the default floor
#: keys on it (1 = the native fast path; 0 = the dict control, gated
#: at the old floor)
PACKED = os.environ.get("SERVING_SMOKE_PACKED", "1") != "0"
#: throughput floor, raised for the r19 native fast path (216k was
#: 3x the pre-replica 72k row; the native hot-row table + packed
#: lookups measured ~500k+ here — 350k keeps scheduler-noise headroom
#: while a regression to the GIL-bound hit path trips it)
MIN_LOOKUPS_PER_S = float(os.environ.get(
    "SERVING_SMOKE_MIN_LOOKUPS_PER_S",
    350_000 if PACKED else 216_000))
#: exported by tier1.sh when the up-front native build succeeded: the
#: smoke then FAILS if the serving plane silently fell back to the
#: Python cache (no vacuous green on the native gates)
REQUIRE_NATIVE = os.environ.get(
    "SERVING_REQUIRE_NATIVE_HOTCACHE") == "1"
QUOTA_ROWS = int(os.environ.get("SERVING_SMOKE_QUOTA_ROWS", 8192))
#: keys per client request: the serving frontend shape — a fan-in of
#: point lookups amortized into request batches (the recorded 72k row
#: used the same 256-key batches, so the 3x floor is apples-to-apples)
LOOKUP_BATCH = int(os.environ.get("SERVING_SMOKE_LOOKUP_BATCH", 256))
#: client inter-request pause: models request interarrival AND keeps
#: unthrottled client spin from GIL-starving the single scheduler
#: thread (point-lookup mode is implicitly paced by the coalescer's
#: ride-collection window; explicit batches are not)
CLIENT_PAUSE_MS = float(os.environ.get(
    "SERVING_SMOKE_CLIENT_PAUSE_MS", 5.0 if LOOKUP_BATCH > 1 else 0.0))
#: replica A/B lever: 0 = legacy live-plane path (control-queue
#: coalescers only) — the floor and replica vacuity gates disable
REPLICA = os.environ.get("SERVING_SMOKE_REPLICA", "1") != "0"
#: boundary publishes batched under this interval (staleness bound)
PUBLISH_INTERVAL_MS = int(os.environ.get(
    "SERVING_SMOKE_PUBLISH_INTERVAL_MS", 25))
#: replica staleness p99 budget (ms): a client shape that starves the
#: ingest/publish loop can post huge lookup numbers against a frozen
#: replica — that is a DIFFERENT product. The r19 pause sweep showed
#: exactly this: the GIL-held dict path at 2 ms pause reached 724k/s
#: with staleness p99 2.5 s (rejected), the packed path 1.05M/s at
#: 350 ms (accepted). 0 disables.
STALENESS_BUDGET_MS = float(os.environ.get(
    "SERVING_SMOKE_STALENESS_BUDGET_MS", 1000))
#: per-optimization A/B levers: hot-row
#: cache capacity (0 = every lookup resolves on the replica) and the
#: serving worker-pool size (1 = one drain loop for all shards)
CACHE_ENTRIES = int(os.environ.get(
    "SERVING_SMOKE_CACHE_ENTRIES", 1 << 18))
WORKERS = int(os.environ.get("SERVING_SMOKE_WORKERS", 2))


def _pipeline(sink):
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.core.config import Configuration
    from flink_tpu.datastream.environment import StreamExecutionEnvironment
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    from flink_tpu.tenancy.quotas import TenantQuota

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 4096,
        "parallelism.default": 4,
        # the latency tier composes with the serving plane: deadline
        # splitting bounds each ingest dispatch, so a lookup miss batch
        # queued behind the device never waits out a full-batch program
        "latency.fire-deadline-ms": 25,
        "serving.replica": REPLICA,
        "serving.replica.publish-interval-ms": PUBLISH_INTERVAL_MS,
        # spill tier sized to the quota's per-shard slice (so the quota
        # has somewhere to shed and steady state stays under it)
        "state.slot-table.max-device-slots": TenantQuota(
            max_resident_rows=QUOTA_ROWS).per_shard_slots(4),
    }))
    (env.add_source(
        DataGenSource(total_records=RECORDS, num_keys=KEYS,
                      events_per_second_of_eventtime=50_000, seed=13),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(TumblingEventTimeWindows.of(60_000))
        .sum("value").sink_to(sink))
    return env


def main():
    import warnings

    warnings.filterwarnings("ignore")
    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.metrics.core import quantile_sorted
    from flink_tpu.observe import RecompileSentinel
    from flink_tpu.tenancy.program_cache import PROGRAM_CACHE
    from flink_tpu.tenancy.quotas import TenantQuota
    from flink_tpu.tenancy.session_cluster import SessionCluster

    operator = "window_agg(SumAggregate)"

    def run_with_lookups(cluster, job_names, n_clients):
        """Drive the cluster while client threads hammer lookups;
        returns (elapsed_s, errors, max_generations, staleness_ms[])."""
        stop = threading.Event()
        errors = []
        seen = {"gens": 0}
        staleness = []

        def sampler():
            # replica observability: max generations seen (the jobs
            # unbind their replicas at finish, so read DURING the run)
            # and a staleness reservoir for the p99
            while not stop.is_set():
                g = cluster.serving.replica_generations()
                if g > seen["gens"]:
                    seen["gens"] = g
                staleness.append(
                    cluster.serving.replica_staleness_ms())
                time.sleep(0.01)

        def client(i):
            import numpy as np

            rng = np.random.default_rng(100 + i)
            checked = False
            while not stop.is_set():
                try:
                    job = job_names[i % len(job_names)]
                    if LOOKUP_BATCH > 1 and PACKED and REPLICA:
                        ks = rng.integers(0, KEYS,
                                          LOOKUP_BATCH).tolist()
                        res = cluster.lookup_batch_packed(
                            job, operator, ks)
                        if not checked and i == 0:
                            # materialized cross-check: the packed fast
                            # path must match the dict path (the test
                            # suite pins bit-identity; this catches a
                            # broken wire). A publish can land between
                            # the two calls, so only REPEATED mismatch
                            # counts — one moved boundary does not.
                            for _ in range(5):
                                if res.to_dicts() == \
                                        cluster.lookup_batch(
                                            job, operator, ks):
                                    checked = True
                                    break
                                res = cluster.lookup_batch_packed(
                                    job, operator, ks)
                            else:
                                errors.append(
                                    "packed != dict lookup results")
                                return
                    elif LOOKUP_BATCH > 1:
                        cluster.lookup_batch(
                            job, operator,
                            rng.integers(0, KEYS,
                                         LOOKUP_BATCH).tolist())
                    else:
                        cluster.lookup(job, operator,
                                       int(rng.integers(0, KEYS)))
                except RuntimeError as e:
                    if ("is not serving" in str(e)
                            or "already terminated" in str(e)
                            or "shut down" in str(e)):
                        # clean-shutdown shapes: the plane's unbound-job
                        # error, the executor's terminal control-queue
                        # drain, and the worker-pool shutdown
                        return  # job finished: lookups drain off
                    # any OTHER RuntimeError is a serving-path
                    # regression: swallowing it here would kill every
                    # client early while the gate still printed OK
                    errors.append(f"client {i}: {e!r}")
                    return
                except TimeoutError:
                    errors.append(f"client {i}: lookup timed out")
                    return
                if CLIENT_PAUSE_MS:
                    time.sleep(CLIENT_PAUSE_MS / 1e3)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        threads.append(threading.Thread(target=sampler, daemon=True))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        cluster.run(timeout_s=600)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        return (time.perf_counter() - t0, errors, seen["gens"],
                staleness)

    # ---- phase 1: job-1 warms the cluster — ingest, fire, serving AND
    # replica publish/gather programs all compile here
    warm = SessionCluster(quantum_records=8192,
                          serving_workers=WORKERS,
                          serving_cache_entries=CACHE_ENTRIES)
    warm.submit(_pipeline(CollectSink()), "job-1")
    run_with_lookups(warm, ["job-1"], 2)

    # ---- phase 2: two FRESH jobs on a fresh cluster + lookup load,
    # zero compiles allowed
    PROGRAM_CACHE.reset_stats()
    cluster = SessionCluster(quantum_records=8192,
                             serving_workers=WORKERS,
                             serving_cache_entries=CACHE_ENTRIES)
    s2, s3 = CollectSink(), CollectSink()
    cluster.submit(_pipeline(s2), "job-2",
                   quota=TenantQuota(max_resident_rows=QUOTA_ROWS))
    cluster.submit(_pipeline(s3), "job-3")
    with RecompileSentinel(max_compiles=0,
                           label="second job on warm cluster") as s:
        elapsed, errors, gens, staleness = run_with_lookups(
            cluster, ["job-2", "job-3"], CLIENTS)

    ok = True
    if errors:
        print(f"FAIL: {errors[:3]}")
        ok = False
    from flink_tpu.tenancy.hot_cache import HotRowCache

    native_cache = not isinstance(cluster.serving.hot_cache,
                                  HotRowCache)
    if REQUIRE_NATIVE and not native_cache:
        print("FAIL: native hotcache built but the serving plane fell "
              "back to the Python cache (vacuous native gates)")
        ok = False
    metrics = cluster.serving.metrics()
    lookups = int(metrics["lookups_total"])
    p99 = float(metrics["lookup_p99_ms"])
    hit_rate = float(metrics["hot_row_hit_rate"])
    staleness_p99 = quantile_sorted(sorted(staleness), 0.99) \
        if staleness else 0.0
    lookups_per_s = lookups / elapsed if elapsed > 0 else 0.0
    for job in ("job-2", "job-3"):
        misses = PROGRAM_CACHE.stats_for(job)["misses"]
        if misses:
            print(f"FAIL: {job} paid {misses} program-cache misses on a "
                  "warm cluster (cache key leaking engine/job identity?)")
            ok = False
    if lookups == 0:
        print("FAIL: zero lookups served — vacuous run")
        ok = False
    if p99 > P99_BUDGET_MS:
        print(f"FAIL: lookup p99 {p99:.1f} ms over the "
              f"{P99_BUDGET_MS:.0f} ms budget")
        ok = False
    if REPLICA:
        if STALENESS_BUDGET_MS and staleness_p99 > STALENESS_BUDGET_MS:
            print(f"FAIL: replica staleness p99 {staleness_p99:.0f} ms "
                  f"over the {STALENESS_BUDGET_MS:.0f} ms budget — "
                  "lookups are outrunning a starved publish loop")
            ok = False
        if lookups_per_s < MIN_LOOKUPS_PER_S:
            print(f"FAIL: {lookups_per_s:,.0f} lookups/s under the "
                  f"{MIN_LOOKUPS_PER_S:,.0f} floor (3x the recorded "
                  "pre-replica row)")
            ok = False
        if hit_rate <= 0.0:
            print("FAIL: hot-row cache never served a hit — the "
                  "replica path is vacuously off")
            ok = False
        if gens < 2:
            print(f"FAIL: replica generations advanced only {gens} "
                  "times — boundary publishes are vacuously off")
            ok = False
    viol = cluster.jobs["job-2"].ledger.quota_violations
    if viol:
        print(f"FAIL: {viol} quota violations on job-2")
        ok = False
    for name, sink in (("job-2", s2), ("job-3", s3)):
        if len(sink.result()) == 0:
            print(f"FAIL: {name} produced no output")
            ok = False
    print(json.dumps({
        "metric": "queryable_lookups_per_s",
        "value": round(lookups_per_s, 1),
        "unit": "lookups/s",
        "shape": f"{CLIENTS} client threads x "
                 f"{'point lookups' if LOOKUP_BATCH == 1 else f'{LOOKUP_BATCH}-key request batches'} "
                 f"against 2 concurrent ingesting jobs "
                 f"({RECORDS} records each, mesh of 4) "
                 f"— read-replica serving plane "
                 f"({'armed' if REPLICA else 'DISARMED: legacy live-plane path'}), "
                 f"native hot-row table "
                 f"{'armed' if native_cache else 'OFF (Python cache)'}"
                 f"{', packed zero-copy lookups' if PACKED and REPLICA else ', dict lookups'}: "
                 f"hot-row hit rate {hit_rate:.3f}, "
                 f"replica staleness p99 {staleness_p99:.1f} ms "
                 f"({gens} generations), p99 {p99:.2f} ms, "
                 f"0 steady-state compiles (compiles={s.compiles})",
    }), flush=True)
    print(f"serving smoke: lookups={lookups} "
          f"batches={int(metrics['lookup_batches_total'])} "
          f"p99={p99:.2f}ms lookups/s={lookups_per_s:,.0f} "
          f"hit_rate={hit_rate:.3f} generations={gens} "
          f"staleness_p99={staleness_p99:.1f}ms "
          f"compiles={s.compiles} quota_violations={viol} "
          f"native_cache={native_cache} "
          f"=> {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
