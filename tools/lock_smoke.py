"""Runtime lock-order + contention smoke under the LockSentinel (tier-1).

The runtime complement of the flint concurrency rules (LCK01..LCK03):
install ONE :class:`flink_tpu.observe.LockSentinel` across the hot
multi-threaded surfaces and gate on what it actually observed:

1. **Cluster phase** — a session cluster runs TWO jobs while client
   threads hammer batched queryable-state lookups (the serving plane's
   coalescer/worker/cache locks all see cross-thread traffic). When the
   native hot cache is available the same cluster arms the shm serving
   tier and a 2-process :class:`FrontendPool` serves part of the load
   (the ``frontend.pipe`` dispatch locks join the graph); otherwise the
   frontend leg is LOUDLY skipped — the cluster gates still run.
2. **Backend churn phase** — threads race :func:`backend_scope` /
   :func:`set_backend` / :func:`backend_of` on the state-plane backend
   registry (the regression surface of the r24 thread-safety fix).
3. **Program-cache churn phase** — threads race ``get_or_build`` on a
   fresh :class:`SharedProgramCache` (same ``tenancy.program_cache``
   lock name): the once-latch protocol's release boundaries — the ones
   LCK03 suppresses by design argument — run under the sentinel.

The run FAILS on:

- ANY observed lock-order cycle (``sentinel.check`` — a cycle raised in
  a daemon thread is still recorded and still fails here),
- any single hold over ``LOCK_SMOKE_HOLD_BUDGET_S`` (default 2 s — a
  lock held across a compile or device call, not scheduler noise),
- fewer than 2 DISTINCT locks actually contended (vacuity: on the
  1-core box the phases above must produce real cross-thread traffic,
  or the whole order graph is an artifact of one thread),
- any expected lock family with zero acquisitions (unguarded-hit
  regression: a hot class quietly reverting ``named_lock`` to the bare
  primitive disappears from the sentinel — this gate notices),
- any client error or empty job output (the load must be real).

    JAX_PLATFORMS=cpu python tools/lock_smoke.py
    LOCK_SMOKE_RECORDS=... LOCK_SMOKE_CLIENTS=... to scale.
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

RECORDS = int(os.environ.get("LOCK_SMOKE_RECORDS", 40_000))
CLIENTS = int(os.environ.get("LOCK_SMOKE_CLIENTS", 8))
KEYS = int(os.environ.get("LOCK_SMOKE_KEYS", 2048))
LOOKUP_BATCH = int(os.environ.get("LOCK_SMOKE_LOOKUP_BATCH", 128))
FRONTENDS = int(os.environ.get("LOCK_SMOKE_FRONTENDS", 2))
HOLD_BUDGET_S = float(os.environ.get("LOCK_SMOKE_HOLD_BUDGET_S", 2.0))
CHURN_THREADS = int(os.environ.get("LOCK_SMOKE_CHURN_THREADS", 4))
CHURN_ITERS = int(os.environ.get("LOCK_SMOKE_CHURN_ITERS", 400))

#: locks EXEMPT from the hold budget: 'frontend.pipe' serializes one
#: owner-side dispatcher onto a frontend's bounded request pipe — it
#: holds across a blocking IPC round trip BY DESIGN (one in-flight
#: request per frontend), so wall-clock holds there measure the
#: frontend's service time, not a forgotten critical section
HOLD_BUDGET_EXEMPT = frozenset({"frontend.pipe"})

#: lock families that MUST appear in the sentinel's accounting — each
#: tuple is alternatives (e.g. the cache plane is either the Python
#: LRU's lock or the native writer lock, depending on the build)
EXPECTED_LOCK_FAMILIES = [
    ("stateplane.backends",),
    ("tenancy.program_cache",),
    ("tenancy.hot_rows", "tenancy.native_cache"),
    ("serving.coalescer", "serving.worker", "serving.workers",
     "serving.pool"),
]


def _pipeline(sink):
    from flink_tpu.connectors.sinks import CollectSink  # noqa: F401
    from flink_tpu.connectors.sources import DataGenSource
    from flink_tpu.core.config import Configuration
    from flink_tpu.datastream.environment import StreamExecutionEnvironment
    from flink_tpu.runtime.watermarks import WatermarkStrategy
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    env = StreamExecutionEnvironment(Configuration({
        "execution.micro-batch.size": 4096,
        "parallelism.default": 4,
        "serving.replica": True,
        "serving.replica.publish-interval-ms": 25,
    }))
    (env.add_source(
        DataGenSource(total_records=RECORDS, num_keys=KEYS,
                      events_per_second_of_eventtime=50_000, seed=13),
        WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key")
        .window(TumblingEventTimeWindows.of(60_000))
        .sum("value").sink_to(sink))
    return env


def cluster_phase(sentinel, tmp, frontend_armed):
    """Two jobs + concurrent lookup clients (+ frontend pool when the
    native shm cache exists). Returns (errors, sink_rows, fe_live)."""
    import warnings

    warnings.filterwarnings("ignore")
    import numpy as np

    from flink_tpu.connectors.sinks import CollectSink
    from flink_tpu.tenancy.session_cluster import SessionCluster

    operator = "window_agg(SumAggregate)"
    cluster = SessionCluster(
        quantum_records=8192, serving_workers=2,
        serving_shm_dir=(os.path.join(tmp, "serving-shm")
                         if frontend_armed else None))
    s1, s2 = CollectSink(), CollectSink()
    cluster.submit(_pipeline(s1), "job-1")
    cluster.submit(_pipeline(s2), "job-2")
    pool = None
    if frontend_armed:
        from flink_tpu.tenancy.frontend import FrontendPool

        pool = FrontendPool(cluster.serving, n_frontends=FRONTENDS)

    stop = threading.Event()
    errors = []

    def client(i):
        rng = np.random.default_rng(300 + i)
        while not stop.is_set():
            job = "job-1" if i % 2 == 0 else "job-2"
            ks = rng.integers(0, KEYS, LOOKUP_BATCH).tolist()
            try:
                # odd clients route through the frontend pool when it
                # exists (the pipe-dispatch locks join the graph)
                if pool is not None and i % 2 == 1:
                    pool.lookup_batch(job, operator, ks)
                else:
                    cluster.lookup_batch(job, operator, ks)
            except (RuntimeError, TimeoutError) as e:
                msg = str(e)
                if ("is not serving" in msg
                        or "already terminated" in msg
                        or "shut down" in msg
                        or "FrontendPool is closed" in msg):
                    return  # job finished: lookups drain off
                errors.append(f"client {i}: {e!r}")
                return
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    fe_live = None
    try:
        cluster.run(timeout_s=600)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        if pool is not None:
            fe_live = len(pool.live_frontends())
            pool.close()
            cluster.serving.hot_cache.close()
    return errors, len(s1.result()) + len(s2.result()), fe_live


def backend_churn_phase():
    """Threads race scope/set/read on the backend registry; the module
    lock ('stateplane.backends') must come out contended and the final
    state must be the default (no override leaked by a lost restore
    race the r24 compare-and-restore fix removed)."""
    from flink_tpu.stateplane.backends import (
        backend_of,
        backend_scope,
        set_backend,
    )

    errors = []

    def churn(i):
        try:
            for _ in range(CHURN_ITERS):
                if i % 2 == 0:
                    with backend_scope("exchange-rank", "pallas"):
                        backend_of("exchange-rank")
                else:
                    set_backend("exchange-rank", "pallas")
                    backend_of("exchange-rank")
                    set_backend("exchange-rank", "xla")
        except Exception as e:  # noqa: BLE001 - surfaced as a gate
            errors.append(f"backend churn {i}: {e!r}")

    _run_churn(churn, errors)
    set_backend("exchange-rank", "xla")  # deterministic end state
    return errors


def _run_churn(fn, errors):
    """Run ``fn(i)`` on CHURN_THREADS threads under a tiny GIL switch
    interval: the default 5 ms quantum lets a microsecond critical
    section finish unpreempted, so the contention the 1-core box CAN
    produce never shows — shrinking the quantum makes the interleaving
    real instead of making the gate vacuous."""
    prev = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    try:
        threads = [threading.Thread(target=fn, args=(i,), daemon=True)
                   for i in range(CHURN_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)
    return errors


def program_cache_churn_phase():
    """Threads race get_or_build on a fresh cache instance: the
    once-latch protocol (one builder per key, waiters re-probe) runs
    under the sentinel — same 'tenancy.program_cache' lock name."""
    from flink_tpu.tenancy.program_cache import SharedProgramCache

    cache = SharedProgramCache()
    errors = []
    built = {"n": 0}
    built_mu = threading.Lock()

    def builder_for(key):
        def build():
            time.sleep(0.001)  # a build long enough for waiters to park
            with built_mu:
                built["n"] += 1
            return ("program", key)
        return build

    def churn(i):
        try:
            for k in range(CHURN_ITERS // 4):
                got = cache.get_or_build("smoke", k, builder_for(k))
                if got != ("program", k):
                    errors.append(f"cache churn {i}: wrong value {got!r}")
                    return
        except Exception as e:  # noqa: BLE001 - surfaced as a gate
            errors.append(f"cache churn {i}: {e!r}")

    _run_churn(churn, errors)
    if built["n"] != CHURN_ITERS // 4 and not errors:
        errors.append(
            f"once-latch broke: {built['n']} builds for "
            f"{CHURN_ITERS // 4} keys (duplicate or lost builds)")
    return errors


def main():
    import tempfile

    from flink_tpu.native import hotcache_available
    from flink_tpu.observe import LockOrderViolation, LockSentinel

    frontend_armed = hotcache_available()
    if not frontend_armed:
        print("LOCK SMOKE: native hotcache unavailable — frontend-pool "
              "leg SKIPPED (cluster/backend/cache gates still run)")

    sentinel = LockSentinel()
    with tempfile.TemporaryDirectory(prefix="lock_smoke_") as tmp:
        with sentinel:
            errors, rows, fe_live = cluster_phase(
                sentinel, tmp, frontend_armed)
            errors += backend_churn_phase()
            errors += program_cache_churn_phase()

    ok = True
    if errors:
        print(f"FAIL: {errors[:3]}")
        ok = False
    if rows == 0:
        print("FAIL: jobs produced no output — vacuous run")
        ok = False
    if frontend_armed and fe_live == 0:
        print("FAIL: every frontend died during the run")
        ok = False

    # gate 1: no observed order cycle
    try:
        sentinel.check()
    except LockOrderViolation as e:
        print(f"FAIL: {e}")
        ok = False

    rep = sentinel.report()
    locks = rep["locks"]

    # gate 1b: hold budget, minus the documented IPC-wait exemption
    over = sorted((n, st["hold_max_s"]) for n, st in locks.items()
                  if st["hold_max_s"] > HOLD_BUDGET_S
                  and n not in HOLD_BUDGET_EXEMPT)
    if over:
        print(f"FAIL: lock hold budget {HOLD_BUDGET_S:.3f}s exceeded: "
              f"{over}")
        ok = False

    # gate 2 (vacuity): >= 2 DISTINCT locks really contended — the
    # order graph of an uncontended run proves nothing
    contended = sentinel.contended_locks()
    if len(contended) < 2:
        print(f"FAIL: only {contended} contended — the smoke load is "
              "vacuous (no real cross-thread lock traffic)")
        ok = False

    # gate 3 (unguarded-hit regression): every expected family must
    # have been acquired through its NamedLock at least once
    for family in EXPECTED_LOCK_FAMILIES:
        hits = sum(locks.get(n, {}).get("acquisitions", 0)
                   for n in family)
        if hits == 0:
            print(f"FAIL: no acquisitions observed for any of "
                  f"{family} — a hot class reverted named_lock to the "
                  "bare primitive (unguarded-hit regression)")
            ok = False
    if frontend_armed:
        if locks.get("frontend.pipe", {}).get("acquisitions", 0) == 0:
            print("FAIL: frontend pool armed but 'frontend.pipe' never "
                  "acquired — the dispatch path went unobserved")
            ok = False

    print(json.dumps({
        "locks_observed": len(locks),
        "edges": len(rep["edges"]),
        "cycles": len(rep["cycles"]),
        "contended": contended,
        "hold_max_s": max((st["hold_max_s"] for st in locks.values()),
                          default=0.0),
        "frontend_armed": frontend_armed,
    }), flush=True)
    print(f"lock smoke: locks={len(locks)} edges={len(rep['edges'])} "
          f"cycles={len(rep['cycles'])} contended={len(contended)} "
          f"frontend={'armed' if frontend_armed else 'SKIPPED'} "
          f"=> {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
