"""LockSentinel + named_lock (flink_tpu/observe/lock_sentinel).

Covers: the acquisition-order graph (cycle raised AND recorded, clean
orders pass), reentrant re-acquisition recording no edge, the same-name
two-instance nesting hazard, hold-budget and contention accounting, the
no-sentinel fast path, and one sentinel over real lock traffic: a
two-job cluster under lookup load and a program-cache build race."""

import sys
import threading
import time

import pytest

from flink_tpu.observe.lock_sentinel import (
    LockOrderViolation,
    LockSentinel,
    NamedLock,
    current_sentinel,
    named_lock,
)


class TestNamedLock:
    def test_factory_returns_wrapper_with_name(self):
        lk = named_lock("t.basic")
        assert isinstance(lk, NamedLock)
        assert lk.name == "t.basic"
        assert not lk.reentrant

    def test_plain_lock_semantics_without_sentinel(self):
        assert current_sentinel() is None
        lk = named_lock("t.plain")
        with lk:
            assert lk.locked()
            assert not lk.acquire(blocking=False)
        assert not lk.locked()

    def test_reentrant_without_sentinel(self):
        lk = named_lock("t.re0", reentrant=True)
        with lk:
            with lk:
                assert lk.locked()
        assert not lk.locked()


class TestLockSentinel:
    def test_cycle_raises_and_is_recorded(self):
        a, b = named_lock("t.a"), named_lock("t.b")
        s = LockSentinel()
        with s:
            with a:
                with b:
                    pass
            with b:
                with pytest.raises(LockOrderViolation,
                                   match="lock order cycle"):
                    with a:
                        pass
        assert len(s.cycles) == 1
        assert set(s.cycles[0][0]) == {"t.a", "t.b"}
        with pytest.raises(LockOrderViolation):
            s.check()

    def test_consistent_order_is_clean(self):
        a, b = named_lock("t.c"), named_lock("t.d")
        s = LockSentinel()
        with s:
            for _ in range(3):
                with a:
                    with b:
                        pass
        s.check()
        assert s.cycles == []
        assert s.edges == {"t.c": {"t.d"}}

    def test_reentrant_reacquire_records_no_edge(self):
        lk = named_lock("t.re", reentrant=True)
        s = LockSentinel()
        with s:
            with lk:
                with lk:
                    pass
        s.check()
        assert s.edges == {}
        assert s.stats["t.re"].acquisitions == 1  # one real acquire

    def test_same_name_two_instances_nested_is_a_cycle(self):
        # two objects, one name: undefined intra-name order — the ABBA
        # hazard the 'staggered, never nested' discipline prevents
        l1, l2 = named_lock("t.same"), named_lock("t.same")
        s = LockSentinel()
        with s:
            with l1:
                with pytest.raises(LockOrderViolation,
                                   match="two instances"):
                    with l2:
                        pass
        assert s.cycles

    def test_hold_budget(self):
        lk = named_lock("t.hold")
        s = LockSentinel()
        with s:
            with lk:
                time.sleep(0.05)
        s.check()  # no budget: clean
        with pytest.raises(LockOrderViolation, match="hold budget"):
            s.check(hold_budget_s=0.01)
        s.check(hold_budget_s=10.0)

    def test_contention_is_counted(self):
        lk = named_lock("t.cont")
        s = LockSentinel()
        entered = threading.Event()

        def taker():
            entered.wait(5)
            with lk:
                pass

        with s:
            t = threading.Thread(target=taker, daemon=True)
            t.start()
            with lk:
                entered.set()
                time.sleep(0.05)  # taker parks on the held lock
            t.join(5)
        assert s.stats["t.cont"].acquisitions == 2
        assert s.stats["t.cont"].contended >= 1
        assert s.contended_locks() == ["t.cont"]
        assert s.stats["t.cont"].wait_s > 0

    def test_report_shape(self):
        a, b = named_lock("t.r1"), named_lock("t.r2")
        s = LockSentinel()
        with s:
            with a:
                with b:
                    pass
        rep = s.report()
        assert set(rep["locks"]) == {"t.r1", "t.r2"}
        assert rep["locks"]["t.r1"]["acquisitions"] == 1
        assert rep["cycles"] == []
        assert len(rep["edges"]) == 1
        assert rep["edges"][0][:2] == ["t.r1", "t.r2"]
        assert "t.r1@" in rep["edges"][0][2]  # witness carries the site

    def test_second_install_rejected_and_uninstall_clears(self):
        s1, s2 = LockSentinel(), LockSentinel()
        with s1:
            assert current_sentinel() is s1
            with pytest.raises(RuntimeError, match="already installed"):
                s2.install()
        assert current_sentinel() is None
        with s2:
            assert current_sentinel() is s2



# ------------------------------------------------- under real lock traffic


def _churn(fn, threads=4):
    """Run ``fn(i)`` on a few threads under a tiny GIL switch interval:
    the default 5 ms quantum lets a microsecond critical section finish
    unpreempted, so contention a small box can produce never shows."""
    prev = sys.getswitchinterval()
    sys.setswitchinterval(5e-5)
    try:
        ts = [threading.Thread(target=fn, args=(i,), daemon=True)
              for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(prev)


class TestSentinelUnderLoad:
    """ONE sentinel observes the hot multi-threaded surfaces — the
    runtime complement of flint's LCK rules."""

    #: lock families that must show acquisitions: each tuple lists
    #: alternatives (the cache plane is the Python LRU's lock or the
    #: native writer lock, depending on the build)
    EXPECTED_FAMILIES = [
        ("tenancy.program_cache",),
        ("tenancy.hot_rows", "tenancy.native_cache"),
        ("serving.coalescer", "serving.worker", "serving.workers",
         "serving.pool"),
    ]

    def test_two_job_cluster_with_lookup_clients(self, tmp_path):
        """Two jobs on a session cluster while client threads hammer
        batched lookups (and, with the native hot cache, a two-process
        frontend pool serves half of them): no lock-order cycle, locks
        really contended, and every expected family acquired through
        its NamedLock (a hot class reverting to a bare primitive would
        vanish from the accounting)."""
        import numpy as np

        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.native import hotcache_available
        from flink_tpu.runtime.watermarks import WatermarkStrategy
        from flink_tpu.tenancy.session_cluster import SessionCluster
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        keys, operator = 2048, "window_agg(SumAggregate)"

        def pipeline(sink):
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 4096,
                "parallelism.default": 4,
                "serving.replica": True,
                "serving.replica.publish-interval-ms": 25,
            }))
            (env.add_source(
                DataGenSource(total_records=40_000, num_keys=keys,
                              events_per_second_of_eventtime=50_000,
                              seed=13),
                WatermarkStrategy.for_bounded_out_of_orderness(0))
                .key_by("key")
                .window(TumblingEventTimeWindows.of(60_000))
                .sum("value").sink_to(sink))
            return env

        frontends = hotcache_available()
        sentinel = LockSentinel()
        errors = []
        stop = threading.Event()
        with sentinel:
            cluster = SessionCluster(
                quantum_records=8192, serving_workers=2,
                serving_shm_dir=(str(tmp_path / "shm") if frontends
                                 else None))
            s1, s2 = CollectSink(), CollectSink()
            cluster.submit(pipeline(s1), "job-1")
            cluster.submit(pipeline(s2), "job-2")
            pool = None
            if frontends:
                from flink_tpu.tenancy.frontend import FrontendPool

                pool = FrontendPool(cluster.serving, n_frontends=2)
                pool.wait_ready()

            def client(i):
                rng = np.random.default_rng(300 + i)
                job = "job-1" if i % 2 == 0 else "job-2"
                while not stop.is_set():
                    ks = rng.integers(0, keys, 128).tolist()
                    try:
                        if pool is not None and i % 2 == 1:
                            pool.lookup_batch(job, operator, ks)
                        else:
                            cluster.lookup_batch(job, operator, ks)
                    except (RuntimeError, TimeoutError) as e:
                        if not any(m in str(e) for m in (
                                "is not serving", "already terminated",
                                "shut down")):
                            errors.append(f"client {i}: {e!r}")
                        return
                    time.sleep(0.002)

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True) for i in range(8)]
            for t in threads:
                t.start()
            try:
                cluster.run(timeout_s=600)
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=10)
                if pool is not None:
                    live = len(pool.live_frontends())
                    pool.close()
                    cluster.serving.hot_cache.close()
        assert errors == []
        assert len(s1.result()) + len(s2.result()) > 0
        sentinel.check()  # no lock-order cycle was observed
        locks = sentinel.report()["locks"]
        # vacuity: the order graph of an uncontended run proves nothing
        assert len(sentinel.contended_locks()) >= (2 if frontends else 1)
        for family in self.EXPECTED_FAMILIES:
            assert sum(locks.get(n, {}).get("acquisitions", 0)
                       for n in family) > 0, family
        if pool is not None:
            assert live == 2
            assert locks.get("frontend.pipe", {}).get(
                "acquisitions", 0) > 0

    def test_program_cache_once_latch_under_churn(self):
        """Threads race ``get_or_build`` on a fresh cache: exactly one
        build per key, every caller gets that key's program, and the
        once-latch's release boundaries run under the sentinel (through
        the cache's NamedLock) without an order cycle."""
        from flink_tpu.tenancy.program_cache import SharedProgramCache

        cache = SharedProgramCache()
        n_keys = 100
        built = {"n": 0}
        built_mu = threading.Lock()
        errors = []

        def builder_for(key):
            def build():
                time.sleep(0.001)  # long enough for waiters to park
                with built_mu:
                    built["n"] += 1
                return ("program", key)
            return build

        def churn(i):
            try:
                for k in range(n_keys):
                    got = cache.get_or_build("churn", k, builder_for(k))
                    if got != ("program", k):
                        errors.append(f"thread {i}: {got!r} for {k}")
                        return
            except Exception as e:  # noqa: BLE001 - reported below
                errors.append(f"thread {i}: {e!r}")

        sentinel = LockSentinel()
        with sentinel:
            _churn(churn)
        assert errors == []
        assert built["n"] == n_keys
        sentinel.check()
        locks = sentinel.report()["locks"]
        assert locks["tenancy.program_cache"]["acquisitions"] > 0
