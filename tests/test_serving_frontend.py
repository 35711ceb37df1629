"""Multi-process serving tier (flink_tpu/tenancy/frontend.py + the shm
arena in native/hotcache.cpp): shared-memory attach semantics, the
frontend process pool's hit/miss/failover paths, cross-process seqlock
safety under a live writer, and DCN-aware lookup routing.

The contracts under test:

- an ATTACHED mapping is read-only BY ROLE: every table-write entry
  point refuses on an attached handle, and the owner's epoch word lets
  a frontend detect an owner restart and re-attach;
- frontend results are BIT-IDENTICAL to the owner's own lookup path
  (same tables, same miss resolution) — including across a frontend
  death mid-burst, which fails over to a live sibling;
- the seqlock read protocol holds ACROSS PROCESSES: reader processes
  probing while the owner mutates continuously never surface a torn
  row — every hit matches the deterministic value scheme of exactly
  one generation (verified against a dict-oracle formula, not
  wall-clock luck);
- lookup routing follows ``host_of_key_group`` under the LIVE
  key-group assignment, reassembling results in input order.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from flink_tpu.native import hotcache_available

native = pytest.mark.skipif(not hotcache_available(),
                            reason="native hotcache unavailable")

JOB, OP = "job-a", "window_agg"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shm_cache(tmp, max_entries=1 << 12):
    from flink_tpu.tenancy.hot_cache import make_hot_row_cache

    return make_hot_row_cache(max_entries=max_entries,
                              shm_dir=os.path.join(tmp, "shm"))


def _prime(cache, n=64, gen=1):
    keys = list(range(n))
    vals = [{0: {"count": float(k), "sum": float(k * 2 + gen)}}
            for k in keys]
    cache.put_many(JOB, OP, keys, gen, vals)
    return keys, vals


class _StubPlane:
    """The minimal owner the pool needs: a shm-backed hot cache plus a
    miss resolver standing in for the replica path (deterministic, so
    parity is assertable without a device mesh)."""

    def __init__(self, cache):
        self.hot_cache = cache
        self.miss_calls = []

    def lookup_batch(self, job, op, keys):
        self.miss_calls.append(list(keys))
        return [{"cold": float(k)} for k in keys]


# ------------------------------------------------------------ shm arena


@native
class TestShmArena:
    def test_frontend_client_bit_identical_to_owner_probe(self):
        from flink_tpu.tenancy.hot_cache_native import (
            FrontendCacheClient,
        )

        with tempfile.TemporaryDirectory() as tmp:
            cache = _shm_cache(tmp)
            keys, vals = _prime(cache)
            client = FrontendCacheClient(cache.shm_dir, frontend_id=0)
            try:
                hits, probe, misses = client.probe(
                    JOB, OP, np.asarray(keys, dtype=np.int64))
                assert hits == len(keys) and misses == []
                got = [probe.materialize(i) for i in range(len(keys))]
                # the owner's own probe, for bit-identity
                out = [None] * len(keys)
                m = []
                cache.get_many(JOB, OP, keys, 1, out, m, exact=False)
                assert got == out == vals
            finally:
                client.close()
                cache.close()

    def test_attached_handle_refuses_writes(self):
        from flink_tpu.native import load_hotcache

        with tempfile.TemporaryDirectory() as tmp:
            cache = _shm_cache(tmp)
            keys, vals = _prime(cache)
            lib = load_hotcache()
            tbl = cache._tables[(JOB, OP)]
            h = lib.hc_attach(tbl.shm_path.encode())
            assert h
            try:
                assert lib.hc_is_attached(h) == 1
                assert lib.hc_epoch(h) == tbl.epoch
                before = lib.hc_len(h)
                # every write entry point refuses by role (returns the
                # no-op value, mutates nothing)
                k = np.asarray([999], dtype=np.int64)
                g = np.asarray([5], dtype=np.int64)
                off = np.asarray([0, 1], dtype=np.int64)
                ns = np.asarray([0], dtype=np.int64)
                va = np.asarray([7], dtype=np.int64)
                tg = np.asarray([0], dtype=np.uint64)
                from flink_tpu.tenancy.hot_cache_native import (
                    _ptr_i64,
                    _u64p,
                )

                wrote = lib.hc_put_batch(
                    h, 1, _ptr_i64(k), _ptr_i64(g), _ptr_i64(off),
                    _ptr_i64(ns), _ptr_i64(va),
                    tg.ctypes.data_as(_u64p))
                assert wrote == 0
                assert lib.hc_len(h) == before
                lib.hc_clear(h)
                assert lib.hc_len(h) == before  # refused too
            finally:
                lib.hc_destroy(h)
                cache.close()

    def test_owner_restart_epoch_detected_and_reattached(self):
        from flink_tpu.tenancy.hot_cache_native import (
            FrontendCacheClient,
        )

        with tempfile.TemporaryDirectory() as tmp:
            cache = _shm_cache(tmp)
            _prime(cache, gen=1)
            client = FrontendCacheClient(cache.shm_dir, frontend_id=0)
            try:
                hits, probe, _ = client.probe(
                    JOB, OP, np.asarray([3], dtype=np.int64))
                assert hits == 1
                assert probe.materialize(0)[0]["sum"] == 7.0  # 3*2+1
                shm_dir = cache.shm_dir
                cache.close()  # owner "dies": manifest + arenas unlink

                from flink_tpu.tenancy.hot_cache import (
                    make_hot_row_cache,
                )

                cache = make_hot_row_cache(max_entries=1 << 12,
                                           shm_dir=shm_dir)
                _prime(cache, gen=2)  # restarted owner, NEW epoch
                hits, probe, _ = client.probe(
                    JOB, OP, np.asarray([3], dtype=np.int64))
                assert hits == 1
                # the client followed the manifest to the new arena:
                # it serves the restarted owner's values, not ghosts
                assert probe.materialize(0)[0]["sum"] == 8.0  # 3*2+2
            finally:
                client.close()
                cache.close()

    def test_manifest_lists_tables_and_cleans_up(self):
        from flink_tpu.tenancy.hot_cache_native import MANIFEST_NAME

        with tempfile.TemporaryDirectory() as tmp:
            cache = _shm_cache(tmp)
            _prime(cache)
            man = os.path.join(cache.shm_dir, MANIFEST_NAME)
            with open(man) as f:
                doc = json.load(f)
            rows = [r for r in doc["tables"]
                    if r["job"] == JOB and r["operator"] == OP]
            assert len(rows) == 1
            assert os.path.exists(rows[0]["path"])
            assert rows[0]["epoch"] != 0
            cache.close()
            assert not os.path.exists(man)
            assert not os.path.exists(rows[0]["path"])

    def test_shm_dir_without_native_plane_raises(self, monkeypatch):
        from flink_tpu.tenancy.hot_cache import make_hot_row_cache

        monkeypatch.setenv("FLINK_TPU_NO_NATIVE", "1")
        with tempfile.TemporaryDirectory() as tmp:
            with pytest.raises(RuntimeError, match="shm_dir"):
                make_hot_row_cache(shm_dir=os.path.join(tmp, "shm"))


# -------------------------------------------------------- frontend pool


@native
class TestFrontendPool:
    def _pool(self, tmp, n=2):
        from flink_tpu.tenancy.frontend import FrontendPool

        cache = _shm_cache(tmp)
        plane = _StubPlane(cache)
        return FrontendPool(plane, n_frontends=n), plane, cache

    def test_hit_path_and_miss_crossing_bit_identical(self):
        with tempfile.TemporaryDirectory() as tmp:
            pool, plane, cache = self._pool(tmp)
            try:
                keys, vals = _prime(cache)
                # all-hit: answered in the frontend, no owner crossing
                out = pool.lookup_batch(JOB, OP, [3, 7, 11])
                assert out == [vals[3], vals[7], vals[11]]
                assert plane.miss_calls == []
                # mixed: misses cross once, merged in INPUT order
                out = pool.lookup_batch(JOB, OP,
                                        [1, 900, 2, 901, 3])
                assert out == [vals[1], {"cold": 900.0}, vals[2],
                               {"cold": 901.0}, vals[3]]
                assert plane.miss_calls == [[900, 901]]
                rows = cache.fe_stats(pool.n_frontends)
                tot = {k: sum(r[k] for r in rows) for k in rows[0]}
                assert tot["probes"] == 8 and tot["hits"] == 6
                assert tot["miss_crossings"] == 2
            finally:
                pool.close()
                cache.close()

    def test_dead_frontend_fails_over_to_sibling(self):
        with tempfile.TemporaryDirectory() as tmp:
            pool, plane, cache = self._pool(tmp)
            try:
                keys, vals = _prime(cache)
                pool._kill(pool._frontends[0])
                # pinned at the dead frontend: the request fails over
                out = pool.lookup_batch(JOB, OP, [8, 9], frontend=0)
                assert out == [vals[8], vals[9]]
                assert pool.failovers == 1
                assert pool.live_frontends() == [1]
                # owner and sibling unharmed: metrics + further lookups
                m = pool.metrics()
                assert m["frontends_live"] == 1.0
                assert pool.lookup_batch(JOB, OP, [5]) == [vals[5]]
            finally:
                pool.close()
                cache.close()

    def test_all_frontends_dead_fails_fast(self):
        with tempfile.TemporaryDirectory() as tmp:
            pool, plane, cache = self._pool(tmp)
            try:
                _prime(cache)
                for fe in pool._frontends:
                    pool._kill(fe)
                with pytest.raises(RuntimeError,
                                   match="no live frontend"):
                    pool.lookup_batch(JOB, OP, [1])
            finally:
                pool.close()
                cache.close()

    def test_pool_requires_shm_plane(self):
        from flink_tpu.tenancy.frontend import FrontendPool
        from flink_tpu.tenancy.hot_cache import HotRowCache

        with pytest.raises(RuntimeError, match="shm"):
            FrontendPool(_StubPlane(HotRowCache()), n_frontends=1)

    def test_drive_loop_reports_real_counters(self):
        with tempfile.TemporaryDirectory() as tmp:
            pool, plane, cache = self._pool(tmp, n=2)
            try:
                keys, _ = _prime(cache, n=128)
                res = pool.drive(JOB, OP, keys, batch=32, batches=20)
                assert len(res) == 2
                for r in res:
                    assert r["probes"] == 32 * 20
                    assert r["hits"] == r["probes"]  # pre-primed
                    assert r["wall_s"] > 0.0
                rows = cache.fe_stats(2)
                # the drive probes are REAL shm-header counters
                assert all(r["probes"] >= 32 * 20 for r in rows)
            finally:
                pool.close()
                cache.close()


# --------------------------------------- cross-process seqlock fuzzing

# Reader process body: attach, probe continuously, verify EVERY hit
# against the generation-deterministic value scheme v == g * 1e6 + key
# (both columns written under ONE seqlock stamp cycle — a torn read
# would surface as an inconsistent (g, v) pair). Reports JSON.
_READER_SRC = r"""
import json, os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from flink_tpu.tenancy.hot_cache_native import FrontendCacheClient

shm_dir, fe_id, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
client = FrontendCacheClient(shm_dir, frontend_id=fe_id)
keys = np.arange(64, dtype=np.int64)
probes = hits = bad = 0
gens = set()
deadline = time.monotonic() + seconds
# under heavy box load the probe window can land after the writer's
# first generations — extend (bounded) until live mutation was seen
hard = deadline + 20.0
while (time.monotonic() < deadline
       or (len(gens) < 2 and time.monotonic() < hard)):
    n, probe, misses = client.probe("job-a", "window_agg", keys)
    probes += len(keys)
    hits += n
    if probe is None:
        continue
    for i in range(len(keys)):
        if not probe.hit[i]:
            continue
        row = probe.materialize(i)[0]
        g, v = row["g"], row["v"]
        gens.add(g)
        if v != g * 1_000_000.0 + float(keys[i]):
            bad += 1
client.close()
print(json.dumps({"probes": probes, "hits": hits, "bad": bad,
                  "gens": sorted(gens)}))
"""


@native
class TestCrossProcessSeqlock:
    def test_readers_never_see_torn_rows_under_live_writer(self):
        """Owner mutates CONTINUOUSLY (put_batch through the put_many
        wrapper — full-row rewrites under the seqlock) while two
        reader processes probe the same arena over shm. Zero torn
        reads: every hit's (g, v) pair satisfies the oracle formula of
        exactly one generation, and the readers observe MULTIPLE
        generations (the writer really was live under them)."""
        with tempfile.TemporaryDirectory() as tmp:
            cache = _shm_cache(tmp)
            try:
                keys = list(range(64))

                def write_gen(gen):
                    cache.put_many(
                        JOB, OP, keys, gen,
                        [{0: {"g": float(gen),
                              "v": gen * 1_000_000.0 + float(k)}}
                         for k in keys])

                write_gen(1)  # manifest + first rows exist up front
                env = dict(os.environ)
                env["PYTHONPATH"] = (
                    REPO + os.pathsep + env.get("PYTHONPATH", ""))
                env.setdefault("JAX_PLATFORMS", "cpu")
                seconds = 2.0
                readers = [
                    subprocess.Popen(
                        [sys.executable, "-c", _READER_SRC,
                         cache.shm_dir, str(fe), str(seconds)],
                        stdout=subprocess.PIPE,
                        stderr=subprocess.PIPE, env=env, text=True)
                    for fe in (1, 2)]
                # keep writing generations while the readers run —
                # bounded only as a hang backstop: on a loaded box the
                # readers' interpreter boot alone can outlast a tight
                # wall-clock budget, and a writer that stops early
                # turns the multi-generation guard into a flake
                gen = 1
                deadline = time.monotonic() + 60.0
                while (any(r.poll() is None for r in readers)
                       and time.monotonic() < deadline):
                    gen += 1
                    write_gen(gen)
                reports = []
                for r in readers:
                    out, err = r.communicate(timeout=30)
                    assert r.returncode == 0, err
                    reports.append(json.loads(out))
                for rep in reports:
                    assert rep["bad"] == 0, rep
                    assert rep["hits"] > 0, rep
                assert gen > 2  # the writer really wrote under them
                # at least one reader saw >1 generation: the probes
                # genuinely overlapped live mutation
                assert any(len(rep["gens"]) > 1 for rep in reports), \
                    (gen, reports)
                # torn RETRIES may legitimately occur; torn RESULTS
                # may not — and the retries are attributed per reader
                rows = cache.fe_stats(3)
                assert rows[1]["probes"] > 0 and rows[2]["probes"] > 0
            finally:
                cache.close()


# ------------------------------------------------------------- routing


class TestLookupRouter:
    def _router(self, fns=None, assignment=None):
        from flink_tpu.tenancy.frontend import LookupRouter

        return LookupRouter(
            num_hosts=4, local_devices=2, max_parallelism=128,
            local_host=0,
            lookup_fns=fns if fns is not None else {
                h: (lambda job, op, ks, h=h:
                    [{"host": h, "key": int(k)} for k in ks])
                for h in range(4)},
            assignment=assignment)

    def test_routes_by_owning_host_and_reassembles_in_order(self):
        r = self._router()
        keys = list(range(64))
        hosts = r.plan(keys)
        assert len(set(hosts.tolist())) > 1  # really fans out
        out = r.lookup_batch(JOB, OP, keys)
        for i, k in enumerate(keys):
            assert out[i] == {"host": int(hosts[i]), "key": k}
        m = r.metrics()
        assert m["router_local_keys"] + m["router_remote_keys"] == 64

    def test_follows_live_assignment(self):
        from flink_tpu.state.keygroups import KeyGroupAssignment

        # every group pinned to shard 7 -> host 7 // 2 == 3
        asg = KeyGroupAssignment(0, 8,
                                 np.full(128, 7, dtype=np.int32))
        r = self._router()
        r.set_assignment(asg)
        assert (r.plan(list(range(32))) == 3).all()
        out = r.lookup_batch(JOB, OP, list(range(8)))
        assert all(o["host"] == 3 for o in out)

    def test_plan_matches_host_of_key_group(self):
        from flink_tpu.state.keygroups import (
            assign_key_groups,
            hash_keys_to_i64,
            host_of_key_group,
        )

        r = self._router()
        keys = np.arange(100)
        want = host_of_key_group(
            assign_key_groups(hash_keys_to_i64(keys), 128),
            4, 2, 128)
        assert (r.plan(keys) == want).all()

    def test_missing_endpoint_raises(self):
        r = self._router(fns={0: lambda job, op, ks: [None] * len(ks)})
        with pytest.raises(KeyError, match="host"):
            r.lookup_batch(JOB, OP, list(range(64)))


# ------------------------------------------------------------- metrics


class _StubCoalescer:
    def __init__(self, n, b, ms):
        self._s = (n, b, list(ms))

    def stats_snapshot(self):
        return self._s


def test_aggregate_lookup_stats_folds_frontend_counters():
    from flink_tpu.tenancy.serving import aggregate_lookup_stats

    fe = [{"probes": 100, "hits": 90, "torn_retries": 1,
           "miss_crossings": 10},
          {"probes": 50, "hits": 40, "torn_retries": 0,
           "miss_crossings": 10}]
    s = aggregate_lookup_stats([_StubCoalescer(20, 2, (1.0, 2.0))],
                               frontend_stats=fe)
    assert s["frontend_probes"] == 150.0
    assert s["frontend_hits"] == 130.0
    assert s["frontend_torn_retries"] == 1.0
    assert s["frontend_miss_crossings"] == 20.0
    # frontend hits are served lookups that never reached a coalescer;
    # crossings DID reach one (already in the coalescer counters)
    assert s["lookups_total"] == 20 + 130
    # without frontend rows: the canonical dict, unchanged
    s2 = aggregate_lookup_stats([_StubCoalescer(20, 2, (1.0,))])
    assert s2["lookups_total"] == 20
    assert not any(k.startswith("frontend_") for k in s2)


@native
def test_serving_plane_metrics_include_frontend_counters():
    from flink_tpu.tenancy.serving import ServingPlane

    with tempfile.TemporaryDirectory() as tmp:
        plane = ServingPlane(workers=1,
                             shm_dir=os.path.join(tmp, "shm"))
        try:
            keys, vals = _prime(plane.hot_cache)
            from flink_tpu.tenancy.frontend import FrontendPool

            pool = FrontendPool(plane, n_frontends=1)
            try:
                assert pool.lookup_batch(JOB, OP, [3]) == [vals[3]]
                m = plane.metrics()
                assert m["frontend_probes"] >= 1.0
                assert m["frontend_hits"] >= 1.0
            finally:
                pool.close()
        finally:
            plane.shutdown_workers()
            plane.hot_cache.close()
