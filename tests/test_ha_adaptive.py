"""Adaptive scheduler, HA leader election, job graph store, blob store.

reference test models: scheduler/adaptive tests (WaitingForResources /
Executing transitions), leaderelection tests, Dispatcher HA recovery
ITCases, BlobServer tests.
"""

import os
import time

import numpy as np
import pytest

from flink_tpu.cluster.ha import (
    BlobStore,
    FileLeaderElectionDriver,
    JobGraphStore,
    LeaderContender,
    LeaderElectionService,
)
from flink_tpu.cluster.minicluster import (
    FAILED,
    FINISHED,
    RUNNING,
    WAITING_FOR_RESOURCES,
    MiniCluster,
)
from flink_tpu.connectors.sinks import JsonLinesFileSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.core.config import Configuration
from flink_tpu.datastream.environment import StreamExecutionEnvironment
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.windowing.assigners import TumblingEventTimeWindows


class SlowDataGen(DataGenSource):
    def poll_batch(self, max_records):
        b = super().poll_batch(max_records)
        if b is not None:
            time.sleep(0.01)
        return b


def build(env, out_path, total=4_000, source_cls=DataGenSource):
    (env.add_source(source_cls(total_records=total, num_keys=5,
                               events_per_second_of_eventtime=4000),
                    WatermarkStrategy.for_bounded_out_of_orderness(0))
     .key_by("key").window(TumblingEventTimeWindows.of(500)).count()
     .sink_to(JsonLinesFileSink(out_path)))


class TestAdaptiveScheduler:
    def test_default_mode_fails_fast_without_slots(self, tmp_path):
        cluster = MiniCluster(Configuration(
            {"rest.port": -1, "cluster.task-executors": 0}))
        try:
            env = StreamExecutionEnvironment(Configuration(
                {"execution.micro-batch.size": 512}))
            build(env, str(tmp_path / "o.jsonl"))
            client = cluster.submit(env, "nores")
            st = client.wait(timeout=20)
            assert st["status"] == FAILED
            assert "no slots" in st["error"]
        finally:
            cluster.shutdown()

    def test_adaptive_waits_for_resources_then_runs(self, tmp_path):
        cluster = MiniCluster(Configuration(
            {"rest.port": -1, "cluster.task-executors": 0}))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 512,
                "jobmanager.scheduler": "adaptive",
            }))
            build(env, str(tmp_path / "o.jsonl"))
            client = cluster.submit(env, "adaptive-wait")
            # the job parks in WaitingForResources instead of failing
            deadline = time.monotonic() + 5
            seen_waiting = False
            while time.monotonic() < deadline:
                if client.status()["status"] == WAITING_FOR_RESOURCES:
                    seen_waiting = True
                    break
                time.sleep(0.02)
            assert seen_waiting
            cluster.add_task_executor()  # resources arrive
            st = client.wait(timeout=30)
            assert st["status"] == FINISHED
            states = [h["state"] for h in st["state_history"]]
            assert states[:1] == ["CREATED"]
            assert WAITING_FOR_RESOURCES in states and RUNNING in states
        finally:
            cluster.shutdown()

    def test_adaptive_wait_timeout_fails(self, tmp_path):
        cluster = MiniCluster(Configuration(
            {"rest.port": -1, "cluster.task-executors": 0}))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 512,
                "jobmanager.scheduler": "adaptive",
                "jobmanager.adaptive-scheduler.resource-wait-timeout-ms":
                    300,
            }))
            build(env, str(tmp_path / "o.jsonl"))
            client = cluster.submit(env, "adaptive-timeout")
            st = client.wait(timeout=20)
            assert st["status"] == FAILED
            assert "resource wait timeout" in st["error"]
        finally:
            cluster.shutdown()

    def test_adaptive_rescales_on_new_resources(self, tmp_path):
        """A running adaptive job redeploys (from its checkpoint) when the
        resource picture changes — and still produces exactly-once totals
        (reference: reactive mode rescale)."""
        ck = str(tmp_path / "ck")
        out = str(tmp_path / "o.jsonl")
        cluster = MiniCluster(Configuration({"rest.port": -1}))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 256,
                "jobmanager.scheduler": "adaptive",
                "state.checkpoints.dir": ck,
                "execution.checkpointing.every-n-source-batches": 2,
            }))
            build(env, out, total=40_000, source_cls=SlowDataGen)
            client = cluster.submit(env, "adaptive-rescale")
            # wait until running, then add an executor -> reactive restart
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.status()["status"] == RUNNING:
                    break
                time.sleep(0.02)
            time.sleep(0.3)  # let some checkpoints land
            cluster.add_task_executor()
            st = client.wait(timeout=60)
            assert st["status"] == FINISHED
            assert st["attempt"] >= 1  # redeployed at least once
            states = [h["state"] for h in st["state_history"]]
            assert "RESTARTING" in states
            # exactly-once despite the rescale restart: every record
            # counted exactly once across all fired windows
            rows = JsonLinesFileSink.read_rows(out)
            per_window = {}
            for r in rows:  # later refires overwrite earlier partials
                per_window[(int(r["key"]), int(r["window_start"]))] = \
                    int(r["count"])
            assert sum(per_window.values()) == 40_000
        finally:
            cluster.shutdown()


class _Contender(LeaderContender):
    def __init__(self):
        self.granted = []
        self.revoked = 0

    def grant_leadership(self, token):
        self.granted.append(token)

    def revoke_leadership(self):
        self.revoked += 1


class TestLeaderElection:
    def test_single_leader_and_takeover(self, tmp_path):
        d = str(tmp_path)
        c1, c2 = _Contender(), _Contender()
        s1 = LeaderElectionService(
            FileLeaderElectionDriver(d, "dispatcher", lease_timeout_s=0.4),
            c1, poll_interval_s=0.05)
        s2 = LeaderElectionService(
            FileLeaderElectionDriver(d, "dispatcher", lease_timeout_s=0.4),
            c2, poll_interval_s=0.05)
        s1.start()
        time.sleep(0.3)
        assert s1.is_leader and c1.granted
        s2.start()
        time.sleep(0.3)
        assert not s2.is_leader  # exactly one leader
        token1 = c1.granted[0]
        # leader dies (stops renewing without releasing)
        s1._stop.set()
        s1._thread.join(timeout=2)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not s2.is_leader:
            time.sleep(0.05)
        assert s2.is_leader and c2.granted
        assert c2.granted[0] != token1  # fresh fencing token
        s2.stop()
        s1.driver.release()

    def test_explicit_release_hands_over_fast(self, tmp_path):
        d = str(tmp_path)
        c1, c2 = _Contender(), _Contender()
        s1 = LeaderElectionService(
            FileLeaderElectionDriver(d, "rm", lease_timeout_s=5.0), c1,
            poll_interval_s=0.05)
        s2 = LeaderElectionService(
            FileLeaderElectionDriver(d, "rm", lease_timeout_s=5.0), c2,
            poll_interval_s=0.05)
        s1.start()
        time.sleep(0.2)
        s2.start()
        s1.stop()  # graceful: releases the lock
        deadline = time.monotonic() + 3
        while time.monotonic() < deadline and not s2.is_leader:
            time.sleep(0.05)
        assert s2.is_leader
        s2.stop()


class TestJobGraphStoreAndBlobs:
    def test_dispatcher_recovers_jobs_after_failover(self, tmp_path):
        ha = str(tmp_path / "ha")
        ck = str(tmp_path / "ck")
        out = str(tmp_path / "o.jsonl")
        cfg = {
            "rest.port": -1,
            "high-availability.type": "filesystem",
            "high-availability.storageDir": ha,
        }
        cluster1 = MiniCluster(Configuration(cfg))
        job_cfg = Configuration({
            "execution.micro-batch.size": 256,
            "state.checkpoints.dir": ck,
            "execution.checkpointing.every-n-source-batches": 2,
        })
        env = StreamExecutionEnvironment(job_cfg)
        build(env, out, total=60_000, source_cls=SlowDataGen)
        client1 = cluster1.submit(env, "ha-job")
        job_id = client1.job_id
        # let it run + checkpoint, then the whole cluster dies
        time.sleep(1.0)
        cluster1.shutdown()
        assert JobGraphStore(ha).job_ids() == [job_id]

        # new cluster over the same HA dir: the job recovers, resumes from
        # its checkpoint and finishes
        cluster2 = MiniCluster(Configuration(cfg))
        try:
            # recovery happens on leadership grant (async): cluster1's
            # graceful shutdown released the lease, cluster2 acquires it
            deadline = time.monotonic() + 10
            master = None
            while time.monotonic() < deadline and master is None:
                master = cluster2.dispatcher.master(job_id)
                time.sleep(0.05)
            assert master is not None, "job not recovered"
            assert master.wait(timeout=60) == FINISHED
            # terminal job leaves the store
            assert JobGraphStore(ha).job_ids() == []
            rows = JsonLinesFileSink.read_rows(out)
            per_window = {}
            for r in rows:
                per_window[(int(r["key"]), int(r["window_start"]))] = \
                    int(r["count"])
            assert sum(per_window.values()) == 60_000
        finally:
            cluster2.shutdown()

    def test_blob_store_roundtrip_and_cache(self, tmp_path):
        store = BlobStore(str(tmp_path / "ha"),
                          cache_dir=str(tmp_path / "cache"))
        key = store.put(b"artifact-bytes")
        assert store.exists(key)
        assert store.get(key) == b"artifact-bytes"
        # cached copy survives deletion at the server
        store.delete(key)
        assert store.get(key) == b"artifact-bytes"
        # content addressing: same bytes -> same key
        assert store.put(b"artifact-bytes") == key
        # corruption is detected
        k2 = BlobStore(str(tmp_path / "ha2")).put(b"x")
        with open(os.path.join(str(tmp_path / "ha2"), "blobs", k2),
                  "wb") as f:
            f.write(b"tampered")
        with pytest.raises(IOError, match="verification"):
            BlobStore(str(tmp_path / "ha2")).get(k2)

    def test_blob_store_corrupted_cache_entry_is_repaired(self, tmp_path):
        """A corrupted LOCAL cache entry must not be served: the
        content-addressed contract holds on the cache-hit path too, falling
        back to a store re-fetch and re-caching the good bytes."""
        cache = str(tmp_path / "cache")
        store = BlobStore(str(tmp_path / "ha"), cache_dir=cache)
        key = store.put(b"artifact-bytes")
        assert store.get(key) == b"artifact-bytes"  # now cached
        with open(os.path.join(cache, key), "wb") as f:
            f.write(b"bit-rot")
        assert store.get(key) == b"artifact-bytes"  # repaired from store
        with open(os.path.join(cache, key), "rb") as f:
            assert f.read() == b"artifact-bytes"  # cache re-populated

    def test_lease_renew_detects_concurrent_steal(self, tmp_path,
                                                  monkeypatch):
        """renew() races a stale-lease os.replace steal: if the steal lands
        between renew's read and its utime, the loser must observe the loss
        (post-touch ownership verification) — otherwise both dispatchers
        believe they hold the lease (split brain)."""
        import json as _json

        d = str(tmp_path / "ha")
        os.makedirs(d)
        a = FileLeaderElectionDriver(d, "dispatcher", lease_timeout_s=60)
        b = FileLeaderElectionDriver(d, "dispatcher", lease_timeout_s=60)
        assert a.try_acquire()
        real_utime = os.utime

        def steal_then_utime(path, *args, **kwargs):
            # interleave: b's steal lands exactly between a's read and touch
            tmp = path + ".steal"
            with open(tmp, "w") as f:
                f.write(_json.dumps({"owner": b.owner_id,
                                     "ts": time.time()}))
            os.replace(tmp, path)
            return real_utime(path, *args, **kwargs)

        monkeypatch.setattr(os, "utime", steal_then_utime)
        assert a.renew() is False  # a must see it lost the lease
        monkeypatch.setattr(os, "utime", real_utime)
        assert b.renew() is True

    def test_revoked_leader_suspends_running_jobs(self, tmp_path):
        """Split-brain guard: when a dispatcher loses its lease, it must
        suspend its running jobs — the new leader resubmits them from the
        JobGraphStore, and two clusters must not run the same job against
        the same checkpoint dir/sinks."""
        import json as _json

        ha = str(tmp_path / "ha")
        cluster = MiniCluster(Configuration({
            "rest.port": -1,
            "high-availability.type": "filesystem",
            "high-availability.storageDir": ha,
            "high-availability.lease-timeout-ms": 400,
        }))
        try:
            env = StreamExecutionEnvironment(Configuration(
                {"execution.micro-batch.size": 64}))
            build(env, str(tmp_path / "o.jsonl"), total=2_000_000,
                  source_cls=SlowDataGen)
            client = cluster.submit(env, "long-job")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.status()["status"] == "RUNNING":
                    break
                time.sleep(0.02)
            assert client.status()["status"] == "RUNNING"
            # steal the lease out from under the running dispatcher
            lock = os.path.join(ha, "dispatcher.lock")
            with open(lock + ".steal", "w") as f:
                f.write(_json.dumps({"owner": "other-cluster",
                                     "ts": time.time()}))
            # the other cluster keeps its lease (a lease is as old as its
            # file): were it to lapse after 400 ms, this dispatcher would
            # win it back and resume the job before the poll below has
            # seen it suspended
            held_until = time.time() + 60
            os.utime(lock + ".steal", (held_until, held_until))
            os.replace(lock + ".steal", lock)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.status()["status"] in ("SUSPENDED", "CANCELED"):
                    break
                time.sleep(0.05)
            assert client.status()["status"] in ("SUSPENDED", "CANCELED")
            # the job stays in the HA store for the new leader
            store = JobGraphStore(ha)
            assert "long-job" in [store.get(j)["job_name"]
                                  for j in store.job_ids()]
        finally:
            cluster.shutdown()

    def test_a_grant_during_submission_starts_the_job_once(
            self, tmp_path, monkeypatch):
        """A leadership grant that lands between the store's put and the
        master's registration finds the job in the store with no master:
        it must leave the job to the submission, not start it a second
        time (the second master would outlive a suspend, fail on the
        closed cluster and take the job out of the store)."""
        import threading

        from flink_tpu.cluster import minicluster

        cluster = MiniCluster(Configuration({
            "rest.port": -1,
            "high-availability.type": "filesystem",
            "high-availability.storageDir": str(tmp_path / "ha"),
        }))
        try:
            started, grants, recovered = [], [], []

            class CountedMaster(minicluster.JobMasterThread):
                def __init__(self, *a, **kw):
                    started.append(a[1])
                    super().__init__(*a, **kw)

            monkeypatch.setattr(minicluster, "JobMasterThread",
                                CountedMaster)
            put = cluster.job_graph_store.put

            def put_then_grant(*a, **kw):
                put(*a, **kw)
                grants.append(threading.Thread(
                    target=lambda: recovered.extend(
                        cluster.dispatcher.recover_jobs())))
                grants[0].start()
                time.sleep(0.3)  # room for the recovery to get ahead

            monkeypatch.setattr(cluster.job_graph_store, "put",
                                put_then_grant)
            env = StreamExecutionEnvironment(Configuration(
                {"execution.micro-batch.size": 512}))
            build(env, str(tmp_path / "o.jsonl"), total=2_000)
            client = cluster.submit(env, "once")
            grants[0].join(timeout=10)
            assert not grants[0].is_alive() and recovered == []
            assert started == [client.job_id]
        finally:
            cluster.shutdown()

    def test_standby_cluster_does_not_run_jobs(self, tmp_path):
        """Two clusters over one HA storageDir: only the leader recovers
        and runs jobs; the standby waits (reference: standby dispatcher)."""
        ha = str(tmp_path / "ha")
        cfg = {"rest.port": -1,
               "high-availability.type": "filesystem",
               "high-availability.storageDir": ha}
        # seed a job in the store without running it: write directly
        env = StreamExecutionEnvironment(Configuration(
            {"execution.micro-batch.size": 512}))
        build(env, str(tmp_path / "o.jsonl"), total=2_000)
        graph = env.get_stream_graph()
        JobGraphStore(ha).put("job-x", "seeded", graph,
                              {"execution.micro-batch.size": 512})
        leader = MiniCluster(Configuration(cfg))
        standby = MiniCluster(Configuration(cfg))
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and \
                    leader.dispatcher.master("job-x") is None and \
                    standby.dispatcher.master("job-x") is None:
                time.sleep(0.05)
            ran_on = [c for c in (leader, standby)
                      if c.dispatcher.master("job-x") is not None]
            assert len(ran_on) == 1, "exactly one cluster recovers the job"
        finally:
            standby.shutdown()
            leader.shutdown()
