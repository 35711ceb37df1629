"""Multi-tenant session cluster: N jobs on one mesh (flink_tpu/tenancy/).

The tenancy claims, executed:
- job K+1 on a warm cluster compiles NOTHING (shared program cache,
  sentinel-verified), also while serving batched lookups through the
  read-replica plane and its hot-row cache;
- two jobs with IDENTICAL key spaces on one mesh are bit-identical to
  each running alone — windows, sessions, paged spill with forced
  eviction (cross-job state isolation is structural);
- a quota-exceeding job spills its OWN rows; its neighbor's resident
  rows never move (no cross-job reclaim);
- deficit-round-robin shares the loop (a hot job cannot starve the
  rest), and the serving plane coalesces concurrent lookups into
  device batches;
- crash mid-serving-burst restores each job INDEPENDENTLY and stays
  oracle-identical; an injected ``serving.lookup`` fault retries
  without corrupting engine state;
- arbiter-driven live rescale between jobs preserves oracle-identity.
"""

import queue as _q
import threading
import time

import numpy as np
import pytest

from flink_tpu.chaos import injection as chaos
from flink_tpu.chaos.harness import run_crash_restore_verify_multi
from flink_tpu.chaos.injection import FaultPlan, FaultRule
from flink_tpu.connectors.sinks import CollectSink
from flink_tpu.connectors.sources import DataGenSource
from flink_tpu.core.config import Configuration
from flink_tpu.datastream.environment import StreamExecutionEnvironment
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
from flink_tpu.parallel.sharded_windower import MeshWindowEngine
from flink_tpu.runtime.watermarks import WatermarkStrategy
from flink_tpu.tenancy.arbiter import JobDemand, ShardArbiter
from flink_tpu.tenancy.fairness import DeficitRoundRobin
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE
from flink_tpu.tenancy.quotas import QuotaLedger, TenantQuota
from flink_tpu.tenancy.serving import LookupCoalescer
from flink_tpu.tenancy.session_cluster import SessionCluster
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.assigners import TumblingEventTimeWindows
from tests.test_sessions import keyed_batch

GAP = 1_000
FINAL = 1 << 60


def _fired_dict(batches):
    out = {}
    for b in batches:
        for r in b.to_rows():
            out[(int(r["__key_id__"]), int(r["window_start"]),
                 int(r["window_end"]))] = float(r["sum_v"])
    return out


def _drive_sessions(engine, seed=7, n_batches=12, batch=512, keys=300):
    """Deterministic stream; returns fired {(}key, start, end) -> sum}."""
    rng = np.random.default_rng(seed)
    fired = {}
    t = 0
    for i in range(n_batches):
        ks = rng.integers(0, keys, batch)
        vs = np.ones(batch, dtype=np.float32)
        ts = t + np.sort(rng.integers(0, 400, batch))
        engine.process_batch(keyed_batch(ks, vs, ts))
        fired.update(_fired_dict(engine.on_watermark(t - 2 * GAP)))
        t += 700  # < gap: sessions span batches; > 0: watermark advances
    fired.update(_fired_dict(engine.on_watermark(FINAL)))
    return fired


def _drive_windows(engine, seed=3, n_batches=10, batch=512, keys=200):
    rng = np.random.default_rng(seed)
    fired = {}
    for i in range(n_batches):
        ks = rng.integers(0, keys, batch)
        vs = np.ones(batch, dtype=np.float32)
        ts = i * 500 + np.sort(rng.integers(0, 500, batch))
        engine.process_batch(keyed_batch(ks, vs, ts))
        fired.update(_fired_dict(engine.on_watermark(i * 500 - 1000)))
    fired.update(_fired_dict(engine.on_watermark(FINAL)))
    return fired


# ------------------------------------------------------------ program cache


class TestSharedProgramCache:
    def test_second_job_zero_misses_and_zero_compiles(self):
        """Job B's engines on a warm mesh must reuse job A's compiled
        programs: zero cache misses attributed to B AND zero real XLA
        compiles (the sentinel counts backend compilations)."""
        from flink_tpu.observe import RecompileSentinel

        mesh = make_mesh(4)

        def make():
            return MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                                     capacity_per_shard=2048,
                                     max_device_slots=2048)

        with PROGRAM_CACHE.job_scope("warm-a"):
            _drive_sessions(make())
        PROGRAM_CACHE.reset_stats()
        with PROGRAM_CACHE.job_scope("warm-b"):
            with RecompileSentinel(max_compiles=0,
                                   label="second job") as s:
                _drive_sessions(make())
        stats = PROGRAM_CACHE.stats_for("warm-b")
        assert stats["misses"] == 0 and stats["hits"] >= 1
        assert s.compiles == 0

    def test_cache_key_is_what_not_who(self):
        """Same (devices, layout) from different jobs -> one program
        family; a different layout is a genuine miss."""
        from flink_tpu.parallel.sharded_windower import build_mesh_steps
        from flink_tpu.windowing.aggregates import CountAggregate

        mesh = make_mesh(2)
        PROGRAM_CACHE.reset_stats()
        with PROGRAM_CACHE.job_scope("k-a"):
            a = build_mesh_steps(mesh, SumAggregate("v"))
        with PROGRAM_CACHE.job_scope("k-b"):
            b = build_mesh_steps(mesh, SumAggregate("v"))
            c = build_mesh_steps(mesh, CountAggregate())
        assert a is b and c is not a
        assert PROGRAM_CACHE.stats_for("k-b")["hits"] >= 1

    def test_build_does_not_stall_other_keys_and_retries_on_failure(self):
        """The builder runs OUTSIDE the cache lock behind a per-key
        once-latch: while one thread compiles, a hit on a DIFFERENT key
        proceeds; two racers on the SAME key cost one build; a failed
        build releases its latch so the next caller retries."""
        import threading as th

        from flink_tpu.tenancy.program_cache import SharedProgramCache

        cache = SharedProgramCache()
        in_build = th.Event()
        release = th.Event()
        builds = []

        def slow_builder():
            in_build.set()
            assert release.wait(10)
            builds.append(1)
            return "slow"

        cache.get_or_build("other", ("k2",), lambda: "fast")
        t = th.Thread(target=cache.get_or_build,
                      args=("kind", ("k1",), slow_builder))
        t.start()
        assert in_build.wait(10)
        # mid-build: an unrelated cached key answers without stalling
        assert cache.get_or_build("other", ("k2",),
                                  lambda: "never") == "fast"
        # a same-key racer waits for the latch, then hits
        racer_out = []
        r = th.Thread(target=lambda: racer_out.append(
            cache.get_or_build("kind", ("k1",), slow_builder)))
        r.start()
        release.set()
        t.join(10), r.join(10)
        assert racer_out == ["slow"] and builds == [1]  # ONE build
        # a failed build releases the latch; the next caller retries
        with pytest.raises(RuntimeError):
            cache.get_or_build("kind", ("boom",),
                               lambda: (_ for _ in ()).throw(
                                   RuntimeError("compile failed")))
        assert cache.get_or_build("kind", ("boom",),
                                  lambda: "recovered") == "recovered"


# ------------------------------------------------------- cross-job isolation


class TestCrossJobIsolation:
    def test_two_session_jobs_identical_keyspace_bit_identical(self):
        """Sessions + paged spill with forced eviction: jobs A and B run
        the same key space interleaved on one mesh; each must produce
        outputs bit-identical to a solo run."""
        mesh = make_mesh(2)
        KEYS, BATCH, ADV = 50_000, 1024, 300  # live set >> 2x1024 slots

        def make():
            return MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                                     capacity_per_shard=1024,
                                     max_device_slots=1024)  # forces evict

        def drive_one(eng):
            rng = np.random.default_rng(7)
            fired = {}
            t = 0
            for _ in range(12):
                ks = rng.integers(0, KEYS, BATCH)
                vs = np.ones(BATCH, dtype=np.float32)
                ts = t + np.sort(rng.integers(0, 250, BATCH))
                eng.process_batch(keyed_batch(ks, vs, ts))
                fired.update(_fired_dict(eng.on_watermark(t - 2 * GAP)))
                t += ADV
            fired.update(_fired_dict(eng.on_watermark(FINAL)))
            return fired

        solo = drive_one(make())
        a, b = make(), make()
        rng_a = np.random.default_rng(7)
        rng_b = np.random.default_rng(7)
        fired_a, fired_b = {}, {}
        t = 0
        for _ in range(12):
            for eng, rng, fired in ((a, rng_a, fired_a),
                                    (b, rng_b, fired_b)):
                ks = rng.integers(0, KEYS, BATCH)
                vs = np.ones(BATCH, dtype=np.float32)
                ts = t + np.sort(rng.integers(0, 250, BATCH))
                eng.process_batch(keyed_batch(ks, vs, ts))
                fired.update(_fired_dict(eng.on_watermark(t - 2 * GAP)))
            t += ADV
        fired_a.update(_fired_dict(a.on_watermark(FINAL)))
        fired_b.update(_fired_dict(b.on_watermark(FINAL)))
        assert a.spill_counters()["rows_evicted"] > 0  # genuinely spilled
        assert fired_a == solo
        assert fired_b == solo

    def test_two_window_jobs_identical_keyspace_bit_identical(self):
        mesh = make_mesh(4)

        def make():
            return MeshWindowEngine(
                TumblingEventTimeWindows.of(1_000), SumAggregate("v"),
                mesh, capacity_per_shard=1024, max_device_slots=1024)

        solo = _drive_windows(make())
        a, b = make(), make()
        fired_a, fired_b = {}, {}
        rng_a, rng_b = (np.random.default_rng(3),
                        np.random.default_rng(3))
        for i in range(10):
            for eng, rng, fired in ((a, rng_a, fired_a),
                                    (b, rng_b, fired_b)):
                ks = rng.integers(0, 200, 512)
                vs = np.ones(512, dtype=np.float32)
                ts = i * 500 + np.sort(rng.integers(0, 500, 512))
                eng.process_batch(keyed_batch(ks, vs, ts))
                fired.update(_fired_dict(eng.on_watermark(i * 500 - 1000)))
        fired_a.update(_fired_dict(a.on_watermark(FINAL)))
        fired_b.update(_fired_dict(b.on_watermark(FINAL)))
        assert fired_a == solo and fired_b == solo

    def test_quota_exceeder_spills_own_rows_never_neighbors(self):
        """Job B blows past its resident-row quota; enforcement sheds
        B's rows into B's tier. A's resident rows and spill counters do
        not move, and B's subsequent fires are still exact."""
        mesh = make_mesh(2)

        def make(slots):
            return MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                                     capacity_per_shard=4096,
                                     max_device_slots=slots)

        a, b = make(4096), make(4096)
        # job A: small steady state
        a.process_batch(keyed_batch([1, 2, 3], [1.0, 1.0, 1.0],
                                    [0, 10, 20]))
        a_resident = sum(a.shard_resident_rows())
        a_spill = dict(a.spill_counters())
        # job B floods far past its quota
        ks = np.arange(6000, dtype=np.int64)
        b.process_batch(keyed_batch(ks, np.ones(6000, np.float32),
                                    np.zeros(6000, np.int64)))
        ledger_b = QuotaLedger(job="b",
                               quota=TenantQuota(max_resident_rows=2048))
        ledger_b.bind([b])
        assert ledger_b.resident_rows() > 2048
        shed = ledger_b.enforce()
        assert shed > 0
        assert ledger_b.resident_rows() <= 2048
        assert ledger_b.quota_violations == 0
        # neighbor untouched: same resident rows, same spill traffic
        assert sum(a.shard_resident_rows()) == a_resident
        assert dict(a.spill_counters()) == a_spill
        # B still fires exactly (shed rows reload/fire from its tier)
        fired = _fired_dict(b.on_watermark(FINAL))
        assert len(fired) == 6000
        assert all(v == 1.0 for v in fired.values())

    def test_quota_without_spill_tier_counts_violation(self):
        mesh = make_mesh(2)
        eng = MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                                capacity_per_shard=4096)  # no budget/tier
        eng.process_batch(keyed_batch(np.arange(3000),
                                      np.ones(3000, np.float32),
                                      np.zeros(3000, np.int64)))
        ledger = QuotaLedger(job="x",
                             quota=TenantQuota(max_resident_rows=1024))
        ledger.bind([eng])
        assert ledger.enforce() == 0
        assert ledger.quota_violations >= 1

    def test_quota_counts_single_device_engines(self):
        """Regression: bind() unwrapped operators to their engine, and
        single-device engines define no shard_resident_rows — the quota
        silently became a no-op (resident 0 forever, never enforced,
        never violated). The OPERATOR carries the single-device
        fallback; bind must keep it."""
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            TIMESTAMP_FIELD,
            RecordBatch,
        )
        from flink_tpu.runtime.operators import (
            OperatorContext,
            WindowAggOperator,
        )
        from flink_tpu.state.keygroups import hash_keys_to_i64
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        op = WindowAggOperator(TumblingEventTimeWindows.of(1000),
                               SumAggregate("v"), "key", capacity=4096)
        op.open(OperatorContext(max_parallelism=128))
        n = 2000
        keys = np.arange(n, dtype=np.int64)
        op.process_batch(RecordBatch.from_pydict({
            "key": keys, KEY_ID_FIELD: hash_keys_to_i64(keys),
            "v": np.ones(n, np.float32),
            TIMESTAMP_FIELD: np.zeros(n, np.int64)}))
        ledger = QuotaLedger(job="sd",
                             quota=TenantQuota(max_resident_rows=512))
        ledger.bind([op])
        assert ledger.resident_rows() >= n  # counted, not 0
        assert ledger.pressure() > 1.0
        # no mesh shed path on this layout: the violation must be LOUD
        assert ledger.enforce() == 0
        assert ledger.quota_violations >= 1


# ------------------------------------------------------------------ fairness


class TestDeficitRoundRobin:
    def test_quantum_shares_and_idle_reset(self):
        drr = DeficitRoundRobin(quantum=100)
        drr.add("a")
        drr.add("b", weight=2.0)
        order = drr.begin_round()
        assert order == ["a", "b"]
        assert drr.deficit("a") == 100 and drr.deficit("b") == 200
        drr.charge("a", 150)
        assert not drr.can_run("a")  # over-quantum job yields
        drr.begin_round()
        assert drr.deficit("a") == 50  # deficit carries (DRR law)
        drr.reset_idle("b")
        assert drr.deficit("b") == 0.0  # empty queue forfeits credit
        drr.charge("a", 0)
        assert drr.deficit("a") == 49  # zero-record step costs a token

    def test_hot_job_cannot_starve_sibling(self):
        """Simulated scheduler: a job with 10x the data still cannot
        take more than ~its share of consecutive service."""
        drr = DeficitRoundRobin(quantum=10)
        drr.add("hot")
        drr.add("cold")
        served = {"hot": 0, "cold": 0}
        for _ in range(50):
            for name in drr.begin_round():
                while drr.can_run(name):
                    served[name] += 1
                    drr.charge(name, 10)
        assert served["hot"] == served["cold"]


# ----------------------------------------------------------------- arbiter


class TestShardArbiter:
    def test_backlog_weighted_allocation_conserves_budget(self):
        arb = ShardArbiter(total_shards=8, cooldown_ticks=0,
                           backlog_norm=1000.0)
        alloc = arb.decide([
            JobDemand(job="hungry", current_shards=2, backlog=7000.0),
            JobDemand(job="quiet", current_shards=2, backlog=0.0),
        ])
        assert alloc["hungry"] > alloc["quiet"] >= 1
        assert alloc["hungry"] + alloc["quiet"] <= 8

    def test_quota_pressure_raises_share(self):
        arb = ShardArbiter(total_shards=8, cooldown_ticks=0)
        base = arb.decide([
            JobDemand(job="a", current_shards=4),
            JobDemand(job="b", current_shards=4),
        ])
        arb2 = ShardArbiter(total_shards=8, cooldown_ticks=0)
        pressured = arb2.decide([
            JobDemand(job="a", current_shards=4, quota_pressure=3.0),
            JobDemand(job="b", current_shards=4),
        ])
        assert pressured["a"] > base["a"]

    def test_clamps_and_floors(self):
        arb = ShardArbiter(total_shards=8, cooldown_ticks=0)
        alloc = arb.decide([
            JobDemand(job="a", current_shards=1, backlog=1e9,
                      max_shards=3),
            JobDemand(job="b", current_shards=1, min_shards=2),
        ])
        assert alloc["a"] <= 3 and alloc["b"] >= 2

    def test_hysteresis_suppresses_one_shard_flap(self):
        arb = ShardArbiter(total_shards=9, hysteresis=1,
                           cooldown_ticks=0)
        alloc = arb.decide([
            JobDemand(job="a", current_shards=4, backlog=100.0),
            JobDemand(job="b", current_shards=4),
        ])
        assert alloc == {"a": 4, "b": 4}

    def test_min_clamps_never_oversubscribe_budget(self):
        """Regression: lo clamps lift low-demand jobs above
        floor(ideal); without a shed pass a 4-shard budget handed out 5
        (a=3 from its near-4.0 ideal, b=c=1 from their floors)."""
        arb = ShardArbiter(total_shards=4, cooldown_ticks=0)
        alloc = arb.decide([
            JobDemand(job="a", current_shards=2, backlog=1e9,
                      min_shards=2),
            JobDemand(job="b", current_shards=1),
            JobDemand(job="c", current_shards=1),
        ])
        assert sum(alloc.values()) <= 4
        # floors still honored while shedding the excess
        assert alloc["a"] >= 2 and alloc["b"] >= 1 and alloc["c"] >= 1

    def test_hysteresis_repin_cannot_oversubscribe(self):
        """Regression: the hysteresis re-pin ran AFTER the budget shed,
        handing pinned jobs back the shards the shed pass took — with
        hysteresis=1 and currents (3,3,3), a (5,2,2) allocation
        re-pinned to (5,3,3)=11 on a 9-shard budget."""
        arb = ShardArbiter(total_shards=9, hysteresis=1,
                           cooldown_ticks=0, backlog_norm=100.0)
        alloc = arb.decide([
            JobDemand(job="a", current_shards=3, backlog=200.0),
            JobDemand(job="b", current_shards=3),
            JobDemand(job="c", current_shards=3),
        ])
        assert sum(alloc.values()) <= 9, alloc


# ------------------------------------------------------------------ cluster


def _pipeline(sink, n=30_000, keys=64, par=2, window=10_000, seed=5,
              extra_config=None):
    cfg = {"execution.micro-batch.size": 2048, "parallelism.default": par}
    cfg.update(extra_config or {})
    env = StreamExecutionEnvironment(Configuration(cfg))
    (env.add_source(DataGenSource(total_records=n, num_keys=keys,
                                  events_per_second_of_eventtime=5_000,
                                  seed=seed),
                    WatermarkStrategy.for_bounded_out_of_orderness(0))
        .key_by("key").window(TumblingEventTimeWindows.of(window))
        .sum("value").sink_to(sink))
    return env


def _rows(sink):
    return sorted((r["key"], r["window_end"], r["sum_value"])
                  for r in sink.rows())


class TestSessionCluster:
    def test_two_jobs_oracle_identical_with_fair_interleave(self):
        solo_sink = CollectSink()
        _pipeline(solo_sink).execute("solo")
        solo = _rows(solo_sink)
        sa, sb = CollectSink(), CollectSink()
        cluster = SessionCluster(quantum_records=4096)
        cluster.submit(_pipeline(sa), "job-a")
        cluster.submit(_pipeline(sb), "job-b")
        results = cluster.run(timeout_s=120)
        assert _rows(sa) == solo and _rows(sb) == solo
        assert all(hasattr(r, "metrics") for r in results.values())
        # per-job fairness telemetry exists and registered under the
        # tenancy metric group
        snap = cluster.registry.snapshot() \
            if hasattr(cluster.registry, "snapshot") else None
        for job in cluster.jobs.values():
            assert job.records_total == 30_000
            assert job.busy_ms >= 0.0

    def test_per_job_spill_directories(self, tmp_path):
        """Two jobs sharing one configured state.spill.dir must get
        PRIVATE per-job trees (SpillTier page filenames are per-tier
        sequences — a shared tree would let one job overwrite the
        other's pages)."""
        import os

        base = str(tmp_path / "spill")
        cfg = {"state.slot-table.max-device-slots": 2048,
               "state.spill.dir": base}
        sa, sb = CollectSink(), CollectSink()
        cluster = SessionCluster(quantum_records=4096)
        cluster.submit(_pipeline(sa, extra_config=cfg), "iso-a")
        cluster.submit(_pipeline(sb, extra_config=cfg), "iso-b")
        for name in ("iso-a", "iso-b"):
            dirs = {getattr(e, "_spill_dir", None)
                    for e in cluster.jobs[name].ledger.engines}
            assert dirs == {os.path.join(base, f"job-{name}")}, dirs
        cluster.run(timeout_s=120)
        assert _rows(sa) == _rows(sb) != []

    def test_reused_quota_object_keeps_spill_dirs_private(self, tmp_path):
        """Regression: submit() re-roots quota.spill_dir per job, but it
        used to mutate the CALLER's TenantQuota — one quota object
        reused for two jobs handed job B job A's private spill tree
        (exactly the cross-tenant page overwrite isolation exists to
        prevent). submit must copy the quota, as it copies the config."""
        import os

        base = str(tmp_path / "spill")
        cfg = {"state.slot-table.max-device-slots": 2048,
               "state.spill.dir": base}
        shared = TenantQuota(max_resident_rows=1 << 20)
        sa, sb = CollectSink(), CollectSink()
        cluster = SessionCluster(quantum_records=4096)
        cluster.submit(_pipeline(sa, extra_config=cfg), "share-a",
                       quota=shared)
        cluster.submit(_pipeline(sb, extra_config=cfg), "share-b",
                       quota=shared)
        # the caller's object is untouched; each job got its own tree
        assert shared.spill_dir is None
        for name in ("share-a", "share-b"):
            assert cluster.jobs[name].quota.spill_dir == \
                os.path.join(base, f"job-{name}")
            dirs = {getattr(e, "_spill_dir", None)
                    for e in cluster.jobs[name].ledger.engines}
            assert dirs == {os.path.join(base, f"job-{name}")}, dirs
        cluster.run(timeout_s=120)
        assert _rows(sa) == _rows(sb) != []

    def test_lookup_racing_job_completion_fails_fast(self):
        """A lookup that passes the plane's bound-queue check just as
        the job terminates must get the prompt not-serving error, not a
        dead block until its timeout: _flush re-checks the binding after
        its put and fails everything stranded on the dead queue, and the
        cluster's _finish drains the queue once more after unbinding."""
        import types

        from flink_tpu.cluster.local_executor import (
            StateQueryBatchRequest,
        )
        from flink_tpu.tenancy.serving import ServingPlane

        plane = ServingPlane(timeout_s=5.0)

        class _TerminatingQueue(_q.Queue):
            # the job finishes between the client's bound check and its
            # enqueue landing — the executor's terminal drain missed it
            def put(self, item, *a, **k):
                super().put(item, *a, **k)
                plane.unbind_job("gone")

        plane.bind_job("gone", _TerminatingQueue())
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="not serving"):
            plane._flush("gone", "op", [1, 2], None)
        assert time.perf_counter() - t0 < 1.0

        # cluster side: _finish's drain fails requests already queued
        job = types.SimpleNamespace(control=_q.Queue(), name="dead")
        req = StateQueryBatchRequest("op", [1], None)
        job.control.put(req)
        SessionCluster._fail_stranded_lookups(job)
        with pytest.raises(RuntimeError, match="not serving"):
            req.wait(1.0)

    def test_shared_checkpoint_dir_isolated_per_job(self, tmp_path):
        """Two jobs sharing one configured state.checkpoints.dir must
        checkpoint into PRIVATE per-job trees: chk-N ids are per-storage
        sequences, so a shared tree overwrites — and a crashed job
        would restore whichever job wrote last (cross-tenant state).
        The jobs here have DIFFERENT seeds, so restoring the wrong
        checkpoint diverges from the oracle."""
        import os

        from flink_tpu.connectors.sinks import Sink

        class UpsertSink(Sink):
            def __init__(self):
                self.cells = {}

            def write(self, batch):
                for r in batch.to_rows():
                    self.cells[(r["key"], int(r["window_end"]))] = \
                        float(r["sum_value"])

        solo_a, solo_b = UpsertSink(), UpsertSink()
        _pipeline(solo_a, n=20_000).execute("solo-a")
        _pipeline(solo_b, n=20_000, seed=9).execute("solo-b")
        assert solo_a.cells != solo_b.cells
        ck = str(tmp_path / "ck")
        cfg = {"state.checkpoints.dir": ck,
               "execution.checkpointing.every-n-source-batches": 2}
        sa, sb = UpsertSink(), UpsertSink()
        cluster = SessionCluster(quantum_records=1024, max_restarts=2)
        cluster.submit(_pipeline(sa, n=20_000, extra_config=cfg),
                       "steady")
        cluster.submit(_pipeline(sb, n=20_000, seed=9,
                                 extra_config=cfg), "crashy")
        plan = FaultPlan(rules=[FaultRule(pattern="task.batch", nth=5,
                                          where={"job": "crashy"})])
        with chaos.chaos_active(plan, seed=11):
            results = cluster.run(timeout_s=180)
        assert cluster.jobs["crashy"].restarts == 1
        assert not isinstance(results["crashy"], BaseException)
        assert sa.cells == solo_a.cells
        assert sb.cells == solo_b.cells
        assert sorted(os.listdir(ck)) == ["job-crashy", "job-steady"]

    def test_serving_plane_coalesces_concurrent_lookups(self):
        """Client threads fire point lookups against a running job; the
        plane coalesces them into device batches (batches < lookups)
        and every result matches a direct engine read."""
        sink = CollectSink()
        env = _pipeline(sink, n=120_000, keys=16, window=1 << 40)
        cluster = SessionCluster(quantum_records=2048)
        cluster.submit(env, "serve-job")
        errors = []
        got = {}

        def client(tid):
            try:
                # wait until state exists, then hammer
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    out = cluster.lookup("serve-job",
                                         "window_agg(SumAggregate)",
                                         tid % 16)
                    if out:
                        got[tid] = out
                        return
                    time.sleep(0.01)
                errors.append(f"client {tid}: no state observed")
            except RuntimeError:
                pass  # job finished while we were querying: benign
            except BaseException as e:  # noqa: BLE001
                errors.append(f"client {tid}: {e!r}")

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        cluster.run(timeout_s=120)
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        m = cluster.serving.metrics()
        assert m["lookups_total"] >= len(got) > 0
        assert m["lookup_batches_total"] <= m["lookups_total"]
        for tid, out in got.items():
            (ns, cols), = out.items()
            assert cols["sum_value"] > 0

    def test_packed_lookup_batch_matches_dict_path(self):
        """r19 fast path, end-to-end through the cluster: packed batch
        lookups against a REPLICA-armed running job materialize
        bit-identical to the dict path, and the native probe table
        actually served (when the library is available)."""
        from flink_tpu.tenancy.serving import PackedLookupResult

        sink = CollectSink()
        env = _pipeline(sink, n=120_000, keys=16, window=1 << 40,
                        extra_config={
                            "serving.replica": True,
                            "serving.replica.publish-interval-ms": 5})
        cluster = SessionCluster(quantum_records=2048)
        cluster.submit(env, "packed-job")
        errors = []
        checked = []

        def client():
            try:
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    keys = list(range(16))
                    packed = cluster.lookup_batch_packed(
                        "packed-job", "window_agg(SumAggregate)", keys)
                    assert isinstance(packed, PackedLookupResult)
                    if any(packed.to_dicts()):
                        # the dict path a moment later may see a newer
                        # boundary; only a repeated mismatch counts
                        for _ in range(5):
                            dicts = cluster.lookup_batch(
                                "packed-job",
                                "window_agg(SumAggregate)", keys)
                            if packed == dicts:
                                checked.append(True)
                                return
                            packed = cluster.lookup_batch_packed(
                                "packed-job",
                                "window_agg(SumAggregate)", keys)
                        errors.append("packed != dict results")
                        return
                    time.sleep(0.01)
            except RuntimeError:
                pass  # job finished while we were querying: benign
            except BaseException as e:  # noqa: BLE001
                errors.append(f"packed client: {e!r}")

        t = threading.Thread(target=client)
        t.start()
        cluster.run(timeout_s=120)
        t.join(timeout=30)
        assert not errors, errors
        # the cross-check must have actually RUN (a client that never
        # observed state — or always-empty packed results — would pass
        # vacuously otherwise)
        assert checked, "packed-vs-dict cross-check never executed"
        from flink_tpu.native import hotcache_available
        from flink_tpu.tenancy.hot_cache import HotRowCache

        if hotcache_available():
            assert not isinstance(cluster.serving.hot_cache,
                                  HotRowCache)

    def test_one_job_crash_restarts_from_checkpoint_sibling_unharmed(
            self, tmp_path):
        """task.batch crash in job B: B restores from its checkpoint and
        finishes; job A never notices. Oracle-identity for B's sink is
        asserted via the upsert model (replayed fires land on the same
        window cells)."""
        from flink_tpu.connectors.sinks import Sink

        class UpsertSink(Sink):
            def __init__(self):
                self.cells = {}

            def write(self, batch):
                for r in batch.to_rows():
                    self.cells[(r["key"], int(r["window_end"]))] = \
                        float(r["sum_value"])

        solo = UpsertSink()
        _pipeline(solo, n=20_000).execute("solo")
        sa, sb = UpsertSink(), UpsertSink()
        ck = str(tmp_path / "ck-b")
        cluster = SessionCluster(quantum_records=1024, max_restarts=2)
        cluster.submit(_pipeline(sa, n=20_000), "steady")
        cluster.submit(_pipeline(sb, n=20_000, extra_config={
            "state.checkpoints.dir": ck,
            "execution.checkpointing.every-n-source-batches": 2}),
            "crashy")
        plan = FaultPlan(rules=[FaultRule(pattern="task.batch", nth=5,
                                          where={"job": "crashy"})])
        with chaos.chaos_active(plan, seed=11):
            results = cluster.run(timeout_s=180)
        assert cluster.jobs["crashy"].restarts == 1
        assert cluster.jobs["steady"].restarts == 0
        assert not isinstance(results["steady"], BaseException)
        assert not isinstance(results["crashy"], BaseException)
        assert sa.cells == solo.cells
        assert sb.cells == solo.cells
        # the restore was from a real checkpoint, not a vacuous cold
        # restart (the dir is re-rooted per job by submit)
        import os

        chks = os.listdir(os.path.join(ck, "job-crashy"))
        assert any(d.startswith("chk-") for d in chks), chks

    def test_failed_restart_contained_sibling_survives(self):
        """Regression: a restart that ITSELF raised (unreadable
        checkpoint tree, operator open failure) escaped _on_failure
        through step_round and killed every sibling. It must charge
        the restart budget and fail only that job."""
        solo = CollectSink()
        _pipeline(solo, n=20_000).execute("solo")
        want = _rows(solo)
        sa, sb = CollectSink(), CollectSink()
        cluster = SessionCluster(quantum_records=1024, max_restarts=2)
        cluster.submit(_pipeline(sa, n=20_000), "steady")
        cluster.submit(_pipeline(sb, n=20_000, seed=9), "doomed")
        real_start = cluster._start

        def start(job, restore_from=None):
            if job.name == "doomed" and job.restarts > 0:
                raise RuntimeError("operator open failed")
            return real_start(job, restore_from=restore_from)

        cluster._start = start
        plan = FaultPlan(rules=[FaultRule(pattern="task.batch", nth=3,
                                          where={"job": "doomed"})])
        with chaos.chaos_active(plan, seed=7):
            results = cluster.run(timeout_s=120)
        assert _rows(sa) == want  # sibling finished oracle-identical
        assert isinstance(results["doomed"], BaseException)
        assert cluster.jobs["doomed"].restarts == 2  # budget consumed
        assert not isinstance(results["steady"], BaseException)

    def test_finished_jobs_release_execution_resources(self):
        """Regression: _finish kept TenantJob.handle (operator graph ->
        engines -> device planes) and the per-job gauges alive forever
        — one dead job's working set per HISTORICAL job on a long-lived
        cluster. Terminal jobs must drop both; cheap counters stay."""
        sa, sb = CollectSink(), CollectSink()
        cluster = SessionCluster(quantum_records=4096)
        cluster.submit(_pipeline(sa, n=20_000), "gone-a")
        cluster.submit(_pipeline(sb, n=20_000, seed=9), "gone-b")
        cluster.run(timeout_s=120)
        for j in cluster.jobs.values():
            assert j.handle is None and j.gen is None
            assert len(j.ledger.engines) == 0
            assert j.records_total == 20_000  # counters survive
        snap = cluster.registry.snapshot()
        assert not any(".gone-a." in k or ".gone-b." in k
                       for k in snap), "per-job gauges not unregistered"
        assert any(k.endswith("tenancy.jobs_live") for k in snap)

    def test_arbiter_live_rescale_preserves_oracle_identity(self):
        """A fixed-decision arbiter forces a live 2->4 / 2->1 rescale on
        running jobs; outputs must stay oracle-identical (the PR 4
        key-group migration, driven cross-job)."""

        class FixedArbiter:
            def __init__(self):
                self.calls = 0

            def decide(self, demands, dead_shards=0):
                self.calls += 1
                want = {"grow": 4, "shrink": 1}
                return {d.job: want[d.job] for d in demands}

        solo_sink = CollectSink()
        _pipeline(solo_sink, n=60_000).execute("solo")
        solo = _rows(solo_sink)
        sa, sb = CollectSink(), CollectSink()
        arb = FixedArbiter()
        cluster = SessionCluster(quantum_records=1024, arbiter=arb,
                                 arbitrate_every_s=0.01)
        cluster.submit(_pipeline(sa, n=60_000), "grow")
        cluster.submit(_pipeline(sb, n=60_000), "shrink")
        # grab the engines pre-run: terminal jobs RELEASE their handle
        # (test_finished_jobs_release_execution_resources), so post-run
        # inspection must go through refs taken while the jobs ran
        eng_a = cluster.jobs["grow"].handle.stateful_operators()[
            0].windower
        eng_b = cluster.jobs["shrink"].handle.stateful_operators()[
            0].windower
        cluster.run(timeout_s=180)
        assert arb.calls >= 1
        assert eng_a.reshards_completed >= 1
        assert eng_b.reshards_completed >= 1
        assert int(eng_a.P) == 4
        assert int(eng_b.P) == 1
        assert _rows(sa) == solo and _rows(sb) == solo


# ------------------------------------------- serving on a warm cluster


class TestServingOnWarmCluster:
    """Two FRESH jobs on a fresh cluster, after a first job warmed the
    shared program cache and the replica tier lattice, ingest under
    batched lookup load through the read-replica plane."""

    RECORDS, KEYS, QUOTA_ROWS, LOOKUP_BATCH = 100_000, 4096, 8192, 256
    OPERATOR = "window_agg(SumAggregate)"

    def _pipeline(self, sink):
        cfg = {
            "execution.micro-batch.size": 4096,
            "parallelism.default": 4,
            # deadline splitting bounds each ingest dispatch, so a miss
            # batch never waits out a full-batch program
            "latency.fire-deadline-ms": 25,
            "serving.replica": True,
            "serving.replica.publish-interval-ms": 25,
            # the spill tier sized to the quota's per-shard slice, so
            # the quota has somewhere to shed
            "state.slot-table.max-device-slots": TenantQuota(
                max_resident_rows=self.QUOTA_ROWS).per_shard_slots(4),
        }
        env = StreamExecutionEnvironment(Configuration(cfg))
        (env.add_source(
            DataGenSource(total_records=self.RECORDS, num_keys=self.KEYS,
                          events_per_second_of_eventtime=50_000,
                          seed=13),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
            .key_by("key")
            .window(TumblingEventTimeWindows.of(60_000))
            .sum("value").sink_to(sink))
        return env

    def _serve(self, cluster, jobs, n_clients):
        """Step the cluster to completion with ``n_clients`` background
        threads hammering packed lookups, and between rounds (when no
        publish can land) look one batch up twice per job: the second
        pass must equal the dict path. Returns (client errors, checks
        made, most replica generations seen)."""
        stop = threading.Event()
        errors = []

        def client(i):
            rng = np.random.default_rng(100 + i)
            while not stop.is_set():
                ks = rng.integers(0, self.KEYS, self.LOOKUP_BATCH).tolist()
                try:
                    cluster.lookup_batch_packed(jobs[i % len(jobs)],
                                                self.OPERATOR, ks)
                except RuntimeError as e:
                    if "is not serving" not in str(e) and \
                            "shut down" not in str(e):
                        errors.append(f"client {i}: {e!r}")
                    return
                time.sleep(0.005)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        rng = np.random.default_rng(3)
        checked = gens = 0
        for t in threads:
            t.start()
        try:
            while cluster.step_round():
                g = cluster.serving.replica_generations()
                gens = max(gens, g)
                if g == 0:
                    continue
                for job in jobs:
                    if cluster.jobs[job].finished:
                        continue
                    ks = rng.integers(0, self.KEYS,
                                      self.LOOKUP_BATCH).tolist()
                    cluster.lookup_batch_packed(job, self.OPERATOR, ks)
                    packed = cluster.lookup_batch_packed(
                        job, self.OPERATOR, ks)
                    assert packed.to_dicts() == cluster.lookup_batch(
                        job, self.OPERATOR, ks)
                    checked += 1
            cluster.serving.shutdown_workers()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        return errors, checked, gens

    def test_two_fresh_jobs_serve_lookups_without_compiles(self):
        from flink_tpu.native import hotcache_available
        from flink_tpu.observe import RecompileSentinel
        from flink_tpu.tenancy.hot_cache import HotRowCache

        # job-1 warms ingest, fire, serving and replica programs
        warm = SessionCluster(quantum_records=8192, serving_workers=2)
        warm.submit(self._pipeline(CollectSink()), "job-1")
        errors, _, _ = self._serve(warm, ["job-1"], 2)
        assert errors == []

        PROGRAM_CACHE.reset_stats()
        cluster = SessionCluster(quantum_records=8192, serving_workers=2)
        s2, s3 = CollectSink(), CollectSink()
        cluster.submit(self._pipeline(s2), "job-2",
                       quota=TenantQuota(max_resident_rows=self.QUOTA_ROWS))
        cluster.submit(self._pipeline(s3), "job-3")
        with RecompileSentinel(max_compiles=0,
                               label="two jobs on a warm cluster"):
            errors, checked, gens = self._serve(
                cluster, ["job-2", "job-3"], 4)

        assert errors == []
        assert checked > 0, "the jobs ended before anything was served"
        for job in ("job-2", "job-3"):
            assert PROGRAM_CACHE.stats_for(job)["misses"] == 0, job
        metrics = cluster.serving.metrics()
        assert metrics["lookups_total"] > 0
        assert metrics["hot_row_hit_rate"] > 0.0
        assert gens >= 2, "boundary publishes never happened"
        assert cluster.jobs["job-2"].ledger.quota_violations == 0
        assert len(s2.result()) > 0 and len(s3.result()) > 0
        if hotcache_available():
            assert not isinstance(cluster.serving.hot_cache, HotRowCache)


# ----------------------------------------------------------------- serving


class TestLookupCoalescer:
    def test_concurrent_lookups_share_flushes(self):
        flushes = []
        gate = threading.Event()

        def flush(keys, ns):
            gate.wait(5)
            flushes.append(list(keys))
            return [k * 10 for k in keys]

        co = LookupCoalescer(flush, max_batch=64, window_ms=30.0)
        results = {}

        def worker(k):
            results[k] = co.lookup(k)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.05)
        gate.set()
        for t in threads:
            t.join(timeout=10)
        assert results == {i: i * 10 for i in range(8)}
        assert co.lookups_total == 8

    def test_short_flush_reply_errors_every_rider(self):
        """Regression: a flush returning fewer results than keys left
        the tail riders result=None with no error — indistinguishable
        from 'key has no state'. A short reply must raise to ALL riders
        of the batch."""
        co = LookupCoalescer(lambda keys, ns: [], max_batch=8,
                             window_ms=0.0)
        with pytest.raises(RuntimeError, match="returned 0 results"):
            co.lookup(7)
        assert co.batches_total < 8  # amortization happened
        assert co.p99_ms() >= 0.0

    def test_flush_error_fans_out_and_recovers(self):
        calls = {"n": 0}

        def flush(keys, ns):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("boom")
            return list(keys)

        co = LookupCoalescer(flush, window_ms=0.0)
        with pytest.raises(RuntimeError):
            co.lookup(1)
        assert co.lookup(2) == 2  # coalescer survives a failed batch

    def test_stats_scrape_during_concurrent_lookups(self):
        """A metrics scrape must not crash while client threads serve:
        the reservoir deques and counters are read under the coalescer
        lock (iterating a deque mid-append raises RuntimeError)."""
        from flink_tpu.tenancy.serving import aggregate_lookup_stats

        co = LookupCoalescer(lambda keys, ns: [0.0] * len(keys),
                             max_batch=8, window_ms=0.0)
        stop = threading.Event()
        errs = []

        def hammer():
            while not stop.is_set():
                co.lookup(1)

        def scrape():
            try:
                while not stop.is_set():
                    s = aggregate_lookup_stats([co])
                    assert s["lookups_total"] >= s[
                        "lookup_batches_total"]
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = ([threading.Thread(target=hammer) for _ in range(4)]
                   + [threading.Thread(target=scrape)])
        for t in threads:
            t.start()
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errs, errs
        assert co.stats_snapshot()[0] > 0


class TestServingPlaneRetirement:
    def test_unbind_retires_coalescers_and_keeps_totals(self):
        """Regression: finished jobs' coalescers were never removed, so
        a cluster churning many short jobs grew the map (and every
        scrape's walk, and the latency reservoirs) per HISTORICAL job.
        Retirement must keep the cumulative gauges monotonic."""
        import queue

        from flink_tpu.tenancy.serving import ServingPlane

        plane = ServingPlane(window_ms=0.0)
        for i in range(5):
            name = f"job-{i}"
            plane.bind_job(name, queue.Queue())
            plane._coalescer(name, "op").note_batch(4, 1.0)
            plane.unbind_job(name)
        assert len(plane._pool) == 0  # nothing accumulates
        assert plane.lookups_total() == 20
        assert plane.lookup_batches_total() == 5
        m = plane.metrics()
        assert m["lookups_total"] == 20
        assert m["lookup_batches_total"] == 5
        assert m["lookup_p99_ms"] >= 1.0  # reservoirs retired too


class TestShardArbiterCooldown:
    def test_cooldown_suppresses_exactly_n_ticks(self):
        arb = ShardArbiter(total_shards=8, cooldown_ticks=2,
                           backlog_norm=1000.0)
        demands = [
            JobDemand(job="hungry", current_shards=2, backlog=7000.0),
            JobDemand(job="quiet", current_shards=2),
        ]
        first = arb.decide(demands)  # first tick may act
        assert first["hungry"] > first["quiet"]
        moved = [JobDemand(job="hungry", current_shards=first["hungry"]),
                 JobDemand(job="quiet", current_shards=first["quiet"],
                           backlog=7000.0)]
        hold = {d.job: d.current_shards for d in moved}
        # exactly cooldown_ticks=2 quiet ticks after a reallocation...
        assert arb.decide(moved) == hold
        assert arb.decide(moved) == hold
        # ...then the arbiter acts again
        third = arb.decide(moved)
        assert third["quiet"] > third["hungry"]


# -------------------------------------------------------------------- chaos


def _chaos_steps(seed, n_steps=8, batch=256, keys=120):
    rng = np.random.default_rng(seed)
    steps = []
    t = 0
    for i in range(n_steps):
        ks = rng.integers(0, keys, batch)
        vs = np.ones(batch, dtype=np.float32)
        ts = t + np.sort(rng.integers(0, 400, batch))
        t += 700
        steps.append((ks, vs, ts, t - 2 * GAP))
    return steps


class TestTwoJobChaos:
    def _makers(self):
        mesh = make_mesh(2)

        def mk_mesh():
            return MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                                     capacity_per_shard=1024,
                                     max_device_slots=1024)

        def mk_oracle():
            from flink_tpu.windowing.sessions import SessionWindower

            return SessionWindower(GAP, SumAggregate("v"),
                                   capacity=1 << 15)

        return mk_mesh, mk_oracle

    def test_crash_mid_serving_burst_restores_jobs_independently(
            self, tmp_path):
        """Job B crashes (session-fire fault) while both jobs serve
        batched lookups; B restores from ITS checkpoint, A never
        restores, both end oracle-identical."""
        mk_mesh, mk_oracle = self._makers()
        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.session_fire", nth=6),
        ])
        reports = run_crash_restore_verify_multi(
            make_engines={"a": mk_mesh, "b": mk_mesh},
            make_oracles={"a": mk_oracle, "b": mk_oracle},
            steps_by_job={"a": _chaos_steps(1), "b": _chaos_steps(2)},
            plan=plan, seed=5, ckpt_root=str(tmp_path),
            checkpoint_every=2,
            serve_keys={"a": [1, 2, 3], "b": [4, 5, 6]})
        crashed = sorted(j for j, r in reports.items() if r.crashes)
        assert len(crashed) == 1  # exactly one job took the fault
        other = "a" if crashed == ["b"] else "b"
        assert reports[crashed[0]].restores >= 1
        assert reports[other].restores == 0
        for r in reports.values():
            assert not r.diverged

    def test_serving_lookup_fault_retries_without_corruption(
            self, tmp_path):
        """A recoverable serving.lookup fault at the real injection site
        retries in place: lookups recover, no crash, no divergence."""
        mk_mesh, mk_oracle = self._makers()
        plan = FaultPlan(rules=[
            FaultRule(pattern="serving.lookup", nth=2,
                      recoverable=True),
        ])
        reports = run_crash_restore_verify_multi(
            make_engines={"a": mk_mesh, "b": mk_mesh},
            make_oracles={"a": mk_oracle, "b": mk_oracle},
            steps_by_job={"a": _chaos_steps(3), "b": _chaos_steps(4)},
            plan=plan, seed=9, ckpt_root=str(tmp_path),
            serve_keys={"a": [1, 2], "b": [3, 4]})
        total_faults = sum(
            r.faults_injected.get("serving.lookup", 0)
            for r in reports.values())
        assert total_faults >= 1
        assert sum(r.retries for r in reports.values()) >= 1
        assert sum(r.recoveries for r in reports.values()) >= 1
        # per-job attribution: the fault landed in exactly one job's
        # serving burst, and only that job's report carries it
        carrying = [j for j, r in reports.items()
                    if r.faults_injected.get("serving.lookup", 0)]
        assert len(carrying) == 1
        for r in reports.values():
            assert r.crashes == 0 and not r.diverged

    def test_torn_checkpoint_skip_counted_per_job(self, tmp_path):
        """Regression: the multi-job restore path dropped the
        single-job harness's corrupt_checkpoints_skipped accounting.
        Tear job a's first checkpoint (rename durable, bytes not),
        then crash a — job-targeted serving fault — before its next
        good one: a's restore must fall past the torn snapshot AND
        count the skip; b's report stays clean."""
        mk_mesh, mk_oracle = self._makers()
        plan = FaultPlan(rules=[
            # first checkpoint write overall is job a's chk-1 (jobs
            # step round-robin, a first)
            FaultRule(pattern="checkpoint.write.torn", nth=1,
                      kind="drop"),
            # crash a at its 3rd serving burst (pos 3: after torn
            # chk-1, before chk-2) — a non-recoverable raise
            # propagates through run_recoverable as the crash path
            FaultRule(pattern="serving.lookup", nth=3,
                      where={"job": "a"}),
        ])
        reports = run_crash_restore_verify_multi(
            make_engines={"a": mk_mesh, "b": mk_mesh},
            make_oracles={"a": mk_oracle, "b": mk_oracle},
            steps_by_job={"a": _chaos_steps(5), "b": _chaos_steps(6)},
            plan=plan, seed=13, ckpt_root=str(tmp_path),
            checkpoint_every=2,
            serve_keys={"a": [1, 2], "b": [3, 4]})
        ra, rb = reports["a"], reports["b"]
        assert ra.faults_injected.get("checkpoint.write.torn", 0) == 1
        assert ra.crashes == 1
        assert ra.corrupt_checkpoints_skipped >= 1
        assert ra.cold_restarts == 1  # the only checkpoint was torn
        assert rb.crashes == 0
        assert rb.corrupt_checkpoints_skipped == 0
        # points_hit is attributed per job like faults_injected: a
        # replayed after its cold restart, so it performed strictly
        # more checkpoint writes than b (the old global copy made both
        # reports claim the identical union)
        assert ra.points_hit.get("checkpoint.write", 0) > \
            rb.points_hit.get("checkpoint.write", 0)
        for r in reports.values():
            assert not r.diverged

    def test_serving_lookup_fault_via_executor_control_plane(self):
        """The OTHER real site: LocalExecutor._serve_query wraps the
        batched lookup in run_recoverable — an injected transient fault
        retries and the caller still gets correct values."""
        sink = CollectSink()
        env = _pipeline(sink, n=60_000, keys=8, window=1 << 40)
        cluster = SessionCluster(quantum_records=1024)
        cluster.submit(env, "qs")
        # job-targeted: the executor's fault ctx must carry job= or this
        # where filter can never match and the plan silently no-ops
        plan = FaultPlan(rules=[
            FaultRule(pattern="serving.lookup", nth=1,
                      recoverable=True, where={"job": "qs"})])
        got = {}

        def client():
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    out = cluster.lookup_batch(
                        "qs", "window_agg(SumAggregate)", [3, 5])
                except RuntimeError:
                    return
                if all(out):
                    got["out"] = out
                    return
                time.sleep(0.01)

        with chaos.chaos_active(plan, seed=1) as ctl:
            t = threading.Thread(target=client)
            t.start()
            cluster.run(timeout_s=120)
            t.join(timeout=30)
            assert ctl.faults_injected.get("serving.lookup", 0) >= 1
            assert ctl.retries >= 1
        assert "out" in got
        for per_key in got["out"]:
            (ns, cols), = per_key.items()
            assert cols["sum_value"] > 0
