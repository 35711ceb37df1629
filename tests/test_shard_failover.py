"""Partial failover: shard-granular checkpoints, the device watchdog,
and bounded replay — lose one shard, not the job.

Covers (1) the DeviceWatchdog policy (deadline misses, declare-dead at
batch boundaries, quarantine/rebind), (2) ShardedCheckpointStorage
(per-range units, torn-unit fallback to an older checkpoint's unit,
torn-aware retention), (3) the engines' shard-loss surgery
(``lose_shard`` + ``restore_key_groups`` + metadata merge), and (4) the
end-to-end ``run_shard_loss_verify`` claim: a ``device.lost`` fault
killing 1 of N shards mid-stream (paged spill armed, forced eviction)
restores ONLY that shard's key groups, replays ONLY that range's
records (bounded by ~events/shards), and commits output bit-identical
to the fault-free single-device oracle — seed-deterministic.

Satellites pinned here too: torn-aware flat-checkpoint retention,
the global retry budget, restore-path metrics through the job metric
tree, graceful native-plane degradation, and the arbiter's dead-shard
budget.
"""

import os

import numpy as np
import pytest

from flink_tpu.chaos import injection as chaos
from flink_tpu.chaos.harness import run_shard_loss_verify
from flink_tpu.chaos.injection import (
    FaultPlan,
    FaultRule,
    RetryBudgetExhaustedError,
)
from flink_tpu.runtime.watchdog import (
    DeviceWatchdog,
    MeshStalledError,
    ShardFailedError,
)

GAP = 100


def _steps(n_steps=8, per_step=800, num_keys=3000, seed=17):
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.random(per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        out.append((keys, vals, ts, (s - 1) * 80))
    return out


def _mk_session_engine(shards=4, slots=1024):
    from flink_tpu.parallel.mesh import make_mesh
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    return MeshSessionEngine(
        GAP, SumAggregate("v"), make_mesh(shards),
        capacity_per_shard=1 << 14, max_device_slots=slots,
        max_dispatch_ahead=2)


def _mk_session_oracle():
    from flink_tpu.windowing.aggregates import SumAggregate
    from flink_tpu.windowing.sessions import SessionWindower

    return SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)


# ---------------------------------------------------------------- watchdog


class TestDeviceWatchdog:
    def test_in_deadline_sections_heartbeat_and_reset_misses(self):
        t = [0.0]
        wd = DeviceWatchdog(2, deadline_ms=10, max_misses=2,
                            clock=lambda: t[0])
        with wd.section("op", shard=0):
            t[0] += 0.005  # 5 ms < 10 ms deadline
        assert wd.deadline_misses == 0
        assert wd.sections_timed == 1
        wd.boundary_probe()  # no raise

    def test_miss_streak_declares_dead_at_boundary_only(self):
        t = [0.0]
        wd = DeviceWatchdog(2, deadline_ms=10, max_misses=2,
                            clock=lambda: t[0])
        for _ in range(2):
            with wd.section("op", shard=1):
                t[0] += 0.05  # 50 ms > deadline
        # misses recorded mid-batch, never raised there
        assert wd.deadline_misses == 2
        with pytest.raises(ShardFailedError) as ei:
            wd.boundary_probe()
        assert ei.value.shard == 1
        assert wd.quarantined == {1}
        assert wd.available(2) == 1

    def test_successful_section_resets_the_streak(self):
        t = [0.0]
        wd = DeviceWatchdog(1, deadline_ms=10, max_misses=2,
                            clock=lambda: t[0])
        with wd.section("op", shard=0):
            t[0] += 0.05
        with wd.section("op", shard=0):
            t[0] += 0.001  # healthy: streak resets
        with wd.section("op", shard=0):
            t[0] += 0.05
        wd.boundary_probe()  # 1 < max_misses: alive
        assert not wd.quarantined

    def test_whole_mesh_miss_streak_is_a_mesh_stall_not_shard_0(self):
        # SPMD sections charge every shard: a uniform streak carries NO
        # shard attribution — quarantining shard 0 would evacuate a
        # healthy device; the honest escalation is a whole-job failure
        t = [0.0]
        wd = DeviceWatchdog(3, deadline_ms=10, max_misses=1,
                            clock=lambda: t[0])
        with wd.section("op"):  # shard=-1
            t[0] += 0.05
        with pytest.raises(MeshStalledError):
            wd.boundary_probe()
        assert not wd.quarantined  # nobody was falsely declared dead

    def test_attributed_subset_miss_still_declares_that_shard(self):
        t = [0.0]
        wd = DeviceWatchdog(3, deadline_ms=10, max_misses=1,
                            clock=lambda: t[0])
        with wd.section("op", shard=2):
            t[0] += 0.05
        with pytest.raises(ShardFailedError) as ei:
            wd.boundary_probe()
        assert ei.value.shard == 2 and wd.quarantined == {2}

    def test_quarantined_device_ids_dedupe_across_watchdogs(self):
        t = [0.0]
        wd_a = DeviceWatchdog(2, deadline_ms=10, max_misses=1,
                              clock=lambda: t[0], device_ids=[5, 9])
        wd_b = DeviceWatchdog(2, deadline_ms=10, max_misses=1,
                              clock=lambda: t[0], device_ids=[5, 9])
        for wd in (wd_a, wd_b):
            with wd.section("op", shard=1):
                t[0] += 0.05
            with pytest.raises(ShardFailedError):
                wd.boundary_probe()
        # both tenants quarantined the SAME physical device
        assert wd_a.quarantined_devices | wd_b.quarantined_devices \
            == {9}

    def test_rebind_keeps_cumulative_counters(self):
        t = [0.0]
        wd = DeviceWatchdog(4, deadline_ms=10, max_misses=1,
                            clock=lambda: t[0])
        with wd.section("op", shard=2):
            t[0] += 0.05
        with pytest.raises(ShardFailedError):
            wd.boundary_probe()
        assert wd.declared_dead == 1
        wd.rebind(3)
        assert wd.num_shards == 3 and not wd.quarantined
        assert wd.declared_dead == 1  # history survives

    def test_metrics_registration(self):
        from flink_tpu.metrics import MetricRegistry

        registry = MetricRegistry()
        g = registry.root_group("job", "j")
        wd = DeviceWatchdog(2, deadline_ms=0)
        wd.register_metrics(g)
        snap = registry.snapshot()
        assert snap["job.j.watchdog.shards_quarantined"] == 0
        assert "job.j.watchdog.heartbeat_age_s" in snap


# ---------------------------------------------------- sharded checkpoints


class TestShardedCheckpointStorage:
    def _units(self, val):
        return {
            (0, 63): {"table": {"x": np.asarray([val])},
                      "next_sid": 5},
            (64, 127): {"table": {"x": np.asarray([val + 1])},
                        "next_sid": 5},
        }

    def test_roundtrip_units_and_positions(self, tmp_path):
        from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage

        st = ShardedCheckpointStorage(str(tmp_path))
        st.write_checkpoint(1, "j", self._units(10),
                            positions={(0, 63): 2, (64, 127): 2})
        assert st.latest_checkpoint_id() == 1
        assert st.unit_ranges(1) == [(0, 63), (64, 127)]
        state, pos = st.read_unit(1, (0, 63))
        assert pos == 2 and int(state["table"]["x"][0]) == 10

    def test_torn_unit_falls_back_to_that_ranges_older_unit(
            self, tmp_path):
        from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage

        st = ShardedCheckpointStorage(str(tmp_path))
        st.write_checkpoint(1, "j", self._units(10),
                            positions={(0, 63): 2, (64, 127): 2})
        st.write_checkpoint(2, "j", self._units(20),
                            positions={(0, 63): 4, (64, 127): 4})
        # tear chk-2's (0, 63) unit: flip a byte in a payload file
        unit = os.path.join(str(tmp_path), "chk-2", "shard-0-63")
        victim = next(os.path.join(unit, n) for n in os.listdir(unit)
                      if n != "manifest.json")
        with open(victim, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        # the torn RANGE falls back to chk-1; the sibling stays on chk-2
        cid, states, pos = st.latest_units_for_groups(range(0, 64))
        assert cid == 1 and pos == 2
        assert int(states[0]["table"]["x"][0]) == 10
        cid2, states2, pos2 = st.latest_units_for_groups(range(64, 128))
        assert cid2 == 2 and pos2 == 4
        newest, units, skipped = st.read_all_units_with_fallback()
        assert newest == 2 and skipped == 1
        by_range = {r: (s, p) for r, s, p in units}
        assert by_range[(0, 63)][1] == 2      # fell back
        assert by_range[(64, 127)][1] == 4    # newest

    def test_retention_never_strands_below_a_torn_newest(self, tmp_path):
        from flink_tpu.checkpoint.sharded import ShardedCheckpointStorage

        st = ShardedCheckpointStorage(str(tmp_path))
        for cid in (1, 2, 3):
            st.write_checkpoint(cid, "j", self._units(cid * 10),
                                positions={(0, 63): cid * 2,
                                           (64, 127): cid * 2})
        # tear the NEWEST checkpoint's unit
        unit = os.path.join(str(tmp_path), "chk-3", "shard-0-63")
        victim = next(os.path.join(unit, n) for n in os.listdir(unit)
                      if n != "manifest.json")
        with open(victim, "r+b") as f:
            f.truncate(4)
        st.retain(1)
        # chk-2 (the newest that VERIFIES) must survive; chk-1 may go
        assert (3 in st.checkpoint_ids()
                and 2 in st.checkpoint_ids())
        assert st.latest_units_for_groups(range(0, 64)) is not None


class TestFlatRetentionTornAware:
    def test_torn_newest_never_strands_zero_restorable(self, tmp_path):
        from flink_tpu.checkpoint.storage import CheckpointStorage

        st = CheckpointStorage(str(tmp_path))
        for cid in (1, 2, 3):
            st.write_checkpoint(cid, "j",
                                {"op": {"x": np.asarray([cid])}})
        # tear chk-3 (truncate a payload file under its manifest CRC)
        d = os.path.join(str(tmp_path), "chk-3")
        victim = next(os.path.join(d, n) for n in os.listdir(d)
                      if n != "manifest.json")
        with open(victim, "r+b") as f:
            f.truncate(4)
        st.retain(1)
        # the fallback chain below the torn newest survives: chk-2 is
        # the newest COMPLETE checkpoint and must not be GC'd
        assert st.latest_checkpoint_id(verify=True) == 2
        assert os.path.isdir(os.path.join(str(tmp_path), "chk-2"))

    def test_delta_anchor_with_corrupt_base_never_strands(
            self, tmp_path):
        from flink_tpu.checkpoint.storage import CheckpointStorage

        st = CheckpointStorage(str(tmp_path))
        st.write_checkpoint(1, "j", {"op": {"x": np.asarray([1])}})
        st.write_checkpoint(2, "j", {"op": {"x": np.asarray([2])}})
        st.write_checkpoint(
            3, "j", {"op": {"x": np.asarray([3])}},
            extra={"incremental": True, "base": 2})
        # corrupt the delta's BASE: chk-3 alone verifies, but the
        # restorable artifact (its chain) does not — anchoring it would
        # let GC delete chk-1, the only complete snapshot left
        d = os.path.join(str(tmp_path), "chk-2")
        victim = next(os.path.join(d, n) for n in os.listdir(d)
                      if n != "manifest.json")
        with open(victim, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        st.retain(1)
        assert os.path.isdir(os.path.join(str(tmp_path), "chk-1"))
        assert st.latest_checkpoint_id(verify=True) in (1, 3)

    def test_healthy_retention_still_prunes(self, tmp_path):
        from flink_tpu.checkpoint.storage import CheckpointStorage

        st = CheckpointStorage(str(tmp_path))
        for cid in (1, 2, 3):
            st.write_checkpoint(cid, "j",
                                {"op": {"x": np.asarray([cid])}})
        st.retain(2)
        assert not os.path.isdir(os.path.join(str(tmp_path), "chk-1"))
        assert st.latest_checkpoint_id(verify=True) == 3


# --------------------------------------------------- engine shard surgery


class TestEngineShardSurgery:
    def test_shard_key_groups_invert_the_routing_formula(self):
        from flink_tpu.parallel.shuffle import shard_records

        eng = _mk_session_engine(shards=4)
        ranges = eng.shard_key_groups()
        assert len(ranges) == 4
        assert ranges[0][0] == 0 and ranges[-1][1] == \
            eng.max_parallelism - 1
        keys = np.arange(5000, dtype=np.int64)
        shards = shard_records(keys, eng.P, eng.max_parallelism,
                               eng.key_group_range)
        from flink_tpu.state.keygroups import assign_key_groups

        kg = assign_key_groups(keys, eng.max_parallelism)
        for p, (g0, g1) in enumerate(ranges):
            sel = shards == p
            assert kg[sel].min() >= g0 and kg[sel].max() <= g1

    def test_lose_shard_keeps_survivors_and_drops_the_range(self):
        from tests.test_sessions import keyed_batch

        eng = _mk_session_engine(shards=4)
        keys = np.arange(0, 2000, dtype=np.int64)
        eng.process_batch(keyed_batch(
            keys, np.ones(len(keys), dtype=np.float32),
            np.zeros(len(keys), dtype=np.int64)))
        g0, g1 = eng.lose_shard(1)
        assert eng.P == 3
        from flink_tpu.state.keygroups import assign_key_groups

        # the dead range's sessions are gone from the metadata; the
        # survivors' sessions are intact
        live_groups = {
            int(g) for k in eng.meta.sessions.keys()
            for g in assign_key_groups(np.asarray([k]),
                                       eng.max_parallelism)}
        assert not any(g0 <= g <= g1 for g in live_groups)
        assert live_groups  # survivors kept
        assert eng.last_shard_loss["dead_shard"] == 1

    def test_snapshot_sharded_units_union_to_full_snapshot(self):
        from tests.test_sessions import keyed_batch

        eng = _mk_session_engine(shards=4)
        keys = np.arange(0, 2000, dtype=np.int64)
        eng.process_batch(keyed_batch(
            keys, np.ones(len(keys), dtype=np.float32),
            np.zeros(len(keys), dtype=np.int64)))
        full = eng.snapshot(mode="savepoint")
        units = eng.snapshot_sharded(mode="savepoint")
        assert set(units) == set(
            (g0, g1) for g0, g1 in eng.shard_key_groups())
        merged = eng.merge_unit_snapshots(list(units.values()))
        # same rows (order may differ per unit split): compare sorted
        def rows(t):
            return sorted(zip(np.asarray(t["key_id"]).tolist(),
                              np.asarray(t["namespace"]).tolist(),
                              np.asarray(t["leaf_0"]).tolist()))

        assert rows(merged["table"]) == rows(full["table"])
        assert merged["next_sid"] == full["next_sid"]
        assert len(merged["sessions"]) == len(full["sessions"])


# ---------------------------------------------------- end-to-end failover


class TestRunShardLossVerify:
    def _plan_loss_mid_stream(self, shard=1, nth=11):
        return FaultPlan(rules=[
            FaultRule(pattern="device.lost", nth=nth,
                      where={"shard": shard})])

    def test_session_engine_partial_failover_oracle_identical(
            self, tmp_path):
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(),
            self._plan_loss_mid_stream(), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.shards_lost == 1
        assert report.shard_restores == 1
        # bounded replay: only the dead range's records, only since its
        # unit's position — about events/(shards * steps) per replayed
        # step, and never the whole stream
        assert 0 < report.records_replayed <= report.events // 4
        assert report.shard_loss_recovery_ms > 0

    def test_forced_eviction_stays_on_the_path(self, tmp_path):
        # the paged spill must genuinely engage (the acceptance shape)
        holder = {}

        def mk():
            holder["eng"] = _mk_session_engine(slots=1024)
            return holder["eng"]

        report = run_shard_loss_verify(
            mk, _mk_session_oracle, _steps(num_keys=6000,
                                           per_step=1500),
            self._plan_loss_mid_stream(), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert holder["eng"].spill_counters()["rows_evicted"] > 0

    def test_seed_deterministic_signature(self, tmp_path):
        sigs = []
        for i in range(2):
            r = run_shard_loss_verify(
                _mk_session_engine, _mk_session_oracle, _steps(),
                self._plan_loss_mid_stream(), seed=7,
                ckpt_root=str(tmp_path / f"c{i}"), checkpoint_every=2)
            sigs.append(r.signature())
        assert sigs[0] == sigs[1]
        assert sigs[0]["shards_lost"] == 1

    def test_torn_unit_falls_back_and_replays_further(self, tmp_path):
        # chk-3 (pos 6) shard-1 unit torn; shard 1 dies after it: the
        # range restores from chk-2@pos4 and replays [4, ...) — more
        # replay than the healthy case, still only ITS range
        plan = FaultPlan(rules=[
            FaultRule(pattern="checkpoint.write.torn", nth=10,
                      kind="drop"),
            FaultRule(pattern="device.lost", nth=15,
                      where={"shard": 1})])
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(), plan,
            seed=7, ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.shard_restores == 1
        assert report.records_replayed > 0

    def test_crash_takes_whole_job_path_with_unit_fallback(
            self, tmp_path):
        # a corrupt unit in the newest checkpoint + an engine crash:
        # whole-job restore assembles mixed-age units and gates the
        # catch-up replay; output stays oracle-identical
        plan = FaultPlan(rules=[
            FaultRule(pattern="checkpoint.write.torn", nth=10,
                      kind="corrupt"),
            FaultRule(pattern="mesh.session_fire", nth=5,
                      kind="raise")])
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(), plan,
            seed=7, ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.crashes == 1 and report.restores == 1
        assert report.corrupt_checkpoints_skipped == 1

    def test_loss_before_first_checkpoint_replays_cold(self, tmp_path):
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(),
            self._plan_loss_mid_stream(nth=1), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.shards_lost == 1
        assert report.shard_restores == 0  # nothing checkpointed yet

    def test_window_engine_partial_failover(self, tmp_path):
        # the protocol is engine-agnostic: tumbling mesh windows lose a
        # shard mid-stream; the book merge re-opens the windows the
        # restored range must re-fire
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows
        from flink_tpu.windowing.windower import SliceSharedWindower

        def mk_engine():
            return MeshWindowEngine(
                TumblingEventTimeWindows.of(100), SumAggregate("v"),
                make_mesh(4), capacity_per_shard=1 << 14)

        def mk_oracle():
            return SliceSharedWindower(
                TumblingEventTimeWindows.of(100), SumAggregate("v"),
                capacity=1 << 15)

        report = run_shard_loss_verify(
            mk_engine, mk_oracle, _steps(),
            self._plan_loss_mid_stream(), seed=7,
            ckpt_root=str(tmp_path / "c"), checkpoint_every=2)
        assert not report.diverged
        assert report.shards_lost == 1 and report.shard_restores == 1
        assert 0 < report.records_replayed <= report.events // 4


# ------------------------------------------------------ satellite: budget


class TestGlobalRetryBudget:
    def test_budget_exhaustion_escalates_to_real_failure(self):
        plan = FaultPlan(
            rules=[FaultRule(pattern="spill.page_reload", every=1,
                             kind="raise", recoverable=True,
                             max_injections=0)],
            retry_max_attempts=100, retry_budget_total=3)
        calls = {"n": 0}

        def attempt():
            calls["n"] += 1
            chaos.fault_point("spill.page_reload", page=1)
            return "ok"

        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(RetryBudgetExhaustedError):
                chaos.run_recoverable("spill.page_reload", attempt)
            assert c.retries == 3
            assert c.budget_exhausted == 1
            assert c.counters()["retry_budget_exhausted"] == 1

    def test_unlimited_budget_keeps_per_site_semantics(self):
        plan = FaultPlan(rules=[
            FaultRule(pattern="x", nth=1, kind="raise",
                      recoverable=True)])
        with chaos.chaos_active(plan, seed=0) as c:
            out = chaos.run_recoverable(
                "x", lambda: chaos.fault_point("x") or 41)
            assert out == 41
            assert c.retries == 1 and c.recoveries == 1
            assert c.budget_exhausted == 0

    def test_budget_counts_across_sites(self):
        plan = FaultPlan(
            rules=[FaultRule(pattern="*", every=1, kind="raise",
                             recoverable=True, max_injections=0)],
            retry_budget_total=2)
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises((RetryBudgetExhaustedError,
                                chaos.InjectedFault)):
                chaos.run_recoverable(
                    "a.one", lambda: chaos.fault_point("a.one"))
                chaos.run_recoverable(
                    "a.two", lambda: chaos.fault_point("a.two"))
            assert c.budget_exhausted >= 0  # escalation is budgeted
            assert c.retries <= 2

    def test_budget_gauge_in_chaos_metric_group(self):
        from flink_tpu.metrics import MetricRegistry

        plan = FaultPlan(rules=[
            FaultRule(pattern="spill.page_reload", nth=1)],
            retry_budget_total=1)
        registry = MetricRegistry()
        g = registry.root_group("job", "j")
        with chaos.chaos_active(plan, seed=0):
            chaos.register_chaos_metrics(g)
            snap = registry.snapshot()
            assert snap["job.j.chaos.retry_budget_exhausted"] == 0


# ----------------------------------------------- satellite: restore metrics


class TestRestorePathMetrics:
    def test_harness_counters_surface_through_metric_tree(
            self, tmp_path):
        from flink_tpu.metrics import MetricRegistry

        registry = MetricRegistry()
        group = registry.root_group("job", "shard-loss")
        plan = FaultPlan(rules=[
            FaultRule(pattern="device.lost", nth=11,
                      where={"shard": 1})])
        report = run_shard_loss_verify(
            _mk_session_engine, _mk_session_oracle, _steps(), plan,
            seed=7, ckpt_root=str(tmp_path / "c"), checkpoint_every=2,
            metric_group=group)
        snap = registry.snapshot()
        assert snap["job.shard-loss.chaos.shard_restores"] == \
            report.shard_restores == 1
        assert snap["job.shard-loss.chaos.records_replayed"] == \
            report.records_replayed > 0
        assert snap["job.shard-loss.chaos.restores"] == report.restores
        assert snap["job.shard-loss.chaos.corrupt_checkpoints_skipped"] \
            == report.corrupt_checkpoints_skipped

    def test_crash_restore_verify_also_registers(self, tmp_path):
        from flink_tpu.chaos.harness import run_crash_restore_verify
        from flink_tpu.metrics import MetricRegistry

        registry = MetricRegistry()
        group = registry.root_group("job", "crv")
        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.session_fire", nth=2)])
        report = run_crash_restore_verify(
            lambda: _mk_session_engine(shards=2), _mk_session_oracle,
            _steps(n_steps=6, per_step=300, num_keys=500), plan,
            seed=3, ckpt_root=str(tmp_path / "c"), checkpoint_every=2,
            metric_group=group)
        snap = registry.snapshot()
        assert snap["job.crv.chaos.restores"] == report.restores >= 1


# ------------------------------------------- satellite: native degradation


class TestNativePlaneDegradation:
    def test_build_failure_falls_back_loudly_with_identical_output(
            self, monkeypatch):
        import flink_tpu.native as native
        import flink_tpu.windowing.session_meta as sm
        from flink_tpu.windowing.session_meta import (
            SessionIntervalSet,
            make_session_meta,
        )

        from tests.test_sessions import keyed_batch

        # baseline: an engine on whatever plane the container selects
        eng_ref = _mk_session_engine(shards=2)
        # forced build failure: the loader reports unavailable while
        # native was NOT explicitly disabled
        native.reset_fallbacks_for_testing()
        monkeypatch.setattr(native, "sessions_available", lambda: False)
        monkeypatch.setattr(native, "native_disabled", lambda: False)
        with pytest.warns(RuntimeWarning, match="degraded to Python"):
            meta = make_session_meta(GAP, 0)
        assert type(meta) is SessionIntervalSet
        assert native.native_fallbacks() >= 1
        # output identity: the degraded engine's fires equal the
        # reference engine's row for row
        eng_fb = _mk_session_engine(shards=2)
        assert type(eng_fb.meta) is SessionIntervalSet
        keys = np.arange(0, 400, dtype=np.int64)
        vals = np.ones(400, dtype=np.float32)
        ts = np.arange(400, dtype=np.int64) % 50
        for eng in (eng_ref, eng_fb):
            eng.process_batch(keyed_batch(keys, vals, ts))
        fired_ref = eng_ref.on_watermark(1 << 60)
        fired_fb = eng_fb.on_watermark(1 << 60)
        rows_ref = sorted(tuple(sorted(r.items()))
                          for b in fired_ref for r in b.to_rows())
        rows_fb = sorted(tuple(sorted(r.items()))
                         for b in fired_fb for r in b.to_rows())
        assert rows_ref == rows_fb
        native.reset_fallbacks_for_testing()

    def test_runtime_sweep_failure_degrades_once_not_crash(self):
        from flink_tpu.windowing.session_meta import (
            NativePlaneError,
            SessionIntervalSet,
        )

        from tests.test_sessions import keyed_batch

        import flink_tpu.native as native

        native.reset_fallbacks_for_testing()
        eng = _mk_session_engine(shards=2)
        oracle = _mk_session_oracle()
        # wrap the CURRENT meta so its next absorb raises like a failed
        # C sweep AFTER partially registering the batch's sessions —
        # the engine must degrade to the Python plane and finish the
        # batch, not crash it
        inner = eng.meta
        real_absorb = inner.absorb_batch_ex
        state = {"armed": True}

        def failing_absorb(keys, ts, want_fresh=True):
            if state["armed"]:
                state["armed"] = False
                real_absorb(keys[: len(keys) // 2],
                            ts[: len(ts) // 2], want_fresh=want_fresh)
                raise NativePlaneError("injected sweep failure")
            return real_absorb(keys, ts, want_fresh=want_fresh)

        inner.absorb_batch_ex = failing_absorb
        steps = _steps(n_steps=4, per_step=300, num_keys=500)
        eng_fired = []
        with pytest.warns(RuntimeWarning, match="degraded"):
            for keys, vals, ts, wm in steps:
                eng.process_batch(keyed_batch(keys, vals, ts))
                eng_fired.extend(eng.on_watermark(int(wm)))
        assert type(eng.meta) is SessionIntervalSet
        assert native.native_fallbacks() >= 1
        # output correctness: the fired windows equal the oracle's
        from flink_tpu.core.records import KEY_ID_FIELD
        from flink_tpu.windowing.windower import (
            WINDOW_END_FIELD,
            WINDOW_START_FIELD,
        )

        def fold(fired, out):
            for b in fired:
                for r in b.to_rows():
                    out[(int(r[KEY_ID_FIELD]),
                         int(r[WINDOW_START_FIELD]),
                         int(r[WINDOW_END_FIELD]))] = float(r["sum_v"])

        expected = {}
        got = {}
        for keys, vals, ts, wm in steps:
            oracle.process_batch(keyed_batch(keys, vals, ts))
            fold(oracle.on_watermark(int(wm)), expected)
        fold(oracle.on_watermark(1 << 60), expected)
        fold(eng_fired, got)
        fold(eng.on_watermark(1 << 60), got)
        assert set(got) == set(expected)
        for k in expected:
            assert got[k] == pytest.approx(expected[k], rel=1e-4)
        native.reset_fallbacks_for_testing()


# --------------------------------------------- satellite: arbiter budget


class TestArbiterDeadShardBudget:
    def test_dead_shards_shrink_the_divided_budget(self):
        from flink_tpu.tenancy.arbiter import JobDemand, ShardArbiter

        demands = [
            JobDemand(job="a", current_shards=4, backlog=1000),
            JobDemand(job="b", current_shards=4, backlog=1000),
        ]
        arb = ShardArbiter(total_shards=8, cooldown_ticks=0)
        healthy = arb.decide(demands)
        assert sum(healthy.values()) == 8
        arb2 = ShardArbiter(total_shards=8, cooldown_ticks=0)
        degraded = arb2.decide(demands, dead_shards=2)
        assert sum(degraded.values()) <= 6


# --------------------------------------------------- executor integration


class TestExecutorWatchdogWiring:
    def test_watchdog_enabled_attaches_and_registers_gauges(self):
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        config = Configuration({
            "watchdog.enabled": True,
            "watchdog.deadline-ms": 10_000,
            "parallelism.default": 2,
        })
        env = StreamExecutionEnvironment(config)
        sink = CollectSink()
        rows = [{"k": i % 7, "v": 1, "ts": i * 10} for i in range(300)]
        env.from_collection(rows, timestamp_field="ts") \
            .key_by("k").window(TumblingEventTimeWindows.of(500)) \
            .sum("v").sink_to(sink)
        result = env.execute("wd-job")
        snap = result.registry.snapshot()
        assert "job.wd-job.watchdog.shards_quarantined" in snap
        assert snap["job.wd-job.watchdog.sections_timed"] > 0
        assert snap["job.wd-job.watchdog.deadline_misses"] == 0
        assert sink.batches  # the job genuinely ran on the mesh path
