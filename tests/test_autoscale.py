"""Autoscaler: live key-group migration + DS2-style policy.

The reshard tests pin both mesh engines' mid-stream rescale (4 -> 8 ->
2, no stop-redeploy, paged spill under forced eviction) row-for-row to
the never-rescaled single-device oracle; the chaos test proves the
handoff stays exactly-once under an injected crash (restore from the
latest checkpoint, replay, re-rescale). The policy suite drives
hysteresis / cooldown / bounds / backlog thresholds / the skew guard
with an injectable clock — pure arithmetic, no devices.
"""

import numpy as np
import pytest

from flink_tpu.autoscale.controller import (
    AutoscaleController,
    SignalSample,
)
from flink_tpu.autoscale.policy import PolicyInput, ScalingPolicy
from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.assigners import TumblingEventTimeWindows
from flink_tpu.windowing.sessions import SessionWindower
from flink_tpu.windowing.windower import SliceSharedWindower

GAP = 100


def keyed_batch(keys, vals, ts):
    return RecordBatch.from_pydict(
        {KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
         "v": np.asarray(vals, dtype=np.float32)},
        timestamps=np.asarray(ts, dtype=np.int64))


def _stream(num_keys=9_000, n_steps=8, per_step=4_000, seed=17):
    """Live state well past a 1024-slot/shard budget so eviction,
    reload and the reshard's resident/cold split are all on the path."""
    rng = np.random.default_rng(seed)
    steps = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.random(per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        steps.append((keys, vals, ts, (s - 1) * 80))
    return steps


def _run(engine, steps, reshards=None):
    """Drive steps; reshards = {step index -> shard count} applied
    BEFORE that step (mid-stream, state live)."""
    fired = []
    for i, (keys, vals, ts, wm) in enumerate(steps):
        if reshards and i in reshards:
            report = engine.reshard(reshards[i])
            assert report["to"] == reshards[i]
            assert engine.P == reshards[i]
        engine.process_batch(keyed_batch(keys, vals, ts))
        fired.extend(engine.on_watermark(wm))
    fired.extend(engine.on_watermark(1 << 60))
    out = {}
    for b in fired:
        for r in b.to_rows():
            out[(r[KEY_ID_FIELD], r["window_start"],
                 r["window_end"])] = r["sum_v"]
    return out


def _assert_equal(got, expected):
    assert len(expected) > 0
    assert set(got) == set(expected)
    for k in expected:
        assert got[k] == pytest.approx(expected[k], rel=1e-4,
                                       abs=1e-3), k


def _session_engine(mesh, **kw):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(GAP, SumAggregate("v"), mesh,
                             capacity_per_shard=1 << 14, **kw)


def _window_engine(mesh, **kw):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine

    return MeshWindowEngine(TumblingEventTimeWindows.of(100),
                            SumAggregate("v"), mesh,
                            capacity_per_shard=1 << 14, **kw)


# ---------------------------------------------------------------------------
# live reshard: oracle equivalence
# ---------------------------------------------------------------------------


class TestLiveReshard:
    def test_session_paged_forced_eviction_up_and_down(self):
        """Paged spill, 1024 slots/shard vs ~9k live sessions: rescale
        4 -> 8 mid-stream, then 8 -> 2, results row-for-row equal to the
        never-rescaled single-device oracle."""
        steps = _stream()
        eng = _session_engine(make_mesh(4), max_device_slots=1024)
        oracle = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        got = _run(eng, steps, reshards={3: 8, 6: 2})
        _assert_equal(got, _run(oracle, steps))
        assert eng.reshards_completed == 2
        assert eng.P == 2
        # the handoff itself moved state both ways: some rows landed
        # resident, the overflow (2 shards x 1024 budget) went cold
        assert eng.last_reshard["rows_moved"] > 2048
        assert eng.last_reshard["spilled_rows"] > 0
        c = eng.spill_counters()
        assert c["pages_evicted"] > 0 and c["pages_reloaded"] > 0

    def test_session_namespace_layout_reshard(self):
        steps = _stream(seed=23)
        eng = _session_engine(make_mesh(4), max_device_slots=1024,
                              spill_layout="namespaces")
        oracle = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        got = _run(eng, steps, reshards={4: 2})
        _assert_equal(got, _run(oracle, steps))
        assert eng.P == 2

    def test_window_engine_up_and_down(self):
        steps = _stream(seed=5)
        eng = _window_engine(make_mesh(4))
        oracle = SliceSharedWindower(TumblingEventTimeWindows.of(100),
                                     SumAggregate("v"), capacity=1 << 15)
        got = _run(eng, steps, reshards={2: 8, 5: 2})
        _assert_equal(got, _run(oracle, steps))
        assert eng.reshards_completed == 2

    def test_window_engine_budgeted_scale_down(self):
        """Scale-down under a namespace-layout budget: whole namespaces
        either stay resident or land in the new shards' spill tiers —
        never split (a split namespace would double-apply on reload)."""
        steps = _stream(seed=5)
        eng = _window_engine(make_mesh(8), max_device_slots=2048)
        oracle = SliceSharedWindower(TumblingEventTimeWindows.of(100),
                                     SumAggregate("v"), capacity=1 << 15)
        got = _run(eng, steps, reshards={4: 2})
        _assert_equal(got, _run(oracle, steps))
        for p in range(eng.P):
            resident_ns = {int(n) for n in eng.indexes[p].namespaces
                           if len(eng.indexes[p].slots_for_namespace(
                               int(n)))}
            spilled_ns = {int(n) for n in eng.spills[p].namespaces}
            assert not (resident_ns & spilled_ns)

    def test_reshard_preserves_dirty_rows_for_delta(self):
        """A reshard between two delta checkpoints must not lose the
        dirty rows: full + delta(s) across the reshard materializes to
        the same logical rows as a straight full snapshot."""
        from flink_tpu.checkpoint.storage import apply_table_delta

        steps = _stream(seed=31, n_steps=6)
        eng = _session_engine(make_mesh(4), max_device_slots=1024)
        for keys, vals, ts, wm in steps[:2]:
            eng.process_batch(keyed_batch(keys, vals, ts))
            eng.on_watermark(wm)
        acc = dict(eng.snapshot()["table"])  # full base, dirty reset
        for keys, vals, ts, wm in steps[2:4]:
            eng.process_batch(keyed_batch(keys, vals, ts))
            eng.on_watermark(wm)
        eng.reshard(8)  # dirty rows + freed tombstones must survive
        acc = apply_table_delta(acc, eng.snapshot(mode="delta")["table"])
        for keys, vals, ts, wm in steps[4:]:
            eng.process_batch(keyed_batch(keys, vals, ts))
            eng.on_watermark(wm)
        eng.reshard(2)
        acc = apply_table_delta(acc, eng.snapshot(mode="delta")["table"])
        full = eng.snapshot(mode="savepoint")["table"]

        def rows(t):
            return {(int(k), int(n)): float(v) for k, n, v in
                    zip(t["key_id"], t["namespace"], t["leaf_0"])}

        assert rows(acc) == rows(full)

    def test_reshard_validation(self):
        eng = _window_engine(make_mesh(2), max_parallelism=8)
        with pytest.raises(ValueError, match="max_parallelism"):
            eng.reshard(16)
        with pytest.raises(ValueError):
            eng.reshard(0)
        report = eng.reshard(2)  # no-op
        assert report.get("noop")
        assert eng.reshards_completed == 0

    def test_reshard_keeps_counters_monotonic_and_reclaims_fs(
            self, tmp_path):
        """The job-lifetime spill counters must not reset when the mesh
        resizes, and the OLD tiers' fs-resident pages must be reclaimed
        (not orphaned) — every file on disk after the reshard belongs
        to a live tier."""
        import glob
        import os

        spill_dir = str(tmp_path / "spill")
        steps = _stream()
        # ~1KB host budget per shard (pages are ~20KB): every spilled
        # page overflows to the fs tier
        eng = _session_engine(make_mesh(4), max_device_slots=1024,
                              spill_dir=spill_dir,
                              spill_host_max_bytes=4096)
        for keys, vals, ts, wm in steps[:4]:
            eng.process_batch(keyed_batch(keys, vals, ts))
            eng.on_watermark(wm)
        before = eng.spill_counters()
        assert before["pages_evicted"] > 0
        assert glob.glob(os.path.join(spill_dir, "**", "*.npz"),
                         recursive=True)
        eng.reshard(8)
        after = eng.spill_counters()
        for name, v in before.items():
            assert after[name] >= v, name  # monotonic across the move
        on_disk = {
            os.path.abspath(p) for p in glob.glob(
                os.path.join(spill_dir, "**", "*.npz"), recursive=True)}
        referenced = {
            os.path.abspath(path.split("://")[-1])
            for sp in eng.spills for path in sp._fs.values()}
        assert on_disk == referenced  # no orphans from the old tiers
        # and the engine still works against the fs tier afterwards
        oracle = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        got = _run(eng, steps[4:])
        for keys, vals, ts, wm in steps[:4]:
            oracle.process_batch(keyed_batch(keys, vals, ts))
            oracle.on_watermark(wm)
        _assert_equal(got, _run(oracle, steps[4:]))

    def test_key_imbalance_matches_policy_definition(self):
        """One formula: the engine gauge IS the policy's skew guard."""
        from flink_tpu.autoscale.policy import key_imbalance

        eng = _session_engine(make_mesh(4))
        keys, vals, ts, wm = _stream()[0]
        eng.process_batch(keyed_batch(keys, vals, ts))
        assert eng.key_imbalance() == key_imbalance(
            eng.shard_resident_rows())
        assert ScalingPolicy.imbalance((10, 10)) == key_imbalance(
            (10, 10))

    def test_key_imbalance_gauge(self):
        eng = _session_engine(make_mesh(4))
        assert eng.key_imbalance() == 1.0  # empty = balanced
        keys, vals, ts, wm = _stream()[0]
        eng.process_batch(keyed_batch(keys, vals, ts))
        rows = eng.shard_resident_rows()
        assert sum(rows) > 0
        expected = max(rows) * len(rows) / sum(rows)
        assert eng.key_imbalance() == pytest.approx(expected)


# ---------------------------------------------------------------------------
# chaos: a crashed handoff stays exactly-once
# ---------------------------------------------------------------------------


class TestReshardUnderChaos:
    def test_mid_stream_rescale_with_crashes_is_exactly_once(
            self, tmp_path):
        """4 -> 8 -> 2 mid-stream with (1) a crash at the hardest
        handoff point (state lifted, new plane empty) and (2) a later
        engine crash: committed output stays bit-identical to the
        fault-free single-device oracle, and the harness replays
        through at least one LIVE handoff."""
        from flink_tpu.chaos.harness import run_crash_restore_verify
        from flink_tpu.chaos.injection import FaultPlan, FaultRule

        mesh = make_mesh(4)
        steps = _stream(num_keys=5_000, per_step=1_500)
        plan = FaultPlan(rules=[
            FaultRule(pattern="rescale.handoff", nth=2, kind="raise",
                      where={"stage": "commit"}),
            FaultRule(pattern="mesh.dispatch_fence", nth=8,
                      kind="raise"),
        ])

        def make_engine():
            return _session_engine(mesh, max_device_slots=1024)

        def make_oracle():
            return SessionWindower(GAP, SumAggregate("v"),
                                   capacity=1 << 15)

        report = run_crash_restore_verify(
            make_engine, make_oracle, steps, plan, seed=11,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2,
            rescales={2: 8, 6: 2})
        assert not report.diverged
        assert report.crashes == 2
        assert "rescale.handoff" in report.faults_injected
        assert report.live_handoffs >= 1
        assert report.restores >= 1

    def test_rescale_determinism(self, tmp_path):
        """Same (plan, seed, steps, rescales) -> identical signature."""
        from flink_tpu.chaos.harness import run_crash_restore_verify
        from flink_tpu.chaos.injection import FaultPlan, FaultRule

        mesh = make_mesh(4)
        steps = _stream(num_keys=3_000, per_step=800, n_steps=6)
        sigs = []
        for rep in range(2):
            plan = FaultPlan(rules=[
                FaultRule(pattern="rescale.handoff", nth=1,
                          kind="raise")])
            report = run_crash_restore_verify(
                lambda: _session_engine(mesh, max_device_slots=1024),
                lambda: SessionWindower(GAP, SumAggregate("v"),
                                        capacity=1 << 15),
                steps, plan, seed=3,
                ckpt_root=str(tmp_path / f"ckpt-{rep}"),
                checkpoint_every=2, rescales={3: 8})
            sigs.append(report.signature())
        assert sigs[0] == sigs[1]


class TestRebalanceUnderChaos:
    def test_mid_stream_rebalance_crash_at_commit_is_exactly_once(
            self, tmp_path):
        """A skew-driven key-group MOVE (unchanged P) crashed at the
        hardest point — commit: the hot range's rows are lifted off the
        old layout, the plane is rebuilt, nothing redistributed yet.
        Committed output stays bit-identical to the fault-free oracle:
        the assignment is runtime routing state, so the restored engine
        comes back contiguous and re-applies the move on replay."""
        from flink_tpu.chaos.harness import run_crash_restore_verify
        from flink_tpu.chaos.injection import FaultPlan, FaultRule

        mesh = make_mesh(4)
        steps = _stream(num_keys=5_000, per_step=1_500)
        plan = FaultPlan(rules=[
            FaultRule(pattern="rebalance.handoff", nth=1, kind="raise",
                      where={"stage": "commit"}),
        ])

        def move_first_groups(engine):
            cur = engine.key_group_assignment
            src = int(cur.table[0])
            groups = np.nonzero(cur.table == src)[0][:8] + cur.first
            return cur.move(groups, (src + 1) % engine.P)

        report = run_crash_restore_verify(
            lambda: _session_engine(mesh, max_device_slots=1024),
            lambda: SessionWindower(GAP, SumAggregate("v"),
                                    capacity=1 << 15),
            steps, plan, seed=11,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2,
            rebalances={3: move_first_groups})
        assert not report.diverged
        assert report.crashes == 1
        assert report.faults_injected.get("rebalance.handoff", 0) == 1
        assert report.live_handoffs >= 1  # the re-applied move
        assert report.restores >= 1


# ---------------------------------------------------------------------------
# policy unit suite (injectable clock, no devices)
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _inp(cur=4, rate=1000.0, busy=0.7, backlog=0.0, growth=0.0,
         rows=()):
    return PolicyInput(current_shards=cur, processing_rate=rate,
                       busy_fraction=busy, backlog=backlog,
                       backlog_growth=growth, shard_resident_rows=rows)


class TestScalingPolicy:
    def test_no_signal_keeps(self):
        p = ScalingPolicy(clock=FakeClock())
        assert p.decide(_inp(rate=0.0)).reason == "no-signal"
        assert p.decide(_inp(busy=0.0)).reason == "no-signal"

    def test_steady_at_target_utilization(self):
        # busy == utilization target -> required == capacity * target
        p = ScalingPolicy(utilization_target=0.7, clock=FakeClock())
        d = p.decide(_inp(cur=4, rate=1000.0, busy=0.7))
        assert d.target == 4 and d.reason == "steady"

    def test_scale_up_when_saturated(self):
        # busy ~1.0: true rate == observed rate; required/target-rate
        # = 1/0.5 = 2x shards
        p = ScalingPolicy(utilization_target=0.5, hysteresis=0.25,
                          cooldown_s=0, clock=FakeClock())
        d = p.decide(_inp(cur=4, rate=1000.0, busy=1.0))
        assert d.target == 8 and d.reason == "scale-up"

    def test_backlog_growth_forces_scale_up(self):
        p = ScalingPolicy(utilization_target=0.8, hysteresis=0.1,
                          cooldown_s=0, clock=FakeClock())
        calm = p.decide(_inp(cur=4, rate=1000.0, busy=0.8))
        assert calm.reason == "steady"
        d = p.decide(_inp(cur=4, rate=1000.0, busy=0.8, growth=900.0))
        assert d.reason == "scale-up" and d.target > 4

    def test_standing_backlog_drains_within_horizon(self):
        p = ScalingPolicy(utilization_target=0.8, hysteresis=0.1,
                          cooldown_s=0, backlog_drain_s=10.0,
                          clock=FakeClock())
        # 20k backlog / 10 s = +2000 rec/s on top of 1000 arriving
        d = p.decide(_inp(cur=4, rate=1000.0, busy=0.8, backlog=20_000))
        assert d.reason == "scale-up" and d.target >= 8

    def test_hysteresis_dead_band(self):
        p = ScalingPolicy(utilization_target=0.7, hysteresis=0.3,
                          cooldown_s=0, clock=FakeClock())
        # target would be 5 (25% over 4): inside the 30% band -> stay
        d = p.decide(_inp(cur=4, rate=1000.0, busy=0.85))
        assert d.target == 4 and d.reason == "hysteresis"

    def test_cooldown_blocks_then_allows(self):
        clk = FakeClock()
        p = ScalingPolicy(utilization_target=0.5, hysteresis=0.1,
                          cooldown_s=30.0, clock=clk)
        saturated = _inp(cur=4, rate=1000.0, busy=1.0)
        assert p.decide(saturated).reason == "scale-up"
        p.mark_rescaled()
        clk.advance(10.0)
        assert p.decide(saturated).reason == "cooldown"
        clk.advance(25.0)  # past the 30 s cooldown
        assert p.decide(saturated).reason == "scale-up"

    def test_scale_down_when_idle(self):
        p = ScalingPolicy(utilization_target=0.7, hysteresis=0.25,
                          cooldown_s=0, clock=FakeClock())
        d = p.decide(_inp(cur=8, rate=1000.0, busy=0.2,
                          rows=(10, 10, 10, 10, 10, 10, 10, 10)))
        assert d.reason == "scale-down" and d.target < 8

    def test_imbalance_refuses_scale_down(self):
        """The hot shard explains the load: max/mean above the limit
        vetoes the scale-down the rate math asks for."""
        p = ScalingPolicy(utilization_target=0.7, hysteresis=0.25,
                          cooldown_s=0, imbalance_limit=2.0,
                          clock=FakeClock())
        skewed = (1000, 10, 10, 10, 10, 10, 10, 10)
        d = p.decide(_inp(cur=8, rate=1000.0, busy=0.2, rows=skewed))
        assert d.reason == "imbalance" and d.target == 8
        balanced = (100,) * 8
        d2 = p.decide(_inp(cur=8, rate=1000.0, busy=0.2, rows=balanced))
        assert d2.reason == "scale-down"

    def test_bounds_enforced_immediately(self):
        p = ScalingPolicy(min_shards=4, max_shards=8, cooldown_s=0,
                          clock=FakeClock())
        assert p.decide(_inp(cur=2, rate=0.0)).target == 4
        assert p.decide(_inp(cur=2, rate=0.0)).reason == "bounds"
        assert p.decide(_inp(cur=16, rate=0.0)).target == 8

    def test_target_clamped_to_max(self):
        p = ScalingPolicy(utilization_target=0.5, hysteresis=0.1,
                          cooldown_s=0, max_shards=6, clock=FakeClock())
        d = p.decide(_inp(cur=4, rate=1000.0, busy=1.0))  # raw target 8
        assert d.target == 6

    def test_rejects_bad_config(self):
        with pytest.raises(ValueError):
            ScalingPolicy(utilization_target=0.0)
        with pytest.raises(ValueError):
            ScalingPolicy(min_shards=4, max_shards=2)


# ---------------------------------------------------------------------------
# controller
# ---------------------------------------------------------------------------


class _FakeEngine:
    def __init__(self, shards=2):
        self.P = shards
        self.calls = []

    def reshard(self, n):
        self.calls.append(n)
        old, self.P = self.P, n
        return {"from": old, "to": n, "rows_moved": 123,
                "seconds": 0.01}


class TestAutoscaleController:
    def test_differentiates_samples_and_rescales_live(self):
        clk = FakeClock()
        eng = _FakeEngine(shards=2)
        samples = iter([
            SignalSample(records_total=0, busy_ms_total=0),
            # +10k records over 10 s at 100% busy -> saturated
            SignalSample(records_total=10_000, busy_ms_total=10_000),
        ])
        ctl = AutoscaleController(
            ScalingPolicy(utilization_target=0.5, hysteresis=0.1,
                          cooldown_s=0, clock=clk),
            sample_fn=lambda: next(samples), engine=eng,
            interval_s=0.0, clock=clk)
        assert ctl.tick() is None  # first sample: no rate yet
        clk.advance(10.0)
        event = ctl.tick()
        assert event is not None and event.mode == "live"
        assert eng.calls == [4]  # 2 shards at 100% busy, target 0.5
        assert ctl.live_handoffs == 1
        assert event.rows_moved == 123

    def test_interval_gates_ticks(self):
        clk = FakeClock()
        calls = []

        def sample():
            calls.append(1)
            return SignalSample()

        ctl = AutoscaleController(
            ScalingPolicy(clock=clk), sample_fn=sample,
            engine=_FakeEngine(), interval_s=5.0, clock=clk)
        ctl.tick()
        clk.advance(1.0)
        ctl.tick()  # inside the interval: not even sampled
        assert len(calls) == 1
        clk.advance(5.0)
        ctl.tick()
        assert len(calls) == 2

    def test_cold_path_via_job(self):
        clk = FakeClock()

        class FakeJob:
            current_parallelism = 2

            def __init__(self):
                self.requests = []

            def request_rescale(self, n):
                self.requests.append(n)
                self.current_parallelism = n
                return True

        job = FakeJob()
        samples = iter([SignalSample(0, 0),
                        SignalSample(10_000, 10_000)])
        ctl = AutoscaleController(
            ScalingPolicy(utilization_target=0.5, hysteresis=0.1,
                          cooldown_s=0, clock=clk),
            sample_fn=lambda: next(samples), job=job,
            interval_s=0.0, clock=clk)
        ctl.tick()
        clk.advance(10.0)
        event = ctl.tick()
        assert event is not None and event.mode == "cold"
        assert job.requests == [4]

    def test_refused_cold_rescale_does_not_burn_cooldown(self):
        clk = FakeClock()

        class RefusingJob:
            current_parallelism = 2

            def request_rescale(self, n):
                return False  # e.g. no checkpointing configured

        samples = iter([SignalSample(0, 0),
                        SignalSample(10_000, 10_000)])
        policy = ScalingPolicy(utilization_target=0.5, hysteresis=0.1,
                               cooldown_s=60.0, clock=clk)
        ctl = AutoscaleController(
            policy, sample_fn=lambda: next(samples), job=RefusingJob(),
            interval_s=0.0, clock=clk)
        ctl.tick()
        clk.advance(10.0)
        assert ctl.tick() is None
        assert not policy.in_cooldown()
        assert ctl.events == []

    def test_requires_exactly_one_mechanism(self):
        with pytest.raises(ValueError):
            AutoscaleController(ScalingPolicy(),
                                sample_fn=SignalSample)
        with pytest.raises(ValueError):
            AutoscaleController(ScalingPolicy(), sample_fn=SignalSample,
                                engine=_FakeEngine(),
                                job=object())
        with pytest.raises(TypeError):
            AutoscaleController(ScalingPolicy(), sample_fn=SignalSample,
                                engine=object())

    def test_live_rescale_through_controller_matches_oracle(self):
        """End-to-end: the controller's bounds convergence drives a REAL
        mesh engine 4 -> 8 live, mid-stream, and the stream finishes
        oracle-identical."""
        clk = FakeClock()
        steps = _stream(num_keys=4_000, per_step=1_500, n_steps=6)
        eng = _session_engine(make_mesh(4), max_device_slots=1024)
        ctl = AutoscaleController(
            ScalingPolicy(min_shards=8, max_shards=8, cooldown_s=0,
                          clock=clk),
            sample_fn=lambda: SignalSample(), engine=eng,
            interval_s=0.0, clock=clk)
        fired = []
        for i, (keys, vals, ts, wm) in enumerate(steps):
            clk.advance(1.0)
            ctl.tick()
            eng.process_batch(keyed_batch(keys, vals, ts))
            fired.extend(eng.on_watermark(wm))
        fired.extend(eng.on_watermark(1 << 60))
        got = {}
        for b in fired:
            for r in b.to_rows():
                got[(r[KEY_ID_FIELD], r["window_start"],
                     r["window_end"])] = r["sum_v"]
        oracle = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        _assert_equal(got, _run(oracle, steps))
        assert eng.P == 8
        assert ctl.live_handoffs == 1  # converged once, then steady

    def test_load_ramp_scales_up_and_back_down_live(self):
        """A low -> high -> low load ramp through the DS2 policy: the
        busy fraction is scripted off the ramp (load / peak), so at the
        peak the target is ceil(2 * 1.0 / 0.5) = 4 and at the trough
        it clamps to min 2. The engine must rescale 2 -> 4 -> 2 LIVE,
        mid-stream, and finish oracle-identical."""
        phases = [1500] * 3 + [6000] * 4 + [1500] * 4
        rng = np.random.default_rng(23)
        steps = []
        for s, n in enumerate(phases):
            steps.append((
                rng.integers(0, 6000, n).astype(np.int64),
                rng.random(n).astype(np.float32),
                rng.integers(s * 80, s * 80 + 60, n).astype(np.int64),
                (s - 1) * 80))
        clk = FakeClock()
        eng = _session_engine(make_mesh(2), max_device_slots=1024)
        cum = {"records": 0.0, "busy_ms": 0.0}
        ctl = AutoscaleController(
            ScalingPolicy(utilization_target=0.5, hysteresis=0.25,
                          cooldown_s=2.0, min_shards=2, max_shards=4,
                          clock=clk),
            sample_fn=lambda: SignalSample(
                records_total=cum["records"],
                busy_ms_total=cum["busy_ms"],
                shard_resident_rows=eng.shard_resident_rows()),
            engine=eng, interval_s=0.0, clock=clk)
        fired = []
        for keys, vals, ts, wm in steps:
            cum["records"] += len(keys)
            cum["busy_ms"] += min(len(keys) / 6000.0, 1.0) * 1000.0
            clk.advance(1.0)
            ctl.tick()
            eng.process_batch(keyed_batch(keys, vals, ts))
            fired.extend(eng.on_watermark(wm))
        fired.extend(eng.on_watermark(1 << 60))
        got = {}
        for b in fired:
            for r in b.to_rows():
                got[(r[KEY_ID_FIELD], r["window_start"],
                     r["window_end"])] = r["sum_v"]
        path = [(e.source, e.target) for e in ctl.events]
        assert (2, 4) in path and (4, 2) in path, path
        assert ctl.live_handoffs >= 2
        assert all(e.mode == "live" for e in ctl.events)
        oracle = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        _assert_equal(got, _run(oracle, steps))


# ---------------------------------------------------------------------------
# skew guard surface: refusal counter + gauges + rebalancer hand-off
# ---------------------------------------------------------------------------


class TestSkewGuardSurface:
    def test_policy_counts_refusals_and_records_imbalance(self):
        from flink_tpu.autoscale.policy import key_imbalance

        p = ScalingPolicy(utilization_target=0.7, hysteresis=0.25,
                          cooldown_s=0, imbalance_limit=2.0,
                          clock=FakeClock())
        skewed = (1000, 10, 10, 10, 10, 10, 10, 10)
        assert p.skew_guard_refusals == 0
        d = p.decide(_inp(cur=8, rate=1000.0, busy=0.2, rows=skewed))
        assert d.reason == "imbalance"
        assert p.skew_guard_refusals == 1
        assert p.last_imbalance == key_imbalance(skewed)
        assert p.last_imbalance > 2.0
        # a balanced decision does not bump the counter but refreshes
        # the measured imbalance gauge value
        p.decide(_inp(cur=8, rate=1000.0, busy=0.2, rows=(100,) * 8))
        assert p.skew_guard_refusals == 1
        assert p.last_imbalance == 1.0

    def test_controller_exports_skew_gauges_and_fires_hook(self):
        """The refusal count and the measured imbalance are pinned on
        the job metric tree (autoscale group), and the refusal hands
        the PolicyInput to the on_imbalance hook exactly once per
        refusing tick."""
        from flink_tpu.metrics.core import MetricRegistry

        clk = FakeClock()
        seen = []
        samples = iter([
            SignalSample(records_total=0, busy_ms_total=0),
            # +10k records / 10 s at 20% busy on 8 shards -> the rate
            # math wants a scale-down; the skewed resident rows veto it
            SignalSample(records_total=10_000, busy_ms_total=2_000,
                         shard_resident_rows=(1000, 10, 10, 10,
                                              10, 10, 10, 10)),
        ])
        ctl = AutoscaleController(
            ScalingPolicy(utilization_target=0.7, hysteresis=0.25,
                          cooldown_s=0, imbalance_limit=2.0, clock=clk),
            sample_fn=lambda: next(samples), engine=_FakeEngine(shards=8),
            interval_s=0.0, clock=clk, on_imbalance=seen.append)
        reg = MetricRegistry()
        ctl.register_metrics(reg.root_group("job"))
        assert ctl.tick() is None
        clk.advance(10.0)
        assert ctl.tick() is None  # refused: no rescale event
        assert len(seen) == 1
        assert isinstance(seen[0], PolicyInput)
        snap = reg.snapshot()
        assert snap["job.autoscale.skew_guard_refusals"] == 1
        assert snap["job.autoscale.key_imbalance"] == pytest.approx(
            1000 * 8 / 1070)
        assert snap["job.autoscale.last_decision"] == "imbalance"


# ---------------------------------------------------------------------------
# executor + minicluster integration
# ---------------------------------------------------------------------------


class TestExecutorAutoscale:
    def _run_job(self, conf_extra, total=30_000):
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.runtime.watermarks import WatermarkStrategy

        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 1024,
            "parallelism.default": 2,
            **conf_extra,
        }))
        sink = CollectSink()
        (env.add_source(
            DataGenSource(total_records=total, num_keys=40,
                          events_per_second_of_eventtime=20_000),
            WatermarkStrategy.for_bounded_out_of_orderness(0))
         .key_by("key").window(TumblingEventTimeWindows.of(1000))
         .count().sink_to(sink))
        result = env.execute("autoscale-job")
        return {(int(r["key"]), int(r["window_start"])): int(r["count"])
                for r in sink.rows()}, result

    def test_enabled_autoscale_converges_to_bounds_and_matches(self):
        """A job deployed at parallelism 2 with min-shards pinned to 4
        live-rescales on the first policy tick (bounds convergence, no
        stop-redeploy) and still produces the exact baseline results."""
        baseline, _ = self._run_job({})
        scaled, result = self._run_job({
            "autoscale.enabled": True,
            "autoscale.interval-ms": 0,
            "autoscale.cooldown-ms": 0,
            "autoscale.min-shards": 4,
            "autoscale.max-shards": 4,
        })
        assert len(baseline) > 0
        assert scaled == baseline
        auto = result.metrics.get("autoscale")
        assert auto is not None and auto["live_handoffs"] >= 1
        assert auto["path"][0] == (2, 4)

    def test_disabled_autoscale_adds_no_metrics(self):
        _, result = self._run_job({}, total=5_000)
        assert "autoscale" not in result.metrics

    def test_bounds_clamped_to_engine_limits(self):
        """min/max-shards far beyond the visible devices must be
        clamped at setup — a policy allowed to target 64 shards would
        crash the task loop with reshard()'s ValueError."""
        baseline, _ = self._run_job({}, total=10_000)
        scaled, result = self._run_job({
            "autoscale.enabled": True,
            "autoscale.interval-ms": 0,
            "autoscale.cooldown-ms": 0,
            "autoscale.min-shards": 64,
            "autoscale.max-shards": 64,
        }, total=10_000)
        auto = result.metrics.get("autoscale")
        assert auto is not None
        assert auto["path"][0] == (2, 8)  # clamped to the 8 devices
        assert scaled == baseline


class TestMiniclusterColdRescale:
    def test_request_rescale_redeploys_from_checkpoint(self, tmp_path):
        """The controller's cold path: request_rescale() retargets the
        stage parallelism and the adaptive supervision loop redeploys
        from the latest checkpoint without consuming restart budget."""
        import time

        from flink_tpu.cluster.minicluster import (
            FINISHED,
            RUNNING,
            MiniCluster,
        )
        from flink_tpu.connectors.sinks import JsonLinesFileSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.runtime.watermarks import WatermarkStrategy

        class SlowDataGen(DataGenSource):
            def poll_batch(self, max_records):
                b = super().poll_batch(max_records)
                if b is not None:
                    time.sleep(0.01)
                return b

        ck = str(tmp_path / "ck")
        out = str(tmp_path / "o.jsonl")
        total = 40_000
        cluster = MiniCluster(Configuration({"rest.port": -1}))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 256,
                "jobmanager.scheduler": "adaptive",
                "state.checkpoints.dir": ck,
                "execution.checkpointing.every-n-source-batches": 2,
            }))
            (env.add_source(
                SlowDataGen(total_records=total, num_keys=5,
                            events_per_second_of_eventtime=4000),
                WatermarkStrategy.for_bounded_out_of_orderness(0))
             .key_by("key").window(TumblingEventTimeWindows.of(500))
             .count().sink_to(JsonLinesFileSink(out)))
            client = cluster.submit(env, "cold-rescale")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.status()["status"] == RUNNING:
                    break
                time.sleep(0.02)
            time.sleep(0.3)  # let checkpoints land
            jm = cluster.dispatcher.master(client.job_id)
            assert jm.request_rescale(2) is True
            assert jm.current_parallelism == 2
            st = client.wait(timeout=60)
            assert st["status"] == FINISHED
            assert st["attempt"] >= 1  # redeployed, budget untouched
            states = [h["state"] for h in st["state_history"]]
            assert "RESTARTING" in states
            rows = JsonLinesFileSink.read_rows(out)
            per_window = {}
            for r in rows:  # refires overwrite earlier partials
                per_window[(int(r["key"]), int(r["window_start"]))] = \
                    int(r["count"])
            assert sum(per_window.values()) == total
        finally:
            cluster.shutdown()

    def test_request_rescale_refused_without_checkpointing(self,
                                                           tmp_path):
        import time

        from flink_tpu.cluster.minicluster import RUNNING, MiniCluster
        from flink_tpu.connectors.sinks import JsonLinesFileSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.runtime.watermarks import WatermarkStrategy

        class SlowDataGen(DataGenSource):
            def poll_batch(self, max_records):
                b = super().poll_batch(max_records)
                if b is not None:
                    time.sleep(0.01)
                return b

        cluster = MiniCluster(Configuration({"rest.port": -1}))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 256,
                "jobmanager.scheduler": "adaptive",
            }))
            (env.add_source(
                SlowDataGen(total_records=20_000, num_keys=5,
                            events_per_second_of_eventtime=4000),
                WatermarkStrategy.for_bounded_out_of_orderness(0))
             .key_by("key").window(TumblingEventTimeWindows.of(500))
             .count().sink_to(JsonLinesFileSink(
                 str(tmp_path / "o.jsonl"))))
            client = cluster.submit(env, "no-ckpt-rescale")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if client.status()["status"] == RUNNING:
                    break
                time.sleep(0.02)
            jm = cluster.dispatcher.master(client.job_id)
            # no checkpointing: a redeploy would replay from record 0
            # and double-emit — the request must be refused
            assert jm.request_rescale(2) is False
            client.wait(timeout=60)
        finally:
            cluster.shutdown()
