"""Chaos engine: deterministic fault injection + crash-restore-verify.

Covers (1) the injection core (seeded schedules, pattern/ctx matching,
recoverable retries), (2) checkpoint integrity (CRC32 manifest, torn
writes detected, fallback to the previous complete checkpoint), (3) the
crash-restore-verify harness against the fault-free oracle across the
mesh session engine (paged spill under forced eviction), the tumbling
mesh window engine and the async-fire/dispatch-ahead pipeline path, and
(4) the cluster restart path (task crash -> RestartStrategy -> restore).

The LAST test asserts every fault point in the CANONICAL inventory
(``flink_tpu.chaos.KNOWN_FAULT_POINTS`` — one source of truth, shared
with flint's REG01 registry check) was
injected at least once across this suite — the tier-1 guarantee that no
injection site silently goes stale.
"""

import os

import numpy as np
import pytest

from flink_tpu.chaos import KNOWN_FAULT_POINTS
from flink_tpu.core.records import RecordBatch
from flink_tpu.chaos import injection as chaos
from flink_tpu.chaos.harness import (
    ChaosDivergenceError,
    run_crash_restore_verify,
)
from flink_tpu.chaos.injection import FaultPlan, FaultRule, InjectedFault

GAP = 100

#: fault points injected so far across this suite (reachability ledger;
#: asserted by the final test against chaos.KNOWN_FAULT_POINTS)
REACHED = {}


def _note_reached(injected):
    for k, v in injected.items():
        REACHED[k] = REACHED.get(k, 0) + v


# --------------------------------------------------------------- injection


class TestInjectionCore:
    def test_disarmed_is_noop(self):
        assert not chaos.armed()
        chaos.fault_point("anything.at.all", shard=3)
        assert chaos.payload_action("anything.at.all") is None
        assert chaos.run_recoverable("x", lambda: 41) == 41

    def test_nth_hit_fires_once(self):
        plan = FaultPlan(rules=[FaultRule(pattern="a.b", nth=3)])
        with chaos.chaos_active(plan, seed=0) as c:
            chaos.fault_point("a.b")
            chaos.fault_point("a.b")
            with pytest.raises(InjectedFault):
                chaos.fault_point("a.b")
            chaos.fault_point("a.b")  # max_injections=1: spent
            assert c.faults_injected == {"a.b": 1}
            assert c.points_hit["a.b"] == 4

    def test_every_schedule_and_unlimited(self):
        plan = FaultPlan(rules=[
            FaultRule(pattern="p.*", every=2, kind="delay",
                      delay_ms=0, max_injections=0)])
        with chaos.chaos_active(plan, seed=0) as c:
            for _ in range(6):
                chaos.fault_point("p.q")
            assert c.faults_injected["p.q"] == 3

    def test_where_filter_pins_context(self):
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.bucket_send", nth=1,
                      where={"shard": 2})])
        with chaos.chaos_active(plan, seed=0) as c:
            chaos.fault_point("shuffle.bucket_send", shard=0)
            chaos.fault_point("shuffle.bucket_send", shard=1)
            with pytest.raises(InjectedFault):
                chaos.fault_point("shuffle.bucket_send", shard=2)
            assert c.faults_injected == {"shuffle.bucket_send": 1}

    def test_prob_schedule_is_seed_deterministic(self):
        def run(seed):
            plan = FaultPlan(rules=[
                FaultRule(pattern="r.*", prob=0.3, kind="delay",
                          delay_ms=0, max_injections=0)])
            with chaos.chaos_active(plan, seed=seed) as c:
                for _ in range(200):
                    chaos.fault_point("r.s")
                return c.faults_injected.get("r.s", 0)

        a, b = run(42), run(42)
        assert a == b and 20 < a < 100  # same seed => identical draws
        assert run(43) != a or run(44) != a  # not constant across seeds

    def test_arming_twice_fails(self):
        plan = FaultPlan(rules=[FaultRule(pattern="task.batch", nth=1)])
        with chaos.chaos_active(plan, seed=0):
            with pytest.raises(RuntimeError, match="already armed"):
                chaos.arm(plan, 0)
        assert not chaos.armed()

    def test_rule_validation(self):
        with pytest.raises(ValueError, match="no schedule"):
            FaultRule(pattern="task.batch")
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultRule(pattern="task.batch", nth=1, kind="explode")

    def test_recoverable_retry_then_recover(self):
        plan = FaultPlan(rules=[
            FaultRule(pattern="io.read", nth=1, recoverable=True)])
        with chaos.chaos_active(plan, seed=0) as c:
            calls = []

            def attempt():
                calls.append(1)
                chaos.fault_point("io.read")
                return "ok"

            assert chaos.run_recoverable("io.read", attempt) == "ok"
            assert len(calls) == 2
            assert c.retries == 1 and c.recoveries == 1

    def test_recoverable_budget_exhausts(self):
        plan = FaultPlan(rules=[
            FaultRule(pattern="io.read", every=1, recoverable=True,
                      max_injections=0)],
            retry_max_attempts=3)
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                chaos.run_recoverable(
                    "io.read",
                    lambda: chaos.fault_point("io.read"))
            # max_attempts=3 failures => 2 retries, then give up
            assert c.retries == 2 and c.recoveries == 0

    def test_nonrecoverable_fault_skips_retry(self):
        plan = FaultPlan(rules=[FaultRule(pattern="io.read", nth=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                chaos.run_recoverable(
                    "io.read",
                    lambda: chaos.fault_point("io.read"))
            assert c.retries == 0

    def test_from_spec_and_describe(self):
        plan = FaultPlan.from_spec([
            {"pattern": "a.*", "nth": 2},
            {"pattern": "b", "prob": 0.5, "kind": "delay"},
        ])
        assert len(plan.rules) == 2
        assert any("nth=2" in line for line in plan.describe())

    def test_chaos_metrics_ride_the_job_group(self):
        from flink_tpu.metrics import MetricRegistry

        plan = FaultPlan(rules=[FaultRule(pattern="m.n", nth=1,
                                          kind="delay", delay_ms=0)])
        reg = MetricRegistry()
        with chaos.chaos_active(plan, seed=0):
            chaos.register_chaos_metrics(reg.root_group("job", "j"))
            chaos.fault_point("m.n")
            snap = reg.snapshot()
            assert snap["job.j.chaos.faults_injected"] == 1
            assert snap["job.j.chaos.points_hit"] == 1


# ----------------------------------------------------- checkpoint integrity


class TestCheckpointIntegrity:
    def _write(self, root, cid, n=64):
        from flink_tpu.checkpoint.storage import CheckpointStorage

        st = CheckpointStorage(root)
        rng = np.random.default_rng(cid)
        st.write_checkpoint(cid, "job", {"op": {
            "key_id": np.arange(n, dtype=np.int64),
            "namespace": np.arange(n, dtype=np.int64),
            "leaf_0": rng.random(n).astype(np.float32),
            "host_meta": {"positions": [cid, 1, 2]},
        }})
        return st

    def test_manifest_carries_crcs_and_roundtrips(self, tmp_path):
        from flink_tpu.checkpoint.storage import (
            read_manifest,
            read_snapshot_dir,
        )

        st = self._write(str(tmp_path), 1)
        m = read_manifest(st._dir(1))
        assert m["file_crcs"] and all(
            isinstance(v, int) for v in m["file_crcs"].values())
        state = read_snapshot_dir(st._dir(1))
        assert len(state["op"]["key_id"]) == 64

    def test_truncated_npz_detected_with_clear_error(self, tmp_path):
        from flink_tpu.checkpoint.storage import (
            CheckpointCorruptedError,
            read_snapshot_dir,
        )

        st = self._write(str(tmp_path), 1)
        npz = os.path.join(st._dir(1), "op-op.npz")
        with open(npz, "r+b") as f:
            f.truncate(os.path.getsize(npz) // 2)
        with pytest.raises(CheckpointCorruptedError,
                           match="op-op.npz.*CRC32"):
            read_snapshot_dir(st._dir(1))

    def test_single_bitflip_detected(self, tmp_path):
        from flink_tpu.checkpoint.storage import (
            CheckpointCorruptedError,
            read_snapshot_dir,
        )

        st = self._write(str(tmp_path), 1)
        pkl = os.path.join(st._dir(1), "op-op.meta.pkl")
        size = os.path.getsize(pkl)
        with open(pkl, "r+b") as f:
            f.seek(size // 2)
            b = f.read(1)
            f.seek(size // 2)
            f.write(bytes([b[0] ^ 0x01]))
        with pytest.raises(CheckpointCorruptedError, match="corrupt"):
            read_snapshot_dir(st._dir(1))

    def test_missing_file_detected(self, tmp_path):
        from flink_tpu.checkpoint.storage import (
            CheckpointCorruptedError,
            read_snapshot_dir,
        )

        st = self._write(str(tmp_path), 1)
        os.remove(os.path.join(st._dir(1), "op-op.npz"))
        with pytest.raises(CheckpointCorruptedError, match="missing"):
            read_snapshot_dir(st._dir(1))

    def test_latest_checkpoint_falls_back_past_corruption(self,
                                                          tmp_path):
        """Truncate one npz in chk-3, flip one byte in chk-2: the
        verified newest-complete id must fall back to chk-1 (the
        harness's restore source)."""
        root = str(tmp_path)
        st = self._write(root, 1)
        self._write(root, 2)
        self._write(root, 3)
        npz3 = os.path.join(st._dir(3), "op-op.npz")
        with open(npz3, "r+b") as f:
            f.truncate(os.path.getsize(npz3) // 2)
        npz2 = os.path.join(st._dir(2), "op-op.npz")
        with open(npz2, "r+b") as f:
            f.seek(5)
            f.write(b"\xff")
        assert st.latest_checkpoint_id() == 3  # unverified: newest dir
        assert st.latest_checkpoint_id(verify=True) == 1

    def test_manifestless_dir_never_counts(self, tmp_path):
        st = self._write(str(tmp_path), 1)
        os.makedirs(os.path.join(str(tmp_path), "chk-9"))
        assert st.latest_checkpoint_id() == 1
        assert st.latest_checkpoint_id(verify=True) == 1

    def test_torn_write_fault_is_detectable(self, tmp_path):
        """An injected torn write (rename durable, bytes not) must
        leave a checkpoint that READS as corrupt, not as state."""
        from flink_tpu.checkpoint.storage import (
            CheckpointCorruptedError,
            CheckpointStorage,
            read_snapshot_dir,
        )

        plan = FaultPlan(rules=[
            FaultRule(pattern="checkpoint.write.torn", nth=1,
                      kind="drop")])
        with chaos.chaos_active(plan, seed=0) as c:
            st = CheckpointStorage(str(tmp_path))
            st.write_checkpoint(1, "job", {"op": {
                "key_id": np.arange(512, dtype=np.int64)}})
            assert c.faults_injected["checkpoint.write.torn"] == 1
            _note_reached(c.faults_injected)
        with pytest.raises(CheckpointCorruptedError):
            read_snapshot_dir(st._dir(1))
        assert st.latest_checkpoint_id(verify=True) is None

    def test_torn_point_rejects_raise_kind(self, tmp_path):
        """A raise-kind rule on checkpoint.write.torn must NOT fire:
        the point sits AFTER the atomic rename, so raising there would
        model a crash of a checkpoint that is in fact durable — the
        harness would discard a committed epoch and report a false
        exactly-once violation. Tear kinds only."""
        from flink_tpu.checkpoint.storage import (
            CheckpointStorage,
            read_snapshot_dir,
        )

        plan = FaultPlan(rules=[
            FaultRule(pattern="checkpoint.write.torn", nth=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            st = CheckpointStorage(str(tmp_path))
            st.write_checkpoint(1, "job", {"op": {
                "key_id": np.arange(8, dtype=np.int64)}})
            assert c.faults_injected == {}
        # and the checkpoint is intact (no tear happened either)
        assert len(read_snapshot_dir(st._dir(1))["op"]["key_id"]) == 8

    def test_recoverable_write_and_read_faults_retry(self, tmp_path):
        from flink_tpu.checkpoint.storage import (
            CheckpointStorage,
            read_snapshot_dir,
        )

        plan = FaultPlan(rules=[
            FaultRule(pattern="checkpoint.write", nth=1,
                      recoverable=True),
            FaultRule(pattern="checkpoint.read", nth=1,
                      recoverable=True),
        ])
        with chaos.chaos_active(plan, seed=0) as c:
            st = CheckpointStorage(str(tmp_path))
            st.write_checkpoint(1, "job", {"op": {
                "key_id": np.arange(8, dtype=np.int64)}})
            state = read_snapshot_dir(st._dir(1))
            assert len(state["op"]["key_id"]) == 8
            assert c.retries == 2 and c.recoveries == 2
            assert c.faults_injected["checkpoint.write"] == 1
            assert c.faults_injected["checkpoint.read"] == 1
            _note_reached(c.faults_injected)


# ------------------------------------------------------------ shuffle layer


class TestShuffleBucketFaults:
    def _bucket(self, n=64, shards=4):
        rng = np.random.default_rng(3)
        shard_of = rng.integers(0, shards, n)
        cols = [rng.integers(0, 100, n).astype(np.int32),
                rng.random(n).astype(np.float32)]
        return shard_of, cols

    def test_drop_empties_one_shard_bucket(self):
        from flink_tpu.parallel.shuffle import bucket_by_shard

        shard_of, cols = self._bucket()
        base_counts, base_blocked = bucket_by_shard(
            shard_of, 4, cols, fills=[0, 0.0])
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.bucket_send", nth=1, kind="drop",
                      where={"shard": 2})])
        with chaos.chaos_active(plan, seed=0) as c:
            counts, blocked = bucket_by_shard(
                shard_of, 4, cols, fills=[0, 0.0])
            assert counts[2] == 0 and base_counts[2] > 0
            assert (blocked[0][2] == 0).all()  # refilled with fill
            np.testing.assert_array_equal(blocked[0][1],
                                          base_blocked[0][1])
            _note_reached(c.faults_injected)

    def test_duplicate_replays_one_shard_bucket(self):
        from flink_tpu.parallel.shuffle import bucket_by_shard

        shard_of, cols = self._bucket()
        base_counts, _ = bucket_by_shard(
            shard_of, 4, cols, fills=[0, 0.0])
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.bucket_send", nth=1,
                      kind="duplicate", where={"shard": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            counts, blocked = bucket_by_shard(
                shard_of, 4, cols, fills=[0, 0.0])
            cbase = int(base_counts[1])
            assert counts[1] == 2 * cbase
            np.testing.assert_array_equal(
                blocked[1][1][:cbase], blocked[1][1][cbase:2 * cbase])
            _note_reached(c.faults_injected)

    def test_disarmed_output_is_identical(self):
        from flink_tpu.parallel.shuffle import bucket_by_shard

        shard_of, cols = self._bucket()
        c1, b1, o1 = bucket_by_shard(shard_of, 4, cols, fills=[0, 0.0],
                                     want_order=True)
        c2, b2, o2 = bucket_by_shard(shard_of, 4, cols, fills=[0, 0.0],
                                     want_order=True)
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(o1, o2)
        for x, y in zip(b1, b2):
            np.testing.assert_array_equal(x, y)


class TestDeviceExchangeFaults:
    """The device data plane's fault point, at its REAL sites: payload
    kinds (drop/duplicate) apply in ``stage_device_exchange`` before the
    flat columns go up, and raise/delay fire at the engines'
    post-dispatch site — a crash lands mid-batch with the fused
    exchange+scatter already on the device queue."""

    def _flat(self, n=64, shards=4):
        rng = np.random.default_rng(5)
        shard_of = rng.integers(0, shards, n)
        cols = [rng.integers(1, 100, n).astype(np.int32),
                rng.random(n).astype(np.float32)]
        return shard_of, cols

    def test_drop_routes_shard_lanes_to_padding(self):
        from flink_tpu.parallel.shuffle import stage_device_exchange

        shard_of, cols = self._flat()
        dst0, _, _ = stage_device_exchange(shard_of, 4, cols,
                                           fills=[0, 0.0])
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=1,
                      kind="drop", where={"shard": 2})])
        with chaos.chaos_active(plan, seed=0) as c:
            dst, staged, _ = stage_device_exchange(shard_of, 4, cols,
                                                   fills=[0, 0.0])
            n = len(shard_of)
            # the dropped shard's lanes re-route to the padding
            # destination (they vanish before the collective); every
            # other lane is untouched
            assert (dst0[:n] == 2).sum() > 0
            assert not (dst[:n] == 2).any()
            assert ((dst[:n] == 4) == (shard_of == 2)).all()
            np.testing.assert_array_equal(staged[0][:n], cols[0])
            _note_reached(c.faults_injected)

    def test_duplicate_replays_shard_records(self):
        from flink_tpu.parallel.shuffle import stage_device_exchange

        shard_of, cols = self._flat()
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=1,
                      kind="duplicate", where={"shard": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            dst, staged, _ = stage_device_exchange(shard_of, 4, cols,
                                                   fills=[0, 0.0])
            n = len(shard_of)
            c1 = int((shard_of == 1).sum())
            assert c1 > 0
            # the duplicated rows ride as extra real lanes after the
            # original batch
            assert (dst[n:n + c1] == 1).all()
            np.testing.assert_array_equal(
                staged[1][n:n + c1], cols[1][shard_of == 1])
            _note_reached(c.faults_injected)

    def test_raise_fires_after_fused_dispatch(self, eight_device_mesh):
        """An engine in device mode crashes AT the post-dispatch site:
        process_batch raises with the exchange+scatter already
        dispatched (no fence pushed)."""
        from tests.test_sessions import keyed_batch

        make = _make_session_engine(eight_device_mesh,
                                    shuffle_mode="device")
        eng = make()
        assert eng.shuffle_mode == "device"
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                eng.process_batch(keyed_batch(
                    [1, 2, 3], [1.0, 2.0, 3.0], [0, 10, 20]))
            assert c.faults_injected.get(
                "shuffle.device_exchange", 0) == 1
            _note_reached(c.faults_injected)

    def test_device_mode_crash_restore_matches_oracle(
            self, eight_device_mesh, tmp_path):
        """The satellite scenario: shuffle.mode=device, crash mid-batch
        after the fused dispatch, restore from the latest complete
        checkpoint, replay — committed output oracle-identical, and the
        run is seed-deterministic."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=5)])

        def run(tag):
            return run_crash_restore_verify(
                _make_session_engine(eight_device_mesh,
                                     shuffle_mode="device"),
                _make_session_oracle(),
                _session_steps(seed=47), plan, seed=9,
                ckpt_root=str(tmp_path / f"ckpt-{tag}"),
                checkpoint_every=2)

        r1 = run("a")
        assert not r1.diverged and r1.windows > 0
        assert r1.crashes == 1 and r1.restores == 1
        assert r1.faults_injected.get("shuffle.device_exchange", 0) == 1
        r2 = run("b")
        assert r2.signature() == r1.signature()
        _note_reached(r1.faults_injected)

    def test_device_negative_control_drop_diverges(
            self, eight_device_mesh, tmp_path):
        """A dropped shard on the DEVICE data plane must diverge from
        the oracle — the same loss-detection proof the host path's
        negative control gives."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=4,
                      kind="drop")])
        r = run_crash_restore_verify(
            _make_session_engine(eight_device_mesh,
                                 shuffle_mode="device"),
            _make_session_oracle(),
            _session_steps(seed=53), plan, seed=5,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2,
            check=False)
        assert r.diverged and r.crashes == 0
        assert r.faults_injected.get("shuffle.device_exchange", 0) == 1
        _note_reached(r.faults_injected)


# -------------------------------------------------------- restart satellites


class TestRestartStrategySatellites:
    def test_jitter_bounds_and_seed_determinism(self):
        from flink_tpu.cluster.restart_strategies import (
            ExponentialDelayRestartStrategy,
        )

        def backoffs(seed):
            s = ExponentialDelayRestartStrategy(
                initial_ms=1000, max_ms=60_000, multiplier=2.0,
                max_attempts=10, jitter_factor=0.25, seed=seed)
            out = []
            for _ in range(5):
                s.notify_failure()
                out.append(s.backoff_ms())
            return out

        a, b = backoffs(7), backoffs(7)
        assert a == b  # seeded jitter is deterministic
        base = 1000
        for got in a:
            assert 0.75 * base <= got <= 1.25 * base
            base = min(base * 2, 60_000)

    def test_backoff_resets_after_quiet_period(self):
        from flink_tpu.cluster.restart_strategies import (
            ExponentialDelayRestartStrategy,
        )

        now = [0.0]
        s = ExponentialDelayRestartStrategy(
            initial_ms=100, max_ms=60_000, multiplier=2.0,
            max_attempts=3, reset_backoff_threshold_ms=10_000,
            clock=lambda: now[0])
        for _ in range(3):
            s.notify_failure()
        assert s.backoff_ms() == 400
        assert not s.can_restart()  # budget spent
        now[0] = 11.0  # 11 s of healthy running
        s.notify_failure()
        assert s.backoff_ms() == 100  # backoff reset...
        assert s.can_restart()  # ...and the attempt budget too

    def test_no_reset_within_quiet_period(self):
        from flink_tpu.cluster.restart_strategies import (
            ExponentialDelayRestartStrategy,
        )

        now = [0.0]
        s = ExponentialDelayRestartStrategy(
            initial_ms=100, multiplier=2.0, max_attempts=10,
            reset_backoff_threshold_ms=10_000, clock=lambda: now[0])
        s.notify_failure()
        now[0] = 5.0  # inside the threshold
        s.notify_failure()
        assert s.backoff_ms() == 200

    def test_from_config_honors_exponential_options(self):
        from flink_tpu.cluster.restart_strategies import (
            restart_strategy_from_config,
        )
        from flink_tpu.core.config import Configuration

        s = restart_strategy_from_config(Configuration({
            "restart-strategy.type": "exponential-delay",
            "restart-strategy.delay-ms": 50,
            "restart-strategy.max-attempts": 7,
            "restart-strategy.exponential-delay.max-backoff-ms": 400,
            "restart-strategy.exponential-delay.backoff-multiplier": 3.0,
            "restart-strategy.exponential-delay.jitter-factor": 0.1,
            "restart-strategy.exponential-delay."
            "reset-backoff-threshold-ms": 9000,
        }))
        assert s.initial_ms == 50 and s.max_attempts == 7
        assert s.max_ms == 400 and s.multiplier == 3.0
        assert s.jitter_factor == 0.1
        assert s.reset_backoff_threshold_ms == 9000
        # the ceiling is actually enforced: 50 -> 150 -> 400 (capped)
        for _ in range(4):
            s.notify_failure()
        assert s._current == 400

    def test_from_config_honors_failure_rate_interval(self):
        from flink_tpu.cluster.restart_strategies import (
            restart_strategy_from_config,
        )
        from flink_tpu.core.config import Configuration

        s = restart_strategy_from_config(Configuration({
            "restart-strategy.type": "failure-rate",
            "restart-strategy.max-attempts": 5,
            "restart-strategy.failure-rate."
            "failure-rate-interval-ms": 1234,
        }))
        assert s.interval_ms == 1234 and s.max_failures == 5

    def test_failure_rate_interval_expires_failures(self):
        from flink_tpu.cluster.restart_strategies import (
            FailureRateRestartStrategy,
        )

        now = [0.0]
        s = FailureRateRestartStrategy(
            max_failures=2, interval_ms=1000, clock=lambda: now[0])
        s.notify_failure()
        s.notify_failure()
        assert not s.can_restart()
        now[0] = 2.0  # both failures age out of the window
        s.notify_failure()
        assert s.can_restart()


# ------------------------------------------------- crash-restore-verify


def _session_steps(num_keys=6000, n_steps=8, per_step=1500, seed=17):
    """Live session set far beyond the 1024-slot/shard budget: paged
    eviction + reload are genuinely on the path (same shape as
    tests/test_mesh_paged_spill)."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.random(per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        out.append((keys, vals, ts, (s - 1) * 80))
    return out


def _make_session_engine(mesh, dispatch_ahead=2, shuffle_mode="host"):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    # shuffle_mode="host" pins the EXPLICIT fallback data plane for the
    # long-standing scenarios, keeping shuffle.bucket_send/_prep
    # semantics and the host-path negative control exercised; the
    # device data plane's scenarios live in TestDeviceExchangeFaults
    return lambda: MeshSessionEngine(
        GAP, SumAggregate("v"), mesh, capacity_per_shard=1 << 14,
        max_device_slots=1024, max_dispatch_ahead=dispatch_ahead,
        shuffle_mode=shuffle_mode)


def _make_session_oracle():
    from flink_tpu.windowing.aggregates import SumAggregate
    from flink_tpu.windowing.sessions import SessionWindower

    return lambda: SessionWindower(GAP, SumAggregate("v"),
                                   capacity=1 << 15)


class TestCrashRestoreVerify:
    def test_mesh_sessions_paged_forced_eviction(self, eight_device_mesh,
                                                 tmp_path):
        """The acceptance scenario: mesh session engine with
        spill_layout='pages' under forced eviction; crashes at the
        dispatch fence, in a page reload and in a session fire; one
        torn checkpoint write; deferred (recoverable) compaction.
        Committed output must equal the fault-free oracle exactly, and
        the run must be bit-deterministic for the same seed."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.dispatch_fence", nth=5),
            FaultRule(pattern="spill.page_reload", nth=3),
            FaultRule(pattern="mesh.session_fire", nth=6),
            FaultRule(pattern="checkpoint.write.torn", nth=2,
                      kind="drop"),
            FaultRule(pattern="spill.page_compact", nth=1,
                      recoverable=True),
            # a zero-ms delay: proves the batch-level prep point is
            # live without perturbing behavior (stays deterministic)
            FaultRule(pattern="shuffle.bucket_prep", nth=3,
                      kind="delay", delay_ms=0),
        ])

        def run(tag):
            return run_crash_restore_verify(
                _make_session_engine(eight_device_mesh),
                _make_session_oracle(),
                _session_steps(), plan, seed=7,
                ckpt_root=str(tmp_path / f"ckpt-{tag}"),
                checkpoint_every=2)

        r1 = run("a")
        assert not r1.diverged
        assert r1.crashes == 3 and r1.restores == 3
        assert r1.corrupt_checkpoints_skipped >= 1
        for point in ("mesh.dispatch_fence", "spill.page_reload",
                      "mesh.session_fire", "checkpoint.write.torn",
                      "spill.page_compact"):
            assert r1.faults_injected.get(point, 0) >= 1, point
        assert r1.recoveries >= 1  # the deferred compaction
        # determinism: same (plan, seed, steps) => identical signature
        r2 = run("b")
        assert r1.signature() == r2.signature()
        _note_reached(r1.faults_injected)

    def test_tumbling_mesh_engine(self, eight_device_mesh, tmp_path):
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows
        from flink_tpu.windowing.windower import SliceSharedWindower

        def make_engine():
            return MeshWindowEngine(
                TumblingEventTimeWindows.of(200), SumAggregate("v"),
                eight_device_mesh, capacity_per_shard=1 << 14)

        def make_oracle():
            return SliceSharedWindower(
                TumblingEventTimeWindows.of(200), SumAggregate("v"),
                capacity=1 << 15)

        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.window_fire", nth=2),
            FaultRule(pattern="mesh.dispatch_fence", nth=5),
            FaultRule(pattern="checkpoint.write.torn", nth=3,
                      kind="corrupt"),
        ])
        r = run_crash_restore_verify(
            make_engine, make_oracle,
            _session_steps(num_keys=800, per_step=1200), plan, seed=11,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2)
        assert not r.diverged and r.windows > 0
        assert r.crashes == 2 and r.restores == 2
        assert r.faults_injected.get("mesh.window_fire", 0) == 1
        assert r.corrupt_checkpoints_skipped >= 1
        _note_reached(r.faults_injected)

    def test_dispatch_ahead_async_fire_pipeline(self, eight_device_mesh,
                                                tmp_path):
        """dispatch-ahead 3 + async fires: crashes land mid-pipeline
        (batches in flight past the fence) and in the coalesced
        harvest; exactly-once must still hold."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="harvest.pending_fire", nth=3),
            FaultRule(pattern="mesh.dispatch_fence", nth=8),
        ])
        r = run_crash_restore_verify(
            _make_session_engine(eight_device_mesh, dispatch_ahead=3),
            _make_session_oracle(),
            _session_steps(seed=23), plan, seed=5,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2,
            async_fires=True)
        assert not r.diverged
        assert r.crashes == 2 and r.restores == 2
        assert r.faults_injected.get("harvest.pending_fire", 0) == 1
        _note_reached(r.faults_injected)

    def test_harness_catches_lossy_shuffle(self, eight_device_mesh,
                                           tmp_path):
        """The negative control: a genuinely lossy fault (a dropped
        shard bucket, never crashed over) MUST diverge — proving the
        oracle diff actually detects data loss rather than vacuously
        passing."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.bucket_send", nth=4,
                      kind="drop")])
        r = run_crash_restore_verify(
            _make_session_engine(eight_device_mesh),
            _make_session_oracle(),
            _session_steps(seed=31), plan, seed=3,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2,
            check=False)
        assert r.diverged and r.crashes == 0
        assert r.faults_injected.get("shuffle.bucket_send", 0) == 1
        _note_reached(r.faults_injected)
        with pytest.raises(ChaosDivergenceError):
            run_crash_restore_verify(
                _make_session_engine(eight_device_mesh),
                _make_session_oracle(),
                _session_steps(seed=31), plan, seed=3,
                ckpt_root=str(tmp_path / "ckpt2"), checkpoint_every=2)

    def test_cold_restart_before_first_checkpoint(self,
                                                  eight_device_mesh,
                                                  tmp_path):
        """A crash before any checkpoint exists restarts from scratch
        (source position 0) and still matches the oracle."""
        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.dispatch_fence", nth=1)])
        r = run_crash_restore_verify(
            _make_session_engine(eight_device_mesh),
            _make_session_oracle(),
            _session_steps(n_steps=4, seed=41), plan, seed=2,
            ckpt_root=str(tmp_path / "ckpt"), checkpoint_every=2)
        assert not r.diverged
        assert r.cold_restarts == 1 and r.restores == 0
        _note_reached(r.faults_injected)


# ------------------------------------------------------------ cluster layer


class TestClusterRestartPath:
    def test_task_crash_restarts_and_finishes(self, tmp_path):
        """An injected task crash consumes restart budget, the job
        restores from its checkpoint and FINISHES — the minicluster
        form of the harness loop (reference: recovery ITCases)."""
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.cluster.minicluster import FINISHED, MiniCluster
        from flink_tpu.connectors.sinks import JsonLinesFileSink
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        cluster = MiniCluster(Configuration({
            "cluster.task-executors": 2,
            "heartbeat.interval-ms": 100,
        }))
        try:
            env = StreamExecutionEnvironment(Configuration({
                "execution.micro-batch.size": 256,
                "state.checkpoints.dir": str(tmp_path / "ckpt"),
                "execution.checkpointing.every-n-source-batches": 2,
                "restart-strategy.max-attempts": 3,
                "restart-strategy.delay-ms": 10,
            }))
            rows = [{"k": i % 5, "v": 1, "ts": i * 10}
                    for i in range(5000)]
            sink = JsonLinesFileSink(str(tmp_path / "out.jsonl"))
            env.from_collection(rows, timestamp_field="ts") \
                .map(lambda b: b, name="chaosmap") \
                .key_by("k") \
                .window(TumblingEventTimeWindows.of(1000)) \
                .sum("v").sink_to(sink)
            plan = FaultPlan(rules=[
                FaultRule(pattern="task.batch", nth=12,
                          where={"op": "chaosmap"})])
            with chaos.chaos_active(plan, seed=0) as c:
                client = cluster.submit(env, "chaos-task-crash")
                st = client.wait(timeout=120)
                assert st["status"] == FINISHED, st
                assert st["attempt"] == 1  # exactly one restart
                assert c.faults_injected.get("task.batch", 0) == 1
                _note_reached(c.faults_injected)
        finally:
            cluster.shutdown()

    def test_subtask_crash_fails_stage_parallel_attempt(self):
        """The stage-parallel execution path: an injected subtask crash
        propagates through the coordinator as the attempt failure the
        cluster failover would consume."""
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.runtime.watermarks import WatermarkStrategy
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        env = StreamExecutionEnvironment(Configuration({
            "execution.micro-batch.size": 1000,
            "execution.stage-parallelism": 2,
        }))
        src = DataGenSource(total_records=8000, num_keys=64,
                            events_per_second_of_eventtime=10_000,
                            seed=5)
        ds = env.from_source(
            src, WatermarkStrategy.for_bounded_out_of_orderness(0))
        ds.key_by("key").window(TumblingEventTimeWindows.of(1000)) \
            .sum("value").sink_to(CollectSink())
        plan = FaultPlan(rules=[
            FaultRule(pattern="task.subtask_batch", nth=3)])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                env.execute("chaos-subtask-crash")
            assert c.faults_injected.get("task.subtask_batch", 0) == 1
            _note_reached(c.faults_injected)


# ---------------------------------------------------------- reachability


class TestRescaleHandoffPoint:
    """The autoscaler's live-migration fault point, injected at its real
    production site (MeshSpillSupport.reshard) so the canonical
    inventory's reachability ledger covers it in THIS suite too (the
    full crash-restore-verify exercise lives in tests/test_autoscale.py)."""

    def test_handoff_drain_crash_at_real_site(self):
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.windowing.aggregates import SumAggregate

        from tests.test_sessions import keyed_batch

        eng = MeshSessionEngine(GAP, SumAggregate("v"), make_mesh(2),
                                capacity_per_shard=1024)
        eng.process_batch(keyed_batch([1, 2, 3], [1.0, 2.0, 3.0],
                                      [0, 10, 20]))
        plan = FaultPlan(rules=[
            FaultRule(pattern="rescale.handoff", nth=1,
                      where={"stage": "drain"})])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                eng.reshard(4)
            assert c.faults_injected.get("rescale.handoff", 0) == 1
            _note_reached(c.faults_injected)
        # reshard is not exception-atomic: the engine is dead here; the
        # recovery path (restore at the new parallelism) is proven by
        # tests/test_autoscale.py's chaos crash test


class TestRebalanceHandoffPoint:
    """The skew rebalancer's fault point, injected at its real site
    (MeshSpillSupport.reassign_key_groups — a key-group MOVE at
    unchanged P) so the canonical inventory's reachability ledger
    covers it in THIS suite too (the crash-at-commit crash-restore-
    verify exercise lives in tests/test_autoscale.py)."""

    def test_rebalance_commit_crash_at_real_site(self):
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.windowing.aggregates import SumAggregate

        from tests.test_sessions import keyed_batch

        eng = MeshSessionEngine(GAP, SumAggregate("v"), make_mesh(2),
                                capacity_per_shard=1024)
        eng.process_batch(keyed_batch([1, 2, 3], [1.0, 2.0, 3.0],
                                      [0, 10, 20]))
        cur = eng.key_group_assignment
        moved = cur.move(
            np.arange(cur.first, cur.first + cur.span // 2), 1)
        plan = FaultPlan(rules=[
            FaultRule(pattern="rebalance.handoff", nth=1,
                      where={"stage": "commit"})])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(InjectedFault):
                eng.reassign_key_groups(moved)
            assert c.faults_injected.get("rebalance.handoff", 0) == 1
            _note_reached(c.faults_injected)
        # commit crashed with the hot range's rows lifted: the engine
        # is dead; recovery restores a contiguous engine and re-applies
        # the move on replay (proven in tests/test_autoscale.py)


class TestServingLookupPoint:
    """The serving plane's fault point, injected at its real site (the
    batched queryable-state lookup wrapped in run_recoverable): a
    transient fault retries in place — lookups are read-only, so a
    retry cannot corrupt engine state (the full two-job serving-burst
    exercise lives in tests/test_tenancy.py)."""

    def test_serving_lookup_retries_at_real_site(self, tmp_path):
        from flink_tpu.chaos.harness import run_crash_restore_verify_multi
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.sessions import SessionWindower

        def mk_mesh():
            return MeshSessionEngine(GAP, SumAggregate("v"),
                                     make_mesh(2),
                                     capacity_per_shard=1024)

        def mk_oracle():
            return SessionWindower(GAP, SumAggregate("v"))

        rng = np.random.default_rng(0)
        steps = []
        for i in range(4):
            ks = rng.integers(0, 50, 128)
            steps.append((ks, np.ones(128, dtype=np.float32),
                          i * 300 + np.sort(rng.integers(0, 200, 128)),
                          i * 300 - 2 * GAP))
        plan = FaultPlan(rules=[
            FaultRule(pattern="serving.lookup", nth=1,
                      recoverable=True)])
        reports = run_crash_restore_verify_multi(
            make_engines={"j": mk_mesh}, make_oracles={"j": mk_oracle},
            steps_by_job={"j": steps}, plan=plan, seed=3,
            ckpt_root=str(tmp_path), serve_keys={"j": [1, 2, 3]})
        r = reports["j"]
        assert r.faults_injected.get("serving.lookup", 0) >= 1
        assert r.retries >= 1 and r.recoveries >= 1
        assert r.crashes == 0 and not r.diverged
        _note_reached(r.faults_injected)


class TestReplicaPublishPoint:
    """``serving.replica_publish``, injected at its real site — INSIDE
    a boundary publish, before the seal swap. The crash-restore shape:
    readers keep serving the intact sealed generation through the torn
    publish, the restored engine republishes, and lookups never observe
    a torn replica (the snapshot-isolation-under-fault pin; the full
    scenario with checkpoint restore lives in
    tests/test_serving_replica.py::TestReplicaChaos)."""

    def test_replica_publish_injected_at_real_site(self):
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.tenancy.replica import WindowReplicaAdapter
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        eng = MeshWindowEngine(
            TumblingEventTimeWindows(1000), SumAggregate("v"),
            make_mesh(2), capacity_per_shard=1024, max_parallelism=128)
        plane = eng.arm_replica()
        ad = WindowReplicaAdapter(plane, eng.agg, eng.assigner)
        ad.cold_fetch = lambda ks: eng.query_batch(
            np.asarray(ks, dtype=np.int64))

        def step(t):
            eng.process_batch(RecordBatch({
                "__key_id__": np.arange(16, dtype=np.int64),
                "__ts__": np.full(16, t, dtype=np.int64),
                "v": np.ones(16, dtype=np.float32),
            }))

        step(100)
        eng.on_watermark(50)  # first publish seals generation 1
        before, gen = ad.lookup_batch([3])
        plan = FaultPlan(rules=[
            FaultRule(pattern="serving.replica_publish", nth=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            step(600)
            with pytest.raises(InjectedFault):
                eng.on_watermark(550)
            assert c.faults_injected.get("serving.replica_publish",
                                         0) == 1
            _note_reached(c.faults_injected)
        # torn publish: the sealed generation is untouched
        again, gen2 = ad.lookup_batch([3])
        assert gen2 == gen and again == before
        # the engine recovers at its next boundary (the publish is
        # re-derivable: dirty marks and metadata survived the raise)
        out = eng.on_watermark(550)
        fresh, gen3 = ad.lookup_batch([3])
        assert gen3 > gen
        assert fresh == eng.query_batch(np.asarray([3],
                                                   dtype=np.int64))


class TestServingCacheProbePoint:
    """``serving.cache_probe``, injected at its real site — the batched
    hot-row probe in ``ServingPlane.lookup_batch``. A ``drop`` kind
    makes the probe fall to the MISS path (the system-level shape of a
    torn native read): the request still answers, bit-identical,
    resolved against the sealed replica instead of the cache. A
    ``raise`` kind surfaces to the client as the crash path."""

    def _serving(self):
        import queue as _q

        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.tenancy.replica import WindowReplicaAdapter
        from flink_tpu.tenancy.serving import ServingPlane
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        eng = MeshWindowEngine(
            TumblingEventTimeWindows(1000), SumAggregate("v"),
            make_mesh(2), capacity_per_shard=1024, max_parallelism=128)
        plane = eng.arm_replica()
        ad = WindowReplicaAdapter(plane, eng.agg, eng.assigner)
        serving = ServingPlane(workers=1)
        serving.bind_job("j", _q.Queue())
        serving.bind_replica("j", "op", ad)
        eng.process_batch(RecordBatch({
            "__key_id__": np.arange(16, dtype=np.int64),
            "__ts__": np.full(16, 100, dtype=np.int64),
            "v": np.ones(16, dtype=np.float32),
        }))
        eng.on_watermark(50)  # publish + harvest-prime the cache
        return eng, serving

    def test_drop_kind_falls_to_miss_path_bit_identical(self):
        eng, serving = self._serving()
        keys = list(range(8))
        try:
            want = serving.lookup_batch("j", "op", keys)
            hits_before = serving.hot_cache.hits
            assert hits_before > 0  # primed: the probe actually served
            plan = FaultPlan(rules=[
                FaultRule(pattern="serving.cache_probe", kind="drop",
                          every=1)])
            with chaos.chaos_active(plan, seed=0) as c:
                got = serving.lookup_batch("j", "op", keys)
                assert c.faults_injected.get("serving.cache_probe",
                                             0) >= 1
                _note_reached(c.faults_injected)
            # the dropped probe NEVER serves a mixed row — the whole
            # batch re-resolved against the sealed replica, bit-equal
            assert got == want
        finally:
            serving.shutdown_workers()

    def test_raise_kind_surfaces_to_client(self):
        eng, serving = self._serving()
        try:
            plan = FaultPlan(rules=[
                FaultRule(pattern="serving.cache_probe", nth=1)])
            with chaos.chaos_active(plan, seed=0) as c:
                with pytest.raises(InjectedFault):
                    serving.lookup_batch("j", "op", [1, 2, 3])
                assert c.faults_injected.get("serving.cache_probe",
                                             0) == 1
                _note_reached(c.faults_injected)
            # disarmed again: the probe path is intact
            assert serving.lookup_batch("j", "op", [1])[0] == \
                eng.query_batch(np.asarray([1], dtype=np.int64))[0]
        finally:
            serving.shutdown_workers()


class TestServingFrontendPoint:
    """``serving.frontend``, injected at its real site — the owner-side
    dispatch in ``FrontendPool.lookup_batch``. The ``drop`` kind KILLS
    the chosen frontend process for real (death mid-burst): the
    in-flight lookup must fail over to a live sibling and the surviving
    results stay bit-identical to the dict oracle (the owner's own
    lookup path); owner and siblings are unharmed. ``raise`` surfaces
    to the client as the crash path."""

    def _serving_shm(self, tmp_path):
        import queue as _q

        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.tenancy.replica import WindowReplicaAdapter
        from flink_tpu.tenancy.serving import ServingPlane
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        eng = MeshWindowEngine(
            TumblingEventTimeWindows(1000), SumAggregate("v"),
            make_mesh(2), capacity_per_shard=1024, max_parallelism=128)
        plane = eng.arm_replica()
        ad = WindowReplicaAdapter(plane, eng.agg, eng.assigner)
        serving = ServingPlane(workers=1,
                               shm_dir=str(tmp_path / "shm"))
        serving.bind_job("j", _q.Queue())
        serving.bind_replica("j", "op", ad)
        eng.process_batch(RecordBatch({
            "__key_id__": np.arange(16, dtype=np.int64),
            "__ts__": np.full(16, 100, dtype=np.int64),
            "v": np.ones(16, dtype=np.float32),
        }))
        eng.on_watermark(50)  # publish + harvest-prime the shm cache
        return eng, serving

    @pytest.mark.skipif(
        not __import__("flink_tpu.native", fromlist=["x"])
        .hotcache_available(),
        reason="native hotcache unavailable")
    def test_drop_kind_kills_frontend_failover_bit_identical(
            self, tmp_path):
        from flink_tpu.tenancy.frontend import FrontendPool

        eng, serving = self._serving_shm(tmp_path)
        pool = None
        keys = list(range(8))
        try:
            want = serving.lookup_batch("j", "op", keys)  # dict oracle
            pool = FrontendPool(serving, n_frontends=2)
            assert pool.lookup_batch("j", "op", keys) == want
            plan = FaultPlan(rules=[
                FaultRule(pattern="serving.frontend", kind="drop",
                          nth=1)])
            with chaos.chaos_active(plan, seed=0) as c:
                got = pool.lookup_batch("j", "op", keys)
                assert c.faults_injected.get("serving.frontend",
                                             0) >= 1
                _note_reached(c.faults_injected)
            # the killed frontend's in-flight lookup failed over to the
            # sibling, bit-identical to the oracle
            assert got == want
            assert pool.failovers >= 1
            assert len(pool.live_frontends()) == 1
            # owner and sibling unharmed: both paths still serve
            assert pool.lookup_batch("j", "op", keys) == want
            assert serving.lookup_batch("j", "op", keys) == want
        finally:
            if pool is not None:
                pool.close()
            serving.shutdown_workers()
            serving.hot_cache.close()

    @pytest.mark.skipif(
        not __import__("flink_tpu.native", fromlist=["x"])
        .hotcache_available(),
        reason="native hotcache unavailable")
    def test_raise_kind_surfaces_to_client(self, tmp_path):
        from flink_tpu.tenancy.frontend import FrontendPool

        eng, serving = self._serving_shm(tmp_path)
        pool = None
        try:
            pool = FrontendPool(serving, n_frontends=1)
            plan = FaultPlan(rules=[
                FaultRule(pattern="serving.frontend", nth=1)])
            with chaos.chaos_active(plan, seed=0) as c:
                with pytest.raises(InjectedFault):
                    pool.lookup_batch("j", "op", [1, 2, 3])
                assert c.faults_injected.get("serving.frontend",
                                             0) == 1
                _note_reached(c.faults_injected)
            # disarmed again: the frontend path is intact
            assert pool.lookup_batch("j", "op", [1]) == \
                serving.lookup_batch("j", "op", [1])
        finally:
            if pool is not None:
                pool.close()
            serving.shutdown_workers()
            serving.hot_cache.close()


class TestWatchdogPoints:
    """The partial-failover fault points, injected at their real sites:
    ``device.lost`` fires inside the watchdog's batch-boundary probe on
    the mesh engine's ingest path, and ``watchdog.deadline`` (a
    delay-kind injection — a slow device, not an exception) stretches a
    deadline-tracked device section past its budget until the next
    boundary declares the shard dead. The full recovery protocol lives
    in tests/test_shard_failover.py."""

    def _engine_with_watchdog(self, deadline_ms=0.0, max_misses=3):
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.runtime.watchdog import DeviceWatchdog
        from flink_tpu.windowing.aggregates import SumAggregate

        eng = MeshSessionEngine(GAP, SumAggregate("v"), make_mesh(2),
                                capacity_per_shard=1024)
        eng.attach_watchdog(DeviceWatchdog(
            eng.P, deadline_ms=deadline_ms, max_misses=max_misses))
        return eng

    def test_device_lost_declares_shard_dead_at_real_site(self):
        from flink_tpu.runtime.watchdog import ShardFailedError

        from tests.test_sessions import keyed_batch

        eng = self._engine_with_watchdog()
        plan = FaultPlan(rules=[
            FaultRule(pattern="device.lost", nth=1,
                      where={"shard": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(ShardFailedError) as ei:
                eng.process_batch(keyed_batch([1, 2, 3],
                                              [1.0, 2.0, 3.0],
                                              [0, 10, 20]))
            assert ei.value.shard == 1
            assert 1 in eng._watchdog.quarantined
            assert c.faults_injected.get("device.lost", 0) == 1
            _note_reached(c.faults_injected)

    def test_deadline_delay_escalates_at_the_boundary(self):
        from flink_tpu.runtime.watchdog import MeshStalledError

        from tests.test_sessions import keyed_batch

        # every deadline-tracked section sleeps 20 ms against a 1 ms
        # deadline: timeout -> retry (miss streak) -> escalated at the
        # next batch boundary once the miss budget is spent. The
        # engine's sections are whole-mesh (SPMD), so the uniform
        # streak carries no shard attribution and escalates as a
        # MESH STALL (whole-job restart), never a false shard death
        eng = self._engine_with_watchdog(deadline_ms=1.0, max_misses=2)
        plan = FaultPlan(rules=[
            FaultRule(pattern="watchdog.deadline", every=1,
                      kind="delay", delay_ms=20, max_injections=0)])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(MeshStalledError):
                for i in range(8):
                    eng.process_batch(keyed_batch(
                        [1, 2, 3], [1.0, 2.0, 3.0],
                        [i * 10, i * 10 + 1, i * 10 + 2]))
            assert eng._watchdog.deadline_misses >= 2
            assert not eng._watchdog.quarantined
            assert c.faults_injected.get("watchdog.deadline", 0) >= 2
            _note_reached(c.faults_injected)


class TestPodFaultPoints:
    """The pod-scale fault points at their real sites: ``host.lost``
    fires inside the watchdog's boundary probe once per live HOST (the
    process-granular death the multi-process chaos scenario injects),
    and ``exchange.dcn_send`` models a lossy DCN link in the two-level
    exchange staging — drop/duplicate per CROSS-host (src, dst) bucket.
    The full host-failover protocol lives in
    tests/test_host_failover.py."""

    def _pod_engine(self, watchdog=True):
        from flink_tpu.parallel.mesh import HostTopology, make_mesh
        from flink_tpu.parallel.sharded_sessions import (
            MeshSessionEngine,
        )
        from flink_tpu.runtime.watchdog import DeviceWatchdog
        from flink_tpu.windowing.aggregates import SumAggregate

        eng = MeshSessionEngine(GAP, SumAggregate("v"), make_mesh(4),
                                capacity_per_shard=1024,
                                host_topology=HostTopology(2, 2))
        if watchdog:
            eng.attach_watchdog(DeviceWatchdog(eng.P))
        return eng

    def test_host_lost_declares_whole_host_at_real_site(self):
        from flink_tpu.runtime.watchdog import HostFailedError

        from tests.test_sessions import keyed_batch

        eng = self._pod_engine()
        plan = FaultPlan(rules=[
            FaultRule(pattern="host.lost", nth=1,
                      where={"host": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            with pytest.raises(HostFailedError) as ei:
                eng.process_batch(keyed_batch([1, 2, 3],
                                              [1.0, 2.0, 3.0],
                                              [0, 10, 20]))
            assert ei.value.host == 1
            # the whole host's slice quarantines in one declaration
            assert ei.value.shards == (2, 3)
            assert eng._watchdog.quarantined == {2, 3}
            assert eng._watchdog.hosts_declared_dead == 1
            assert c.faults_injected.get("host.lost", 0) == 1
            _note_reached(c.faults_injected)

    def test_dcn_send_drop_loses_the_cross_host_bucket(self):
        from flink_tpu.parallel.exchange2 import (
            stage_two_level_exchange,
        )
        from flink_tpu.parallel.mesh import HostTopology

        topo = HostTopology(2, 2)
        # records in chunk 0 (source host 0) destined to shards 2 and 3
        # (host 1) — the (0 -> 1) DCN bucket
        shards = np.array([2, 3, 0, 2], dtype=np.int64)
        slots = np.arange(1, 5, dtype=np.int32)
        plan = FaultPlan(rules=[
            FaultRule(pattern="exchange.dcn_send", nth=1, kind="drop",
                      where={"src_host": 0, "dst_host": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            dst, (s_col,), w1, w2 = stage_two_level_exchange(
                shards, topo, columns=[slots], fills=[0])
            assert c.faults_injected.get("exchange.dcn_send", 0) == 1
            _note_reached(c.faults_injected)
        # the cross-host rows re-routed to the padding destination
        # (they vanish before the stage-1 collective); the intra-host
        # row survives
        np.testing.assert_array_equal(dst[:4], [4, 4, 0, 4])

    def test_dcn_send_duplicate_replays_the_bucket(self):
        from flink_tpu.parallel.exchange2 import (
            stage_two_level_exchange,
        )
        from flink_tpu.parallel.mesh import HostTopology

        topo = HostTopology(2, 2)
        shards = np.array([2, 3, 0], dtype=np.int64)
        slots = np.arange(1, 4, dtype=np.int32)
        plan = FaultPlan(rules=[
            FaultRule(pattern="exchange.dcn_send", nth=1,
                      kind="duplicate",
                      where={"src_host": 0, "dst_host": 1})])
        with chaos.chaos_active(plan, seed=0) as c:
            dst, (s_col,), w1, w2 = stage_two_level_exchange(
                shards, topo, columns=[slots], fills=[0])
            assert c.faults_injected.get("exchange.dcn_send", 0) == 1
        # the (0 -> 1) bucket's rows replay at the tail
        np.testing.assert_array_equal(dst[:5], [2, 3, 0, 2, 3])
        np.testing.assert_array_equal(s_col[:5], [1, 2, 3, 1, 2])


class _IntervalJoinHarnessEngine:
    """Adapts the device interval-join engine to the crash-restore
    harness protocol: each step batch splits by row parity into the
    two inputs (values carry the row's own timestamp, so every joined
    pair lands in a unique ``(key, lts, rts)`` upsert cell — a lost or
    duplicated pair changes the committed cells, never hides)."""

    def __init__(self, backend="device", shards=2, **kw):
        from flink_tpu.joins import MeshIntervalJoinEngine

        if backend == "device":
            from flink_tpu.parallel.mesh import make_mesh

            self.eng = MeshIntervalJoinEngine(
                -60, 60, mesh=make_mesh(shards), **kw)
        else:
            self.eng = MeshIntervalJoinEngine(
                -60, 60, backend="host", num_shards=shards, **kw)
        self._buf = []

    @property
    def P(self):
        return self.eng.P

    def reshard(self, n):
        return self.eng.reshard(n)

    def process_batch(self, batch):
        left = np.arange(len(batch)) % 2 == 0
        self._buf += self.eng.process_batch(batch.filter(left), 0)
        self._buf += self.eng.process_batch(batch.filter(~left), 1)

    def on_watermark(self, wm, async_ok=False):
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            TIMESTAMP_FIELD,
            RecordBatch,
        )
        from flink_tpu.windowing.windower import (
            WINDOW_END_FIELD,
            WINDOW_START_FIELD,
        )

        out = []
        for b in self._buf:
            lts = np.asarray(b["v_l"], dtype=np.int64)
            rts = np.asarray(b["v_r"], dtype=np.int64)
            out.append(RecordBatch({
                KEY_ID_FIELD: b[KEY_ID_FIELD],
                WINDOW_START_FIELD: lts,
                WINDOW_END_FIELD: rts + 1,
                TIMESTAMP_FIELD: b[TIMESTAMP_FIELD],
                "val": np.asarray(b["v_l"])
                + np.asarray(b["v_r"]),
            }))
        self._buf = []
        self.eng.on_watermark(int(wm))
        return out

    def snapshot(self):
        return self.eng.snapshot()

    def restore(self, snap):
        self.eng.restore(snap)
        self._buf = []


def _join_steps(n_steps=6, n=96, keys=24, seed=4):
    """Harness steps whose values ARE the row timestamps. Event time
    OVERLAPS across steps (step stride 60 < in-step span 96, band
    +-60), so buffered rows of one step match probes of later steps —
    a row lost at INGEST (after the arriving batch's own probe) still
    changes the committed cells."""
    rng = np.random.default_rng(seed)
    steps = []
    for i in range(n_steps):
        ks = rng.integers(0, keys, n)
        ts = i * 60 + np.arange(n, dtype=np.int64)
        steps.append((ks, ts.astype(np.float32), ts, i * 60 - 300))
    return steps


class TestJoinExchangePoint:
    """The two-input data plane's fault point at its real site
    (JoinEngineBase._ingest): a raise crashes mid-batch with the join
    put on the device queue — crash-restore must stay oracle-identical
    — and a DROPPED side bucket must DIVERGE (the negative control:
    the harness catches genuine loss in the join plane)."""

    def test_join_job_crash_restore_oracle_identical(self, tmp_path):
        # nth=7 = step 3's left ingest: past the first checkpoint, so
        # the recovery is a genuine RESTORE (not a cold restart)
        plan = FaultPlan(rules=[
            FaultRule(pattern="join.exchange", nth=7)])
        report = run_crash_restore_verify(
            make_engine=lambda: _IntervalJoinHarnessEngine("device"),
            make_oracle=lambda: _IntervalJoinHarnessEngine("host"),
            steps=_join_steps(), plan=plan, seed=7,
            ckpt_root=str(tmp_path))
        assert report.crashes >= 1 and report.restores >= 1
        assert report.faults_injected.get("join.exchange", 0) >= 1
        assert not report.diverged
        assert report.windows > 0
        _note_reached(report.faults_injected)

    def test_join_job_crash_restore_is_deterministic(self, tmp_path):
        plan = FaultPlan(rules=[
            FaultRule(pattern="join.exchange", nth=7)])
        sigs = []
        for i in range(2):
            r = run_crash_restore_verify(
                make_engine=lambda: _IntervalJoinHarnessEngine(
                    "device"),
                make_oracle=lambda: _IntervalJoinHarnessEngine(
                    "host"),
                steps=_join_steps(), plan=plan, seed=7,
                ckpt_root=str(tmp_path / f"run{i}"))
            sigs.append(r.signature())
        assert sigs[0] == sigs[1]

    def test_dropped_side_bucket_diverges(self, tmp_path):
        # negative control: one shard's bucket of ONE side vanishes in
        # flight — its pairs never form and the diff MUST catch it
        plan = FaultPlan(rules=[
            FaultRule(pattern="join.exchange", nth=2, kind="drop",
                      where={"side": 1})])
        report = run_crash_restore_verify(
            make_engine=lambda: _IntervalJoinHarnessEngine("device"),
            make_oracle=lambda: _IntervalJoinHarnessEngine("host"),
            steps=_join_steps(), plan=plan, seed=7,
            ckpt_root=str(tmp_path), check=False)
        assert report.faults_injected.get("join.exchange", 0) >= 1
        assert report.diverged, (
            "a dropped join-side bucket produced identical output — "
            "the harness cannot catch join data-plane loss")
        _note_reached(report.faults_injected)

    def test_payload_injection_at_real_site(self):
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            TIMESTAMP_FIELD,
            RecordBatch,
        )
        from flink_tpu.joins import MeshIntervalJoinEngine

        eng = MeshIntervalJoinEngine(-60, 60, backend="host",
                                     num_shards=2)
        b = RecordBatch({
            KEY_ID_FIELD: np.arange(32, dtype=np.int64),
            "v": np.ones(32, dtype=np.float32),
            TIMESTAMP_FIELD: np.arange(32, dtype=np.int64)})
        plan = FaultPlan(rules=[
            FaultRule(pattern="join.exchange", nth=1,
                      kind="duplicate", where={"shard": 0})])
        with chaos.chaos_active(plan, seed=0) as c:
            eng.process_batch(b, 0)
            assert c.faults_injected.get("join.exchange", 0) == 1
            _note_reached(c.faults_injected)
        # shard 0's rows were replayed in flight: more rows buffered
        # than sent on that shard
        assert sum(len(m) for m in eng.sides[0].meta) > 32


class _TemporalJoinHarnessEngine:
    """Temporal-join adapter: odd rows are versions, even rows probe;
    matches emit at the watermark with the left time as the cell."""

    def __init__(self, backend="device", shards=2, **kw):
        from flink_tpu.joins import MeshTemporalJoinEngine

        if backend == "device":
            from flink_tpu.parallel.mesh import make_mesh

            self.eng = MeshTemporalJoinEngine(
                mesh=make_mesh(shards), **kw)
        else:
            self.eng = MeshTemporalJoinEngine(
                backend="host", num_shards=shards, **kw)

    @property
    def P(self):
        return self.eng.P

    def process_batch(self, batch):
        left = np.arange(len(batch)) % 2 == 0
        self.eng.process_batch(batch.filter(~left), 1)
        self.eng.process_batch(batch.filter(left), 0)

    def on_watermark(self, wm, async_ok=False):
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            TIMESTAMP_FIELD,
            RecordBatch,
        )
        from flink_tpu.windowing.windower import (
            WINDOW_END_FIELD,
            WINDOW_START_FIELD,
        )

        out = []
        for b in self.eng.on_watermark(int(wm)):
            lts = np.asarray(b[TIMESTAMP_FIELD], dtype=np.int64)
            out.append(RecordBatch({
                KEY_ID_FIELD: b[KEY_ID_FIELD],
                WINDOW_START_FIELD: lts,
                WINDOW_END_FIELD: lts + 1,
                TIMESTAMP_FIELD: lts,
                "val": np.asarray(b["v_l"]) + np.asarray(b["v_r"]),
            }))
        return out

    def snapshot(self):
        return self.eng.snapshot()

    def restore(self, snap):
        self.eng.restore(snap)


class TestJoinVersionedLookupPoint:
    """The versioned-plane lookup fault point at its real site (the
    temporal engine's watermark probe): a crash there happens with the
    pending left buffer intact, so restore + replay stays
    oracle-identical."""

    def test_crash_at_versioned_lookup_restores_identical(
            self, tmp_path):
        plan = FaultPlan(rules=[
            FaultRule(pattern="join.versioned_lookup", nth=2)])
        report = run_crash_restore_verify(
            make_engine=lambda: _TemporalJoinHarnessEngine("device"),
            make_oracle=lambda: _TemporalJoinHarnessEngine("host"),
            steps=_join_steps(seed=5), plan=plan, seed=9,
            ckpt_root=str(tmp_path))
        assert report.crashes >= 1 and report.restores >= 1
        assert report.faults_injected.get(
            "join.versioned_lookup", 0) >= 1
        assert not report.diverged
        assert report.windows > 0
        _note_reached(report.faults_injected)


class _CepHarnessEngine:
    """CEP adapter for the crash-restore harness: the pattern is a
    2-stage strict sequence over the value stream (``v%3==0`` then
    ``v%3==1``, within 120), SKIP_PAST_LAST_EVENT — device-eligible.
    Each emitted match maps to the harness upsert cell
    ``(key, start_ts, end_ts+1)`` with the stage counts as the value,
    so a lost/duplicated event changes which matches form — it can
    shift a cell, drop a cell, or change a count, never hide."""

    def __init__(self, backend="device", shards=2):
        from flink_tpu.cep.mesh_engine import MeshCepEngine
        from flink_tpu.cep.pattern import (
            AfterMatchSkipStrategy,
            Pattern,
        )

        pat = (Pattern.begin(
                "a", skip=AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT)
               .where(lambda b: np.asarray(b["v"]) % 3 == 0)
               .next("b")
               .where(lambda b: np.asarray(b["v"]) % 3 == 1)
               .within(120))
        if backend == "device":
            from flink_tpu.parallel.mesh import make_mesh

            self.eng = MeshCepEngine(pat, mesh=make_mesh(shards),
                                     capacity_per_shard=256,
                                     backend="device")
        else:
            self.eng = MeshCepEngine(pat, num_shards=shards,
                                     backend="host",
                                     shuffle_mode="host")

    @property
    def P(self):
        return self.eng.P

    def reshard(self, n):
        return self.eng.reshard(n)

    def process_batch(self, batch):
        self.eng.process_batch(batch)

    def on_watermark(self, wm, async_ok=False):
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            TIMESTAMP_FIELD,
            RecordBatch,
        )
        from flink_tpu.windowing.windower import (
            WINDOW_END_FIELD,
            WINDOW_START_FIELD,
        )

        out = []
        for b in self.eng.on_watermark(int(wm)):
            rows = b.to_rows()
            out.append(RecordBatch({
                KEY_ID_FIELD: np.asarray(
                    [r["key"] for r in rows], dtype=np.int64),
                WINDOW_START_FIELD: np.asarray(
                    [r["start_ts"] for r in rows], dtype=np.int64),
                WINDOW_END_FIELD: np.asarray(
                    [r["end_ts"] + 1 for r in rows], dtype=np.int64),
                TIMESTAMP_FIELD: np.asarray(b.timestamps,
                                            dtype=np.int64),
                "val": np.asarray(
                    [r["a_count"] * 10 + r["b_count"] for r in rows],
                    dtype=np.float64),
            }))
        return out

    def snapshot(self):
        return self.eng.snapshot()

    def restore(self, snap):
        self.eng.restore(snap)


def _cep_steps(n_steps=6, n=96, keys=24, seed=13):
    """Value stream for the CEP pattern: small integers so the 0-mod-3
    -> 1-mod-3 sequence occurs often per key; timestamps advance 60
    per step with in-step spread, watermark trails one step."""
    rng = np.random.default_rng(seed)
    steps = []
    for i in range(n_steps):
        ks = rng.integers(0, keys, n)
        vs = rng.integers(0, 9, n).astype(np.float32)
        ts = i * 60 + np.sort(rng.integers(0, 60, n)).astype(np.int64)
        steps.append((ks, vs, ts, i * 60 - 30))
    return steps


class TestCepAdvancePoint:
    """The CEP data plane's fault points at their real sites: a raise
    at ``cep.advance`` (post-dispatch, ingest) crashes mid-batch with
    the pending scatter already on the device queue — crash-restore
    must stay oracle-identical — and a DROPPED device-exchange bucket
    must DIVERGE (the negative control: the harness catches genuine
    loss in the CEP pending plane)."""

    def test_cep_crash_restore_oracle_identical(self, tmp_path):
        # nth=4 = step 4's ingest: past the first checkpoint, so the
        # recovery is a genuine RESTORE (not a cold restart)
        plan = FaultPlan(rules=[
            FaultRule(pattern="cep.advance", nth=4)])
        report = run_crash_restore_verify(
            make_engine=lambda: _CepHarnessEngine("device"),
            make_oracle=lambda: _CepHarnessEngine("host"),
            steps=_cep_steps(), plan=plan, seed=11,
            ckpt_root=str(tmp_path))
        assert report.crashes >= 1 and report.restores >= 1
        assert report.faults_injected.get("cep.advance", 0) >= 1
        assert not report.diverged
        assert report.windows > 0
        _note_reached(report.faults_injected)

    def test_cep_crash_restore_is_deterministic(self, tmp_path):
        plan = FaultPlan(rules=[
            FaultRule(pattern="cep.advance", nth=4)])
        sigs = []
        for i in range(2):
            r = run_crash_restore_verify(
                make_engine=lambda: _CepHarnessEngine("device"),
                make_oracle=lambda: _CepHarnessEngine("host"),
                steps=_cep_steps(), plan=plan, seed=11,
                ckpt_root=str(tmp_path / f"run{i}"))
            sigs.append(r.signature())
        assert sigs[0] == sigs[1]

    def test_dropped_cep_exchange_diverges(self, tmp_path):
        # negative control: one shard's staged CEP columns vanish in
        # flight (re-routed to the padding destination) — the device
        # pending rows keep hits=0 while the host mirror retains the
        # real events, so those matches never fire and the diff MUST
        # catch it
        plan = FaultPlan(rules=[
            FaultRule(pattern="shuffle.device_exchange", nth=2,
                      kind="drop")])
        report = run_crash_restore_verify(
            make_engine=lambda: _CepHarnessEngine("device"),
            make_oracle=lambda: _CepHarnessEngine("host"),
            steps=_cep_steps(), plan=plan, seed=11,
            ckpt_root=str(tmp_path), check=False)
        assert report.faults_injected.get(
            "shuffle.device_exchange", 0) >= 1
        assert report.diverged, (
            "a dropped CEP exchange bucket produced identical output "
            "— the harness cannot catch CEP data-plane loss")
        _note_reached(report.faults_injected)

    def test_advance_injection_at_real_site(self):
        from flink_tpu.core.records import (
            KEY_ID_FIELD,
            RecordBatch,
        )

        eng = _CepHarnessEngine("device").eng
        b = RecordBatch.from_pydict(
            {KEY_ID_FIELD: np.arange(32, dtype=np.int64),
             "v": np.ones(32, dtype=np.float32)},
            timestamps=np.arange(32, dtype=np.int64))
        plan = FaultPlan(rules=[
            FaultRule(pattern="cep.advance", nth=1, kind="delay",
                      delay_ms=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            eng.process_batch(b)
            assert c.faults_injected.get("cep.advance", 0) == 1
            _note_reached(c.faults_injected)
        # the batch survived the delay: pending mirrors hold the rows
        assert sum(len(sh.p_key) for sh in eng._st) == 32


class TestCepMatchFirePoint:
    """``cep.match_fire`` at its real site (after the match-store
    write, before the watermark commits): a crash there lands with
    matches already on the device match planes but the pending rows
    unconsumed — restore + replay must re-fire them identically."""

    def test_crash_at_match_fire_restores_identical(self, tmp_path):
        plan = FaultPlan(rules=[
            FaultRule(pattern="cep.match_fire", nth=3)])
        report = run_crash_restore_verify(
            make_engine=lambda: _CepHarnessEngine("device"),
            make_oracle=lambda: _CepHarnessEngine("host"),
            steps=_cep_steps(seed=29), plan=plan, seed=17,
            ckpt_root=str(tmp_path))
        assert report.crashes >= 1 and report.restores >= 1
        assert report.faults_injected.get("cep.match_fire", 0) >= 1
        assert not report.diverged
        assert report.windows > 0
        _note_reached(report.faults_injected)

    def test_fire_injection_at_real_site(self):
        eng = _CepHarnessEngine("device").eng
        plan = FaultPlan(rules=[
            FaultRule(pattern="cep.match_fire", nth=1, kind="delay",
                      delay_ms=1)])
        with chaos.chaos_active(plan, seed=0) as c:
            eng.on_watermark(10)
            assert c.faults_injected.get("cep.match_fire", 0) == 1
            _note_reached(c.faults_injected)


class TestZZFaultPointReachability:
    """Must run LAST in this file (pytest preserves definition order):
    every fault point of the CANONICAL inventory was injected somewhere
    above."""

    def test_every_fault_point_injected_at_least_once(self):
        from flink_tpu.native import hotcache_available

        known = list(KNOWN_FAULT_POINTS)
        if not hotcache_available():
            # the frontend-pool dispatch site cannot be built without
            # the native shm plane (FrontendPool refuses) — its tests
            # skip above, so the point is unreachable by construction
            known.remove("serving.frontend")
        missing = [p for p in known
                   if REACHED.get(p, 0) < 1]
        assert not missing, (
            f"fault points never injected across the suite: {missing} "
            f"(reached: {REACHED}) — an injection site moved or a "
            "schedule went stale; update chaos.KNOWN_FAULT_POINTS "
            "and tests/test_chaos.py together")
