"""Skew detection + rebalance planning — pure host-side arithmetic.

ShardLoadAccountant turns routed key columns into EWMA per-key-group
load estimates (plus a Misra-Gries hot-key sketch); RebalancePolicy
scores greedy group moves against them with hysteresis and cooldown.
Everything runs on an injectable clock, the same idiom as the
scaling-policy suite; only the last case drives a mesh engine, to pin
that the responder's moves lower the measured imbalance without
changing a single output window.
"""

import numpy as np
import pytest

from flink_tpu.autoscale import RebalancePolicy
from flink_tpu.parallel.load import ShardLoadAccountant, busy_from_flight
from flink_tpu.state.keygroups import (
    KeyGroupAssignment,
    assign_key_groups,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _keys_for_group(group, max_parallelism, n, start=0):
    """n distinct key ids that murmur into ``group``."""
    out = []
    k = start
    while len(out) < n:
        if int(assign_key_groups(np.array([k]), max_parallelism)[0]) == group:
            out.append(k)
        k += 1
    return np.array(out, dtype=np.int64)


class TestShardLoadAccountant:
    def test_group_counts_then_ewma_rates(self):
        clk = FakeClock()
        acc = ShardLoadAccountant(4, 16, ewma_alpha=0.5, clock=clk)
        hot = _keys_for_group(3, 16, 5)
        acc.note_batch(np.repeat(hot, 20))  # 100 records into group 3
        # before any differentiating tick: raw counts
        assert acc.group_load()[3] == 100
        acc.tick()
        clk.advance(10.0)
        acc.note_batch(np.repeat(hot, 20))
        acc.tick()
        # first differentiated rate: 100 / 10 s
        assert acc.group_load()[3] == pytest.approx(10.0)
        clk.advance(10.0)
        acc.tick()  # nothing arrived: EWMA halves toward zero
        assert acc.group_load()[3] == pytest.approx(5.0)
        assert acc.hottest_group() == 3
        assert acc.ticks == 3 and acc.records_seen == 200

    def test_imbalance_through_proposed_assignment(self):
        """The point of the accountant: score a move BEFORE applying
        it. Piling load onto shard 0's groups shows imbalance under
        the contiguous layout and ~balance under the fixed table."""
        acc = ShardLoadAccountant(4, 16, clock=FakeClock())
        for g, n in [(0, 300), (1, 300), (4, 100), (8, 100), (12, 100)]:
            acc.note_batch(np.repeat(_keys_for_group(g, 16, 1), n))
        cur = KeyGroupAssignment.contiguous(4, 16)
        before = acc.imbalance(cur)
        assert before == pytest.approx(600 * 4 / 900)
        fixed = cur.move([1], 3)  # hot group 1 off the hot shard
        assert acc.imbalance(fixed) < before
        np.testing.assert_allclose(
            acc.shard_load(fixed), [300, 100, 100, 400])

    def test_hot_key_sketch_flags_dominant_key(self):
        acc = ShardLoadAccountant(4, 16, top_k=4, clock=FakeClock())
        hot = _keys_for_group(5, 16, 1)[0]
        cold = np.arange(1000, 1200, dtype=np.int64)
        acc.note_batch(np.concatenate([np.full(800, hot, dtype=np.int64),
                                       cold]))
        cands = acc.hot_key_candidates()
        assert cands and cands[0][0] == int(hot)
        assert cands[0][1] == 5
        assert cands[0][2] > 0.9  # the key IS its group's load

    def test_register_metrics_skew_group(self):
        from flink_tpu.metrics.core import MetricRegistry

        acc = ShardLoadAccountant(4, 16, clock=FakeClock())
        acc.note_batch(np.repeat(_keys_for_group(0, 16, 1), 50))
        reg = MetricRegistry()
        acc.register_metrics(reg.root_group("job"))
        snap = reg.snapshot()
        assert snap["job.skew.records_seen"] == 50
        assert snap["job.skew.hottest_group"] == 0
        assert snap["job.skew.hottest_shard"] == 0
        assert snap["job.skew.imbalance"] == pytest.approx(4.0)
        assert snap["job.skew.hot_key_count"] == 1

    def test_rejects_bad_alpha(self):
        with pytest.raises(ValueError):
            ShardLoadAccountant(4, 16, ewma_alpha=0.0)
        with pytest.raises(ValueError):
            ShardLoadAccountant(4, 16, ewma_alpha=1.5)

    def test_busy_from_flight_sums_shard_spans(self):
        class Rec:
            def __init__(self, kind, shard, duration_s):
                self.kind = kind
                self.shard = shard
                self.duration_s = duration_s

        class Recorder:
            def snapshot(self):
                return [Rec("fire.shard", 0, 0.25),
                        Rec("fire.shard", 0, 0.25),
                        Rec("fire.shard", 2, 0.10),
                        Rec("batch", 1, 9.0),       # wrong kind
                        Rec("fire.shard", 7, 1.0)]  # out of range

        np.testing.assert_allclose(busy_from_flight(Recorder(), 3),
                                   [0.5, 0.0, 0.1])


class TestRebalancePolicy:
    def _loaded(self, loads, P=4, mp=16):
        """Accountant whose group_load() equals ``loads`` exactly."""
        acc = ShardLoadAccountant(P, mp, clock=FakeClock())
        for g, n in enumerate(loads):
            if n:
                acc.note_batch(np.repeat(_keys_for_group(g, mp, 1), n))
        return acc

    def test_balanced_load_plans_nothing(self):
        acc = self._loaded([10] * 16)
        pol = RebalancePolicy(imbalance_trigger=1.5, clock=FakeClock())
        plan = pol.plan(acc, KeyGroupAssignment.contiguous(4, 16))
        assert plan.assignment is None and plan.reason == "balanced"

    def test_moves_hot_groups_and_improves_imbalance(self):
        loads = [0] * 16
        loads[0], loads[1] = 300, 300  # both on shard 0
        for g in (4, 8, 12):
            loads[g] = 100
        acc = self._loaded(loads)
        pol = RebalancePolicy(imbalance_trigger=1.2, hysteresis=0.1,
                              cooldown_s=0.0, clock=FakeClock())
        plan = pol.plan(acc, KeyGroupAssignment.contiguous(4, 16))
        assert plan.reason == "rebalance" and plan.assignment is not None
        assert plan.imbalance_after < plan.imbalance_before
        # the first move lifts one of the hot groups off the hot shard
        g0, src0, dst0 = plan.moves[0]
        assert g0 in (0, 1) and src0 == 0 and dst0 != 0
        assert not plan.assignment.is_contiguous

    def test_hysteresis_discards_marginal_plans(self):
        loads = [0] * 16
        loads[0], loads[4], loads[8], loads[12] = 110, 100, 100, 100
        acc = self._loaded(loads)
        pol = RebalancePolicy(imbalance_trigger=1.0, hysteresis=0.9,
                              cooldown_s=0.0, clock=FakeClock())
        plan = pol.plan(acc, KeyGroupAssignment.contiguous(4, 16))
        assert plan.assignment is None
        assert plan.reason in ("hysteresis", "no-improving-move")

    def test_cooldown_blocks_then_allows(self):
        loads = [0] * 16
        loads[0], loads[1] = 300, 300
        for g in (4, 8, 12):
            loads[g] = 100
        acc = self._loaded(loads)
        clk = FakeClock()
        pol = RebalancePolicy(imbalance_trigger=1.2, hysteresis=0.05,
                              cooldown_s=30.0, clock=clk)
        cur = KeyGroupAssignment.contiguous(4, 16)
        assert pol.plan(acc, cur).reason == "rebalance"
        pol.mark_rebalanced()
        clk.advance(10.0)
        assert pol.plan(acc, cur).reason == "cooldown"
        clk.advance(25.0)
        assert pol.plan(acc, cur).reason == "rebalance"

    def test_dominant_key_reported_as_split_candidate(self):
        """One key carrying its whole group: moves cannot help (the
        group is atomic) — the policy must say SPLIT."""
        P, mp = 4, 16
        acc = ShardLoadAccountant(P, mp, clock=FakeClock())
        hot = _keys_for_group(0, mp, 1)[0]
        acc.note_batch(np.full(900, hot, dtype=np.int64))
        for g in (4, 8, 12):
            acc.note_batch(np.repeat(_keys_for_group(g, mp, 1), 50))
        pol = RebalancePolicy(imbalance_trigger=1.2, dominance_share=0.5,
                              cooldown_s=0.0, clock=FakeClock())
        plan = pol.plan(acc, KeyGroupAssignment.contiguous(P, mp))
        assert int(hot) in plan.split_candidates
        # and no move can fix it: shard 0 owns ONE loaded group
        assert plan.reason in ("no-improving-move", "rebalance")

    def test_one_group_shard_is_never_drained_into_a_swap(self):
        """max_moves=8 on a 2-shard layout with one hot group: the
        planner must not bounce the hot group back and forth."""
        loads = [0] * 8
        loads[0] = 100
        acc = self._loaded(loads, P=2, mp=8)
        pol = RebalancePolicy(imbalance_trigger=1.1, hysteresis=0.0,
                              cooldown_s=0.0, max_moves=8,
                              clock=FakeClock())
        plan = pol.plan(acc, KeyGroupAssignment.contiguous(2, 8))
        # moving the only loaded group just relocates the hot spot
        assert plan.assignment is None
        assert plan.reason == "no-improving-move"

    def test_rejects_bad_trigger(self):
        with pytest.raises(ValueError):
            RebalancePolicy(imbalance_trigger=0.5)


# ---------------------------------------------------------------------------
# the responder on a live mesh engine: measured imbalance and exactness
# ---------------------------------------------------------------------------


class TestSkewResponderOnLiveEngine:
    def test_moves_improve_measured_imbalance_and_output_stays_exact(
            self):
        """One key carries ~40% of all records through a paged mesh
        session engine with the SkewResponder live. The responder must
        move key groups (the assignment leaves the contiguous layout),
        split the dominant key into salted partials, and lower the
        accountant's measured imbalance below the contiguous layout's;
        integer-valued float32 values keep the salted fold exact, so
        the output is bit-identical to the single-device oracle."""
        from flink_tpu.autoscale import SkewResponder
        from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
        from flink_tpu.parallel.mesh import make_mesh
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.sessions import SessionWindower

        gap, hot, per_step = 100, 7, 1 << 15
        rng = np.random.default_rng(47)
        steps = []
        for s in range(8):
            keys = rng.integers(0, 20_000, per_step).astype(np.int64)
            keys[rng.random(per_step) < 0.4] = hot
            vals = rng.integers(1, 6, per_step).astype(np.float32)
            ts = rng.integers(s * 80, s * 80 + 60,
                              per_step).astype(np.int64)
            steps.append((keys, vals, ts, (s - 1) * 80))

        def batch(keys, vals, ts):
            return RecordBatch.from_pydict(
                {KEY_ID_FIELD: keys, "v": vals}, timestamps=ts)

        def collect(fired, out):
            for b in fired:
                for r in b.to_rows():
                    out[(r[KEY_ID_FIELD], r["window_start"],
                         r["window_end"])] = r["sum_v"]

        expected = {}
        oracle = SessionWindower(gap, SumAggregate("v"), capacity=1 << 16)
        for keys, vals, ts, wm in steps:
            oracle.process_batch(batch(keys, vals, ts))
            collect(oracle.on_watermark(wm), expected)
        collect(oracle.on_watermark(1 << 60), expected)

        # paged layout (hot-key splitting needs it) with a slot budget
        # small enough that the skewed run genuinely evicts
        engine = MeshSessionEngine(
            gap, SumAggregate("v"), make_mesh(4),
            capacity_per_shard=1 << 15, max_device_slots=4096)
        clk = FakeClock()
        acc = ShardLoadAccountant(engine.P, engine.max_parallelism,
                                  ewma_alpha=0.5, top_k=32, clock=clk)
        responder = SkewResponder(
            engine, acc,
            policy=RebalancePolicy(imbalance_trigger=1.3, hysteresis=0.02,
                                   cooldown_s=0.0, clock=clk),
            salts=8, hot_key_share=0.5, allow_inexact=True)
        got = {}
        for keys, vals, ts, wm in steps:
            clk.advance(1.0)
            responder.note_batch(keys)
            engine.process_batch(batch(keys, vals, ts))
            collect(engine.on_watermark(wm), got)
            responder.maybe_respond()
        collect(engine.on_watermark(1 << 60), got)

        assert responder.rebalances >= 1 and responder.groups_moved >= 1
        assignment = engine.key_group_assignment
        assert not assignment.is_contiguous
        stats = engine.hot_key_stats()
        assert responder.keys_split >= 1 and hot in stats["keys"]
        assert stats["salted_records"] > 0 and stats["salted_fires"] > 0
        assert acc.imbalance(assignment) < acc.imbalance(
            KeyGroupAssignment.contiguous(engine.P,
                                          engine.max_parallelism))
        assert len(expected) > 0 and got == expected
