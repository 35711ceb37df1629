"""Device-native streaming joins (flink_tpu/joins/): the interval and
temporal join engines over dual keyed slot tables.

The contract under test, in order of importance:

1. BIT-IDENTITY: the device engine (both shuffle modes) equals the
   host-numpy oracle backend row for row — same values, same emission
   order — including under forced paged eviction and a mid-stream live
   ``reshard()``. The oracle shares every metadata decision; the value
   path is pure movement, so equality is exact, not approximate.
2. CHECKPOINTS: snapshot -> restore -> snapshot round-trips bit-exactly;
   ``key_group_filter`` restores exactly one range;
   ``snapshot_sharded`` units union back to the full snapshot through
   ``merge_unit_snapshots``.
3. SEMANTICS: out-of-order and late rows behave exactly like the host
   operators (``runtime/join_operators.py`` — the reference-derived
   IntervalJoinOperator / TemporalJoinOperator), pinned as pair-set
   equality over identical streams.
"""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.joins import (
    MeshIntervalJoinEngine,
    MeshTemporalJoinEngine,
    pair_lower_bound,
)
from flink_tpu.parallel.mesh import make_mesh
from flink_tpu.state.keygroups import assign_key_groups


def kb(keys, vals, ts, name="v", dtype=np.float32):
    return RecordBatch({
        KEY_ID_FIELD: np.asarray(keys, dtype=np.int64),
        name: np.asarray(vals, dtype=dtype),
        TIMESTAMP_FIELD: np.asarray(ts, dtype=np.int64),
    })


def assert_batches_equal(got, want):
    assert len(got) == len(want), (len(got), len(want))
    for a, b in zip(got, want):
        assert sorted(a.names()) == sorted(b.names())
        assert len(a) == len(b), (len(a), len(b))
        for n in a.names():
            np.testing.assert_array_equal(a[n], b[n], err_msg=n)


def interval_stream(steps=6, n=400, keys=500, span=100, seed=0):
    """(side, keys, vals, ts, watermark) steps, deterministically out
    of order within each batch."""
    rng = np.random.default_rng(seed)
    out = []
    for step in range(steps):
        for side in (0, 1):
            ks = rng.integers(0, keys, n)
            ts = step * span + rng.integers(0, span, n)
            vs = rng.random(n).astype(np.float32)
            out.append((side, ks, vs, ts, step * span - 2 * span))
    return out


def drive_interval(eng, stream):
    out = []
    for side, ks, vs, ts, wm in stream:
        out += eng.process_batch(
            kb(ks, vs, ts, name="v" if side == 0 else "w"), side)
        eng.on_watermark(wm)
    return out


def pairs_of(batches):
    """Canonical (sorted) tuple set of joined rows — the order-free
    comparison for semantics pinning."""
    rows = set()
    for b in batches:
        for r in b.to_rows():
            rows.add(tuple(sorted(
                (k, round(float(v), 6) if isinstance(
                    v, (float, np.floating)) else int(v))
                for k, v in r.items())))
    return rows


class TestPairLowerBound:
    def test_matches_reference_lexicographic_search(self):
        rng = np.random.default_rng(3)
        k = np.sort(rng.integers(0, 20, 200))
        t = rng.integers(0, 50, 200)
        order = np.lexsort((t, k))
        k, t = k[order], t[order]
        qk = rng.integers(-1, 22, 64)
        qt = rng.integers(-5, 55, 64)
        got = pair_lower_bound(k, t, qk, qt)
        pairs = list(zip(k.tolist(), t.tolist()))
        for i in range(64):
            want = sum(1 for p in pairs if p < (qk[i], qt[i]))
            assert got[i] == want, (qk[i], qt[i])

    def test_empty_inputs(self):
        e = np.empty(0, dtype=np.int64)
        assert len(pair_lower_bound(e, e, e, e)) == 0
        assert pair_lower_bound(e, e, np.array([1]),
                                np.array([2]))[0] == 0


class TestIntervalOracle:
    def _host(self, **kw):
        return MeshIntervalJoinEngine(-30, 40, backend="host",
                                      num_shards=4, **kw)

    def _device(self, shuffle_mode="device", **kw):
        return MeshIntervalJoinEngine(-30, 40, mesh=make_mesh(4),
                                      shuffle_mode=shuffle_mode, **kw)

    @pytest.mark.parametrize("shuffle_mode", ["device", "host"])
    def test_device_matches_host_oracle_bitwise(self, shuffle_mode):
        stream = interval_stream()
        got = drive_interval(self._device(shuffle_mode=shuffle_mode),
                             stream)
        want = drive_interval(self._host(), stream)
        assert sum(len(b) for b in want) > 0
        assert_batches_equal(got, want)

    def test_bit_identity_under_forced_paged_eviction(self):
        # key space >> slots, watermark far behind: the plane thrashes
        stream = interval_stream(steps=8, n=700, keys=20_000, span=40)
        dev = self._device(capacity_per_shard=256,
                           max_device_slots=256)
        host = self._host(capacity_per_shard=256,
                          max_device_slots=256)
        got = drive_interval(dev, stream)
        want = drive_interval(host, stream)
        assert_batches_equal(got, want)
        sc = dev.spill_counters()
        assert sc["rows_evicted"] > 0, "spill never engaged — vacuous"
        assert sc["cold_rows_served"] > 0, \
            "no probe ever hit the page tier — vacuous"
        # the oracle's spill bookkeeping is the same code
        assert sc == host.spill_counters()

    def test_bit_identity_across_midstream_reshard(self):
        stream = interval_stream(steps=8, n=500, keys=8_000, span=40)
        dev = self._device(capacity_per_shard=256,
                           max_device_slots=256)
        host = self._host(capacity_per_shard=256,
                          max_device_slots=256)
        got, want = [], []
        for i, (side, ks, vs, ts, wm) in enumerate(stream):
            if i == 7:
                r1 = dev.reshard(2)
                r2 = host.reshard(2)
                assert r1["rows_moved"] == r2["rows_moved"] > 0
            if i == 12:
                dev.reshard(4)
                host.reshard(4)
            name = "v" if side == 0 else "w"
            got += dev.process_batch(kb(ks, vs, ts, name=name), side)
            want += host.process_batch(kb(ks, vs, ts, name=name), side)
            dev.on_watermark(wm)
            host.on_watermark(wm)
        assert sum(len(b) for b in want) > 0
        assert_batches_equal(got, want)

    def test_int64_columns_ride_the_host_shadow_bitwise(self):
        # int64 cannot ride the x32 device plane — the shadow store
        # carries it in BOTH modes, so 2^53+ values stay exact
        big = (1 << 60) + 7
        dev = self._device()
        host = self._host()
        got, want = [], []
        for eng, sink in ((dev, got), (host, want)):
            sink += eng.process_batch(
                kb([1, 2], [big, big + 1], [0, 10], name="snowflake",
                   dtype=np.int64), 0)
            sink += eng.process_batch(
                kb([1, 2], [5, 6], [5, 15], name="w"), 1)
        assert_batches_equal(got, want)
        assert got[0]["snowflake"].dtype == np.int64
        # emission is shard-major — compare as a set
        assert set(got[0]["snowflake"].tolist()) == {big, big + 1}

    def test_shared_key_routing_makes_probes_shard_local(self):
        # both sides of one key land on the same shard: a pair whose
        # sides were co-partitioned differently could never match
        eng = self._device()
        out = eng.process_batch(kb([123], [1.0], [0]), 0)
        out += eng.process_batch(kb([123], [2.0], [5]), 1)
        assert sum(len(b) for b in out) == 1

    def test_invalid_modes_rejected(self):
        with pytest.raises(ValueError):
            MeshIntervalJoinEngine(-1, 1, backend="gpu")
        with pytest.raises(ValueError):
            MeshIntervalJoinEngine(-1, 1, backend="host",
                                   shuffle_mode="magic")
        with pytest.raises(ValueError):
            MeshIntervalJoinEngine(5, 4, backend="host")


class TestSteadyState:
    """After one pass warms the shared program cache, a FRESH device
    engine replaying the same stream compiles ZERO XLA programs, with
    its spill tier engaged (rows evicted AND cold band candidates
    served from pages) so the eviction and cold-probe programs are
    part of what is guarded."""

    STEPS, BATCH, KEYS = 8, 2048, 40_000
    #: slots per shard and side, far below the live set; a band deep
    #: enough to probe into the paged tier
    BUDGET, BAND, WM_LAG = 512, 2500, 3000

    def _drive(self, engine, seed=23):
        rng = np.random.default_rng(seed)
        out = []
        t = 0
        for _ in range(self.STEPS):
            for side, name in ((0, "v"), (1, "w")):
                keys = rng.integers(0, self.KEYS, self.BATCH)
                ts = t + np.arange(self.BATCH, dtype=np.int64) // 4
                out += engine.process_batch(
                    kb(keys, rng.random(self.BATCH), ts, name=name),
                    side)
                t = int(ts[-1]) + 1
            engine.on_watermark(t - self.WM_LAG)
        return out

    def test_temporal_engine_matches_oracle_at_depth(self):
        """The temporal join over the same 40,000-key stream on 8
        shards of 512 slots, versions first, then the probe side, a
        lagging watermark: bit-identical to the host oracle, and it
        matches something."""
        mesh = make_mesh(8)

        def drive(engine, seed=29):
            rng = np.random.default_rng(seed)
            out = []
            t = 0
            for _ in range(self.STEPS):
                for side, name in ((1, "rate"), (0, "v")):
                    keys = rng.integers(0, self.KEYS, self.BATCH)
                    ts = t + np.arange(self.BATCH, dtype=np.int64) // 4
                    out += engine.process_batch(
                        kb(keys, rng.random(self.BATCH), ts, name=name),
                        side)
                    if side == 0:
                        t = int(ts[-1]) + 1
                out += engine.on_watermark(t - self.WM_LAG)
            return out + engine.on_watermark(1 << 40)

        kw = dict(capacity_per_shard=self.BUDGET,
                  max_device_slots=self.BUDGET)
        got = drive(MeshTemporalJoinEngine(mesh=mesh, **kw))
        want = drive(MeshTemporalJoinEngine(backend="host", num_shards=8,
                                            **kw))
        assert sum(len(b) for b in want) > 0, "vacuous: nothing matched"
        assert_batches_equal(got, want)

    def test_fresh_interval_engine_compiles_nothing(self):
        from flink_tpu.observe import RecompileSentinel

        mesh = make_mesh(8)

        def make():
            return MeshIntervalJoinEngine(
                -self.BAND, self.BAND, mesh=mesh,
                capacity_per_shard=self.BUDGET,
                max_device_slots=self.BUDGET)

        warm = self._drive(make())
        assert sum(len(b) for b in warm) > 0
        engine = make()
        with RecompileSentinel(max_compiles=0,
                               max_transfers=self.STEPS * 16,
                               label="join steady state"):
            got = self._drive(engine)
        assert_batches_equal(got, warm)
        sc = engine.spill_counters()
        assert sc["rows_evicted"] > 0, "spill never engaged — vacuous"
        assert sc["cold_rows_served"] > 0, \
            "no probe ever hit the page tier — vacuous"


class TestTemporalOracle:
    def _drive(self, eng, steps=8, seed=1):
        rng = np.random.default_rng(seed)
        out = []
        for step in range(steps):
            n = 300
            ks = rng.integers(0, 150, n)
            ts = step * 100 + rng.integers(0, 100, n)
            out += eng.process_batch(
                kb(ks, rng.random(n).astype(np.float32), ts), 0)
            vk = rng.integers(0, 150, 60)
            vt = step * 100 + rng.integers(0, 100, 60)
            out += eng.process_batch(
                kb(vk, rng.random(60).astype(np.float32), vt,
                   name="rate"), 1)
            out += eng.on_watermark(step * 100 - 50)
        out += eng.on_watermark(1 << 40)
        return out

    def test_device_matches_host_oracle_bitwise(self):
        got = self._drive(MeshTemporalJoinEngine(mesh=make_mesh(4)))
        want = self._drive(MeshTemporalJoinEngine(backend="host",
                                                  num_shards=4))
        assert sum(len(b) for b in want) > 0
        assert_batches_equal(got, want)

    def test_versioned_plane_under_forced_eviction(self):
        dev = MeshTemporalJoinEngine(mesh=make_mesh(4),
                                     capacity_per_shard=256,
                                     max_device_slots=256)
        host = MeshTemporalJoinEngine(backend="host", num_shards=4,
                                      capacity_per_shard=256,
                                      max_device_slots=256)
        rng = np.random.default_rng(5)
        got, want = [], []
        for step in range(6):
            vk = rng.integers(0, 30_000, 900)
            vt = step * 50 + rng.integers(0, 50, 900)
            vv = rng.random(900).astype(np.float32)
            lk = rng.integers(0, 30_000, 400)
            lt = step * 50 + rng.integers(0, 50, 400)
            lv = rng.random(400).astype(np.float32)
            for eng, sink in ((dev, got), (host, want)):
                sink += eng.process_batch(
                    kb(vk, vv, vt, name="rate"), 1)
                sink += eng.process_batch(kb(lk, lv, lt), 0)
                # watermark far behind: versions pile up and spill
                sink += eng.on_watermark(step * 50 - 500)
        for eng, sink in ((dev, got), (host, want)):
            sink += eng.on_watermark(1 << 40)
        assert_batches_equal(got, want)
        sc = dev.spill_counters()
        assert sc["rows_evicted"] > 0 and sc["cold_rows_served"] > 0
        assert sc == host.spill_counters()

    def test_late_left_rows_drop_with_counter(self):
        eng = MeshTemporalJoinEngine(backend="host", num_shards=2)
        eng.process_batch(kb([1], [9.0], [100], name="rate"), 1)
        eng.on_watermark(200)
        out = eng.process_batch(kb([1, 1], [1.0, 2.0], [150, 300]), 0)
        assert out == []
        assert eng.late_left_dropped == 1  # ts=150 <= watermark 200
        out = eng.on_watermark(400)
        assert sum(len(b) for b in out) == 1  # the ts=300 row joined


class TestSemanticsVsHostOperators:
    """Out-of-order / late-row semantics pinned against the
    reference-derived host operators over identical streams."""

    def test_interval_pairs_equal_interval_join_operator(self):
        from flink_tpu.runtime.join_operators import (
            IntervalJoinOperator,
        )

        stream = interval_stream(steps=6, n=250, keys=60, span=80,
                                 seed=9)
        eng = MeshIntervalJoinEngine(-30, 40, backend="host",
                                     num_shards=4)
        op = IntervalJoinOperator(-30, 40)
        got, want = [], []
        for side, ks, vs, ts, wm in stream:
            name = "v" if side == 0 else "w"
            got += eng.process_batch(kb(ks, vs, ts, name=name), side)
            want += op.process_batch(kb(ks, vs, ts, name=name), side)
            eng.on_watermark(wm)
            op.process_watermark(wm)
        assert pairs_of(got) == pairs_of(want)
        assert len(pairs_of(got)) > 0

    def test_pruned_rows_never_match_like_host_operator(self):
        from flink_tpu.runtime.join_operators import (
            IntervalJoinOperator,
        )

        eng = MeshIntervalJoinEngine(0, 10, backend="host",
                                     num_shards=2)
        op = IntervalJoinOperator(0, 10)
        for o in (eng, op):
            o.process_batch(kb([7], [1.0], [100]), 0)
        # watermark passes 100 + upper: the left row is dead in both
        eng.on_watermark(200)
        op.process_watermark(200)
        got = eng.process_batch(kb([7], [2.0], [105], name="w"), 1)
        want = op.process_batch(kb([7], [2.0], [105], name="w"), 1)
        assert pairs_of(got) == pairs_of(want) == set()

    def test_temporal_pairs_equal_temporal_join_operator(self):
        from flink_tpu.runtime.join_operators import (
            TemporalJoinOperator,
        )

        rng = np.random.default_rng(11)
        eng = MeshTemporalJoinEngine(backend="host", num_shards=4)
        op = TemporalJoinOperator()
        got, want = [], []
        for step in range(6):
            ks = rng.integers(0, 40, 200)
            ts = step * 100 + rng.integers(0, 100, 200)
            vs = rng.random(200).astype(np.float32)
            vk = rng.integers(0, 40, 40)
            vt = step * 100 + rng.integers(0, 100, 40)
            vv = rng.random(40).astype(np.float32)
            for o, sink in ((eng, got), (op, want)):
                pb = o.process_batch
                sink += pb(kb(ks, vs, ts), 0)
                sink += pb(kb(vk, vv, vt, name="rate"), 1)
                wm = step * 100 - 30
                sink += (o.on_watermark(wm) if o is eng
                         else o.process_watermark(wm))
        got += eng.on_watermark(1 << 40)
        want += op.process_watermark(1 << 40)
        assert pairs_of(got) == pairs_of(want)
        assert len(pairs_of(got)) > 0
        assert eng.late_left_dropped == op.late_left_dropped


class TestCheckpoints:
    def _spilling_engine(self, backend="device"):
        kw = dict(capacity_per_shard=256, max_device_slots=256)
        if backend == "device":
            return MeshIntervalJoinEngine(-30, 40, mesh=make_mesh(4),
                                          **kw)
        return MeshIntervalJoinEngine(-30, 40, backend="host",
                                      num_shards=4, **kw)

    def _loaded(self, backend="device"):
        eng = self._spilling_engine(backend)
        drive_interval(eng, interval_stream(steps=4, n=600,
                                            keys=20_000, span=40))
        return eng

    def test_snapshot_restore_snapshot_roundtrip_bitwise(self):
        eng = self._loaded()
        s1 = eng.snapshot()
        fresh = self._spilling_engine()
        fresh.restore(s1)
        s2 = fresh.snapshot()
        assert s2["next_rid"] == s1["next_rid"]
        for side in ("left", "right"):
            t1, t2 = s1[side]["table"], s2[side]["table"]
            assert set(t1) == set(t2)
            for k in t1:
                if k == "dirty":
                    continue  # restored rows are the checkpoint's: clean
                np.testing.assert_array_equal(
                    np.asarray(t1[k]), np.asarray(t2[k]),
                    err_msg=f"{side}/{k}")

    def test_restored_engine_continues_bit_identical(self):
        stream = interval_stream(steps=8, n=500, keys=20_000, span=40)
        ref = self._spilling_engine()
        cut = self._spilling_engine()
        got, want = [], []
        for i, (side, ks, vs, ts, wm) in enumerate(stream):
            if i == 8:
                snap = cut.snapshot()
                cut = self._spilling_engine()
                cut.restore(snap)
            name = "v" if side == 0 else "w"
            want += ref.process_batch(kb(ks, vs, ts, name=name), side)
            got += cut.process_batch(kb(ks, vs, ts, name=name), side)
            ref.on_watermark(wm)
            cut.on_watermark(wm)
        assert_batches_equal(got, want)

    def test_key_group_filter_restores_exactly_one_range(self):
        eng = self._loaded()
        snap = eng.snapshot()
        g0, g1 = eng.shard_key_groups()[1]
        fresh = self._spilling_engine()
        fresh.restore(snap, key_group_filter=range(g0, g1 + 1))
        s2 = fresh.snapshot()
        for side in ("left", "right"):
            full = snap[side]["table"]
            kept = s2[side]["table"]
            kg_full = np.asarray(full["key_group"])
            in_range = (kg_full >= g0) & (kg_full <= g1)
            assert len(kept["key_id"]) == int(in_range.sum()) > 0
            np.testing.assert_array_equal(
                np.asarray(kept["namespace"]),
                np.asarray(full["namespace"])[in_range])

    def test_sharded_units_union_to_full_snapshot(self):
        eng = self._loaded()
        full = eng.snapshot()
        units = eng.snapshot_sharded()
        assert set(units) == set(
            (g0, g1) for g0, g1 in eng.shard_key_groups())
        # disjoint cover: every row lands in exactly one unit
        merged = eng.merge_unit_snapshots(list(units.values()))
        for side in ("left", "right"):
            t_full, t_merged = (full[side]["table"],
                                merged[side]["table"])
            for k in t_full:
                np.testing.assert_array_equal(
                    np.asarray(t_full[k]), np.asarray(t_merged[k]),
                    err_msg=f"{side}/{k}")
        fresh = self._spilling_engine()
        fresh.restore(merged)
        s2 = fresh.snapshot()
        for side in ("left", "right"):
            np.testing.assert_array_equal(
                np.asarray(s2[side]["table"]["namespace"]),
                np.asarray(full[side]["table"]["namespace"]))

    def test_restore_grows_past_base_capacity_without_spill(self):
        # an engine with no spill tier grows its plane during the run;
        # a fresh engine at BASE capacity must restore that snapshot by
        # growing exactly like ingest does (a recovery path must never
        # be narrower than the run that produced the checkpoint)
        eng = MeshIntervalJoinEngine(-30, 40, backend="host",
                                     num_shards=2,
                                     capacity_per_shard=256)
        rng = np.random.default_rng(7)
        keys = rng.integers(0, 100_000, 2000)
        eng.process_batch(kb(keys, np.ones(2000, np.float32),
                             np.arange(2000)), 0)
        snap = eng.snapshot()
        assert len(snap["left"]["table"]["key_id"]) == 2000
        fresh = MeshIntervalJoinEngine(-30, 40, backend="host",
                                       num_shards=2,
                                       capacity_per_shard=256)
        fresh.restore(snap)
        s2 = fresh.snapshot()
        np.testing.assert_array_equal(
            np.asarray(s2["left"]["table"]["namespace"]),
            np.asarray(snap["left"]["table"]["namespace"]))

    def test_temporal_snapshot_carries_pending_and_watermark(self):
        eng = MeshTemporalJoinEngine(backend="host", num_shards=2)
        eng.process_batch(kb([1, 2], [1.0, 2.0], [50, 60],
                             name="rate"), 1)
        eng.process_batch(kb([1, 2], [3.0, 4.0], [80, 90]), 0)
        eng.on_watermark(70)
        snap = eng.snapshot()
        assert snap["pending"] is not None
        fresh = MeshTemporalJoinEngine(backend="host", num_shards=2)
        fresh.restore(snap)
        assert fresh._emitted_wm == eng._emitted_wm
        out = fresh.on_watermark(1 << 40)
        want = eng.on_watermark(1 << 40)
        assert_batches_equal(out, want)

    def test_temporal_sharded_units_split_pending_by_range(self):
        eng = MeshTemporalJoinEngine(backend="host", num_shards=2)
        keys = np.arange(64, dtype=np.int64)
        eng.process_batch(kb(keys, np.ones(64), keys * 0 + 100), 0)
        units = eng.snapshot_sharded()
        tot = 0
        for (gg0, gg1), u in units.items():
            pend = u["pending"]
            kg = assign_key_groups(
                np.asarray(pend[KEY_ID_FIELD], dtype=np.int64),
                eng.max_parallelism)
            assert ((kg >= gg0) & (kg <= gg1)).all()
            tot += len(pend[KEY_ID_FIELD])
        assert tot == 64
        merged = eng.merge_unit_snapshots(list(units.values()))
        assert len(merged["pending"][KEY_ID_FIELD]) == 64


class TestWatchdogAndOperators:
    def test_watchdog_sections_wrap_device_interactions(self):
        from flink_tpu.runtime.watchdog import DeviceWatchdog

        eng = MeshIntervalJoinEngine(-30, 40, mesh=make_mesh(2))
        wd = DeviceWatchdog(2, deadline_ms=0.0)
        eng.attach_watchdog(wd)
        stream = interval_stream(steps=2, n=100, keys=30)
        drive_interval(eng, stream)
        assert wd.heartbeat_age_s() < 60

    def test_device_interval_join_operator_end_to_end(self):
        from flink_tpu.joins.operators import (
            DeviceIntervalJoinOperator,
        )
        from flink_tpu.runtime.operators import OperatorContext

        op = DeviceIntervalJoinOperator(-30, 40, capacity=2048)
        op.open(OperatorContext(parallelism=2))
        out = op.process_batch(kb([1, 2], [1.0, 2.0], [0, 10]), 0)
        out += op.process_batch(kb([1, 2], [5.0, 6.0], [5, 15],
                                   name="w"), 1)
        assert sum(len(b) for b in out) == 2
        snap = op.snapshot_state()
        op2 = DeviceIntervalJoinOperator(-30, 40, capacity=2048)
        op2.open(OperatorContext(parallelism=2))
        op2.restore_state(snap)
        assert op2.engine.snapshot()["next_rid"] == \
            op.engine.snapshot()["next_rid"]
        assert op.supports_live_rescale()
        op.reshard(1)
        assert op.engine.P == 1

    def test_device_temporal_join_operator_end_to_end(self):
        from flink_tpu.joins.operators import (
            DeviceTemporalJoinOperator,
        )
        from flink_tpu.runtime.operators import OperatorContext

        op = DeviceTemporalJoinOperator(capacity=2048)
        op.open(OperatorContext(parallelism=2))
        op.process_batch(kb([1], [9.5], [100], name="rate"), 1)
        op.process_batch(kb([1], [1.0], [150]), 0)
        out = op.process_watermark(200)
        assert sum(len(b) for b in out) == 1
        row = out[0].to_rows()[0]
        assert row["rate"] == pytest.approx(9.5)

    def test_datastream_join_mode_device_matches_host(self):
        from flink_tpu import Configuration, StreamExecutionEnvironment
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import Source

        class SideSource(Source):
            def __init__(self, seed, col):
                self.seed, self.col, self.done = seed, col, False

            def poll_batch(self, max_records):
                if self.done:
                    return None
                self.done = True
                rng = np.random.default_rng(self.seed)
                n = 600
                ks = rng.integers(0, 40, n).astype(np.int64)
                ts = np.sort(rng.integers(0, 2000, n).astype(np.int64))
                return RecordBatch.from_pydict(
                    {"k": ks,
                     self.col: rng.random(n).astype(np.float32)},
                    timestamps=ts)

        def run(mode):
            env = StreamExecutionEnvironment(Configuration({
                "join.mode": mode,
                "execution.micro-batch.size": 128}))
            sink = CollectSink()
            left = env.add_source(SideSource(1, "price")).key_by("k")
            right = env.add_source(SideSource(2, "rate")).key_by("k")
            left.interval_join(right).between(-100, 100).sink_to(sink)
            env.execute("ij-" + mode)
            return pairs_of(sink.batches)

        host = run("host")
        device = run("device")
        assert host == device
        assert len(host) > 0
