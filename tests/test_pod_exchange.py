"""The pod-scale data plane: process-spanning mesh construction and the
two-level ICI/DCN exchange (parallel/exchange2.py).

Everything here runs SINGLE-process on a 2x4 (and 2x2) VIRTUAL topology
over 8 CPU devices — the exchange programs only see the (hosts, local)
factorization, so plain tier-1 exercises the exact program family the
two-process pod run (``tests/test_host_failover.py``) dispatches across
real process boundaries. The
contracts pinned:

- ``make_mesh`` REFUSES to silently truncate (`num_devices` beyond the
  available devices used to return a smaller mesh, silently re-routing
  key groups),
- the stable host -> key-group-range mapping (``host_key_group_ranges``)
  is contiguous, covers the space, and inverts the routing formula,
- the two-level exchange is BIT-IDENTICAL to the flat single-axis
  program AND to the host-bucketing path on identical input, for both
  mesh engines and the join exchange, under forced paged eviction
  (stream order is preserved end to end, so float folds stay bit-exact),
- per-level bucket tiers + traffic split accounting,
- a reshard / partial failover that changes the device count drops the
  stale factorization instead of programming a mesh it no longer
  covers.
"""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD
from flink_tpu.parallel.mesh import (
    HostTopology,
    make_mesh,
    pod_mesh_view,
)
from flink_tpu.parallel.exchange2 import (
    ExchangeTraffic,
    stage_two_level_exchange,
)
from flink_tpu.state.keygroups import (
    host_key_group_ranges,
    host_of_key_group,
    shard_key_group_ranges,
)
from flink_tpu.windowing.aggregates import SumAggregate

from tests.test_sessions import keyed_batch

GAP = 100


# ------------------------------------------------------------------ mesh


class TestMakeMesh:
    def test_oversized_request_raises_instead_of_truncating(self):
        import jax

        available = len(jax.devices())
        with pytest.raises(ValueError, match=str(available)):
            make_mesh(available + 1)

    def test_exact_and_smaller_requests_still_work(self):
        import jax

        available = len(jax.devices())
        assert make_mesh(available).devices.size == available
        assert make_mesh(2).devices.size == 2

    def test_invalid_span_rejected(self):
        with pytest.raises(ValueError, match="span"):
            make_mesh(span="pod")

    def test_topology_validation(self):
        with pytest.raises(ValueError):
            HostTopology(0, 4)
        t = HostTopology(2, 4)
        assert t.num_shards == 8
        assert t.host_of_shard(0) == 0 and t.host_of_shard(7) == 1
        assert list(t.shards_of_host(1)) == [4, 5, 6, 7]
        with pytest.raises(ValueError):
            t.shards_of_host(2)

    def test_pod_mesh_view_is_sharding_equivalent(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_tpu.parallel.mesh import (
            HOST_AXIS,
            KEY_AXIS,
            LOCAL_AXIS,
        )

        mesh = make_mesh(8)
        view = pod_mesh_view(mesh, HostTopology(2, 4))
        flat = NamedSharding(mesh, P(KEY_AXIS))
        two = NamedSharding(view, P((HOST_AXIS, LOCAL_AXIS)))
        # the whole no-copy handoff between flat and two-level programs
        assert flat.is_equivalent_to(two, 2)
        with pytest.raises(ValueError):
            pod_mesh_view(mesh, HostTopology(2, 3))

    def test_engine_rejects_noncovering_topology(self):
        from flink_tpu.parallel.sharded_sessions import (
            MeshSessionEngine,
        )

        with pytest.raises(ValueError, match="does not cover"):
            MeshSessionEngine(GAP, SumAggregate("v"), make_mesh(8),
                              host_topology=HostTopology(2, 3))


class TestHostKeyGroupRanges:
    def test_contiguous_and_covering(self):
        for mp in (128, 100, 11):
            for h, l in ((2, 4), (4, 2), (3, 2)):
                if h * l > mp:
                    continue
                ranges = host_key_group_ranges(h, l, mp)
                assert ranges[0][0] == 0
                assert ranges[-1][1] == mp - 1
                for (a0, a1), (b0, b1) in zip(ranges, ranges[1:]):
                    assert b0 == a1 + 1  # contiguous, no gap

    def test_union_of_shard_ranges(self):
        sr = shard_key_group_ranges(8, 128)
        hr = host_key_group_ranges(2, 4, 128)
        assert hr == [(sr[0][0], sr[3][1]), (sr[4][0], sr[7][1])]

    def test_host_of_key_group_matches_ranges(self):
        mp = 100
        ranges = host_key_group_ranges(2, 4, mp)
        groups = np.arange(mp, dtype=np.int64)
        owners = host_of_key_group(groups, 2, 4, mp)
        for h, (g0, g1) in enumerate(ranges):
            assert (owners[g0:g1 + 1] == h).all()


# --------------------------------------------------------------- staging


class TestTwoLevelStaging:
    def test_layout_padding_and_tiers(self):
        topo = HostTopology(2, 2)
        rng = np.random.default_rng(3)
        n = 900
        shards = rng.integers(0, 4, n).astype(np.int64)
        slots = rng.integers(1, 64, n).astype(np.int32)
        dst, (s_col,), w1, w2 = stage_two_level_exchange(
            shards, topo, columns=[slots], fills=[0])
        from flink_tpu.parallel.shuffle import exchange_chunk_size

        C = exchange_chunk_size(n, 4)
        assert len(dst) == 4 * C == len(s_col)
        np.testing.assert_array_equal(dst[:n], shards)
        assert (dst[n:] == 4).all()
        # per-level tiers: pow2, bounded by the level above
        assert w1 & (w1 - 1) == 0 and w1 <= C
        assert w2 & (w2 - 1) == 0 and w2 <= topo.local_devices * w1

    def test_traffic_split_accounting(self):
        topo = HostTopology(2, 2)
        tr = ExchangeTraffic()
        # chunk layout: C=256, so records 0..255 are chunk 0 (host 0)
        # — 3 intra (dst shards 0/1), 1 cross (dst shard 2)
        shards = np.array([0, 1, 0, 2], dtype=np.int64)
        stage_two_level_exchange(shards, topo,
                                 columns=[np.ones(4, np.int32)],
                                 fills=[0], traffic=tr)
        assert tr.rows_intra_host == 3
        assert tr.rows_cross_host == 1
        assert tr.batches == 1


# ------------------------------------------------- engine bit-identity


def _stream(num_keys=20_000, n_steps=6, per_step=5000, seed=11):
    """Live set beyond a 1024-slot/shard budget: forced paged eviction
    on the session engine. Integer values keep float sums exact so
    bit-identity across data planes is meaningful."""
    rng = np.random.default_rng(seed)
    steps = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.integers(0, 1000, per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        steps.append((keys, vals, ts, (s - 1) * 80))
    steps.append((np.array([0], dtype=np.int64),
                  np.array([0.0], dtype=np.float32),
                  np.array([n_steps * 80 + 10_000], dtype=np.int64),
                  10 ** 9))
    return steps


def _run(engine, steps):
    fired = []
    for keys, vals, ts, wm in steps:
        engine.process_batch(keyed_batch(keys, vals, ts))
        fired.extend(engine.on_watermark(wm))
    return fired


def _fired_dict(batches, field="sum_v"):
    out = {}
    for b in batches:
        for r in b.to_rows():
            out[(r[KEY_ID_FIELD], r["window_start"],
                 r["window_end"])] = r[field]
    return out


def _session_engine(**kw):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(gap=GAP, agg=SumAggregate("v"),
                             mesh=make_mesh(8),
                             capacity_per_shard=1 << 14,
                             max_device_slots=1024, **kw)


def _window_engine(**kw):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    return MeshWindowEngine(TumblingEventTimeWindows.of(50),
                            SumAggregate("v"), make_mesh(8),
                            capacity_per_shard=1 << 14, **kw)


class TestTwoLevelBitIdentity:
    """The acceptance contract: identical input through the two-level
    program, the flat single-axis program and the host bucketing path
    produces BIT-IDENTICAL fires — stream order survives both hops."""

    def test_sessions_two_level_vs_flat_vs_host(self):
        steps = _stream()
        results = {}
        for name, kw in (
                ("flat", dict(shuffle_mode="device")),
                ("two", dict(shuffle_mode="device",
                             host_topology=HostTopology(2, 4))),
                ("host", dict(shuffle_mode="host"))):
            eng = _session_engine(**kw)
            results[name] = _fired_dict(_run(eng, steps))
            if name == "two":
                tr = eng.exchange2_traffic()
                assert tr["rows_cross_host"] > 0, \
                    "vacuous: no cross-host rows at this shape"
                assert tr["rows_intra_host"] > 0
                assert eng.spill_counters()["rows_evicted"] > 0, \
                    "vacuous: the spill tier never engaged"
        assert results["two"] == results["flat"]
        assert results["two"] == results["host"]
        assert len(results["two"]) > 1000

    def test_windows_two_level_vs_flat_vs_host(self):
        steps = _stream()
        results = {}
        for name, kw in (
                ("flat", dict(shuffle_mode="device")),
                ("two", dict(shuffle_mode="device",
                             host_topology=HostTopology(2, 4))),
                ("host", dict(shuffle_mode="host"))):
            results[name] = _fired_dict(_run(_window_engine(**kw),
                                             steps))
        assert results["two"] == results["flat"]
        assert results["two"] == results["host"]

    def test_windows_valued_two_level_path(self):
        """Two-phase partial batches (the valued exchange variant)
        through the two-level program == flat."""
        from flink_tpu.runtime.local_agg import PARTIAL_LEAF_PREFIX

        steps = _stream(per_step=3000, n_steps=4)

        def run_valued(**kw):
            eng = _window_engine(**kw)
            fired = []
            for keys, vals, ts, wm in steps:
                b = keyed_batch(keys, vals, ts)
                pb = b.with_column(PARTIAL_LEAF_PREFIX + "0", vals)
                eng.process_batch(pb)
                fired.extend(eng.on_watermark(wm))
            return _fired_dict(fired)

        flat = run_valued(shuffle_mode="device")
        two = run_valued(shuffle_mode="device",
                         host_topology=HostTopology(2, 4))
        assert two == flat

    def test_single_host_topology_keeps_flat_fast_path(self):
        eng = _session_engine(shuffle_mode="device",
                              host_topology=HostTopology(1, 8))
        assert not eng._two_level_active()
        steps = _stream(per_step=1000, n_steps=3)
        flat = _fired_dict(_run(_session_engine(), steps))
        one = _fired_dict(_run(eng, steps))
        assert one == flat
        assert eng.exchange2_traffic()["exchange2_batches"] == 0

    def test_reshard_drops_stale_topology(self):
        eng = _session_engine(shuffle_mode="device",
                              host_topology=HostTopology(2, 4))
        steps = _stream(per_step=1000, n_steps=3)
        oracle = _fired_dict(_run(_session_engine(), steps))
        fired = []
        for i, (keys, vals, ts, wm) in enumerate(steps):
            if i == 2:
                eng.reshard(4)
                assert eng.host_topology is None, \
                    "a 2x4 factorization cannot describe 4 shards"
            eng.process_batch(keyed_batch(keys, vals, ts))
            fired.extend(eng.on_watermark(wm))
        assert _fired_dict(fired) == oracle


class TestJoinTwoLevel:
    def _join_steps(self, n_steps=5, per_step=600, seed=5):
        from flink_tpu.core.records import (
            TIMESTAMP_FIELD,
            RecordBatch,
        )

        rng = np.random.default_rng(seed)
        steps = []
        for s in range(n_steps):
            keys = rng.integers(0, 500, per_step).astype(np.int64)
            ts = rng.integers(s * 50, s * 50 + 45,
                              per_step).astype(np.int64)
            vals = rng.integers(0, 100, per_step).astype(np.float32)
            steps.append((RecordBatch({
                KEY_ID_FIELD: keys, "v": vals,
                TIMESTAMP_FIELD: ts}), (s - 1) * 50))
        return steps

    def _run_join(self, topology):
        from flink_tpu.joins import MeshIntervalJoinEngine

        eng = MeshIntervalJoinEngine(
            -40, 40, mesh=make_mesh(8), capacity_per_shard=4096,
            host_topology=topology)
        pairs = []
        for b, wm in self._join_steps():
            left = np.arange(len(b)) % 2 == 0
            eng.process_batch(b.filter(left), 0)
            eng.process_batch(b.filter(~left), 1)
            out = eng.on_watermark(wm)
            for ob in out:
                pairs.extend(tuple(sorted(r.items()))
                             for r in ob.to_rows())
        return eng, pairs

    def test_interval_join_two_level_bit_identical(self):
        _, flat = self._run_join(None)
        eng, two = self._run_join(HostTopology(2, 4))
        assert two == flat  # values AND emission order
        tr = eng.exchange2_traffic()
        assert tr["rows_cross_host"] > 0

    def test_join_rejects_host_backend_topology(self):
        from flink_tpu.joins import MeshIntervalJoinEngine

        with pytest.raises(ValueError, match="device backend"):
            MeshIntervalJoinEngine(-40, 40, backend="host",
                                   num_shards=8,
                                   host_topology=HostTopology(2, 4))


class TestOperatorWiring:
    def test_ctx_host_topology_reaches_the_engine(self):
        """shuffle.hosts (an int host count through OperatorContext)
        factors the engine's mesh into the (hosts, local) topology;
        a count that cannot factor the mesh falls back flat."""
        import jax

        from flink_tpu.runtime.operators import (
            OperatorContext,
            SessionWindowAggOperator,
        )

        par = min(8, len(jax.devices()))
        op = SessionWindowAggOperator(gap=GAP, agg=SumAggregate("v"),
                                      key_field="k")
        op.open(OperatorContext(parallelism=par, host_topology=2))
        t = op.windower.host_topology
        assert t is not None and t.num_hosts == 2
        assert t.num_shards == op.windower.P
        # a non-factoring declaration keeps the flat exchange
        op2 = SessionWindowAggOperator(gap=GAP, agg=SumAggregate("v"),
                                       key_field="k")
        op2.open(OperatorContext(parallelism=par, host_topology=5))
        assert op2.windower.host_topology is None

    def test_executor_config_arms_the_two_level_exchange(self):
        """An end-to-end job with shuffle.hosts=2 produces output
        identical to the flat run — the config plumbs through the
        local executor into the engine."""
        from flink_tpu import (
            Configuration,
            StreamExecutionEnvironment,
        )
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        rng = np.random.default_rng(3)
        n = 4000
        rows = [{"k": int(k), "v": float(v), "t": int(t)}
                for k, v, t in zip(
                    rng.integers(0, 500, n),
                    rng.integers(0, 100, n),
                    rng.integers(0, 400, n))]

        def run(hosts):
            conf = {"parallelism.default": 8}
            if hosts:
                conf["shuffle.hosts"] = hosts
            env = StreamExecutionEnvironment(Configuration(conf))
            result = (
                env.from_collection(rows, timestamp_field="t")
                .key_by("k")
                .window(TumblingEventTimeWindows.of(100))
                .aggregate(SumAggregate("v"))
                .execute_and_collect()
            )
            return sorted((r["k"], r["window_start"], r["sum_v"])
                          for r in result.to_rows())

        assert run(2) == run(0)


class TestPodDataPlane:
    """The DCN record router (parallel/pod.py) in its single-process
    tier-1 mode: same program family the two-process pod run dispatches
    across real process boundaries."""

    def test_routes_to_owner_in_stream_order(self):
        from flink_tpu.parallel.pod import PodDataPlane
        from flink_tpu.state.keygroups import (
            assign_key_groups,
            host_of_key_group,
        )

        topo = HostTopology(2, 4)
        plane = PodDataPlane(
            topo, dtypes=[np.int64, np.int64, np.float32],
            mesh=make_mesh(8))
        rng = np.random.default_rng(0)
        n = 3000
        keys = rng.integers(0, 1 << 62, n)  # full-width identities
        ts = rng.integers(0, 1000, n)
        vals = rng.normal(size=n).astype(np.float32)
        owners = host_of_key_group(
            assign_key_groups(keys, 128), 2, 4, 128)
        arrivals = plane.exchange(owners, [keys, ts, vals])
        total = 0
        for h in (0, 1):
            k2, t2, v2 = arrivals[h]
            total += len(k2)
            sel = owners == h
            # exact rows, exact global stream order, int64 bit-exact
            # through the x32 lane-pair split
            np.testing.assert_array_equal(k2, keys[sel])
            np.testing.assert_array_equal(t2, ts[sel])
            np.testing.assert_array_equal(v2, vals[sel])
        assert total == n
        assert plane.rows_cross_host > 0
        assert plane.rows_intra_host > 0

    def test_deterministic_chunk_bound_skips_the_collective(self):
        from flink_tpu.parallel.pod import PodDataPlane

        topo = HostTopology(2, 2)
        plane = PodDataPlane(topo, dtypes=[np.int64],
                             mesh=make_mesh(4))
        owners = np.array([0, 1, 1, 0], dtype=np.int64)
        keys = np.arange(4, dtype=np.int64)
        arrivals = plane.exchange(owners, [keys], chunk_bound=1)
        np.testing.assert_array_equal(arrivals[0][0], [0, 3])
        np.testing.assert_array_equal(arrivals[1][0], [1, 2])


class TestProgramCaching:
    def test_rebuilt_engine_reuses_the_program_family(self):
        """Two engines with the same (mesh, topology, agg) share the
        cached two-level executables — the multi-tenant zero-recompile
        contract extends to the pod programs."""
        a = _session_engine(host_topology=HostTopology(2, 4))
        b = _session_engine(host_topology=HostTopology(2, 4))
        assert a._exchange2_steps[0] is b._exchange2_steps[0]
        assert a._exchange2_steps[1] is b._exchange2_steps[1]
