"""The on-device keyBy shuffle (shuffle.mode=device, the default).

A batch goes host->device ONCE as flat padded columns and a single
compiled program (``build_exchange_scatter``) segment-sorts records
into per-destination buckets, exchanges them with ``all_to_all`` over
the mesh axis, and feeds the aggregate scatter — keyBy -> window ->
aggregate as ONE XLA program. These tests pin the contract the fused
path must honor:

- staging shapes walk the ``pad_bucket_size`` tiers (bounded program
  shapes — the steady-state cases of ``tests/test_stateplane.py`` gate
  the runtime signal),
- output BIT-IDENTICAL to the explicit host fallback
  (``bucket_by_shard`` + sharded device_put) and to the single-device
  oracle, under forced paged eviction,
- a live ``reshard()`` mid-stream in device mode stays
  oracle-identical,
- the fence/dispatch-ahead discipline holds against the one-hop ingest
  (pooled staging buffers are generation-rotated exactly like the host
  blocks).
"""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.ops.segment_ops import pad_bucket_size
from flink_tpu.parallel.shuffle import (
    ShuffleBufferPool,
    bucket_by_shard,
    exchange_chunk_size,
    stage_device_exchange,
)
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.sessions import SessionWindower

from tests.test_native_slotmap import assert_shards_level
from tests.test_sessions import keyed_batch

GAP = 100


def _session_engine(mesh, mode, **kw):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(gap=GAP, agg=SumAggregate("v"), mesh=mesh,
                             capacity_per_shard=1 << 14,
                             shuffle_mode=mode, **kw)


def _window_engine(mesh, mode, **kw):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    return MeshWindowEngine(TumblingEventTimeWindows.of(50),
                            SumAggregate("v"), mesh,
                            capacity_per_shard=1 << 14,
                            shuffle_mode=mode, **kw)


def _stream(num_keys=24_000, n_steps=8, per_step=6000, seed=17):
    """Live set far beyond a 1024-slot/shard budget — forced paged
    eviction, cold fires, reloads (same shape as test_mesh_paged_spill).
    Values are small integers so float sums are EXACT and bit-identity
    across data planes is meaningful."""
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


def _run(engine, steps, reshard_at=None, reshard_to=None):
    fired = []
    for i, (keys, vals, ts, wm) in enumerate(steps):
        if reshard_at is not None and i == reshard_at:
            engine.reshard(reshard_to)
        engine.process_batch(keyed_batch(keys, vals, ts))
        fired.extend(engine.on_watermark(wm))
    return fired


def _sessions_dict(batches):
    out = {}
    for b in batches:
        for r in b.to_rows():
            out[(r[KEY_ID_FIELD], r["window_start"],
                 r["window_end"])] = r["sum_v"]
    return out


class TestStaging:
    def test_chunk_size_walks_pad_tiers(self):
        assert exchange_chunk_size(0, 8) == 256
        assert exchange_chunk_size(8 * 256, 8) == 256
        assert exchange_chunk_size(8 * 256 + 1, 8) == 512
        assert exchange_chunk_size(65536, 8) == \
            pad_bucket_size(65536 // 8)

    def test_flat_layout_and_padding_sentinel(self):
        rng = np.random.default_rng(1)
        n, P = 1000, 4
        shards = rng.integers(0, P, n).astype(np.int64)
        slots = rng.integers(1, 500, n).astype(np.int32)
        vals = rng.random(n).astype(np.float32)
        dst, (s_col, v_col), width = stage_device_exchange(
            shards, P, [slots, vals], fills=[0, 0.0])
        C = exchange_chunk_size(n, P)
        assert len(dst) == P * C == len(s_col) == len(v_col)
        np.testing.assert_array_equal(dst[:n], shards)
        # padding lanes carry the out-of-range destination and fills
        assert (dst[n:] == P).all()
        assert (s_col[n:] == 0).all() and (v_col[n:] == 0.0).all()
        np.testing.assert_array_equal(s_col[:n], slots)
        # bucket width: a pad tier of the densest (chunk, dest) pair,
        # never wider than the chunk itself
        assert width <= C
        chunk = np.arange(n) // C
        pair_max = int(np.bincount(chunk * P + shards,
                                   minlength=P * P).max())
        assert width == min(pad_bucket_size(pair_max), C)

    def test_pool_buffers_rotate_by_generation(self):
        pool = ShuffleBufferPool(generations=2)
        shards = np.zeros(10, dtype=np.int64)
        cols = [np.arange(10, dtype=np.int32)]
        pool.flip()
        d1, (c1,), _ = stage_device_exchange(shards, 2, cols, [0],
                                             pool=pool)
        pool.flip()
        d2, (c2,), _ = stage_device_exchange(shards, 2, cols, [0],
                                             pool=pool)
        pool.flip()
        d3, (c3,), _ = stage_device_exchange(shards, 2, cols, [0],
                                             pool=pool)
        # generation rotation: gen0's buffers are reused on the third
        # flip, a different generation's never aliased
        assert d1 is d3 and c1 is c3
        assert d1 is not d2 and c1 is not c2


class TestFusedExchangeProgram:
    def test_matches_host_bucket_scatter(self, eight_device_mesh):
        """The fused program's scatter result equals the host
        bucket_by_shard + scatter_step path bit-for-bit."""
        import jax
        import jax.numpy as jnp

        from flink_tpu.parallel.mesh import KEY_AXIS
        from flink_tpu.parallel.shuffle import build_exchange_scatter
        from flink_tpu.parallel.sharded_windower import build_mesh_steps
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = eight_device_mesh
        agg = SumAggregate("v")
        sharding = NamedSharding(mesh, P(KEY_AXIS))
        cap = 4096
        rng = np.random.default_rng(3)
        n = 5000
        shards = rng.integers(0, 8, n).astype(np.int64)
        slots = rng.integers(1, cap, n).astype(np.int32)
        vals = rng.integers(0, 100, n).astype(np.float32)

        def fresh_accs():
            return tuple(
                jax.device_put(jnp.full((8, cap), l.identity,
                                        dtype=l.dtype), sharding)
                for l in agg.leaves)

        xstep = build_exchange_scatter(mesh, agg, valued=False)
        dst, staged, width = stage_device_exchange(
            shards, 8, [slots, vals], fills=[0, 0.0])
        put = jax.device_put((dst, *staged), sharding)
        dev = jax.device_get(list(xstep(
            fresh_accs(), put[0], put[1], tuple(put[2:]), width)))

        scatter = build_mesh_steps(mesh, agg)[0]
        counts, blocked = bucket_by_shard(shards, 8, [slots, vals],
                                          fills=[0, 0.0])
        host = jax.device_get(list(scatter(
            fresh_accs(), jax.device_put(blocked[0], sharding),
            (jax.device_put(blocked[1], sharding),))))
        for d, h in zip(dev, host):
            np.testing.assert_array_equal(np.asarray(d), np.asarray(h))

    def test_invalid_mode_rejected(self, eight_device_mesh):
        with pytest.raises(ValueError, match="shuffle_mode"):
            _session_engine(eight_device_mesh, "netty")


class TestDeviceModeEngines:
    def test_sessions_bit_identical_to_host_mode_under_eviction(
            self, eight_device_mesh):
        steps = _stream()
        dev = _session_engine(eight_device_mesh, "device",
                              max_device_slots=1024)
        host = _session_engine(eight_device_mesh, "host",
                               max_device_slots=1024)
        d_dev = _sessions_dict(_run(dev, steps))
        d_host = _sessions_dict(_run(host, steps))
        assert len(d_dev) > 0 and set(d_dev) == set(d_host)
        diff = [k for k in d_dev if d_dev[k] != d_host[k]]
        assert not diff, f"{len(diff)} windows differ, e.g. {diff[:3]}"
        # the run genuinely thrashed the budget (cold fires, reloads)
        c = dev.spill_counters()
        assert c["pages_evicted"] > 0 and c["rows_reloaded"] > 0

    def test_sessions_match_single_device_oracle(self,
                                                 eight_device_mesh):
        steps = _stream(seed=23)
        dev = _session_engine(eight_device_mesh, "device",
                              max_device_slots=1024)
        single = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        d_dev = _sessions_dict(_run(dev, steps))
        d_ref = _sessions_dict(_run(single, steps))
        assert len(d_ref) > 0 and set(d_dev) == set(d_ref)
        for k in d_ref:
            assert d_dev[k] == pytest.approx(d_ref[k], rel=1e-4), k

    def test_windows_bit_identical_to_host_mode_under_eviction(
            self, eight_device_mesh):
        steps = _stream(seed=29)
        dev = _window_engine(eight_device_mesh, "device",
                             max_device_slots=4096)
        host = _window_engine(eight_device_mesh, "host",
                              max_device_slots=4096)
        d_dev = _sessions_dict(_run(dev, steps))
        d_host = _sessions_dict(_run(host, steps))
        assert len(d_dev) > 0 and set(d_dev) == set(d_host)
        diff = [k for k in d_dev if d_dev[k] != d_host[k]]
        assert not diff, f"{len(diff)} windows differ, e.g. {diff[:3]}"

    def test_two_phase_partial_batches_use_valued_exchange(
            self, eight_device_mesh):
        """Locally pre-aggregated (two-phase) batches route through the
        VALUED exchange variant and stay equal to the host path."""
        from flink_tpu.runtime.local_agg import PARTIAL_LEAF_PREFIX

        rng = np.random.default_rng(7)
        n = 4000
        keys = rng.integers(0, 800, n).astype(np.int64)
        vals = rng.integers(0, 50, n).astype(np.float32)
        ts = rng.integers(0, 40, n).astype(np.int64)

        def partial_batch():
            b = keyed_batch(keys, vals, ts)
            return b.with_column(PARTIAL_LEAF_PREFIX + "0", vals)

        out = {}
        for mode in ("device", "host"):
            eng = _window_engine(eight_device_mesh, mode)
            eng.process_batch(partial_batch())
            out[mode] = _sessions_dict(eng.on_watermark(10 ** 9))
        assert len(out["device"]) > 0
        assert out["device"] == out["host"]

    def test_live_reshard_mid_stream_in_device_mode(
            self, eight_device_mesh):
        """A live reshard() (8 -> 4 shards) mid-stream with the device
        data plane active stays oracle-identical — the rebuilt mesh
        plane rebuilds its exchange programs with it."""
        steps = _stream(seed=31)
        dev = _session_engine(eight_device_mesh, "device",
                              max_device_slots=1024)
        single = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        fired = _run(dev, steps, reshard_at=4, reshard_to=4)
        assert dev.P == 4 and dev.shuffle_mode == "device"
        d_dev = _sessions_dict(fired)
        d_ref = _sessions_dict(_run(single, steps))
        assert len(d_ref) > 0 and set(d_dev) == set(d_ref)
        for k in d_ref:
            assert d_dev[k] == pytest.approx(d_ref[k], rel=1e-4), k

    def test_operator_wires_ctx_shuffle_mode(self, eight_device_mesh):
        """The operator layer hands OperatorContext.shuffle_mode (the
        shuffle.mode config) through to the mesh engine."""
        import jax

        from flink_tpu.runtime.operators import (
            OperatorContext,
            SessionWindowAggOperator,
        )

        for mode in ("host", "device"):
            op = SessionWindowAggOperator(gap=GAP, agg=SumAggregate("v"),
                                          key_field="k")
            op.open(OperatorContext(
                parallelism=min(8, len(jax.devices())),
                shuffle_mode=mode))
            assert op.windower.shuffle_mode == mode


# ------------------------------------------------ the sharded resolve sweep
#
# In device mode a batch's (key, slice) -> slot goes through ONE native
# sweep over all the shards' indexes (``resolve_slices_sharded``) where the
# engine and the batch allow it, else through the path it replaced
# (argsort by destination, a lookup per shard, the scatter-back). Which
# one ran is what the engine observed — spill, a replica, the index's
# type, a late record — never a switch, so the other path is reached
# here by those conditions.

SWEEP_LATE_STEP = 4


def _hop_engine(mesh, **kw):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine
    from flink_tpu.windowing.aggregates import CountAggregate
    from flink_tpu.windowing.assigners import SlidingEventTimeWindows

    kw.setdefault("capacity_per_shard", 1024)
    return MeshWindowEngine(SlidingEventTimeWindows.of(500, 100),
                            CountAggregate(), mesh, **kw)


def _q5_shaped_stream(seed=41, steps=9, per_step=4000, num_keys=2500):
    """HOP(500, 100) COUNT per key over in-order batches of 150 ms each,
    half of the records on the hottest 1 % of the keys; the watermark
    follows every batch, and the batch in the middle holds three records
    of a slice whose every window has fired."""
    rng = np.random.default_rng(seed)
    out = []
    for s in range(steps):
        hot = rng.random(per_step) < 0.5
        keys = np.where(hot, rng.integers(0, num_keys // 100, per_step),
                        rng.integers(0, num_keys, per_step)).astype(np.int64)
        ts = np.sort(rng.integers(s * 150, (s + 1) * 150, per_step))
        if s == SWEEP_LATE_STEP:
            ts[[7, per_step // 2, per_step - 1]] = 20
        out.append((keys, ts.astype(np.int64), (s + 1) * 150 - 1))
    return out


def _drive(engine, stream):
    """Rows fired (sorted), and per batch the recorder's sweep totals."""
    from flink_tpu.observe import flight_recorder as flight

    rec = flight.recorder()
    rows, sweeps = [], []
    for keys, ts, wm in stream:
        rec.clear()
        engine.process_batch(RecordBatch.from_pydict(
            {KEY_ID_FIELD: keys}, timestamps=ts))
        s = rec.kind_totals().get("resolve.sweep", {"count": 0, "work": 0})
        sweeps.append((s["count"], s["work"]))
        for b in engine.on_watermark(wm):
            rows.extend((r[KEY_ID_FIELD], r["window_end"], r["count"])
                        for r in b.to_rows())
    rec.clear()
    return sorted(rows), sweeps


def _snapshot_rows(engine):
    t = engine.snapshot(mode="savepoint")["table"]
    cols = sorted(t)
    return sorted(zip(*[np.asarray(t[c]).tolist() for c in cols]))


@pytest.fixture(scope="module")
def four_device_mesh():
    from flink_tpu.parallel.mesh import make_mesh

    return make_mesh(4)


@pytest.fixture(scope="module")
def swept_run(four_device_mesh):
    """The engine every batch of whose stream may take the sweep, driven
    up to the end-of-input flush; shared by the comparisons below."""
    stream = _q5_shaped_stream()
    engine = _hop_engine(four_device_mesh)
    rows, sweeps = _drive(engine, stream)
    return engine, stream, rows, sweeps


def test_swept_batches_say_so_and_the_late_batch_does_not(swept_run):
    engine, stream, rows, sweeps = swept_run
    for step, ((keys, _, _), got) in enumerate(zip(stream, sweeps)):
        want = (0, 0) if step == SWEEP_LATE_STEP else (1, len(keys))
        assert got == want, step
    assert engine.late_records_dropped == 3
    assert len(rows) > 5000
    # the sweep met an index growing under it (1,024 slots a shard)
    assert engine.capacity > 1024


@pytest.mark.parametrize("forced_by", [
    "an_armed_replica", "a_python_index", "spill", "host_shuffle"])
def test_old_path_by_what_the_engine_observes_gives_the_same(
        swept_run, four_device_mesh, monkeypatch, forced_by):
    """An engine kept off the sweep by spill, an armed replica, a Python
    index or host shuffle fires the same rows, drops the same late
    records and holds the same logical state; where the indexes are the
    same native ones (the replica) the same slots too: ``_dirty`` and
    every slot's metadata bit for bit."""
    from flink_tpu.state import slot_table

    engine, stream, rows, _ = swept_run
    kw = {}
    if forced_by == "a_python_index":
        import flink_tpu.native as native

        monkeypatch.setattr(native, "slotmap_available", lambda: False)
    elif forced_by == "spill":
        kw = dict(max_device_slots=1 << 14)
    elif forced_by == "host_shuffle":
        kw = dict(shuffle_mode="host")
    old = _hop_engine(four_device_mesh, **kw)
    if forced_by == "an_armed_replica":
        old.arm_replica()
    if forced_by == "a_python_index":
        assert type(old.indexes[0]) is slot_table.HostSlotIndex
    else:
        assert type(old.indexes[0]) is slot_table.NativeSlotIndex
    old_rows, old_sweeps = _drive(old, stream)
    assert old_sweeps == [(0, 0)] * len(stream)
    assert old_rows == rows
    assert old.late_records_dropped == engine.late_records_dropped == 3
    assert _snapshot_rows(old) == _snapshot_rows(engine)
    if forced_by == "an_armed_replica":
        assert old.capacity == engine.capacity
        np.testing.assert_array_equal(old._dirty, engine._dirty)
        assert_shards_level(old.indexes, engine.indexes)
        for mode in ("delta", "full"):
            sa, sb = old.snapshot(mode), engine.snapshot(mode)
            assert sorted(sa["table"]) == sorted(sb["table"])
            for col in sa["table"]:
                np.testing.assert_array_equal(sa["table"][col],
                                              sb["table"][col])


@pytest.mark.parametrize("form", ["formula", "range", "assignment"])
def test_sweep_routing_table_is_the_engines_route(four_device_mesh, form):
    """The key group -> shard table the sweep routes by against
    ``_route``, THE engine routing decision, under its three forms; a
    rebalance builds it anew."""
    from flink_tpu.state.keygroups import (
        KeyGroupAssignment,
        assign_key_groups,
    )

    group_range = (32, 95) if form == "range" else None
    engine = _hop_engine(four_device_mesh, key_group_range=group_range)
    keys = np.random.default_rng(2).integers(-10 ** 12, 10 ** 12, 20_000)
    groups = assign_key_groups(keys, engine.max_parallelism)
    if group_range is not None:
        mine = (groups >= 32) & (groups <= 95)
        keys, groups = keys[mine], groups[mine]
    table = engine._group_shard_table()
    assert table is engine._group_shard_table()         # kept
    np.testing.assert_array_equal(table[groups], engine._route(keys))
    if form == "assignment":
        engine.reassign_key_groups(KeyGroupAssignment.contiguous(
            4, engine.max_parallelism).move(np.arange(5, 128, 9), 3))
        moved = engine._group_shard_table()
        assert (moved != table).any()
        np.testing.assert_array_equal(moved[groups], engine._route(keys))
        assert len(np.unique(engine._route(keys))) == 4
    first, last = group_range or (0, engine.max_parallelism - 1)
    every = np.arange(engine.max_parallelism)
    assert ((table >= 0) == ((every >= first) & (every <= last))).all()
    assert table.max() == 3
    # and the engine's sweep sends every record where _route does
    ts = np.sort(np.random.default_rng(3).integers(0, 300, len(keys)))
    engine.process_batch(RecordBatch.from_pydict(
        {KEY_ID_FIELD: keys}, timestamps=ts.astype(np.int64)))
    shard_of = engine._route(keys)
    for p, idx in enumerate(engine.indexes):
        held = np.unique(idx.slot_key[idx.used_slots()])
        np.testing.assert_array_equal(held, np.unique(keys[shard_of == p]))
