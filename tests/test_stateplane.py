"""The shared state-plane kernel library (flink_tpu/stateplane).

Four contracts:

- **One library, one cache entry per (family, key)**: every engine's
  device programs come from the ``families`` builders, keyed on WHAT
  they compute — two owners with the same plane layout share the
  executable object (the multi-tenant zero-recompile contract, now
  enforced at the library boundary).
- **Exchange rank**: the rank-within-destination combinator equals a
  sequential counting pass EXACTLY on random shapes, out-of-range
  lanes included.
- **Golden bit identity**: ported engines driven through
  forced paged eviction plus a live mid-stream reshard pin their
  fires (including emission order), snapshots (including row order),
  deltas and spill counters — run-to-run and against the host data
  plane.
- **Steady state**: after a warmup pass, fresh engines and a second
  job on the warm cache compile nothing, across the mesh engines, the
  flat and two-level exchanges, the interval join and CEP.
"""

import numpy as np
import pytest

from flink_tpu.stateplane import (
    KNOWN_PROGRAM_FAMILIES,
    exchange_rank_flat,
    flat_fence,
    flat_gather,
    flat_put,
    flat_scatter_combine,
    flat_segment_fire,
    xla_rank,
)
from flink_tpu.windowing.aggregates import AvgAggregate, SumAggregate

# ------------------------------------------------------------- families


class TestProgramFamilies:
    def test_registry_is_duplicate_free(self):
        assert len(KNOWN_PROGRAM_FAMILIES) == \
            len(set(KNOWN_PROGRAM_FAMILIES))

    def test_builders_key_on_what_not_who(self):
        """Two aggregate INSTANCES with the same plane layout share
        every program object — the library keys on (methods, dtypes),
        never on an owner identity."""
        a, b = SumAggregate("v"), SumAggregate("w")
        assert flat_scatter_combine(a.leaves) is \
            flat_scatter_combine(b.leaves)
        assert flat_gather(a.leaves) is flat_gather(b.leaves)
        assert flat_put(a.leaves) is flat_put(b.leaves)
        # fire keys on agg.cache_key() (finish parameters count);
        # equal-keyed instances share, distinct fields do not alias
        assert flat_segment_fire(SumAggregate("v")) is \
            flat_segment_fire(SumAggregate("v"))
        assert flat_fence("<f4") is flat_fence("<f4")

    def test_distinct_layouts_do_not_collide(self):
        assert flat_scatter_combine(SumAggregate("v").leaves) is not \
            flat_scatter_combine(AvgAggregate("v").leaves)

    def test_registry_matches_source_literal(self):
        """flint's REG04 parses the tuple statically; the import path
        must agree with the literal (same pin as KNOWN_FAULT_POINTS)."""
        import ast
        from pathlib import Path

        src = (Path(__file__).resolve().parents[1]
               / "flink_tpu/stateplane/families.py").read_text()
        for node in ast.parse(src).body:
            if isinstance(node, ast.Assign) and any(
                    getattr(t, "id", None) == "KNOWN_PROGRAM_FAMILIES"
                    for t in node.targets):
                parsed = tuple(e.value for e in node.value.elts)
                assert parsed == KNOWN_PROGRAM_FAMILIES
                return
        pytest.fail("KNOWN_PROGRAM_FAMILIES literal not found")


# ------------------------------------------------------------------ rank


def _rank_reference(d, num_dests):
    """rank(i) = #{j < i : 0 <= d_j < D and d_j == clip(d_i, 0, D-1)}:
    the count of prior in-range records at the lane's clipped bucket."""
    counts = [0] * num_dests
    out = []
    for x in d.tolist():
        b = min(max(x, 0), num_dests - 1)
        out.append(counts[b])
        if 0 <= x < num_dests:
            counts[b] += 1
    return np.asarray(out, dtype=np.int32)


class TestExchangeRank:
    def test_random_shapes_match_reference(self):
        """Property test: over random (num_dests, length, width) the
        one-hot-cumsum rank equals a sequential counting pass EXACTLY,
        including the out-of-range destinations staging pads with."""
        rng = np.random.default_rng(19)
        for _ in range(25):
            D = int(rng.integers(1, 17))
            n = int(rng.integers(1, 500))
            d = rng.integers(-2, D + 3, size=n).astype(np.int32)
            np.testing.assert_array_equal(
                np.asarray(xla_rank(d, D)), _rank_reference(d, D))

    def test_flat_offsets_drop_out_of_range_and_overflow(self):
        """``exchange_rank_flat`` places in-range lanes at
        ``d * W + rank`` and sends out-of-range destinations and
        bucket-overflow lanes to the sentinel ``D * W``."""
        d = np.asarray([3, 0, 1, 0, 7, 3, 3, -1, 0, 8], dtype=np.int32)
        D, W = 8, 2
        flat = np.asarray(exchange_rank_flat(d, D, W))
        sentinel = D * W
        np.testing.assert_array_equal(
            flat, [6, 0, 2, 1, 14, 7] + [sentinel] * 4)


# ------------------------------------------------------- golden identity


GAP = 100


def _stream(num_keys=20_000, n_steps=6, per_step=5000, seed=41):
    """Live set far beyond the device budget — forced paged eviction
    with integer-valued float sums so bit-identity is meaningful."""
    rng = np.random.default_rng(seed)
    steps = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.integers(0, 1000, per_step).astype(np.float32)
        ts = rng.integers(s * 80, s * 80 + 60, per_step).astype(np.int64)
        steps.append((keys, vals, ts, (s - 1) * 80))
    return steps


def _drive(engine, steps, reshard_at=None, reshard_to=None,
           delta_at=None):
    """Run the stream; returns (fires, deltas) where fires preserve
    emission order and deltas are the engine's mid-stream incremental
    snapshots (mode="delta") taken at ``delta_at`` boundaries."""
    from tests.test_sessions import keyed_batch

    fires, deltas = [], []
    for i, (keys, vals, ts, wm) in enumerate(steps):
        if reshard_at is not None and i == reshard_at:
            engine.reshard(reshard_to)
        engine.process_batch(keyed_batch(keys, vals, ts))
        fires.extend(engine.on_watermark(wm))
        if delta_at is not None and i in delta_at:
            deltas.append(engine.snapshot(mode="delta"))
    return fires, deltas


def _fire_rows(batches):
    """Order-PRESERVING flatten: a reordered emission diverges even
    when the value multiset matches."""
    rows = []
    for b in batches:
        for r, t in zip(b.to_rows(),
                        np.asarray(b.timestamps).tolist()):
            rows.append((t, tuple(sorted(r.items()))))
    return rows


def _assert_deep_equal(a, b, path=""):
    """Bit-exact structural equality — dict key ORDER and array row
    ORDER both count (the snapshot's row order is part of the golden
    contract: a nondeterministic harvest would reorder it)."""
    assert type(a) is type(b), f"{path}: {type(a)} vs {type(b)}"
    if isinstance(a, dict):
        assert list(a.keys()) == list(b.keys()), path
        for k in a:
            _assert_deep_equal(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _assert_deep_equal(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray):
        np.testing.assert_array_equal(a, b, err_msg=path)
    else:
        assert a == b, path


class TestGoldenBitIdentity:
    """Ported engines under forced eviction + live reshard: every
    observable — fires (order included), snapshots (row order
    included), deltas, spill counters — is pinned bit-identical
    run-to-run, and fires are pinned against the host data plane."""

    def _window_engine(self, mesh, mode="device"):
        from flink_tpu.parallel.sharded_windower import MeshWindowEngine
        from flink_tpu.windowing.assigners import TumblingEventTimeWindows

        return MeshWindowEngine(TumblingEventTimeWindows.of(50),
                                SumAggregate("v"), mesh,
                                capacity_per_shard=1 << 14,
                                shuffle_mode=mode,
                                max_device_slots=2048)

    def _session_engine(self, mesh, mode="device"):
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

        return MeshSessionEngine(gap=GAP, agg=SumAggregate("v"),
                                 mesh=mesh,
                                 capacity_per_shard=1 << 14,
                                 shuffle_mode=mode,
                                 max_device_slots=1024)

    def test_window_engine_golden_replay(self, eight_device_mesh):
        steps = _stream(seed=43)
        runs = []
        for _ in range(2):
            eng = self._window_engine(eight_device_mesh)
            fires, deltas = _drive(eng, steps, reshard_at=3,
                                   reshard_to=4, delta_at={2, 4})
            runs.append((_fire_rows(fires), deltas,
                         eng.snapshot(mode="full"),
                         eng.spill_counters()))
        (f1, d1, s1, c1), (f2, d2, s2, c2) = runs
        assert len(f1) > 0, "vacuous run: no fires"
        assert f1 == f2, "fires (or their order) diverge run-to-run"
        _assert_deep_equal(d1, d2, "delta")
        _assert_deep_equal(s1, s2, "snapshot")
        assert c1 == c2, f"spill counters diverge: {c1} vs {c2}"
        assert c1["pages_evicted"] > 0, \
            "vacuous run: eviction never engaged"

    def test_session_engine_golden_replay(self, eight_device_mesh):
        steps = _stream(seed=47)
        runs = []
        for _ in range(2):
            eng = self._session_engine(eight_device_mesh)
            fires, deltas = _drive(eng, steps, reshard_at=3,
                                   reshard_to=4, delta_at={4})
            runs.append((_fire_rows(fires), deltas,
                         eng.snapshot(mode="full"),
                         eng.spill_counters()))
        (f1, d1, s1, c1), (f2, d2, s2, c2) = runs
        assert len(f1) > 0, "vacuous run: no fires"
        assert f1 == f2
        _assert_deep_equal(d1, d2, "delta")
        _assert_deep_equal(s1, s2, "snapshot")
        assert c1 == c2
        assert c1["pages_evicted"] > 0 and c1["rows_reloaded"] > 0

    def test_device_fires_match_host_plane_under_eviction(
            self, eight_device_mesh):
        """The ported device exchange path vs the host bucketing path:
        the fired VALUES must agree per (key, window) even though
        emission grouping differs across data planes."""
        from flink_tpu.core.records import KEY_ID_FIELD

        def vals_of(batches):
            out = {}
            for b in batches:
                for r in b.to_rows():
                    out[(r[KEY_ID_FIELD], r["window_start"],
                         r["window_end"])] = r["sum_v"]
            return out

        steps = _stream(seed=53)
        dev, _ = _drive(self._window_engine(eight_device_mesh,
                                            "device"), steps)
        host, _ = _drive(self._window_engine(eight_device_mesh,
                                             "host"), steps)
        v_dev, v_host = vals_of(dev), vals_of(host)
        assert len(v_dev) > 0 and v_dev == v_host


# ------------------------------------------------- steady-state compiles
#
# One warmup pass compiles a program family at its padded tier shapes;
# then a FRESH engine (or job) replays the same shapes under the
# recompile sentinel and must compile NOTHING. Fresh engines make the
# claim strict: a program keyed on anything unstable (engine identity,
# a per-instance lambda, a device object instead of its id, a raw batch
# length instead of its tier) recompiles here. Spill is armed below the
# live set, and asserted to engage, so the evict/reload programs are
# part of what is guarded.

SS_GAP_MS = 16_000
SS_WINDOW_MS = 5_000
SS_KEYS = 50_000
SS_BATCH = 8_192
SS_RECORDS = 1 << 16
#: records per ms of event time: slow event time keeps the live set of
#: each open window or session gap above the per-shard slot budget
SS_RECORDS_PER_MS = 4
#: per-shard slot budgets below the live set; the window engine's floor
#: is one slice's per-shard key set (a batch's touched namespaces are
#: protected from eviction)
SS_SESSION_SLOTS, SS_WINDOW_SLOTS = 2048, 4096
#: batch-size walks over the same pad tiers with shifted lengths: the
#: warm engine walks both lists, the fresh one replays the second
TIER_WALK_WARM = (8192, 4096, 2048, 6000, 3000, 1900)
TIER_WALK_RUN = (8000, 3500, 2200, 7000, 2600, 1800)
JOIN_WALK_WARM = (4096, 2048, 1024, 3000, 1500, 900)
JOIN_WALK_RUN = (4000, 2200, 1100, 2800, 1300, 1000)
CEP_WALK_WARM = (512, 256, 128, 384, 192, 96)
CEP_WALK_RUN = (448, 288, 144, 336, 224, 112)


def _ss_batches(rep):
    """One rep's stream: identical shape every rep, event time shifted
    by whole windows so watermarks advance and windows close (a
    non-aligned shift would move the fire tiers)."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    stride = SS_RECORDS // SS_RECORDS_PER_MS + 10 * SS_GAP_MS
    stride += -stride % SS_WINDOW_MS
    rng = np.random.default_rng(7)
    produced = 0
    while produced < SS_RECORDS:
        b = min(SS_BATCH, SS_RECORDS - produced)
        ts = rep * stride + (produced + np.arange(b, dtype=np.int64)) \
            // SS_RECORDS_PER_MS
        yield RecordBatch({
            KEY_ID_FIELD: rng.integers(0, SS_KEYS, b).astype(np.int64),
            "v": np.ones(b, dtype=np.float32),
            TIMESTAMP_FIELD: ts,
        }), int(ts[-1])
        produced += b


def _ss_drive(engines, rep, serve_keys=None):
    """The rep's stream through every engine in turn (jobs interleaved
    batch by batch), with a batched lookup per engine per batch when
    ``serve_keys`` is given. Returns the number of rows fired."""
    fired = 0
    last = 0
    for rb, last in _ss_batches(rep):
        for eng in engines:
            eng.process_batch(rb)
            fired += sum(len(b)
                         for b in eng.on_watermark(last - SS_GAP_MS))
            if serve_keys is not None:
                eng.query_batch(serve_keys)
    for eng in engines:
        fired += sum(len(b) for b in
                     eng.on_watermark(last + 100 * SS_GAP_MS))
    return fired


def _ss_drive_sized(engine, sizes, offset):
    """One batch per entry of ``sizes``, event time advancing so
    sessions fire, then a flush. Returns rows fired."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    rng = np.random.default_rng(11)
    fired = 0
    t = offset
    for b in sizes:
        ts = t + np.arange(b, dtype=np.int64) // SS_RECORDS_PER_MS
        engine.process_batch(RecordBatch({
            KEY_ID_FIELD: rng.integers(0, SS_KEYS, b).astype(np.int64),
            "v": np.ones(b, dtype=np.float32),
            TIMESTAMP_FIELD: ts,
        }))
        t = int(ts[-1]) + 1
        fired += sum(len(x) for x in engine.on_watermark(t - SS_GAP_MS))
    fired += sum(len(x)
                 for x in engine.on_watermark(t + 100 * SS_GAP_MS))
    return fired


def _ss_sessions(mesh, **kw):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(SS_GAP_MS, SumAggregate("v"), mesh,
                             capacity_per_shard=SS_SESSION_SLOTS,
                             max_device_slots=SS_SESSION_SLOTS, **kw)


def _ss_windows(mesh):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    return MeshWindowEngine(TumblingEventTimeWindows.of(SS_WINDOW_MS),
                            SumAggregate("v"), mesh,
                            capacity_per_shard=SS_WINDOW_SLOTS,
                            max_device_slots=SS_WINDOW_SLOTS)


def _ss_tier_walk(make):
    """Warm one engine over both size lists, then replay the shifted
    list on a fresh engine under the sentinel. Returns the fresh
    engine."""
    from flink_tpu.observe import RecompileSentinel

    warm = make()
    assert _ss_drive_sized(warm, TIER_WALK_WARM, offset=0) \
        + _ss_drive_sized(warm, TIER_WALK_RUN, offset=1 << 22) > 0
    engine = make()
    with RecompileSentinel(max_compiles=0,
                           max_transfers=max(len(TIER_WALK_RUN) * 8, 64),
                           label="tier walk"):
        fired = _ss_drive_sized(engine, TIER_WALK_RUN, offset=1 << 23)
    assert fired > 0, "vacuous: nothing fired"
    return engine


def _ss_join_drive(engine, sizes, offset):
    """Two-sided interval-join stream, one left and one right batch per
    entry of ``sizes``, with a lagging watermark so the band stays
    populated and the spill tier engages. Returns rows matched."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    rng = np.random.default_rng(17)
    matches = 0
    t = offset
    for b in sizes:
        for side, name in ((0, "v"), (1, "w")):
            ts = t + np.arange(b, dtype=np.int64) // SS_RECORDS_PER_MS
            out = engine.process_batch(RecordBatch({
                KEY_ID_FIELD: rng.integers(0, SS_KEYS,
                                           b).astype(np.int64),
                name: np.ones(b, dtype=np.float32),
                TIMESTAMP_FIELD: ts,
            }), side)
            matches += sum(len(x) for x in out)
        t = int(ts[-1]) + 1
        engine.on_watermark(t - 3000)
    return matches


def _ss_cep_drive(engine, sizes, offset, n_keys, rng):
    """One keyed batch and one trailing-watermark fire per entry of
    ``sizes``. Returns (rows matched, event time reached)."""
    from flink_tpu.core.records import RecordBatch

    matches = 0
    t = offset
    for n in sizes:
        keys = rng.integers(0, n_keys, n).astype(np.int64)
        ts = t + np.sort(rng.integers(0, 30, size=n)).astype(np.int64)
        t += 25
        engine.process_batch(RecordBatch.from_pydict(
            {"k": keys, "v": rng.integers(0, 9, n).astype(np.int64),
             "__key_id__": keys}, timestamps=ts))
        matches += sum(len(b) for b in engine.on_watermark(t - 5))
    return matches, t


class TestSteadyStateCompiles:
    @pytest.mark.parametrize("engine", ["mesh-sessions", "mesh-windows"])
    def test_fresh_engines_compile_nothing(self, eight_device_mesh,
                                           engine):
        """Two measured reps, each on a FRESH engine: the step programs
        hit across engine rebuilds (restarts, rescales), not just across
        batches. Each watermark advance harvests one batched read, so
        the transfer budget also catches an unbatched per-leaf read."""
        from flink_tpu.observe import RecompileSentinel

        make = {"mesh-sessions": _ss_sessions,
                "mesh-windows": _ss_windows}[engine]
        assert _ss_drive([make(eight_device_mesh)], rep=0) > 0
        for rep in (1, 2):
            eng = make(eight_device_mesh)
            with RecompileSentinel(
                    max_compiles=0,
                    max_transfers=(SS_RECORDS // SS_BATCH) * 8,
                    label=f"{engine} rep {rep}"):
                fired = _ss_drive([eng], rep)
            assert fired > 0, "vacuous: nothing fired"
            assert eng.spill_counters()["rows_evicted"] > 0, \
                "spill never engaged: evict/reload not covered"

    def test_device_shuffle_tier_walk(self, eight_device_mesh):
        """The fused exchange's program shapes are (chunk tier, bucket
        width tier): a fresh engine on shifted batch sizes over the same
        tier lattice compiles nothing."""
        def make():
            eng = _ss_sessions(eight_device_mesh)
            assert eng.shuffle_mode == "device"
            return eng

        _ss_tier_walk(make)

    def test_two_level_exchange_tier_walk(self, eight_device_mesh):
        """A virtual (2, 4) topology arms the two-level exchange's
        stage-1/stage-2 pair: its (chunk, W1, W2) tiers hold on a fresh
        engine, and rows really crossed the host axis."""
        from flink_tpu.parallel.mesh import HostTopology

        topo = HostTopology(2, 4)

        def make():
            eng = _ss_sessions(eight_device_mesh, host_topology=topo)
            assert eng._two_level_active()
            return eng

        engine = _ss_tier_walk(make)
        assert engine.exchange2_traffic()["rows_cross_host"] > 0

    def test_interval_join_tier_walk(self, eight_device_mesh):
        """The banded-probe / ingest-exchange / eviction-gather family:
        a fresh interval-join engine on shifted sizes compiles nothing,
        with rows evicted and cold band candidates served from pages."""
        from flink_tpu.joins import MeshIntervalJoinEngine
        from flink_tpu.observe import RecompileSentinel

        def make():
            # band as deep as the pruning horizon: probes reach past the
            # resident rows into the paged tier
            return MeshIntervalJoinEngine(
                -2500, 2500, mesh=eight_device_mesh,
                capacity_per_shard=SS_SESSION_SLOTS // 4,
                max_device_slots=SS_SESSION_SLOTS // 4)

        warm = make()
        assert _ss_join_drive(warm, JOIN_WALK_WARM, offset=0) \
            + _ss_join_drive(warm, JOIN_WALK_RUN, offset=1 << 22) > 0
        engine = make()
        with RecompileSentinel(max_compiles=0,
                               max_transfers=len(JOIN_WALK_RUN) * 16,
                               label="join tier walk"):
            matches = _ss_join_drive(engine, JOIN_WALK_RUN,
                                     offset=1 << 23)
        assert matches > 0, "vacuous: nothing matched"
        sc = engine.spill_counters()
        assert sc["rows_evicted"] > 0 and sc["cold_rows_served"] > 0

    def test_cep_tier_walk(self, eight_device_mesh, tmp_path):
        """Both CEP device program families — a within-window sequence
        (advance + within-prune) and an always-alive pattern (advance +
        evict/restore, keys far beyond the slot budget) — compile
        nothing on fresh engines over shifted batch sizes."""
        from flink_tpu.cep.mesh_engine import MeshCepEngine
        from flink_tpu.cep.pattern import AfterMatchSkipStrategy, Pattern
        from flink_tpu.observe import RecompileSentinel

        skip = AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT
        within_pat = (Pattern.begin("a", skip=skip)
                      .where(lambda b: np.asarray(b["v"]) % 3 == 0)
                      .next("b")
                      .where(lambda b: np.asarray(b["v"]) % 3 == 1)
                      .within(50))
        churn_pat = (Pattern.begin("a", skip=skip)
                     .next("b")
                     .where(lambda b: np.asarray(b["v"]) == 7))

        def make(pat, spill_dir=None):
            return MeshCepEngine(pat, key_field="k",
                                 mesh=eight_device_mesh,
                                 capacity_per_shard=256,
                                 spill_dir=spill_dir)

        rng = np.random.default_rng(19)
        spill = str(tmp_path)
        w_within = make(within_pat)
        warm, t = _ss_cep_drive(w_within, CEP_WALK_WARM, 0, 64, rng)
        warm += _ss_cep_drive(w_within, CEP_WALK_RUN, t, 64, rng)[0]
        assert warm > 0
        w_churn = make(churn_pat, spill_dir=spill)
        _, t = _ss_cep_drive(w_churn, CEP_WALK_WARM, 0, 20_000, rng)
        _ss_cep_drive(w_churn, CEP_WALK_RUN, t, 20_000, rng)

        within = make(within_pat)
        churn = make(churn_pat, spill_dir=spill)
        with RecompileSentinel(max_compiles=0,
                               max_transfers=len(CEP_WALK_RUN) * 6 * 64,
                               label="cep tier walk"):
            m, _ = _ss_cep_drive(within, CEP_WALK_RUN, 0, 64, rng)
            # two passes: the churn engine's live key set must outgrow
            # the 8 x 256 slot budget so evict/restore run here too
            _, t2 = _ss_cep_drive(churn, CEP_WALK_RUN, 0, 20_000, rng)
            cm = _ss_cep_drive(churn, CEP_WALK_RUN, t2, 20_000, rng)[0]
        assert m > 0 and cm > 0, "vacuous: nothing matched"
        assert churn.spill_counters()["rows_evicted"] > 0

    def test_second_job_on_warm_cluster(self, eight_device_mesh):
        """The tenancy contract: after job A warmed the ingest, fire,
        evict and serving programs, a SECOND job's two fresh engines,
        interleaved batch by batch with batched lookups, compile
        nothing and miss the program cache nowhere."""
        from flink_tpu.observe import RecompileSentinel
        from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

        serve_keys = np.arange(0, SS_KEYS, SS_KEYS // 16, dtype=np.int64)
        with PROGRAM_CACHE.job_scope("steady-warm"):
            assert _ss_drive([_ss_sessions(eight_device_mesh)], rep=0,
                             serve_keys=serve_keys) > 0
        PROGRAM_CACHE.reset_stats()
        with PROGRAM_CACHE.job_scope("steady-job2"):
            with RecompileSentinel(
                    max_compiles=0,
                    max_transfers=(SS_RECORDS // SS_BATCH) * 24,
                    label="2 jobs on warm cluster"):
                fired = _ss_drive([_ss_sessions(eight_device_mesh),
                                   _ss_sessions(eight_device_mesh)],
                                  rep=1, serve_keys=serve_keys)
        assert fired > 0, "vacuous: nothing fired"
        assert PROGRAM_CACHE.stats_for("steady-job2")["misses"] == 0
