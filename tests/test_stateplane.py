"""The shared state-plane kernel library (flink_tpu/stateplane).

Three contracts:

- **One library, one cache entry per (family, key)**: every engine's
  device programs come from the ``families`` builders, keyed on WHAT
  they compute — two owners with the same plane layout share the
  executable object (the multi-tenant zero-recompile contract, now
  enforced at the library boundary).
- **Backend hook honesty**: ``stateplane.backend.<family>`` resolves
  per family, rejects unknown families/backends, and refuses a pallas
  request for a family with no pallas implementation (a config typo
  must not vacuously pass an A/B experiment).
- **Golden bit identity**: the Pallas exchange-rank kernel equals the
  XLA one-hot-cumsum EXACTLY on random shapes (ranks AND the
  downstream fold/scatter order), and ported engines driven through
  forced paged eviction plus a live mid-stream reshard pin their
  fires (including emission order), snapshots (including row order),
  deltas and spill counters — run-to-run and against the host data
  plane.
"""

import numpy as np
import pytest

from flink_tpu.stateplane import (
    KNOWN_PROGRAM_FAMILIES,
    backend_of,
    backend_scope,
    build_exchange_rank,
    configure_backends,
    exchange_rank_flat,
    flat_fence,
    flat_gather,
    flat_put,
    flat_scatter_combine,
    flat_segment_fire,
    set_backend,
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


# -------------------------------------------------------------- backends


class TestBackendHook:
    def test_default_is_xla(self):
        assert backend_of("exchange-rank") == "xla"
        assert backend_of("gather") == "xla"

    def test_scope_restores(self):
        with backend_scope("exchange-rank", "pallas"):
            assert backend_of("exchange-rank") == "pallas"
        assert backend_of("exchange-rank") == "xla"

    def test_unknown_family_rejected(self):
        with pytest.raises(ValueError, match="unknown program family"):
            set_backend("exchange-rnak", "pallas")

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown backend"):
            set_backend("exchange-rank", "triton")

    def test_pallas_for_incapable_family_rejected(self):
        """No silent xla fallback: a family without a pallas
        implementation refuses the override outright."""
        with pytest.raises(ValueError, match="no pallas implementation"):
            set_backend("gather", "pallas")

    def test_config_hook_applies_and_restores(self):
        from flink_tpu.core.config import Configuration

        conf = Configuration(
            {"stateplane.backend.exchange-rank": "pallas"})
        try:
            applied = configure_backends(conf)
            assert applied == {"exchange-rank": "pallas"}
            assert backend_of("exchange-rank") == "pallas"
        finally:
            set_backend("exchange-rank", "xla")

    def test_config_hook_rejects_typo_family(self):
        from flink_tpu.core.config import Configuration

        conf = Configuration({"stateplane.backend.gather": "pallas"})
        with pytest.raises(ValueError):
            configure_backends(conf)

    def test_config_hook_scans_keys_not_known_names(self):
        """A typo'd FAMILY in the config key must raise, not be
        silently skipped — the hook scans the key space for the
        prefix (including fallback layers)."""
        from flink_tpu.core.config import Configuration

        conf = Configuration({"stateplane.backend.gahter": "xla"})
        with pytest.raises(ValueError, match="unknown program family"):
            configure_backends(conf)
        layered = Configuration({"unrelated.key": 1}).with_fallback(
            Configuration({"stateplane.backend.exchange-rnak": "xla"}))
        with pytest.raises(ValueError, match="unknown program family"):
            configure_backends(layered)

    def test_executor_applies_backend_config_at_submit(self):
        """A job Configuration's stateplane.backend.* keys take effect
        through the executor — and an invalid one fails the job at
        SUBMIT, before any batch runs."""
        from flink_tpu.connectors.sinks import CollectSink
        from flink_tpu.connectors.sources import DataGenSource
        from flink_tpu.core.config import Configuration
        from flink_tpu.datastream.environment import (
            StreamExecutionEnvironment,
        )
        from flink_tpu.runtime.watermarks import WatermarkStrategy
        from flink_tpu.windowing.assigners import (
            TumblingEventTimeWindows,
        )

        def job(conf):
            env = StreamExecutionEnvironment(conf)
            sink = CollectSink()
            (env.add_source(
                DataGenSource(total_records=2000, num_keys=16,
                              events_per_second_of_eventtime=2000),
                WatermarkStrategy.for_bounded_out_of_orderness(0))
             .key_by("key")
             .window(TumblingEventTimeWindows.of(1000))
             .count()
             .sink_to(sink))
            env.execute()
            return sink

        try:
            sink = job(Configuration(
                {"stateplane.backend.exchange-rank": "xla"}))
            assert len(sink.rows()) > 0
            assert backend_of("exchange-rank") == "xla"
            with pytest.raises(ValueError, match="unknown program"):
                job(Configuration(
                    {"stateplane.backend.gahter": "xla"}))
        finally:
            set_backend("exchange-rank", "xla")


# ---------------------------------------------------- rank kernel parity


class TestPallasRankParity:
    def test_random_shapes_bit_identical(self):
        """Property test: over random (num_dests, length, width) the
        Pallas counting sort equals the XLA one-hot-cumsum EXACTLY —
        ranks and the flattened (dest, rank) scatter positions,
        including the out-of-range destinations staging pads with and
        bucket-overflow lanes."""
        from flink_tpu.stateplane.rank import pallas_rank

        rng = np.random.default_rng(19)
        for _ in range(25):
            D = int(rng.integers(1, 17))
            n = int(rng.integers(1, 500))
            W = int(rng.integers(1, 64))
            d = rng.integers(-2, D + 3, size=n).astype(np.int32)
            np.testing.assert_array_equal(
                np.asarray(pallas_rank(d, D)),
                np.asarray(xla_rank(d, D)))
            np.testing.assert_array_equal(
                np.asarray(exchange_rank_flat(d, D, W, "pallas")),
                np.asarray(exchange_rank_flat(d, D, W, "xla")))

    def test_cached_program_parity_and_distinct_keys(self):
        """The cached exchange-rank programs agree across backends and
        occupy DISTINCT cache entries (cache-key honesty: a backend
        swap is a new key, never a silent retrace)."""
        d = np.asarray([3, 0, 1, 0, 7, 3, 3, -1, 0], dtype=np.int32)
        px = build_exchange_rank(8, "xla")
        pp = build_exchange_rank(8, "pallas")
        assert px is not pp
        np.testing.assert_array_equal(
            np.asarray(px(d, 4)), np.asarray(pp(d, 4)))

    def test_downstream_fold_order_identical(self, eight_device_mesh):
        """The full fused exchange+scatter program under the pallas
        rank backend equals the xla-backed one bit-for-bit — same
        bucket positions means same scatter order means identical
        state planes (the fold-order half of the A/B gate)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_tpu.parallel.mesh import KEY_AXIS
        from flink_tpu.parallel.shuffle import (
            build_exchange_scatter,
            stage_device_exchange,
        )

        mesh = eight_device_mesh
        agg = SumAggregate("v")
        sharding = NamedSharding(mesh, P(KEY_AXIS))
        cap = 2048
        rng = np.random.default_rng(5)
        n = 4000
        shards = rng.integers(0, 8, n).astype(np.int64)
        slots = rng.integers(1, cap, n).astype(np.int32)
        vals = rng.integers(0, 100, n).astype(np.float32)
        dst, staged, width = stage_device_exchange(
            shards, 8, [slots, vals], fills=[0, 0.0])
        put = jax.device_put((dst, *staged), sharding)

        def run():
            accs = tuple(
                jax.device_put(jnp.full((8, cap), l.identity,
                                        dtype=l.dtype), sharding)
                for l in agg.leaves)
            step = build_exchange_scatter(mesh, agg, valued=False)
            return jax.device_get(list(step(
                accs, put[0], put[1], tuple(put[2:]), width)))

        base = run()
        with backend_scope("exchange-rank", "pallas"):
            swapped = run()
        for b, s in zip(base, swapped):
            np.testing.assert_array_equal(np.asarray(b),
                                          np.asarray(s))


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

    def test_session_fires_identical_under_pallas_rank(
            self, eight_device_mesh):
        """The engine-level half of the Pallas A/B gate: a device-mode
        session run with the pallas exchange-rank backend emits
        bit-identical fires IN ORDER vs the xla backend."""
        steps = _stream(seed=59, n_steps=4)
        base, _ = _drive(self._session_engine(eight_device_mesh),
                         steps)
        with backend_scope("exchange-rank", "pallas"):
            swapped, _ = _drive(
                self._session_engine(eight_device_mesh), steps)
        assert len(_fire_rows(base)) > 0
        assert _fire_rows(base) == _fire_rows(swapped)
