"""Mesh-sharded PAGED spill (spill_layout="pages", the default) — the
mesh port of the single-device paged session machinery (NOTES_r5 §2):
per shard, eviction moves COHORTS of the coldest rows (slot-granular
touch clocks), reloads pop whole pages and split requested rows from the
re-bundled rest, and the host indexes run registry-free. Results are
pinned to the single-device oracle under forced eviction (device slots
≪ live sessions).
"""

import numpy as np
import pytest

from flink_tpu.core.records import KEY_ID_FIELD, RecordBatch
from flink_tpu.windowing.aggregates import SumAggregate
from flink_tpu.windowing.sessions import SessionWindower

from tests.test_sessions import keyed_batch

GAP = 100


def _engine(mesh, **kw):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine

    return MeshSessionEngine(gap=GAP, agg=SumAggregate("v"), mesh=mesh,
                             capacity_per_shard=1 << 14, **kw)


def _stream(num_keys=24_000, n_steps=8, per_step=6000, seed=17):
    """A live session set far beyond the 1024-slot per-shard budget:
    ~num_keys keys recur within the gap, the watermark lags a step, so
    >10k sessions stay concurrently live (>1.3k per shard) — forcing
    cohort eviction + reload-on-fire."""
    rng = np.random.default_rng(seed)
    steps = []
    for s in range(n_steps):
        keys = rng.integers(0, num_keys, per_step).astype(np.int64)
        vals = rng.random(per_step).astype(np.float32)
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


def session_dict(batches):
    out = {}
    for b in batches:
        for r in b.to_rows():
            out[(r[KEY_ID_FIELD], r["window_start"],
                 r["window_end"])] = r["sum_v"]
    return out


class TestMeshPagedSpill:
    def test_paged_is_default_and_registry_free(self, eight_device_mesh):
        eng = _engine(eight_device_mesh, max_device_slots=1024)
        assert eng.spill_layout == "pages"
        assert eng._paged
        for idx in eng.indexes:
            assert idx._track_ns is False
            assert idx.namespaces == []

    def test_forced_eviction_matches_single_device_oracle(
            self, eight_device_mesh):
        """1024 device slots/shard vs ~12k live sessions: every result
        must equal the unbounded single-device engine's, and the spill
        traffic must be PAGE-granular (cohorts of many rows per entry,
        not one entry per session)."""
        steps = _stream()
        mesh_eng = _engine(eight_device_mesh, max_device_slots=1024)
        single = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        d_mesh = session_dict(_run(mesh_eng, steps))
        d_single = session_dict(_run(single, steps))
        assert len(d_single) > 0
        assert set(d_mesh) == set(d_single)
        for k in d_single:
            assert d_mesh[k] == pytest.approx(d_single[k], rel=1e-4), k
        for idx in mesh_eng.indexes:
            assert idx.capacity <= 1024
        c = mesh_eng.spill_counters()
        assert c["pages_evicted"] > 0, "budget never became binding"
        assert c["pages_reloaded"] > 0, "fires never touched cold state"
        # page granularity: the unit of movement is a cohort — far
        # fewer spill entries than rows moved (one-entry-per-session
        # would make these equal)
        assert c["rows_evicted"] >= 8 * c["pages_evicted"]
        assert c["rows_reloaded"] >= c["pages_reloaded"]
        # amplification-free reloads: requested rows leave by index,
        # the cohort remainder stays put as lazy tombstones — NOTHING
        # re-bundles on the reload path
        assert c["rows_split_on_reload"] == 0
        # space comes back only through threshold compaction, and a
        # page is rewritten at most O(log rows) times — compaction
        # traffic stays well under the rows actually moved
        assert c["rows_compacted"] <= 2 * c["rows_reloaded"]

    def test_spilled_state_restores_cross_engine(self, eight_device_mesh):
        """Paged spilled rows are part of the logical snapshot: a
        budgeted mesh snapshot taken mid-run restores onto the
        single-device engine (and back onto a budgeted mesh engine) and
        finishes with the oracle's results."""
        steps = _stream(seed=23)
        cut = 4
        oracle = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        d_ref = session_dict(_run(oracle, steps))

        a = _engine(eight_device_mesh, max_device_slots=1024)
        fired = _run(a, steps[:cut])
        assert a.spill_counters()["pages_evicted"] > 0
        snap = a.snapshot()
        # -> single-device (no budget), then back -> budgeted mesh
        single = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        single.restore(snap)
        snap2 = single.snapshot()
        b = _engine(eight_device_mesh, max_device_slots=1024)
        b.restore(snap2)
        fired.extend(_run(b, steps[cut:]))
        d_got = session_dict(fired)
        assert set(d_got) == set(d_ref)
        for k in d_ref:
            assert d_got[k] == pytest.approx(d_ref[k], rel=1e-4), k

    def test_delta_snapshot_covers_dirty_paged_rows(
            self, eight_device_mesh):
        """Rows dirty at eviction time have not been in any snapshot
        since — a delta must carry them from the page tier."""
        eng = _engine(eight_device_mesh, max_device_slots=1024)
        n = 10_000
        keys = np.arange(1, n + 1, dtype=np.int64)
        ts = np.zeros(n, dtype=np.int64)
        for a in range(0, n, 2000):
            eng.process_batch(keyed_batch(
                keys[a:a + 2000], np.full(2000, 1.0, dtype=np.float32),
                ts[:2000]))
        assert eng.spill_counters()["pages_evicted"] > 0
        delta = eng.snapshot(mode="delta")["table"]
        got = {(int(k), int(ns)) for k, ns in zip(delta["key_id"],
                                                  delta["namespace"])}
        # every session (resident or paged out) was dirty since start
        assert len(got) == n

    def test_query_sessions_reads_paged_state(self, eight_device_mesh):
        eng = _engine(eight_device_mesh, max_device_slots=1024)
        n = 10_000
        keys = np.arange(1, n + 1, dtype=np.int64)
        ts = np.zeros(n, dtype=np.int64)
        for a in range(0, n, 2000):
            eng.process_batch(keyed_batch(
                keys[a:a + 2000], np.full(2000, 2.0, dtype=np.float32),
                ts[:2000]))
        c0 = eng.spill_counters()
        assert c0["pages_evicted"] > 0
        # early keys paged out; the query must answer from the page
        # tier without changing residency
        for k in (1, 2, 1500):
            got = eng.query_sessions(k)
            assert got == {GAP: {"sum_v": pytest.approx(2.0)}}, k
        assert eng.spill_counters()["pages_reloaded"] == \
            c0["pages_reloaded"], "a query must not thrash residency"

    def test_pipelined_fires_match_oracle_in_content_and_order(
            self, eight_device_mesh):
        """Dispatch-ahead >= 2 + async fires under forced eviction must
        be invisible: every fired row equals the single-device oracle's,
        AND the fire sequence equals the synchronous mesh engine's —
        pipelining may not reorder or drop fires."""
        from flink_tpu.runtime.pending import PendingFire

        steps = _stream(seed=31)

        def run_async(engine):
            """Pipelined driver: fires dispatch async and harvest
            deferred/coalesced (out of step with dispatch), like the
            bench driver and the task loop."""
            pending, fired = [], []
            for keys, vals, ts, wm in steps:
                engine.process_batch(keyed_batch(keys, vals, ts))
                out = engine.on_watermark(wm, async_ok=True)
                assert all(isinstance(b, PendingFire) for b in out)
                pending.extend(out)
                # harvest lazily: keep up to 3 fires in flight across
                # batches so harvests genuinely coalesce
                while len(pending) > 3:
                    fired.append(pending.pop(0).harvest())
            fired.extend(p.harvest() for p in pending)
            return fired

        sync_eng = _engine(eight_device_mesh, max_device_slots=1024)
        async_eng = _engine(eight_device_mesh, max_device_slots=1024,
                            max_dispatch_ahead=3)
        assert async_eng.supports_async_fires
        d_sync = _run(sync_eng, steps)
        d_async = run_async(async_eng)
        # ORDER: the concatenated fire stream must match row for row
        def rows(batches):
            out = []
            for b in batches:
                out.extend(
                    (r[KEY_ID_FIELD], r["window_start"],
                     r["window_end"], round(float(r["sum_v"]), 4))
                    for r in b.to_rows())
            return out

        assert rows(d_async) == rows(d_sync)
        # CONTENT: and both equal the single-device oracle
        single = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        d_ref = session_dict(_run(single, steps))
        d_got = session_dict(d_async)
        assert len(d_ref) > 0 and set(d_got) == set(d_ref)
        for k in d_ref:
            assert d_got[k] == pytest.approx(d_ref[k], rel=1e-4), k
        c = async_eng.spill_counters()
        assert c["pages_evicted"] > 0, "budget never became binding"
        assert c["rows_split_on_reload"] == 0

    def test_explicit_namespaces_layout_still_works(
            self, eight_device_mesh):
        """spill_layout='namespaces' keeps the registry-driven eviction
        path functional and equal to the oracle."""
        steps = _stream(num_keys=4000, n_steps=6, per_step=1500)
        eng = _engine(eight_device_mesh, max_device_slots=1024,
                      spill_layout="namespaces")
        assert not eng._paged
        for idx in eng.indexes:
            assert idx._track_ns is True
        single = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        d_got = session_dict(_run(eng, steps))
        d_ref = session_dict(_run(single, steps))
        assert len(d_ref) > 0 and set(d_got) == set(d_ref)
        for k in d_ref:
            assert d_got[k] == pytest.approx(d_ref[k], rel=1e-4), k

    def test_unbudgeted_pages_layout_is_registry_free(
            self, eight_device_mesh):
        """Without a device budget the pages layout never spills, but
        the registry-free host bookkeeping (slot-addressed frees) still
        applies — per-batch host work independent of live sessions."""
        steps = _stream(num_keys=3000, n_steps=5, per_step=1000)
        eng = _engine(eight_device_mesh)
        single = SessionWindower(GAP, SumAggregate("v"), capacity=1 << 15)
        d_got = session_dict(_run(eng, steps))
        d_ref = session_dict(_run(single, steps))
        assert len(d_ref) > 0 and set(d_got) == set(d_ref)
        for k in d_ref:
            assert d_got[k] == pytest.approx(d_ref[k], rel=1e-4), k
        for idx in eng.indexes:
            assert idx.namespaces == []
        assert eng.spill_counters() == {
            "pages_evicted": 0, "pages_reloaded": 0, "rows_evicted": 0,
            "rows_reloaded": 0, "rows_split_on_reload": 0,
            "rows_compacted": 0}
