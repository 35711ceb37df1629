"""Native session-metadata plane (native/sessions.cpp via
flink_tpu/windowing/session_native.py).

The acceptance discipline: the native plane and the pure-Python plane
must be BIT-IDENTICAL in everything observable — fires (values, order,
dtypes), snapshots (including row order), spill counters (residency
evolution) — under forced paged eviction; crash-restore-verify must
hold with the native plane on the engine; and snapshot/restore must
rebuild the native interval index exactly (the slotmap restore
discipline). Plus the loader's stale-.so defense: a cached ``_*.so``
is invalidated by a source-hash stamp, so editing the ``.cpp`` can
never load yesterday's binary — even when mtimes lie.
"""

import os
import shutil
import subprocess
import tempfile

import numpy as np
import pytest

from flink_tpu.native import sessions_available

needs_native = pytest.mark.skipif(
    not sessions_available(), reason="native sessions library not built")
needs_gxx = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ compiler")

GAP = 100


def _planes():
    from flink_tpu.windowing.session_meta import SessionIntervalSet
    from flink_tpu.windowing.session_native import (
        NativeSessionIntervalSet,
    )

    return SessionIntervalSet, NativeSessionIntervalSet


def _mesh_engine(mesh, plane: str, spill_dir=None):
    """A paged, budget-bound mesh engine with the requested metadata
    plane swapped in explicitly (both planes in ONE process — the env
    knob only selects the default)."""
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    py_cls, nat_cls = _planes()
    eng = MeshSessionEngine(
        GAP, SumAggregate("v"), mesh, capacity_per_shard=2048,
        max_device_slots=2048,
        spill_dir=spill_dir or tempfile.mkdtemp())
    eng.meta = (nat_cls if plane == "native" else py_cls)(GAP, 0)
    return eng


def _traffic(step, rng, n=3000, num_keys=50_000):
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    keys = rng.integers(0, num_keys, n).astype(np.int64)
    ts = (step * 70 + rng.integers(0, 200, n)).astype(np.int64)
    return RecordBatch({KEY_ID_FIELD: keys,
                        "v": np.ones(n, dtype=np.float32),
                        TIMESTAMP_FIELD: ts})


def _assert_fires_equal(fa, fb):
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        assert sorted(x.columns) == sorted(y.columns)
        for c in x.columns:
            va, vb = np.asarray(x.columns[c]), np.asarray(y.columns[c])
            assert va.dtype == vb.dtype
            np.testing.assert_array_equal(va, vb, err_msg=c)


# ------------------------------------------------------- metadata parity


@needs_native
class TestMetadataPlaneParity:
    def test_absorb_pop_fuzz_parity(self):
        """200 mixed batches at heavy key collision (exercises the
        multi-interval slow path, merges, stale records, extensions):
        sessionization, sid allocation, merge groups, pops and
        snapshots all bit-identical across planes."""
        py_cls, nat_cls = _planes()
        rng = np.random.default_rng(0)
        py, nat = py_cls(GAP, 10), nat_cls(GAP, 10)
        fired = 0
        for step in range(200):
            n = int(rng.integers(1, 400))
            keys = rng.integers(0, 50, n).astype(np.int64)
            ts = (step * 80 + rng.integers(0, 300, n)).astype(np.int64)
            rp = py.absorb_batch_ex(keys, ts)
            rn = nat.absorb_batch_ex(keys, ts)
            for name in ("sess_key", "sess_sid", "rec_to_sess", "order"):
                np.testing.assert_array_equal(
                    getattr(rp, name), getattr(rn, name), err_msg=name)
            # the native fresh set is a SUBSET (slow-path creations
            # probe conservatively — same state, never a wrong skip)
            assert np.all(~rn.fresh | rp.fresh)
            assert len(rp.groups) == len(rn.groups)
            for gp, gn in zip(rp.groups, rn.groups):
                assert gp.sids_dst == gn.sids_dst
                assert gp.sids_src == gn.sids_src
                assert gp.absorbed_sids == gn.absorbed_sids
            if step % 3 == 2:
                pp = py.pop_fired_ex(step * 80)
                pn = nat.pop_fired_ex(step * 80)
                for name in ("keys", "starts", "ends", "sids"):
                    np.testing.assert_array_equal(
                        getattr(pp, name), getattr(pn, name),
                        err_msg=name)
                fired += len(pp.keys)
            assert py._next_sid == nat._next_sid
            assert py.max_fired_watermark == nat.max_fired_watermark
        pp, pn = py.pop_fired_ex(1 << 60), nat.pop_fired_ex(1 << 60)
        for name in ("keys", "starts", "ends", "sids"):
            np.testing.assert_array_equal(getattr(pp, name),
                                          getattr(pn, name))
        assert fired + len(pp.keys) > 0
        assert py.snapshot() == nat.snapshot()

    def test_mesh_engines_bit_identical_under_forced_eviction(
            self, eight_device_mesh, tmp_path):
        """The acceptance pin: mesh engine on the native plane vs the
        Python plane vs the single-device oracle, with the live session
        set far beyond the device budget (paged eviction + reload + the
        hybrid fire genuinely on the path). Fires are bit-identical
        row-for-row, spill counters equal (identical residency
        evolution — the fold-verify path may skip probes but never
        changes hits/misses), snapshots bit-identical including row
        order."""
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.sessions import SessionWindower

        rng = np.random.default_rng(7)
        a = _mesh_engine(eight_device_mesh, "native",
                         str(tmp_path / "sp-a"))
        b = _mesh_engine(eight_device_mesh, "python",
                         str(tmp_path / "sp-b"))
        oracle = SessionWindower(GAP, SumAggregate("v"),
                                 capacity=1 << 15)
        from flink_tpu.windowing.session_native import (
            NativeSessionIntervalSet,
        )

        assert isinstance(a.meta, NativeSessionIntervalSet)
        assert not isinstance(b.meta, NativeSessionIntervalSet)
        fa, fb, fo = [], [], []
        for step in range(20):
            batch = _traffic(step, rng, n=4000, num_keys=60_000)
            a.process_batch(batch)
            b.process_batch(batch)
            oracle.process_batch(batch)
            wm = step * 70
            fa.extend(a.on_watermark(wm))
            fb.extend(b.on_watermark(wm))
            fo.extend(oracle.on_watermark(wm))
        fa.extend(a.on_watermark(1 << 60))
        fb.extend(b.on_watermark(1 << 60))
        fo.extend(oracle.on_watermark(1 << 60))
        _assert_fires_equal(fa, fb)
        assert a.spill_counters() == b.spill_counters()
        assert a.spill_counters()["rows_evicted"] > 0  # not vacuous

        def totals(fires):
            out = {}
            for f in fires:
                cols = f.columns
                names = sorted(cols)
                for i in range(len(f)):
                    row = tuple(np.asarray(cols[n])[i].item()
                                for n in names if n != "sum_v")
                    out[row] = out.get(row, 0.0) + float(
                        np.asarray(cols["sum_v"])[i])
            return out

        assert totals(fa) == totals(fo)  # oracle equivalence
        sa, sb = a.snapshot(), b.snapshot()
        assert sa["sessions"] == sb["sessions"]
        assert sa["next_sid"] == sb["next_sid"]
        assert sorted(sa["table"]) == sorted(sb["table"])
        for k in sa["table"]:
            np.testing.assert_array_equal(
                np.asarray(sa["table"][k]), np.asarray(sb["table"][k]),
                err_msg=k)

    def test_restore_rebuilds_native_index_exactly(
            self, eight_device_mesh, tmp_path):
        """The slotmap restore discipline applied to the metadata
        plane: snapshot a live native engine mid-stream, restore into a
        FRESH native engine and a fresh Python-plane engine, continue
        the stream on both — fires and final snapshots stay
        bit-identical, proving the native interval index (singles
        store, multi membership, fire candidates) was rebuilt
        exactly."""
        rng = np.random.default_rng(11)
        src = _mesh_engine(eight_device_mesh, "native",
                           str(tmp_path / "src"))
        for step in range(8):
            src.process_batch(_traffic(step, rng))
            src.on_watermark(step * 70)
        snap = src.snapshot()
        nat = _mesh_engine(eight_device_mesh, "native",
                           str(tmp_path / "nat"))
        py = _mesh_engine(eight_device_mesh, "python",
                          str(tmp_path / "py"))
        nat.restore(snap)
        py.restore(snap)
        assert nat.meta.snapshot() == py.meta.snapshot()
        rng2 = np.random.default_rng(12)
        fa, fb = [], []
        for step in range(8, 16):
            batch = _traffic(step, rng2)
            nat.process_batch(batch)
            py.process_batch(batch)
            fa.extend(nat.on_watermark(step * 70))
            fb.extend(py.on_watermark(step * 70))
        fa.extend(nat.on_watermark(1 << 60))
        fb.extend(py.on_watermark(1 << 60))
        _assert_fires_equal(fa, fb)
        assert nat.snapshot()["sessions"] == py.snapshot()["sessions"]

    def test_fold_verification_rejects_stale_hints(self):
        """A folded slot is a pure cache: verification against the
        state index's own metadata takes a hint iff the index maps
        exactly that pair at that slot — absent, reused and
        out-of-range hints all fall back to -1 (the probe path)."""
        from flink_tpu.state.slot_table import (
            make_slot_index,
            verify_slot_hints,
        )

        idx = make_slot_index(1024)
        keys = np.array([5, 6, 7], dtype=np.int64)
        nss = np.array([50, 60, 70], dtype=np.int64)
        slots = idx.lookup_or_insert(keys, nss)
        ok = verify_slot_hints(idx, keys, nss, slots)
        np.testing.assert_array_equal(ok, slots)
        # free one pair: its hint must now fail verification
        idx.free_slots(slots[1:2], keys=keys[1:2], nss=nss[1:2])
        after = verify_slot_hints(idx, keys, nss, slots)
        assert after[0] == slots[0] and after[2] == slots[2]
        assert after[1] == -1
        # wrong-pair and out-of-range hints fail; -1 passes through
        bogus = np.array([int(slots[2]), 1 << 20, -1], dtype=np.int32)
        out = verify_slot_hints(idx, keys, nss, bogus)
        assert list(out) == [-1, -1, -1]

    def test_env_knob_selects_python_plane(self, monkeypatch):
        from flink_tpu.windowing.session_meta import (
            SessionIntervalSet,
            make_session_meta,
        )
        from flink_tpu.windowing.session_native import (
            NativeSessionIntervalSet,
        )

        assert isinstance(make_session_meta(GAP),
                          NativeSessionIntervalSet)
        # the per-plane knob is gone: its name is ignored
        monkeypatch.setenv("FLINK_TPU_NATIVE_SESSIONS", "0")
        assert isinstance(make_session_meta(GAP),
                          NativeSessionIntervalSet)
        monkeypatch.setenv("FLINK_TPU_NO_NATIVE", "1")
        meta = make_session_meta(GAP)
        assert isinstance(meta, SessionIntervalSet)
        assert not isinstance(meta, NativeSessionIntervalSet)

    def test_single_device_windower_parity(self):
        """SessionWindower (the single-device engine) drives the same
        absorb -> stage -> fire flow through the plane: fires and
        snapshots bit-identical across planes with a bounded paged
        table (hints exercised on resolve AND fire)."""
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.sessions import SessionWindower

        py_cls, nat_cls = _planes()

        def make(plane):
            w = SessionWindower(
                GAP, SumAggregate("v"), capacity=2048,
                spill={"max_device_slots": 2048,
                       "spill_dir": tempfile.mkdtemp()})
            w.meta = (nat_cls if plane == "native" else py_cls)(GAP, 0)
            return w

        a, b = make("native"), make("python")
        rng = np.random.default_rng(3)
        fa, fb = [], []
        for step in range(15):
            batch = _traffic(step, rng, n=1500, num_keys=20_000)
            a.process_batch(batch)
            b.process_batch(batch)
            fa.extend(a.on_watermark(step * 70))
            fb.extend(b.on_watermark(step * 70))
        fa.extend(a.on_watermark(1 << 60))
        fb.extend(b.on_watermark(1 << 60))
        _assert_fires_equal(fa, fb)
        assert a.spill_counters() == b.spill_counters()
        assert a.spill_counters()["rows_evicted"] > 0


# ------------------------------------------------------ chaos coverage


@needs_native
class TestNativePlaneChaos:
    def test_crash_restore_verify_on_native_plane(
            self, eight_device_mesh, tmp_path):
        """Crash-restore-verify with the NATIVE metadata plane driving
        the engine (the default when compiled): crashes at a session
        fire and inside a page reload, restore from the latest complete
        checkpoint, replay — committed output equals the fault-free
        oracle exactly and the run is seed-deterministic. The restore
        path rebuilds the native interval index from the snapshot
        (mirroring the slotmap restore discipline) — a divergence here
        is exactly a mis-rebuilt index."""
        from flink_tpu.chaos.harness import run_crash_restore_verify
        from flink_tpu.chaos.injection import FaultPlan, FaultRule
        from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
        from flink_tpu.windowing.aggregates import SumAggregate
        from flink_tpu.windowing.session_native import (
            NativeSessionIntervalSet,
        )
        from flink_tpu.windowing.sessions import SessionWindower

        def make_engine():
            eng = MeshSessionEngine(
                GAP, SumAggregate("v"), eight_device_mesh,
                capacity_per_shard=1 << 14, max_device_slots=1024)
            # the native plane must actually be on the engine — a
            # compiler-less environment would silently test the
            # Python plane (needs_native guards, this asserts)
            assert isinstance(eng.meta, NativeSessionIntervalSet)
            return eng

        def make_oracle():
            return SessionWindower(GAP, SumAggregate("v"),
                                   capacity=1 << 15)

        rng = np.random.default_rng(17)
        steps = []
        for s in range(8):
            keys = rng.integers(0, 6000, 1500).astype(np.int64)
            vals = rng.random(1500).astype(np.float32)
            ts = rng.integers(s * 80, s * 80 + 60, 1500).astype(np.int64)
            steps.append((keys, vals, ts, (s - 1) * 80))
        plan = FaultPlan(rules=[
            FaultRule(pattern="mesh.session_fire", nth=4),
            FaultRule(pattern="spill.page_reload", nth=5),
        ])

        def run(tag):
            return run_crash_restore_verify(
                make_engine, make_oracle, steps, plan, seed=23,
                ckpt_root=str(tmp_path / f"ckpt-{tag}"),
                checkpoint_every=2)

        r1 = run("a")
        assert not r1.diverged and r1.windows > 0
        assert r1.crashes >= 1 and r1.restores >= 1
        r2 = run("b")
        assert r2.signature() == r1.signature()


# ---------------------------------------------------- stale-.so defense


@needs_gxx
class TestSourceHashStamp:
    SRC_V1 = 'extern "C" { long probe_value() { return 111; } }\n'
    SRC_V2 = 'extern "C" { long probe_value() { return 222; } }\n'

    def test_source_hash_invalidates_cached_so(self, tmp_path,
                                               monkeypatch):
        """Editing the .cpp can never load yesterday's binary: the
        cached artifact is stamped with the source sha256, and a
        mismatch rebuilds EVEN WHEN the mtimes are identical (git
        checkouts and copies routinely produce exactly that lie)."""
        import ctypes

        import flink_tpu.native as native

        root = tmp_path
        (root / "native").mkdir()
        monkeypatch.setattr(native, "_REPO_ROOT", str(root))
        monkeypatch.setattr(native, "_BUILD_DIR",
                            str(root / "native" / "build"))
        src = root / "native" / "probe.cpp"
        src.write_text(self.SRC_V1)
        lib = native.load_native("probe.cpp", "_probe.so")
        assert lib is not None
        lib.probe_value.restype = ctypes.c_long
        lib.probe_value.argtypes = []
        assert lib.probe_value() == 111
        so = root / "native" / "build" / "_probe.so"
        stamp = root / "native" / "build" / "_probe.so.srchash"
        assert so.exists() and stamp.exists()
        stamp_v1 = stamp.read_text()
        old_stat = src.stat()
        # rewrite the source, then FORGE the old timestamps — an
        # mtime-based check would serve the stale binary
        src.write_text(self.SRC_V2)
        os.utime(src, ns=(old_stat.st_atime_ns, old_stat.st_mtime_ns))
        # drop the v1 handle: dlopen dedupes same-path libraries while
        # a handle is alive (the stamp's job is cross-PROCESS
        # staleness; within one process the loaders cache anyway)
        import _ctypes

        handle = lib._handle
        del lib
        _ctypes.dlclose(handle)
        lib2 = native.load_native("probe.cpp", "_probe.so")
        assert stamp.read_text() != stamp_v1  # rebuilt, not served stale
        lib2.probe_value.restype = ctypes.c_long
        lib2.probe_value.argtypes = []
        assert lib2.probe_value() == 222
        # and a missing stamp (stampless artifact of unknown
        # provenance) also forces a rebuild rather than trusting it
        stamp.unlink()
        assert native.load_native("probe.cpp", "_probe.so") is not None
        assert stamp.exists()

    def test_disabled_env_returns_none(self, tmp_path, monkeypatch):
        import flink_tpu.native as native

        # the removed alias is ignored: FLINK_TPU_NO_NATIVE is the switch
        monkeypatch.setenv("FLINK_TPU_NATIVE", "0")
        assert not native.native_disabled()
        assert native.load_native("slotmap.cpp", "_slotmap.so") is not None
        monkeypatch.delenv("FLINK_TPU_NATIVE")
        monkeypatch.setenv("FLINK_TPU_NO_NATIVE", "1")
        assert native.load_native("slotmap.cpp", "_slotmap.so") is None
