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


# ------------------------------------------- grouped pass against the sort

#: shapes of the batch under test; each keeps every key's timestamps
#: from stepping backwards, so the grouped pass must carry all of them
GROUPED_SHAPES = ("hot_key", "all_distinct", "one_key", "equal_pairs",
                  "gap_edges", "key_twice", "wide_keys")
GROUPED_SIZES = (1, 2, 1000, 131072)


def _grouped_batch(shape, n, rng, t0):
    """``(keys, ts)`` of ``n`` records from event time ``t0`` on,
    arrival order = event-time order (ties included)."""
    if shape == "hot_key":
        # three records in four name one key, the rest 1,000 others
        keys = np.where(rng.random(n) < 0.75, 7,
                        rng.integers(1000, 2000, n))
        ts = t0 + np.sort(rng.integers(0, 3 * GAP, n))
    elif shape == "all_distinct":
        keys = rng.permutation(10 * n + 10)[:n]
        ts = t0 + np.sort(rng.integers(0, 3 * GAP, n))
    elif shape == "one_key":
        keys = np.full(n, 42)
        ts = t0 + np.cumsum(rng.integers(0, GAP // 2, n))
    elif shape == "equal_pairs":
        # few keys, few instants: many records share (key, ts)
        keys = rng.integers(0, 5, n)
        ts = t0 + np.sort(rng.integers(0, 8, n))
    elif shape == "wide_keys":
        # negative keys and the whole int64 range: the rank's bias and
        # its six digit passes, the sort's comparison form
        pool = np.concatenate([
            rng.integers(-(1 << 62), 1 << 62, 40) * 2,
            [np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 0]])
        keys = pool[rng.integers(0, len(pool), n)]
        ts = t0 + np.sort(rng.integers(0, 3 * GAP, n))
    elif shape == "gap_edges":
        # a key's records exactly GAP and GAP + 1 apart: the first
        # shares the session (the intervals touch), the second does not
        keys = rng.integers(0, 3, n)
        ts = t0 + np.cumsum(rng.choice([0, GAP, GAP + 1], n))
    else:  # key_twice
        # every key comes back after more than the gap: two local
        # sessions of one key, the SLOW class
        half = max(n // 2, 1)
        keys = np.concatenate([np.arange(half), np.arange(n - half)])
        ts = t0 + np.concatenate([
            rng.integers(0, 5, half).cumsum() % GAP,
            2 * GAP + 1 + rng.integers(0, 5, n - half).cumsum() % GAP])
        ts = np.sort(ts)
    return keys.astype(np.int64), ts.astype(np.int64)


def _raw_sweep(entry, meta, keys, ts):
    """One raw sweep through ``entry`` against ``meta``'s store, every
    output named, ``order`` / ``rec_to_sess`` made by the counting pass
    where the grouped pass left them out."""
    from flink_tpu.windowing.session_native import (
        _GroupedAbsorbResult,
        _absorb_call,
    )

    out = _absorb_call(
        entry, meta._store, keys, ts, meta.gap, meta.allowed_lateness,
        meta.max_fired_watermark, meta._next_sid)._asdict()
    out["grouped"] = out["order"] is None
    if out["grouped"]:
        lazy = _GroupedAbsorbResult(
            out["sess_key"], out["sess_sid"], None, None, [], None,
            rec_sess=out["rec_sess"])
        out["order"], out["rec_to_sess"] = lazy.order, lazy.rec_to_sess
    return out


def _history(meta_a, meta_b, rng):
    """The same three batches and one pop through both planes, so the
    batch under test meets stored singles (EXTENDED), multi-session
    keys (SLOW) and a fired watermark (STALE under lateness)."""
    for step in range(3):
        n = 600
        keys = rng.integers(0, 1500, n).astype(np.int64)
        ts = (step * 150 + rng.integers(0, 400, n)).astype(np.int64)
        ra = meta_a.absorb_batch_ex(keys, ts)
        rb = meta_b.absorb_batch_ex(keys, ts)
        np.testing.assert_array_equal(ra.sess_sid, rb.sess_sid)
    pa, pb = meta_a.pop_fired_ex(250), meta_b.pop_fired_ex(250)
    np.testing.assert_array_equal(pa.sids, pb.sids)


@needs_native
@pytest.mark.parametrize("n", GROUPED_SIZES)
@pytest.mark.parametrize("shape", GROUPED_SHAPES)
def test_grouped_pass_equals_sorted_path_bit_for_bit(shape, n):
    """The hash pass + session rank + counting pass against the radix
    argsort + gap scan, on two stores with the same history: every
    output array of the sweep equal, dtype and all."""
    _, nat_cls = _planes()
    rng = np.random.default_rng(len(shape) * 1000 + n)
    a, b = nat_cls(GAP, 10), nat_cls(GAP, 10)
    _history(a, b, rng)
    keys, ts = _grouped_batch(shape, n, rng, t0=300)
    got = _raw_sweep(a._lib.sx_absorb, a, keys, ts)
    want = _raw_sweep(b._lib.sx_absorb_sorted, b, keys, ts)
    assert got.pop("grouped") and not want.pop("grouped")
    assert list(got) == list(want)
    for name in got:
        x, y = got[name], want[name]
        assert np.asarray(x).dtype == np.asarray(y).dtype, name
        np.testing.assert_array_equal(x, y, err_msg=name)
    # the maps say the same thing three ways
    np.testing.assert_array_equal(
        got["rec_sess"][got["order"]], got["rec_to_sess"])
    from flink_tpu.windowing.session_meta import AbsorbResult

    plain = AbsorbResult(got["sess_key"], got["sess_sid"], None, None, [],
                         None, rec_sess=got["rec_sess"])
    np.testing.assert_array_equal(plain.order, got["order"])
    np.testing.assert_array_equal(plain.rec_to_sess, got["rec_to_sess"])
    assert plain.rec_to_sess.dtype == got["rec_to_sess"].dtype
    # the counts the sweep hands back are the flag column's
    assert got["n_slow"] == np.count_nonzero(got["sess_flag"] == 2)
    assert got["n_stale"] == np.count_nonzero(got["sess_flag"] == 3) \
        == np.count_nonzero(got["sess_sid"] < 0)
    assert got["n_fast"] == np.count_nonzero(got["sess_flag"] == 0)
    if shape == "key_twice" and n >= 4:
        assert got["n_slow"] > 0  # SLOW was met
    # both stores took the same rows and fire candidates
    assert a.snapshot() == b.snapshot()
    pa, pb = a.pop_fired_ex(1 << 60), b.pop_fired_ex(1 << 60)
    for name in ("keys", "starts", "ends", "sids", "slot_hint"):
        np.testing.assert_array_equal(getattr(pa, name),
                                      getattr(pb, name), err_msg=name)


@needs_native
def test_grouped_pass_fuzz_over_a_stream():
    """Sixty in-order batches of mixed shapes through both forms of the
    sweep with the Python slow path behind them (merges, multi-session
    keys, pops in between): results, stores and fires stay equal and
    every batch says it was grouped."""
    from flink_tpu.observe import flight_recorder as flight
    from flink_tpu.windowing import session_native

    py_cls, nat_cls = _planes()
    rng = np.random.default_rng(11)
    grouped, sorted_, py = nat_cls(GAP, 10), nat_cls(GAP, 10), py_cls(GAP, 10)
    plain = session_native.native_absorb

    def pick(store, *args):
        if store is sorted_._store:
            return session_native._absorb_call(
                store._lib.sx_absorb_sorted, store, *args)
        return plain(store, *args)

    rec = flight.recorder()
    rec.clear()
    records = 0
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(session_native, "native_absorb", pick)
        t0 = 0
        for step in range(60):
            shape = GROUPED_SHAPES[step % len(GROUPED_SHAPES)]
            n = int(rng.integers(1, 500))
            keys, ts = _grouped_batch(shape, n, rng, t0)
            if shape == "all_distinct":
                keys %= 40  # collide with what the store holds
            t0 = int(ts[-1]) - GAP // 2
            rg = grouped.absorb_batch_ex(keys, ts)
            rs = sorted_.absorb_batch_ex(keys, ts)
            rp = py.absorb_batch_ex(keys, ts)
            records += n
            assert type(rg) is session_native._GroupedAbsorbResult
            assert type(rs) is session_native.AbsorbResult
            for name in ("sess_key", "sess_sid", "rec_sess",
                         "rec_to_sess", "order", "fresh", "slot_hint",
                         "meta_row"):
                np.testing.assert_array_equal(
                    getattr(rg, name), getattr(rs, name), err_msg=name)
            for name in ("sess_key", "sess_sid", "rec_sess",
                         "rec_to_sess", "order"):
                np.testing.assert_array_equal(
                    getattr(rg, name), getattr(rp, name), err_msg=name)
            assert [g.sids_dst for g in rg.groups] == \
                [g.sids_dst for g in rs.groups] == \
                [g.sids_dst for g in rp.groups]
            if step % 4 == 3:
                wm = t0 - GAP
                fired = [m.pop_fired_ex(wm) for m in (grouped, sorted_, py)]
                for name in ("keys", "starts", "ends", "sids"):
                    np.testing.assert_array_equal(
                        getattr(fired[0], name), getattr(fired[1], name))
                    np.testing.assert_array_equal(
                        getattr(fired[0], name), getattr(fired[2], name))
    assert grouped.snapshot() == sorted_.snapshot() == py.snapshot()
    # one instant per batch of the grouped store, none from the other two
    said = rec.kind_totals()["sweep.grouped"]
    rec.clear()
    assert said["count"] == 60 and said["work"] == records


@needs_native
@pytest.mark.parametrize("where", ["first_pair", "middle", "last_pair"])
def test_a_backward_step_inside_a_key_takes_the_sort(where):
    """One key's timestamps step backwards once: the sweep sorts (no
    ``sweep.grouped`` instant, ``order`` in hand) and matches the Python
    plane; the same batch with the pair swapped back is grouped."""
    from flink_tpu.observe import flight_recorder as flight
    from flink_tpu.windowing.session_native import _GroupedAbsorbResult

    py_cls, nat_cls = _planes()
    rng = np.random.default_rng(5)
    n = 2000
    keys, ts = _grouped_batch("hot_key", n, rng, t0=0)
    hot = np.nonzero(keys == 7)[0]
    i, j = {"first_pair": hot[:2], "middle": hot[len(hot) // 2:][:2],
            "last_pair": hot[-2:]}[where]
    ts[j:] += 1  # make the pair strictly ordered, then swap it
    back = ts.copy()
    back[[i, j]] = back[[j, i]]
    rec = flight.recorder()
    for stream, want_grouped in ((back, False), (ts, True)):
        py, nat = py_cls(GAP, 0), nat_cls(GAP, 0)
        rec.clear()
        rp = py.absorb_batch_ex(keys, stream)
        rn = nat.absorb_batch_ex(keys, stream)
        said = rec.kind_totals().get("sweep.grouped")
        rec.clear()
        assert isinstance(rn, _GroupedAbsorbResult) == want_grouped
        assert (said is not None) == want_grouped
        if want_grouped:
            assert said == {**said, "count": 1, "work": n}
        for name in ("sess_key", "sess_sid", "rec_to_sess", "order",
                     "rec_sess"):
            np.testing.assert_array_equal(
                getattr(rp, name), getattr(rn, name), err_msg=name)
        assert py.snapshot() == nat.snapshot()


@needs_native
@pytest.mark.parametrize("plane", ["native", "python"])
def test_ingest_record_slots_equal_the_old_expression(plane):
    """``rec_slots = slot_of_sess[rec_sess]`` against what ``_ingest``
    computed before: ``rec_slots[order] = slot_of_sess[rec_to_sess]``,
    batch by batch, stale sessions (slot 0) included."""
    from flink_tpu.windowing.aggregates import SumAggregate
    from flink_tpu.windowing.sessions import SessionWindower

    py_cls, nat_cls = _planes()
    w = SessionWindower(GAP, SumAggregate("v"), capacity=4096)
    w.meta = (nat_cls if plane == "native" else py_cls)(GAP, 0)
    seen = {}
    absorb, note, scatter = (w.meta.absorb_batch_ex, w.meta.note_slots,
                             w.table.scatter)

    def absorb_spy(keys, ts, **kw):
        seen["res"] = absorb(keys, ts, **kw)
        seen["slot_of_sess"] = np.zeros(len(seen["res"].sess_key),
                                        dtype=np.int32)
        return seen["res"]

    def note_spy(keys, sids, slots, rows=None):
        live = seen["res"].sess_sid >= 0
        seen["slot_of_sess"][live] = slots
        return note(keys, sids, slots, rows=rows)

    def scatter_spy(rec_slots, values):
        res = seen["res"]
        old = np.empty(len(rec_slots), dtype=np.int32)
        old[res.order] = seen["slot_of_sess"][res.rec_to_sess]
        assert rec_slots.dtype == old.dtype
        np.testing.assert_array_equal(rec_slots, old)
        seen["checked"] = seen.get("checked", 0) + 1
        return scatter(rec_slots, values)

    w.meta.absorb_batch_ex = absorb_spy
    w.meta.note_slots = note_spy
    w.table.scatter = scatter_spy
    rng = np.random.default_rng(9)
    dropped = 0
    for step in range(12):
        # in-order batches (grouped on the native plane) and, every
        # third, a disordered one with stale records behind the fires
        batch = _traffic(step if step % 3 else max(step - 4, 0), rng,
                         n=1500, num_keys=400)
        if step % 3:
            o = np.argsort(batch.timestamps, kind="stable")
            batch = batch.take(o)
        w.process_batch(batch)
        w.on_watermark(step * 70)
        dropped = w.late_records_dropped
    assert seen["checked"] == 12 and dropped > 0


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
