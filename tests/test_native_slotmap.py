"""Native C++ slot map vs pure-Python index parity + direct behavior."""

import numpy as np
import pytest

from flink_tpu.native import slotmap_available
from flink_tpu.state.slot_table import HostSlotIndex, NativeSlotIndex

needs_native = pytest.mark.skipif(
    not slotmap_available(), reason="native slotmap not built")


@needs_native
class TestNativeSlotIndex:
    def test_basic_insert_lookup(self):
        idx = NativeSlotIndex(1024)
        keys = np.array([5, 6, 5, 7], dtype=np.int64)
        ns = np.array([1, 1, 1, 2], dtype=np.int64)
        slots = idx.lookup_or_insert(keys, ns)
        assert slots[0] == slots[2]
        assert len({slots[0], slots[1], slots[3]}) == 3
        assert slots.min() >= 1
        assert idx.num_used == 3
        # idempotent lookup
        again = idx.lookup_or_insert(keys, ns)
        np.testing.assert_array_equal(slots, again)

    def test_metadata_views(self):
        idx = NativeSlotIndex(1024)
        slots = idx.lookup_or_insert(np.array([42], dtype=np.int64),
                                     np.array([7], dtype=np.int64))
        s = int(slots[0])
        assert idx.slot_key[s] == 42
        assert idx.slot_ns[s] == 7
        assert bool(idx.slot_used[s])

    def test_growth_rewraps_and_notifies(self):
        grows = []
        idx = NativeSlotIndex(1024, on_grow=lambda o, n: grows.append((o, n)))
        n = 5000
        idx.lookup_or_insert(np.arange(n, dtype=np.int64),
                             np.zeros(n, dtype=np.int64))
        assert idx.capacity >= n
        assert grows and grows[-1][1] == idx.capacity
        assert idx.num_used == n

    def test_not_growable_raises(self):
        idx = NativeSlotIndex(1024, growable=False, full_hint="HINT")
        with pytest.raises(RuntimeError, match="HINT"):
            idx.lookup_or_insert(np.arange(2000, dtype=np.int64),
                                 np.zeros(2000, dtype=np.int64))

    def test_free_namespaces_and_reuse(self):
        idx = NativeSlotIndex(1024)
        keys = np.arange(100, dtype=np.int64)
        ns = np.full(100, 9, dtype=np.int64)
        slots = idx.lookup_or_insert(keys, ns)
        freed = idx.free_namespaces([9])
        assert sorted(freed.tolist()) == sorted(slots.tolist())
        assert idx.num_used == 0
        # reinsert reuses freed slots
        slots2 = idx.lookup_or_insert(keys, ns)
        assert idx.num_used == 100
        assert set(slots2.tolist()) <= set(range(1, 1024))

    def test_parity_with_python_index(self):
        rng = np.random.default_rng(0)
        nat = NativeSlotIndex(1 << 12)
        py = HostSlotIndex(1 << 12)
        for step in range(10):
            n = 2000
            keys = rng.integers(0, 500, n).astype(np.int64)
            ns = rng.integers(0, 8, n).astype(np.int64)
            s_n = nat.lookup_or_insert(keys, ns)
            s_p = py.lookup_or_insert(keys, ns)
            # slot numbers may differ; the *mapping* must agree
            assert nat.num_used == py.num_used
            np.testing.assert_array_equal(nat.slot_key[s_n], keys)
            np.testing.assert_array_equal(nat.slot_ns[s_n], ns)
            np.testing.assert_array_equal(py.slot_key[s_p], keys)
            if step % 3 == 2:
                dead = int(rng.integers(0, 8))
                f_n = nat.free_namespaces([dead])
                f_p = py.free_namespaces([dead])
                assert (f_n is None) == (f_p is None)
                if f_n is not None:
                    assert len(f_n) == len(f_p)
                assert nat.num_used == py.num_used

    def test_duplicate_heavy_batch(self):
        idx = NativeSlotIndex(1024)
        keys = np.zeros(10000, dtype=np.int64)
        ns = np.zeros(10000, dtype=np.int64)
        slots = idx.lookup_or_insert(keys, ns)
        assert len(np.unique(slots)) == 1
        assert idx.num_used == 1


# ------------------------------------------------- the one-sweep resolve
#
# ``sm_resolve_grouped`` against the path it replaced, kept here as the
# oracle: slice ends by NumPy, ``sm_lookup_or_insert`` with its is_new
# mask, and the new slots regrouped by a stable argsort of their
# namespaces. Two native indexes fed the same pairs in the same order
# hand out the same slot numbers, so everything compares exactly.

W, OFFSET = 100, 0


def slice_ends(ts, width=W, offset=OFFSET):
    ts = np.asarray(ts, dtype=np.int64)
    return ts - np.remainder(ts - offset, width) + width


def oracle_lookup_or_insert(idx, keys, nss):
    """The parent's ``NativeSlotIndex.lookup_or_insert``, tracking on."""
    import ctypes as ct

    keys = np.ascontiguousarray(keys, dtype=np.int64)
    nss = np.ascontiguousarray(nss, dtype=np.int64)
    out = np.empty(len(keys), dtype=np.int32)
    is_new = np.empty(len(keys), dtype=np.uint8)
    i64p, i32p = ct.POINTER(ct.c_int64), ct.POINTER(ct.c_int32)
    rc = idx._lib.sm_lookup_or_insert(
        idx._h, len(keys), keys.ctypes.data_as(i64p),
        nss.ctypes.data_as(i64p), out.ctypes.data_as(i32p),
        is_new.ctypes.data_as(ct.POINTER(ct.c_uint8)))
    if rc > 0:
        idx._wrap_views()
    new_mask = is_new.view(bool)
    new_slots, new_ns = out[new_mask], nss[new_mask]
    idx.pairs_inserted += len(new_slots)
    order = np.argsort(new_ns, kind="stable")
    for ns in np.unique(new_ns).tolist():
        idx._ns_slots.setdefault(ns, []).append(
            new_slots[order][new_ns[order] == ns])
    return rc, out


def registry(idx):
    """Namespaces in the registry's own order, each with its slots in
    the order they were appended."""
    return [(ns, np.concatenate(chunks).tolist())
            for ns, chunks in idx._ns_slots.items()]


def _in_order(rng, n):
    return np.sort(rng.integers(0, 5 * W, n))


def _shuffled(rng, n):
    return rng.integers(0, 5 * W, n)


def _before_the_epoch(rng, n):
    return rng.integers(-7 * W, 2 * W, n)


SWEEP_CASES = {
    # name: (timestamps of a batch, keys of a batch, width, offset)
    "in_order": (_in_order, lambda rng, n: rng.integers(0, 300, n), W, 0),
    "shuffled": (_shuffled, lambda rng, n: rng.integers(0, 300, n), W, 0),
    "negative_ts_and_offset": (
        _before_the_epoch, lambda rng, n: rng.integers(-50, 300, n), W, 37),
    "duplicates": (_in_order, lambda rng, n: rng.integers(0, 4, n), W, 0),
    "one_slice": (lambda rng, n: rng.integers(3 * W, 4 * W, n),
                  lambda rng, n: rng.integers(0, 3000, n), W, 0),
    "growth_in_mid_batch": (
        _shuffled, lambda rng, n: rng.integers(0, 1 << 40, n), W, 0),
}


@needs_native
@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_sweep_equals_the_path_it_replaced(case):
    make_ts, make_keys, width, offset = SWEEP_CASES[case]
    rng = np.random.default_rng(sorted(SWEEP_CASES).index(case))
    grows = []
    swept = NativeSlotIndex(1024, on_grow=lambda o, n: grows.append((o, n)))
    plain = NativeSlotIndex(1024)
    for step in range(12):
        n = int(rng.integers(1, 1500))
        ts = make_ts(rng, n).astype(np.int64)
        keys = make_keys(rng, n).astype(np.int64)
        ends = slice_ends(ts, width, offset)
        got = swept.resolve_slices(keys, ts, offset, width, -(1 << 62))
        assert got is not None
        slots, uniq, records = got
        _, want = oracle_lookup_or_insert(plain, keys, ends)
        np.testing.assert_array_equal(slots, want)
        want_uniq, want_records = np.unique(ends, return_counts=True)
        np.testing.assert_array_equal(uniq, want_uniq)
        np.testing.assert_array_equal(records, want_records)
        assert uniq.dtype == np.int64 and slots.dtype == np.int32
        # the same namespaces in the same order, the same slots in the
        # same order under each, the same count of pairs
        assert registry(swept) == registry(plain)
        assert swept.pairs_inserted == plain.pairs_inserted
        assert swept.num_used == plain.num_used == swept.pairs_inserted
        np.testing.assert_array_equal(swept.slot_key[slots], keys)
        np.testing.assert_array_equal(swept.slot_ns[slots], ends)
        # what was handed out is the caller's: the next batch's sweep
        # writes none of it
        keep = (slots.copy(), uniq.copy(), records.copy())
        swept.resolve_slices(keys[:5], ts[:5], offset, width, -(1 << 62))
        oracle_lookup_or_insert(plain, keys[:5], ends[:5])
        for arr, copy in zip((slots, uniq, records), keep):
            np.testing.assert_array_equal(arr, copy)
    if case == "growth_in_mid_batch":
        assert grows and grows[-1][1] == swept.capacity == plain.capacity
        assert swept.capacity > 1024
    else:
        assert swept.capacity == plain.capacity


@needs_native
@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_namespaces_entry_groups_natively_as_the_sort_did(case):
    """``lookup_or_insert(keys, namespaces)`` through the same sweep:
    any number of distinct namespaces, with frees in between."""
    make_ts, make_keys, width, offset = SWEEP_CASES[case]
    rng = np.random.default_rng(100 + sorted(SWEEP_CASES).index(case))
    swept, plain = NativeSlotIndex(1024), NativeSlotIndex(1024)
    for step in range(12):
        n = int(rng.integers(1, 1500))
        # slice ends, or (every third step) a namespace of its own for
        # almost every record: far more than MAX_SWEPT_SLICES
        nss = (rng.integers(-10 ** 12, 10 ** 12, n) if step % 3 == 2
               else slice_ends(make_ts(rng, n), width, offset))
        keys = make_keys(rng, n).astype(np.int64)
        slots = swept.lookup_or_insert(keys, nss)
        _, want = oracle_lookup_or_insert(plain, keys, nss)
        np.testing.assert_array_equal(slots, want)
        assert registry(swept) == registry(plain)
        assert swept.pairs_inserted == plain.pairs_inserted
        if step % 4 == 3:
            dead = [ns for ns, _ in registry(swept)[::2]]
            freed, freed_plain = (i.free_namespaces(dead)
                                  for i in (swept, plain))
            np.testing.assert_array_equal(freed, freed_plain)
            assert registry(swept) == registry(plain)
    assert swept.num_used == plain.num_used
    assert len(swept.lookup_or_insert(
        np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))) == 0


@needs_native
class TestSweepLeavesABatchAlone:
    def test_more_distinct_slices_than_it_holds(self):
        idx = NativeSlotIndex(1 << 14)
        n = NativeSlotIndex.MAX_SWEPT_SLICES + 1
        ts = np.arange(n, dtype=np.int64) * W
        keys = np.arange(n, dtype=np.int64)
        assert idx.resolve_slices(keys, ts, 0, W, -(1 << 62)) is None
        assert idx.num_used == 0 and not idx._ns_slots
        assert idx.pairs_inserted == 0
        # one fewer is taken
        got = idx.resolve_slices(keys[1:], ts[1:], 0, W, -(1 << 62))
        assert len(got[1]) == NativeSlotIndex.MAX_SWEPT_SLICES
        assert idx.num_used == n - 1

    @pytest.mark.parametrize("late_at", [0, 617, 999])
    def test_a_slice_end_below_live_from(self, late_at):
        idx = NativeSlotIndex(1 << 12)
        ts = np.full(1000, 5 * W + 3, dtype=np.int64)
        ts[late_at] = 4 * W + 99          # slice end 5 * W: late
        keys = np.arange(1000, dtype=np.int64)
        assert idx.resolve_slices(keys, ts, 0, W, 6 * W) is None
        assert idx.num_used == 0 and not idx._ns_slots
        # at the threshold itself the slice is live
        assert idx.resolve_slices(keys, ts, 0, W, 5 * W) is not None
        assert idx.num_used == 1000


@needs_native
@pytest.mark.parametrize("entry", ["slices", "namespaces"])
def test_table_full_leaves_index_and_registry_level(entry):
    """Full at max_capacity in mid-batch raises as it did; the pairs
    inserted before it are in the registry (the parent left them in the
    index alone), and growth on the way there reached the owner."""
    grows = []
    idx = NativeSlotIndex(1024, max_capacity=4096,
                          on_grow=lambda o, n: grows.append((o, n)))
    rng = np.random.default_rng(9)
    n = 6000
    keys = np.arange(n, dtype=np.int64)
    ts = rng.integers(0, 3 * W, n).astype(np.int64)
    with pytest.raises(RuntimeError, match="slot table full"):
        if entry == "slices":
            idx.resolve_slices(keys, ts, 0, W, -(1 << 62))
        else:
            idx.lookup_or_insert(keys, slice_ends(ts))
    # two doublings inside the one call reach the owner as one growth
    assert grows == [(1024, 4096)] and idx.capacity == 4096
    assert len(idx.slot_key) == 4096          # views re-wrapped
    assert idx.num_used == 4095 == idx.pairs_inserted
    held = np.concatenate([s for _, s in registry(idx)])
    assert sorted(held.tolist()) == sorted(
        np.nonzero(idx.slot_used)[0].tolist())
    for ns, slots in registry(idx):
        assert (idx.slot_ns[slots] == ns).all()
        # record order within a namespace: keys here rise with the record
        assert (np.diff(idx.slot_key[slots]) > 0).all()
    # and the table still serves: free a slice, the room is taken again
    idx.free_namespaces([W])
    room = 4095 - idx.num_used
    assert room > 0
    idx.lookup_or_insert(np.arange(room, dtype=np.int64) + 10 ** 6,
                         np.full(room, 7 * W, dtype=np.int64))
    assert idx.num_used == 4095
