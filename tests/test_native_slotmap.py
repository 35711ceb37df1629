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
# ``sm_resolve_grouped`` over the per-namespace tables against the path
# it replaced, kept here as the oracle: slice ends by NumPy,
# ``sm_lookup_or_insert`` into the flat table with its is_new mask, and the
# new slots regrouped by a stable argsort of their namespaces into a
# registry of chunk lists. The two forms share the free stack, so fed the
# same pairs in the same order they hand out the same slot numbers, and
# everything compares exactly.

W, OFFSET = 100, 0


def slice_ends(ts, width=W, offset=OFFSET):
    ts = np.asarray(ts, dtype=np.int64)
    return ts - np.remainder(ts - offset, width) + width


def oracle_index(capacity):
    """The parent's native index with tracking on: the flat (key,
    namespace) table, and the namespace -> chunks registry it kept in
    Python (here ``registry``, kept by the oracle's two functions)."""
    idx = NativeSlotIndex(capacity, track_namespaces=False)
    idx.registry = {}
    return idx


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
        idx.registry.setdefault(ns, []).append(
            new_slots[order][new_ns[order] == ns])
    return rc, out


def oracle_free_namespaces(idx, namespaces):
    """The parent's ``free_namespaces``: the registry drained, the pairs
    erased one by one in the drained order."""
    chunks = [c for ns in namespaces for c in idx.registry.pop(ns, [])]
    if not chunks:
        return None
    slots = np.concatenate(chunks)
    idx.free_slots(slots)
    return slots


def registry(idx):
    """Namespaces in the order they were first given a slot, each with
    its slots in the order they were given out."""
    if hasattr(idx, "registry"):
        return [(ns, np.concatenate(chunks).tolist())
                for ns, chunks in idx.registry.items()]
    return [(ns, idx.slots_for_namespace(ns).tolist())
            for ns in idx.namespaces]


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
    plain = oracle_index(1024)
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
    swept, plain = NativeSlotIndex(1024), oracle_index(1024)
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
            freed = swept.free_namespaces(dead)
            freed_plain = oracle_free_namespaces(plain, dead)
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
        assert idx.num_used == 0 and not idx.namespaces
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
        assert idx.num_used == 0 and not idx.namespaces
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


# ------------------------------------- the two native forms and the Python
# index side by side
#
# One random walk drives the partitioned native index (a table per
# namespace: what a window job's state sits on), the flat native index
# (the session tables') and ``HostSlotIndex`` through everything an owner
# does, and compares them after every step. Slot numbers are each index's
# own business (the Python index allocates in sorted-pair order), so the
# comparison is by (key, namespace): which pairs are live, which slot each
# record was given, which namespaces exist and which keys each holds.


def live_pairs(idx):
    used = np.nonzero(idx.slot_used)[0]
    pairs = set(zip(idx.slot_key[used].tolist(), idx.slot_ns[used].tolist()))
    assert len(pairs) == len(used) == idx.num_used     # no pair twice
    return pairs


def check_level(part, flat, host):
    """The three indexes hold the same pairs, and the two that track
    namespaces the same namespaces with the same keys under each."""
    want = live_pairs(host)
    assert live_pairs(part) == want and live_pairs(flat) == want
    by_ns = {}
    for key, ns in want:
        by_ns.setdefault(ns, []).append(key)
    assert sorted(part.namespaces) == sorted(host.namespaces) \
        == sorted(by_ns)
    assert flat.namespaces == []
    for ns, keys in by_ns.items():
        for idx in (part, host):
            slots = idx.slots_for_namespace(ns)
            assert idx.slot_used[slots].all()
            assert (idx.slot_ns[slots] == ns).all()
            assert sorted(idx.slot_key[slots].tolist()) == sorted(keys)
        assert len(flat.slots_for_namespace(ns)) == 0


class ThreeIndexes:
    def __init__(self, capacity=1024, max_capacity=0):
        self.kw = dict(max_capacity=max_capacity)
        self.grown = [0, 0, 0]
        self.make(capacity)

    def make(self, capacity):
        def counting(i):
            return lambda old, new: self.grown.__setitem__(i, new)

        self.part = NativeSlotIndex(capacity, on_grow=counting(0), **self.kw)
        self.flat = NativeSlotIndex(capacity, on_grow=counting(1),
                                    track_namespaces=False, **self.kw)
        self.host = HostSlotIndex(capacity, on_grow=counting(2), **self.kw)
        self.all = (self.part, self.flat, self.host)

    def insert(self, keys, nss):
        keys = np.asarray(keys, dtype=np.int64)
        nss = np.asarray(nss, dtype=np.int64)
        for idx in self.all:
            slots = idx.lookup_or_insert(keys, nss)
            np.testing.assert_array_equal(idx.slot_key[slots], keys)
            np.testing.assert_array_equal(idx.slot_ns[slots], nss)
            assert idx.slot_used[slots].all()

    def sweep(self, keys, ts, width, offset):
        keys = np.asarray(keys, dtype=np.int64)
        ends = slice_ends(ts, width, offset)
        for idx in (self.part, self.flat):      # pass B of either form
            slots, uniq, records = idx.resolve_slices(
                keys, np.asarray(ts, dtype=np.int64), offset, width,
                -(1 << 62))
            np.testing.assert_array_equal(idx.slot_key[slots], keys)
            np.testing.assert_array_equal(idx.slot_ns[slots], ends)
            want_uniq, want_records = np.unique(ends, return_counts=True)
            np.testing.assert_array_equal(uniq, want_uniq)
            np.testing.assert_array_equal(records, want_records)
        self.host.lookup_or_insert(keys, ends)

    def lookup(self, keys, nss):
        keys = np.asarray(keys, dtype=np.int64)
        nss = np.asarray(nss, dtype=np.int64)
        want = live_pairs(self.host)
        present = np.array([(k, n) in want
                            for k, n in zip(keys.tolist(), nss.tolist())],
                           dtype=bool)
        for idx in self.all:
            slots = idx.lookup(keys, nss)
            np.testing.assert_array_equal(slots >= 0, present)
            hit = slots[present]
            np.testing.assert_array_equal(idx.slot_key[hit], keys[present])
            np.testing.assert_array_equal(idx.slot_ns[hit], nss[present])
            # a hint is taken where it names the pair's slot, only there
            hints = np.where(np.arange(len(keys)) % 2 == 0, slots,
                             np.int32(1))
            from flink_tpu.state.slot_table import verify_slot_hints
            got = verify_slot_hints(idx, keys, nss, hints)
            np.testing.assert_array_equal(
                got, np.where(hints == slots, slots, -1))

    def free_namespaces(self, namespaces):
        for idx in (self.part, self.host):
            held = sum(len(idx.slots_for_namespace(ns))
                       for ns in set(namespaces))
            freed = idx.free_namespaces(list(namespaces))
            assert (0 if freed is None else len(freed)) == held
            if freed is not None:
                assert not idx.slot_used[freed].any()
                assert len(set(freed.tolist())) == len(freed)
        flat = self.flat
        assert flat.free_namespaces(list(namespaces)) is None
        used = np.nonzero(flat.slot_used)[0].astype(np.int32)
        flat.free_slots(used[np.isin(flat.slot_ns[used], namespaces)])

    def free_pairs(self, keys, nss, give_columns):
        """Per-slot frees of the pairs that are live, each once; with
        the pairs' columns handed along or left to the index's gather."""
        keys = np.asarray(keys, dtype=np.int64)
        nss = np.asarray(nss, dtype=np.int64)
        for idx in self.all:
            slots = idx.lookup(keys, nss)
            slots, first = np.unique(slots, return_index=True)
            first = first[slots >= 0]
            slots = slots[slots >= 0]
            if give_columns:
                idx.free_slots(slots, keys=keys[first], nss=nss[first])
            else:
                idx.free_slots(slots)

    def restore(self):
        """Snapshot -> restore as ``SlotTable.restore`` does it: the
        live pairs' columns into a new index."""
        used = self.host.used_slots()
        keys, nss = self.host.slot_key[used], self.host.slot_ns[used]
        self.make(1024)
        if len(keys):
            self.insert(keys, nss)


WALKS = {
    # name: (namespaces drawn from, keys drawn from, records per step)
    "a_handful_of_big_slices": (12, 3000, 1500),
    "hundreds_of_small_namespaces": (400, 40, 600),
    "one_namespace": (1, 5000, 800),
    "names_dropped_and_made_again": (5, 800, 900),
}


@needs_native
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_partitioned_flat_and_python_index_agree_after_every_step(walk):
    n_ns, n_keys, per_step = WALKS[walk]
    rng = np.random.default_rng(sorted(WALKS).index(walk) + 40)
    three = ThreeIndexes()
    width = 100

    def some_pairs(n):
        """Half live pairs, half anything."""
        live = sorted(live_pairs(three.host))
        keys = rng.integers(0, n_keys, n)
        nss = rng.integers(0, n_ns, n) * width
        if live:
            pick = rng.integers(0, len(live), n // 2)
            keys[:n // 2] = [live[i][0] for i in pick]
            nss[:n // 2] = [live[i][1] for i in pick]
        return keys, nss

    ops = ["sweep", "insert", "lookup", "free_ns", "free_slots", "restore"]
    done = {op: 0 for op in ops}
    for step in range(60):
        op = ops[step] if step < len(ops) else rng.choice(
            ops, p=[0.3, 0.25, 0.1, 0.2, 0.1, 0.05])
        done[op] += 1
        n = int(rng.integers(1, per_step))
        if op == "sweep":
            lo = int(rng.integers(0, n_ns))
            ts = rng.integers(lo * width - 37, (lo + 3) * width - 37, n)
            three.sweep(rng.integers(0, n_keys, n), ts, width, -37)
        elif op == "insert":
            three.insert(rng.integers(0, n_keys, n),
                         rng.integers(0, n_ns, n) * width)
        elif op == "lookup":
            three.lookup(*some_pairs(n))
        elif op == "free_ns":
            names = three.host.namespaces
            dead = (rng.choice(names, size=max(1, len(names) // 3),
                               replace=False).tolist() if names else [])
            three.free_namespaces(dead + [10 ** 9])     # and an absent one
        elif op == "free_slots":
            keys, nss = some_pairs(max(2, n // 10))
            three.free_pairs(keys, nss, give_columns=bool(step % 2))
        else:
            three.restore()
        check_level(*three.all)
    assert min(done.values()) > 0
    if walk != "hundreds_of_small_namespaces":
        assert three.part.capacity > 1024               # growth happened
    assert three.part.capacity == three.flat.capacity \
        == three.host.capacity == three.grown[0] == three.grown[1] \
        == three.grown[2]


@needs_native
def test_table_full_then_on_in_all_three():
    """Full at max_capacity in mid-batch: each index raises with every
    slot taken. The two native forms took the same pairs (record order)
    and the partitioned one's namespaces are level with its slots; the
    Python index allocates in sorted-pair order and registers a batch's
    new slots after its last insert (its owner makes headroom first), so
    it is made anew from the pairs the native ones hold. Emptied by
    namespace, the three are level again and serve on."""
    three = ThreeIndexes(1024, max_capacity=2048)
    rng = np.random.default_rng(3)
    three.insert(rng.integers(0, 300, 900), rng.integers(0, 4, 900) * 100)
    check_level(*three.all)
    keys = np.arange(5000, dtype=np.int64) + 1000
    nss = rng.integers(2, 9, 5000) * 100
    for idx in three.all:
        with pytest.raises(RuntimeError, match="slot table full"):
            idx.lookup_or_insert(keys, nss)
        assert idx.num_used == 2047 == len(live_pairs(idx))
        assert idx.capacity == 2048 == len(idx.slot_used)
    part, flat = three.part, three.flat
    assert live_pairs(part) == live_pairs(flat)
    held = np.concatenate([part.slots_for_namespace(ns)
                           for ns in part.namespaces])
    assert sorted(held.tolist()) == np.nonzero(part.slot_used)[0].tolist()
    assert all(len(part.slots_for_namespace(ns))
               for ns in part.namespaces)               # none left empty
    used = part.used_slots()
    three.host = HostSlotIndex(2048, max_capacity=2048)
    three.host.lookup_or_insert(part.slot_key[used], part.slot_ns[used])
    three.all = (part, flat, three.host)
    check_level(*three.all)
    three.free_namespaces(list(range(0, 900, 100)))
    check_level(*three.all)
    assert part.num_used == 0
    three.insert(rng.integers(0, 300, 900), rng.integers(0, 4, 900) * 100)
    check_level(*three.all)


@needs_native
def test_a_namespace_dropped_and_opened_again_under_its_name():
    idx = NativeSlotIndex(1024)
    first = idx.lookup_or_insert(np.arange(50, dtype=np.int64),
                                 np.full(50, 7, dtype=np.int64))
    assert idx.namespaces == [7]
    np.testing.assert_array_equal(idx.free_namespaces([7]), first)
    assert idx.namespaces == [] and idx.pairs_dropped == 50
    assert (idx.lookup(np.arange(50, dtype=np.int64),
                       np.full(50, 7, dtype=np.int64)) == -1).all()
    # again, other keys: nothing of the first table shows through
    again = idx.lookup_or_insert(np.arange(40, 70, dtype=np.int64),
                                 np.full(30, 7, dtype=np.int64))
    assert idx.namespaces == [7] and idx.num_used == 30
    np.testing.assert_array_equal(idx.slots_for_namespace(7), again)
    found = idx.lookup(np.arange(80, dtype=np.int64),
                       np.full(80, 7, dtype=np.int64))
    np.testing.assert_array_equal(found >= 0,
                                  (np.arange(80) >= 40) & (np.arange(80) < 70))
    assert idx.free_namespaces([7, 7, 8]).tolist() == again.tolist()
    assert idx.free_namespaces([7]) is None and idx.num_used == 0


def _rss_mb():
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * 4096 / 1e6


@needs_native
def test_a_hundred_thousand_small_namespaces_cost_little():
    """One to three keys under each of 100,000 namespaces (session ids on
    the mesh, 1 s slices over a day): the smallest table is tens of bytes
    and the directory a hash, so memory and time stay small — also right
    after a big namespace was dropped, whose size only the next table
    opens at."""
    import time

    idx = NativeSlotIndex(1 << 12)
    idx.lookup_or_insert(np.arange(200_000, dtype=np.int64),
                         np.full(200_000, -5, dtype=np.int64))
    assert len(idx.free_namespaces([-5])) == 200_000
    rng = np.random.default_rng(12)
    n_ns = 100_000
    nss = np.repeat(np.arange(n_ns, dtype=np.int64), 3)
    keys = np.tile(np.arange(3, dtype=np.int64), n_ns)
    keep = rng.random(len(nss)) < 0.7
    keep[::3] = True                       # at least one key each
    nss, keys = nss[keep], keys[keep]
    order = rng.permutation(len(nss))
    before, t0 = _rss_mb(), time.perf_counter()
    for part in np.array_split(order, 20):
        slots = idx.lookup_or_insert(keys[part], nss[part])
        assert (idx.slot_ns[slots] == nss[part]).all()
    assert idx.num_used == len(nss)
    assert sorted(idx.namespaces) == list(range(n_ns))
    assert (idx.lookup(keys, nss) >= 0).all()
    grown_mb = _rss_mb() - before
    # by slot (the session engines' free), then by namespace
    half = idx.lookup(keys[::2], nss[::2])
    idx.free_slots(half)
    assert idx.num_used == len(nss) - len(half)
    left = np.unique(nss[1::2])
    assert sorted(idx.namespaces) == left.tolist()
    assert len(idx.free_namespaces(left.tolist())) == len(nss) - len(half)
    assert idx.num_used == 0 and idx.namespaces == []
    seconds = time.perf_counter() - t0
    # the slot arrays at 262,144 slots are 6 MB; a table per namespace
    # opened at the dropped one's size would be 100,000 x 5 MB
    assert grown_mb < 120, grown_mb
    assert seconds < 30, seconds


@needs_native
@pytest.mark.parametrize("then", ["larger", "smaller", "tiny"])
def test_a_pooled_table_is_reused_at_another_size(then):
    """A dropped table's memory serves the next namespace whatever that
    one grows to: more pairs than it held (grown in place of the pooled
    block), fewer, or a handful."""
    idx = NativeSlotIndex(1 << 12)
    host = HostSlotIndex(1 << 12)
    sizes = {"larger": 9000, "smaller": 700, "tiny": 3}
    rng = np.random.default_rng(7)
    for ns, n in enumerate([2000, sizes[then], 2000, sizes[then]]):
        keys = rng.integers(0, 1 << 40, n)
        for i in (idx, host):
            slots = i.lookup_or_insert(keys, np.full(n, ns, dtype=np.int64))
            assert (i.slot_key[slots] == keys).all()
        # a kept neighbour: its pairs are untouched by the reuse
        for i in (idx, host):
            i.lookup_or_insert(keys[:50], np.full(len(keys[:50]), 100 + ns))
        assert live_pairs(idx) == live_pairs(host)
        np.testing.assert_array_equal(
            idx.slot_key[idx.slots_for_namespace(ns)],
            keys[np.sort(np.unique(keys, return_index=True)[1])])
        for i in (idx, host):
            assert len(i.free_namespaces([ns])) == len(np.unique(keys))
        assert (idx.lookup(keys, np.full(n, ns, dtype=np.int64)) == -1).all()
        assert live_pairs(idx) == live_pairs(host)
    assert sorted(idx.namespaces) == [100, 101, 102, 103]


# ------------------------- the sweep over a mesh's shard indexes at once
#
# ``resolve_slices_sharded`` (``sm_resolve_grouped_sharded``) against the
# path it replaced in ``MeshWindowEngine``, kept here as the oracle: slice
# ends and routing by NumPy, a stable argsort of the destinations, one
# ``lookup_or_insert`` per shard on its contiguous run, the scatter-back.
# Each shard's index sees its own records in record order either way, so
# slot numbers, tables, free stacks and counters compare exactly.

MP = 128            # max_parallelism


def _routing(form, shards):
    """``(key_group_range, assignment)`` of a routing form."""
    from flink_tpu.state.keygroups import KeyGroupAssignment

    if form == "formula":
        return None, None
    if form == "range":
        return (40, 103), None
    moved = np.arange(3, MP, 7)          # every seventh group to the last
    return None, KeyGroupAssignment.contiguous(shards, MP).move(
        moved, shards - 1)


def _owned_keys(rng, n, group_range, high=5000):
    """Keys whose key group the mesh owns (a sub-range mesh is sent no
    others)."""
    from flink_tpu.state.keygroups import assign_key_groups

    keys = rng.integers(-high, high, 4 * n).astype(np.int64)
    if group_range is not None:
        groups = assign_key_groups(keys, MP)
        keys = keys[(groups >= group_range[0]) & (groups <= group_range[1])]
    return keys[:n]


def old_mesh_resolve(indexes, keys, ts, group_range, assignment, dirty):
    """``MeshWindowEngine._process_batch_device``'s resolve before the
    sweep: ``(shards, slots per record)``."""
    from flink_tpu.parallel.shuffle import shard_records

    ends = slice_ends(ts)
    shards = shard_records(keys, len(indexes), MP, group_range, assignment)
    order = np.argsort(shards, kind="stable")
    offsets = np.zeros(len(indexes) + 1, dtype=np.int64)
    np.cumsum(np.bincount(shards, minlength=len(indexes)), out=offsets[1:])
    s_keys, s_ns = keys[order], ends[order]
    slots_sorted = np.empty(len(keys), dtype=np.int32)
    for p, idx in enumerate(indexes):
        a, b = int(offsets[p]), int(offsets[p + 1])
        if a == b:
            continue
        slots_sorted[a:b] = idx.lookup_or_insert(s_keys[a:b], s_ns[a:b])
        dirty[p, slots_sorted[a:b]] = True
    slots = np.empty(len(keys), dtype=np.int32)
    slots[order] = slots_sorted
    return shards, slots


def assert_shards_level(swept, plain):
    for a, b in zip(swept, plain):
        assert registry(a) == registry(b)
        assert a.pairs_inserted == b.pairs_inserted
        assert a.num_used == b.num_used and a.capacity == b.capacity
        np.testing.assert_array_equal(a.slot_used, b.slot_used)
        used = np.nonzero(a.slot_used)[0]
        np.testing.assert_array_equal(a.slot_key[used], b.slot_key[used])
        np.testing.assert_array_equal(a.slot_ns[used], b.slot_ns[used])


@needs_native
@pytest.mark.parametrize("form", ["formula", "range", "assignment"])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_sweep_equals_argsort_and_per_shard_lookups(shards, form):
    from flink_tpu.parallel.shuffle import group_shard_table
    from flink_tpu.state.slot_table import resolve_slices_sharded

    group_range, assignment = _routing(form, shards)
    table = group_shard_table(shards, MP, group_range, assignment)
    assert table.dtype == np.int32 and len(table) == MP
    rng = np.random.default_rng(10 * shards + len(form))
    swept = [NativeSlotIndex(1024) for _ in range(shards)]
    plain = [NativeSlotIndex(1024) for _ in range(shards)]
    cap = 1 << 15
    dirty, dirty_plain = (np.zeros((shards, cap), dtype=bool)
                          for _ in range(2))
    for step in range(14):
        n = int(rng.integers(1, 2500))
        keys = _owned_keys(rng, n, group_range)
        # slices step * W .. : mostly in order, every third batch shuffled
        ts = rng.integers(step * W, (step + 4) * W, len(keys))
        ts = (ts if step % 3 == 2 else np.sort(ts)).astype(np.int64)
        got = resolve_slices_sharded(swept, keys, ts, table, 0, W,
                                     -(1 << 62), dirty=dirty)
        assert got is not None
        rec_shards, slots, uniq, records, new_pairs = got
        before = sum(i.pairs_inserted for i in plain)
        want_shards, want = old_mesh_resolve(
            plain, keys, ts, group_range, assignment, dirty_plain)
        np.testing.assert_array_equal(rec_shards, want_shards)
        np.testing.assert_array_equal(slots, want)
        assert rec_shards.dtype == slots.dtype == np.int32
        want_uniq, want_records = np.unique(slice_ends(ts),
                                            return_counts=True)
        np.testing.assert_array_equal(uniq, want_uniq)
        np.testing.assert_array_equal(records, want_records)
        assert new_pairs == sum(i.pairs_inserted for i in plain) - before
        assert_shards_level(swept, plain)
        np.testing.assert_array_equal(dirty, dirty_plain)
        # the fire's carried matrix reads the tables' own arrays: the
        # same rows from either set
        window = [(step + j) * W + W for j in range(3)]
        for a, b in zip(swept, plain):
            for x, y in zip(a.slice_matrix(window), b.slice_matrix(window)):
                np.testing.assert_array_equal(x, y)
        if step % 4 == 3:
            dead = [ns for ns in uniq.tolist() if ns <= (step - 1) * W + W]
            dead += [(step - 2) * W]
            for a, b in zip(swept, plain):
                freed, freed_plain = (i.free_namespaces(dead)
                                      for i in (a, b))
                assert (freed is None) == (freed_plain is None)
                if freed is not None:
                    np.testing.assert_array_equal(freed, freed_plain)
            assert_shards_level(swept, plain)
    assert all(i.capacity > 1024 for i in swept) or shards == 4


def _four_shards(capacity=1 << 12, **kw):
    from flink_tpu.parallel.shuffle import group_shard_table

    return ([NativeSlotIndex(capacity, **kw) for _ in range(4)],
            group_shard_table(4, MP))


def _untouched(indexes):
    return all(i.num_used == 0 and not i.namespaces
               and i.pairs_inserted == 0 for i in indexes)


@needs_native
@pytest.mark.parametrize("why", [
    "late_first", "late_middle", "late_last", "too_many_slices",
    "a_group_without_a_shard", "a_python_index", "a_flat_index"])
def test_sharded_sweep_leaves_a_batch_alone(why):
    """"Not swept" is None with every index and the dirty map untouched;
    the batch's neighbour that lacks the cause is taken."""
    from flink_tpu.parallel.shuffle import group_shard_table
    from flink_tpu.state.slot_table import resolve_slices_sharded

    indexes, table = _four_shards(1 << 14)
    n = 1000
    keys = np.arange(n, dtype=np.int64)
    ts = np.full(n, 5 * W + 3, dtype=np.int64)
    live_from = 6 * W
    taken = dict(keys=keys, ts=ts + W, table=table, indexes=indexes)
    left = dict(taken)
    if why.startswith("late"):
        late_at = {"late_first": 0, "late_middle": 617, "late_last": n - 1}
        left["ts"] = ts + W
        left["ts"][late_at[why]] = 4 * W + 99      # slice end 5 * W
    elif why == "too_many_slices":
        n = NativeSlotIndex.MAX_SWEPT_SLICES + 1
        keys = np.arange(n, dtype=np.int64)
        left.update(keys=keys, ts=(np.arange(n) + 6) * W)
        taken.update(keys=keys[1:], ts=left["ts"][1:])
    elif why == "a_group_without_a_shard":
        # a sub-range mesh handed a key of a group it does not own
        owned = group_shard_table(4, MP, (0, 63))
        assert (owned[64:] == -1).all() and (owned[:64] >= 0).all()
        left["table"] = owned
        taken.update(table=owned, keys=_owned_keys(
            np.random.default_rng(3), n, (0, 63)))
    elif why == "a_python_index":
        left["indexes"] = indexes[:3] + [HostSlotIndex(1 << 14)]
    else:
        left["indexes"] = indexes[:3] + [
            NativeSlotIndex(1 << 14, track_namespaces=False)]
    dirty = np.zeros((4, 1 << 14), dtype=bool)
    assert resolve_slices_sharded(
        left["indexes"], left["keys"], left["ts"], left["table"], 0, W,
        live_from, dirty=dirty) is None
    assert _untouched(left["indexes"]) and not dirty.any()
    got = resolve_slices_sharded(
        taken["indexes"], taken["keys"], taken["ts"], taken["table"], 0, W,
        live_from, dirty=dirty)
    assert got is not None
    distinct = len(np.unique(taken["keys"]))     # one slice: pairs = keys
    assert sum(i.num_used for i in indexes) == distinct == dirty.sum()


@needs_native
def test_sharded_sweep_growth_of_one_shard_settles_that_shard():
    """One shard's index doubles in mid-sweep: its owner hears of it, its
    views are re-wrapped, the others stay as they were — and the slots
    past the old capacity are the owner's to mark."""
    from flink_tpu.state.keygroups import assign_key_groups
    from flink_tpu.state.slot_table import resolve_slices_sharded

    grows = []
    indexes, table = _four_shards(1024)
    for p, idx in enumerate(indexes):
        idx.on_grow = lambda old, new, p=p: grows.append((p, old, new))
    keys = np.arange(40_000, dtype=np.int64)
    shard_of = table[assign_key_groups(keys, MP)]
    # 1,500 keys of shard 2 (past its 1,023 free slots), 200 of the others
    keys = np.concatenate([keys[shard_of == 2][:1500]]
                          + [keys[shard_of == p][:200] for p in (0, 1, 3)])
    keys = np.random.default_rng(5).permutation(keys)
    ts = np.sort(np.random.default_rng(6).integers(0, 3 * W, len(keys)))
    dirty = np.zeros((4, 1024), dtype=bool)
    shards, slots, _, _, new_pairs = resolve_slices_sharded(
        indexes, keys, ts, table, 0, W, -(1 << 62), dirty=dirty)
    assert grows == [(2, 1024, 2048)]
    assert [i.capacity for i in indexes] == [1024, 1024, 2048, 1024]
    assert len(indexes[2].slot_key) == 2048
    assert new_pairs == len(keys) == sum(i.num_used for i in indexes)
    for p, idx in enumerate(indexes):
        mine = shards == p
        np.testing.assert_array_equal(idx.slot_key[slots[mine]], keys[mine])
        in_map = slots[mine][slots[mine] < 1024]
        assert dirty[p].sum() == len(in_map) and dirty[p, in_map].all()
    assert (slots[shards == 2] >= 1024).sum() == 1500 - 1023


@needs_native
def test_sharded_sweep_full_shard_raises_with_the_others_level():
    """A shard full at max_capacity in mid-sweep raises as
    ``lookup_or_insert`` does; what every shard was given before it is in
    its tables and counters, and a growth on the way reached its owner."""
    from flink_tpu.state.keygroups import assign_key_groups
    from flink_tpu.state.slot_table import (
        SlotTableFullError,
        resolve_slices_sharded,
    )

    grows = []
    indexes, table = _four_shards(1024, max_capacity=2048)
    for p, idx in enumerate(indexes):
        idx.on_grow = lambda old, new, p=p: grows.append((p, old, new))
    keys = np.arange(60_000, dtype=np.int64)
    shard_of = table[assign_key_groups(keys, MP)]
    keys = np.concatenate([keys[shard_of == 1][:3000]]
                          + [keys[shard_of == p][:300] for p in (0, 2, 3)])
    keys = np.random.default_rng(8).permutation(keys)
    ts = np.random.default_rng(9).integers(0, 3 * W, len(keys))
    dirty = np.zeros((4, 2048), dtype=bool)
    with pytest.raises(SlotTableFullError, match="slot table full"):
        resolve_slices_sharded(indexes, keys, ts.astype(np.int64), table,
                               0, W, -(1 << 62), dirty=dirty)
    assert grows == [(1, 1024, 2048)]
    # every key is new here: the marks are the slots given out
    for p, idx in enumerate(indexes):
        np.testing.assert_array_equal(dirty[p, :idx.capacity], idx.slot_used)
    assert indexes[1].num_used == 2047 == indexes[1].pairs_inserted
    assert len(indexes[1].slot_key) == 2048
    for idx in indexes:
        assert idx.num_used == idx.pairs_inserted
        held = [s for _, slots in registry(idx) for s in slots]
        assert sorted(held) == np.nonzero(idx.slot_used)[0].tolist()
        for ns, slots in registry(idx):
            assert slots and (idx.slot_ns[slots] == ns).all()
    # the others stopped where the full one did: fewer than their 300
    assert all(0 < indexes[p].num_used < 300 for p in (0, 2, 3))
    # and the tables still serve
    indexes[1].free_namespaces([W])
    assert indexes[1].num_used < 2047
    assert resolve_slices_sharded(indexes, keys[:50], ts[:50].astype(
        np.int64), table, 0, W, -(1 << 62)) is not None
