"""Mesh-sharded session windows.

The multi-device form of ``flink_tpu.windowing.sessions.SessionWindower``
(reference: WindowOperator.java:159-162 / MergingWindowSet): session interval
*metadata* stays global on the host (``SessionIntervalSet``, shared with the
single-device engine), while accumulator *state* lives in ``[P, capacity]``
device arrays sharded over the key-group mesh axis.

Why this shards cleanly: sessions are per-key, and keys are routed to exactly
one shard by the key-group formula (reference:
KeyGroupRangeAssignment.java:124-127) — so session merges NEVER cross shards.
Every device step (record scatter, session merge, fire, reset) is ONE jitted
``shard_map`` program over the whole mesh; the scatter/fire/reset programs are
the same ones the mesh window engine uses (``build_mesh_steps``), plus one
session-merge program (``acc[dst] op= acc[src]; acc[src] = identity``).

Snapshots use the same logical format as SessionWindower (key_id / namespace
/ key_group / leaf columns + interval metadata), so session checkpoints are
mutually restorable across engines and mesh sizes.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.chaos import injection as chaos
from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.ops.segment_ops import (
    SCATTER_METHOD,
    pad_bucket_size,
    sticky_bucket,
)
from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
from flink_tpu.parallel.sharded_windower import (
    MeshPagedSpillSupport,
    build_delta_fire_step,
    build_mesh_steps,
)
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE
from flink_tpu.parallel.shuffle import (
    bucket_by_shard,
    build_exchange_scatter,
    shard_records,
    stage_device_exchange,
)
from flink_tpu.state.keygroups import _splitmix64, assign_key_groups
from flink_tpu.state.slot_table import resolve_slot_hints
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.windowing.session_meta import MergeGroup, make_session_meta
from flink_tpu.windowing.windower import WINDOW_END_FIELD, WINDOW_START_FIELD

#: hot-key splitting: upper bound on sub-keys per split key. Salted
#: sub-rows live in the SAME state plane as real sessions, addressed by
#: (salted key, salted namespace): ``ssid = -(sid * MAX_SALTS + salt
#: + 1)`` — globally unique NEGATIVE namespaces that can never collide
#: with real (non-negative) session ids, and decode back to (sid, salt).
MAX_SALTS = 64

_SALT_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _salted_keys(key_ids: np.ndarray, salts: np.ndarray) -> np.ndarray:
    """Deterministic synthetic key id for (key, salt) — splitmix64 over
    the XOR-folded pair, so every sub-key lands in its own key group
    (that is the point: the group spread is what moves the load)."""
    x = (np.asarray(key_ids, dtype=np.int64).astype(np.uint64)
         ^ ((np.asarray(salts, dtype=np.uint64) + np.uint64(1))
            * _SALT_GOLDEN))
    return _splitmix64(x).astype(np.int64)


def _salted_ns(sids: np.ndarray, salts: np.ndarray) -> np.ndarray:
    """(sid, salt) -> unique negative namespace (see MAX_SALTS)."""
    return -(np.asarray(sids, dtype=np.int64) * MAX_SALTS
             + np.asarray(salts, dtype=np.int64) + 1)


def build_session_merge_step(mesh: Mesh, agg: AggregateFunction):
    """One shard_map program: ``acc[p, dst] op= acc[p, src]`` for [P, M]
    index blocks, then reset the src slots to identity (the mesh form of
    sessions._merge_jit). Padded lanes use dst == src == 0 (reserved
    identity slot) and are pure no-ops."""
    key = (tuple(d.id for d in mesh.devices.flat), agg.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "session-merge", key, lambda: _build_session_merge_step(mesh, agg))


def _build_session_merge_step(mesh: Mesh, agg: AggregateFunction):
    methods = tuple(SCATTER_METHOD[l.reduce] for l in agg.leaves)
    idents = tuple(l.identity for l in agg.leaves)
    n_leaves = len(agg.leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def merge_step(accs, dst, src):
        def local(*args):
            accs_l = args[:n_leaves]
            d = args[n_leaves][0]
            s = args[n_leaves + 1][0]
            out = []
            for a, m, i in zip(accs_l, methods, idents):
                moved = a[0][s]
                a = getattr(a.at[0, d], m)(moved)
                a = a.at[0, s].set(jnp.asarray(i, dtype=a.dtype))
                out.append(a)
            return tuple(out)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 2),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, dst, src)

    return merge_step


class MeshSessionEngine(MeshPagedSpillSupport):
    """Keyed session windows sharded over a 1-D device mesh.

    Spill layout (mirrors ``SessionWindower``): sessions are one row per
    namespace (sid), so the default ``spill_layout="pages"`` moves
    eviction COHORTS per shard (slot-granular touch clocks,
    split-on-reload — see flink_tpu.state.paged_spill) and runs the host
    indexes registry-free. An explicit ``spill_layout="namespaces"``
    keeps the registry-driven per-namespace eviction."""

    def __init__(
        self,
        gap: int,
        agg: AggregateFunction,
        mesh: Mesh,
        capacity_per_shard: int = 1 << 16,
        max_parallelism: int = 128,
        allowed_lateness: int = 0,
        max_device_slots: int = 0,
        spill_dir: Optional[str] = None,
        spill_host_max_bytes: int = 0,
        key_group_range: Optional[Tuple[int, int]] = None,
        memory=None,
        spill_layout: str = "pages",
        max_dispatch_ahead: int = 2,
        shuffle_mode: str = "device",
        host_topology=None,
    ) -> None:
        self.gap = int(gap)
        self.agg = agg
        self.shuffle_mode = self._check_shuffle_mode(shuffle_mode)
        #: dispatch-ahead depth: how many batches' device work may be in
        #: flight while the host preps the next (double-buffered by
        #: default; see MeshSpillSupport._init_pipeline)
        self.max_dispatch_ahead = max(int(max_dispatch_ahead or 1), 1)
        if spill_layout not in ("namespaces", "pages"):
            raise ValueError(
                f"spill_layout must be 'namespaces' or 'pages', got "
                f"{spill_layout!r}")
        self.spill_layout = spill_layout
        #: registry-backed namespace bookkeeping only for the explicit
        #: "namespaces" layout; the paged layout frees by SLOT and the
        #: per-namespace registry would cost O(live sessions) Python
        #: per batch at one row per sid
        self._track_ns = spill_layout == "namespaces"
        #: (first, last) inclusive GLOBAL key groups this engine owns; the
        #: mesh shards within the range (mesh x stage — see shard_records)
        self.key_group_range = key_group_range
        #: (MemoryManager, owner) — managed [P, capacity] accounting
        self._memory = memory
        self.mesh = mesh
        self.P = int(mesh.devices.size)
        self._set_host_topology(host_topology)
        #: per-SHARD HBM slot budget; cold sessions spill per shard and
        #: reload on access (see MeshSpillSupport — the 10M-key session
        #: capacity of BASELINE row 5 cannot be device-resident)
        self.max_device_slots = int(max_device_slots or 0)
        self.capacity = max(int(capacity_per_shard), 1024)
        if self.max_device_slots:
            self.max_device_slots = max(self.max_device_slots, 1024)
            self.capacity = min(self.capacity, self.max_device_slots)
        self.max_parallelism = max_parallelism
        self.allowed_lateness = int(allowed_lateness)
        if max_parallelism < self.P:
            raise ValueError(
                f"max_parallelism {max_parallelism} < mesh size {self.P}")

        # growable per-shard indexes (see MeshWindowEngine: skew grows the
        # table instead of failing the job)
        self.indexes = self._make_shard_indexes()
        self._init_spill(spill_dir, spill_host_max_bytes)
        self._paged = (spill_layout == "pages"
                       and self.max_device_slots > 0)
        if self._paged:
            self._init_paged()
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        self._reserve_rows(self.P * self.capacity)
        self.accs: Tuple[jnp.ndarray, ...] = tuple(
            jax.device_put(
                jnp.full((self.P, self.capacity), leaf.identity,
                         dtype=leaf.dtype),
                self._sharding)
            for leaf in agg.leaves
        )
        self._build_steps()
        #: session-interval metadata: the native C sweep when compiled,
        #: else the pure-Python plane (bit-identical fires/snapshots)
        self.meta = make_session_meta(self.gap, self.allowed_lateness)
        self._dirty = np.zeros((self.P, self.capacity), dtype=bool)
        #: freed-session tombstone chunks (int64 arrays, deduped at
        #: snapshot time — per-fire tolist round-trips were measurable)
        self._freed_ns: List[np.ndarray] = []
        #: hot-key splitting (two-stage aggregation): key_id -> number of
        #: salts. Records for a hot key are salted into sub-keys whose
        #: partials live as ordinary (salted-key, negative-ns) rows in the
        #: SAME state plane — spill, checkpoint and reshard machinery see
        #: nothing special. Fires and queries fold the sub-rows back in a
        #: fixed order (main row, then salts ascending) on the host.
        self._hot_keys: Dict[int, int] = {}
        #: records diverted through the salting path (skew gauge)
        self._hot_salted_records = 0
        #: fires that folded at least one salted sub-row (skew gauge)
        self._hot_salted_fires = 0
        self._merge_bucket = 0
        self._fire_bucket = 0
        self._reset_bucket = 0
        self._gather_bucket = 0

    @property
    def late_records_dropped(self) -> int:
        return self.meta.late_records_dropped

    def _build_steps(self) -> None:
        (self._scatter_step, self._fire_step, self._reset_step,
         self._gather_step, self._put_step, self._merge_leaves_step,
         self._valued_scatter_step) = build_mesh_steps(self.mesh, self.agg)
        self._merge_step = build_session_merge_step(self.mesh, self.agg)
        # delta-harvest family: fire + reset fused into ONE dispatch —
        # a session fire pops only the sessions that close, merges and
        # finishes them, and resets their slots in a single program
        self._delta_fire_step = build_delta_fire_step(self.mesh, self.agg)
        # fused exchange+scatter (device shuffle mode) — built through
        # the shared program cache regardless of mode (cheap closure;
        # compiles lazily on first use)
        self._exchange_scatter_step = build_exchange_scatter(
            self.mesh, self.agg, valued=False)
        if self._two_level_active():
            from flink_tpu.parallel.exchange2 import (
                build_exchange2_steps,
            )

            self._exchange2_steps = build_exchange2_steps(
                self.mesh, self.host_topology, self.agg, valued=False)

    def _shard_index_grew(self, new_capacity: int) -> None:
        """Uniform-SPMD grow: widen [P, capacity] arrays to the largest
        shard index (same contract as MeshWindowEngine)."""
        if new_capacity <= self.capacity:
            return
        self._reserve_rows(self.P * (new_capacity - self.capacity))
        old = self.capacity
        self.capacity = new_capacity
        grown = []
        accs_host = jax.device_get(list(self.accs))  # ONE batched D2H
        for host, leaf in zip(accs_host, self.agg.leaves):
            padded = np.full((self.P, new_capacity), leaf.identity,
                             dtype=leaf.dtype)
            padded[:, :old] = host
            grown.append(jax.device_put(jnp.asarray(padded),
                                        self._sharding))
        self.accs = tuple(grown)
        dirty = np.zeros((self.P, new_capacity), dtype=bool)
        dirty[:, :old] = self._dirty
        self._dirty = dirty
        if self._paged:
            self._paged_grow(new_capacity)

    def _put_sharded(self, host_block: np.ndarray) -> jnp.ndarray:
        return jax.device_put(host_block, self._sharding)

    # ---------------------------------------------------------------- ingest

    def process_batch(self, batch: RecordBatch) -> None:
        if len(batch) == 0:
            return
        with self._flight_ingest():
            self._process_batch_inner(batch)

    def _process_batch_inner(self, batch: RecordBatch) -> None:
        n = len(batch)
        # batch boundary: the engine is consistent at a known source
        # position — the one point the watchdog may declare a shard dead
        self._wd_boundary()
        if self._paged:
            # page sweeps queued by fire-path extractions run HERE, on
            # the ingest step, so fires stay bounded deltas
            self._drain_deferred_sweeps()
        ts = np.asarray(batch.timestamps, dtype=np.int64)
        keys = np.asarray(batch.key_ids, dtype=np.int64)
        if self._spill_active and n > 1:
            # bound one batch's PER-SHARD session working set by the
            # budget (the budget is per device): unique keys per shard
            # upper-bounds the touched sessions there; halving is safe
            # because absorb_batch is incremental. The bound used to
            # compare GLOBAL uniques against the per-shard budget,
            # splitting every batch whose key cardinality exceeded one
            # shard's slots even though each shard only sees ~1/P of
            # them — at the 10M-key bench shape that halved every batch
            # and doubled the per-batch host fixed costs (absorb, slot
            # resolution, dispatch).
            budget = max(self.max_device_slots // 2, 1024)
            if n > budget:
                # cheapest sufficient bound first: per-shard RECORD
                # counts dominate per-shard uniques (one hash pass, no
                # sort); only a shard actually over the record bound
                # pays the np.unique refinement
                rsm = getattr(self.meta, "rec_shard_max", None)
                if self._assignment is not None:
                    # the native sweep hard-codes the contiguous
                    # group->shard formula — a live rebalanced table
                    # must take the numpy path
                    rsm = None
                if rsm is not None:
                    rec_max = rsm(keys, self.P, self.max_parallelism,
                                  self.key_group_range)
                else:
                    rec_max = int(np.bincount(
                        self._route(keys),
                        minlength=self.P).max())
                if rec_max > budget:
                    uniq = np.unique(keys)
                    per_shard = np.bincount(
                        self._route(uniq),
                        minlength=self.P)
                    if int(per_shard.max()) > budget:
                        half = np.zeros(n, dtype=bool)
                        half[: n // 2] = True
                        # split ingest stays ONE failover boundary (the
                        # probe must not land between the halves)
                        self._ingest_subbatch(batch.filter(half))
                        self._ingest_subbatch(batch.filter(~half))
                        return

        from flink_tpu.windowing.session_meta import NativePlaneError

        with flight.span("prep.meta_sweep"):
            try:
                res = self.meta.absorb_batch_ex(keys, ts,
                                                want_fresh=self._paged)
            except NativePlaneError as e:
                # graceful degradation: the absorb is the batch's FIRST
                # mutation (no device state touched yet), so the batch
                # is re-runnable on the Python plane — once, loudly,
                # instead of crashing the job (interval extends are
                # idempotent, so the partially-swept metadata converges;
                # value scatter has not happened)
                self._meta_fallback(e)
                res = self.meta.absorb_batch_ex(keys, ts,
                                                want_fresh=self._paged)
        sess_key, sess_sid = res.sess_key, res.sess_sid
        rec_sess, groups = res.rec_sess, res.groups
        for g in groups:
            self._run_merge_group(g)

        live_sess = sess_sid >= 0
        if not live_sess.all():
            sess_counts = np.bincount(rec_sess, minlength=len(sess_key))
            self.meta.late_records_dropped += int(
                sess_counts[~live_sess].sum())

        # per-shard slot resolution for the live sessions: ONE stable
        # counting sort by shard replaces P boolean-mask scans — the
        # per-shard selections become contiguous slices of one index
        # array (within-shard session order unchanged: the sort is
        # stable over ascending session indices). The native metadata
        # plane runs the shard assignment + grouping + column gather as
        # one C sweep (sx_shard_group, same keygroups formula); the
        # Python plane takes the equivalent numpy path.
        m = len(sess_key)
        per_shard_sel = {}
        shard_slices = {}
        sg = getattr(self.meta, "shard_group", None)
        if self._assignment is not None:
            # sx_shard_group applies the contiguous formula in C; under
            # a rebalanced assignment the equivalent numpy path routes
            # through the table (meta.route_records below stays valid —
            # it consumes the sess_shard we hand it)
            sg = None
        if sg is not None:
            (sess_shard, counts, sorted_idx, key_sorted, sid_sorted,
             fresh_sorted, hint_sorted, row_sorted) = sg(
                res, self.P, self.max_parallelism, self.key_group_range)
            offs = np.concatenate(([0], np.cumsum(counts)))
            for p in np.nonzero(counts)[0].tolist():
                a, b = int(offs[p]), int(offs[p + 1])
                shard_slices[p] = (a, b)
                per_shard_sel[p] = sorted_idx[a:b]
        else:
            sess_shard = self._route(sess_key)
            live_idx = np.nonzero(live_sess)[0]
            sorted_idx = live_idx
            if len(live_idx):
                shards_live = sess_shard[live_idx]
                sorted_idx = live_idx[np.argsort(shards_live,
                                                 kind="stable")]
                counts = np.bincount(shards_live, minlength=self.P)
                offs = np.concatenate(([0], np.cumsum(counts)))
                for p in np.nonzero(counts)[0].tolist():
                    a, b = int(offs[p]), int(offs[p + 1])
                    shard_slices[p] = (a, b)
                    per_shard_sel[p] = sorted_idx[a:b]
            key_sorted = sess_key[sorted_idx]
            sid_sorted = sess_sid[sorted_idx]
            fresh_sorted = (None if res.fresh is None
                            else res.fresh[sorted_idx])
            hint_sorted = (None if res.slot_hint is None
                           else res.slot_hint[sorted_idx])
            row_sorted = (None if res.meta_row is None
                          else res.meta_row[sorted_idx])
        slot_of_sess = None
        if self._paged:
            # sessions CREATED by this absorb (res.fresh: allocated by
            # this absorb, minus merge destinations — see
            # SessionIntervalSet.absorb_batch_ex) cannot be resident or
            # paged: the resolve skips their index probe and page
            # query. Sessions carrying a FOLDED device slot from the
            # native metadata plane (res.slot_hint) skip the hash probe
            # after metadata verification — at high key cardinality the
            # state-plane hash is only probed for rows whose fold went
            # stale (eviction, restore, reshard). Per-shard columns are
            # gathered ONCE through the shard-sorted index and sliced
            # contiguously — no per-shard fancy indexing.
            resolved = self._resolve_slots_paged(
                {p: (key_sorted[a:b], sid_sorted[a:b])
                 for p, (a, b) in shard_slices.items()},
                fresh={p: fresh_sorted[a:b]
                       for p, (a, b) in shard_slices.items()},
                hints=(None if hint_sorted is None else
                       {p: hint_sorted[a:b]
                        for p, (a, b) in shard_slices.items()}))
            slot_sorted = np.zeros(len(sorted_idx), dtype=np.int32)
            for p, (a, b) in shard_slices.items():
                slot_sorted[a:b] = resolved[p]
                self._dirty[p, resolved[p]] = True
                self._rep_mark(p, resolved[p])
            # fold the resolved slots into the metadata rows so the
            # NEXT batch's resolve skips the probe (native plane only)
            self.meta.note_slots(key_sorted, sid_sorted, slot_sorted,
                                 rows=row_sorted)
        else:
            slot_of_sess = np.zeros(m, dtype=np.int32)
            if self._spill_active:
                touched = {p: np.unique(sess_sid[sel])
                           for p, sel in per_shard_sel.items()}
                self._ensure_resident(touched)
                for p, sids in touched.items():
                    self._touch(p, sids.tolist())
            for p, sel in per_shard_sel.items():
                self._reserve(p, sess_key[sel], sess_sid[sel])
                slots = self.indexes[p].lookup_or_insert(
                    sess_key[sel], sess_sid[sel])
                slot_of_sess[sel] = slots
                self._dirty[p, slots] = True
                self._rep_mark(p, slots)
            slot_sorted = slot_of_sess[sorted_idx]

        # route records: each record scatters into its session's slot on
        # its session's shard (stale records keep slot 0 = identity) —
        # one C pass on the native plane, numpy otherwise
        rt = getattr(self.meta, "route_records", None)
        if rt is not None:
            rec_slots, rec_shards = rt(rec_sess, m, sorted_idx,
                                       slot_sorted, sess_shard)
        else:
            if slot_of_sess is None:
                slot_of_sess = np.zeros(m, dtype=np.int32)
                slot_of_sess[sorted_idx] = slot_sorted
            rec_slots = slot_of_sess[rec_sess]
            rec_shards = sess_shard[rec_sess]
        if self._hot_keys:
            rec_slots, rec_shards = self._salt_hot_records(
                keys, ts, sess_key, sess_sid, rec_sess, rec_slots,
                rec_shards)
        values = self.agg.map_input(batch)
        in_leaves = self.agg.input_leaves
        # pipelining: claim a dispatch slot BEFORE rewriting the pooled
        # staging buffers (their previous consumer must have finished),
        # then stage batch k+1 while the device still runs batch k
        self._await_dispatch_slot()
        self._shuffle_pool.flip()
        columns = [np.asarray(rec_slots, dtype=np.int32),
                   *[np.asarray(v, dtype=l.dtype)
                     for v, l in zip(values, in_leaves)]]
        fills = [0, *[l.identity for l in in_leaves]]
        if self._two_level_active():  # implies device shuffle mode
            # pod mesh: the two-level ICI/DCN exchange (see
            # parallel/exchange2.py) — bit-identical to the flat
            # program, two dispatches so ICI vs DCN time attributes
            # as distinct span kinds
            from flink_tpu.parallel.exchange2 import (
                stage_two_level_exchange,
            )

            with flight.span("prep.stage"):
                dst, staged, w1, w2 = stage_two_level_exchange(
                    rec_shards, self.host_topology, columns=columns,
                    fills=fills, pool=self._shuffle_pool,
                    traffic=self._exchange2_traffic)
            s1, s2 = self._exchange2_steps
            with flight.span("exchange.stage1"), \
                    flight.span("device.dispatch"):
                put = jax.device_put((dst, *staged), self._sharding)
                inter = s1(put[0], put[1], tuple(put[2:]), w1)
            with flight.span("exchange.stage2"), \
                    flight.span("device.dispatch"):
                self.accs = s2(self.accs, inter[0], inter[1],
                               tuple(inter[2:]), w2)
            chaos.fault_point("shuffle.device_exchange", records=n)
        elif self.shuffle_mode == "device":
            with flight.span("prep.stage"):
                dst, staged, width = stage_device_exchange(
                    rec_shards, self.P, columns=columns, fills=fills,
                    pool=self._shuffle_pool)
            with flight.span("device.dispatch"):
                # ONE host->device hop: all flat columns in a single
                # device_put, then the fused exchange+scatter program
                put = jax.device_put((dst, *staged), self._sharding)
                self.accs = self._exchange_scatter_step(
                    self.accs, put[0], put[1], tuple(put[2:]), width)
            # "crash mid-batch after the fused dispatch" — the scatter
            # is on the device queue, the host dies before the fence
            chaos.fault_point("shuffle.device_exchange", records=n)
        else:
            with flight.span("prep.stage"):
                counts, blocked = bucket_by_shard(
                    rec_shards, self.P, columns=columns, fills=fills,
                    pool=self._shuffle_pool)
            slot_block = blocked[0]
            value_blocks = blocked[1:]
            with flight.span("device.dispatch"):
                self.accs = self._scatter_step(
                    self.accs,
                    self._put_sharded(slot_block),
                    tuple(self._put_sharded(v) for v in value_blocks),
                )
        self._push_dispatch_fence()

    def _run_merge_group(self, g: MergeGroup) -> None:
        gk = np.asarray(g.keys_dst, dtype=np.int64)
        ds = np.asarray(g.sids_dst, dtype=np.int64)
        ss = np.asarray(g.sids_src, dtype=np.int64)
        if self._hot_keys:
            gk, ds, ss = self._expand_hot_merges(gk, ds, ss)
        shards = self._route(gk)
        # combined dst+src pairs per shard (dst and src share the key,
        # hence the shard): with a spill tier, both sides must be
        # device-resident simultaneously for the merge kernel
        pairs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for p in range(self.P):
            sel = shards == p
            if sel.any():
                pairs[p] = (np.concatenate([gk[sel], gk[sel]]),
                            np.concatenate([ds[sel], ss[sel]]))
        resolved: Dict[int, np.ndarray] = {}
        if self._paged:
            resolved = self._resolve_slots_paged(pairs)
        else:
            if self._spill_active:
                touched = {p: np.unique(sids2)
                           for p, (_, sids2) in pairs.items()}
                self._ensure_resident(touched)
                for p, sids in touched.items():
                    self._touch(p, sids.tolist())
            for p, (keys2, sids2) in pairs.items():
                self._reserve(p, keys2, sids2)
                resolved[p] = self.indexes[p].lookup_or_insert(
                    keys2, sids2)
        m_max = 0
        per_shard: List[Tuple[np.ndarray, np.ndarray]] = []
        for p in range(self.P):
            if p not in pairs:
                per_shard.append((np.empty(0, np.int32),
                                  np.empty(0, np.int32)))
                continue
            both = resolved[p]
            c = len(both) // 2
            d_slots, s_slots = both[:c], both[c:]
            self._dirty[p, d_slots] = True
            self._rep_mark(p, d_slots)
            per_shard.append((d_slots.astype(np.int32),
                              s_slots.astype(np.int32)))
            m_max = max(m_max, c)
        if m_max == 0:
            return
        M = sticky_bucket(m_max, self._merge_bucket)
        self._merge_bucket = M
        dst_block = np.zeros((self.P, M), dtype=np.int32)
        src_block = np.zeros((self.P, M), dtype=np.int32)
        for p, (d_slots, s_slots) in enumerate(per_shard):
            dst_block[p, : len(d_slots)] = d_slots
            src_block[p, : len(s_slots)] = s_slots
        with flight.span("device.dispatch"):
            self.accs = self._merge_step(
                self.accs, self._put_sharded(dst_block),
                self._put_sharded(src_block))
        if self._paged:
            # fold the merge DESTINATIONS' resolved slots into their
            # metadata rows (native plane) — the dst sessions live on
            # and would otherwise pay a probe next batch
            fk, fs, fl = [], [], []
            for p, (d_slots, _) in enumerate(per_shard):
                if p not in pairs or not len(d_slots):
                    continue
                c = len(d_slots)
                pk2, ps2 = pairs[p][0][:c], pairs[p][1][:c]
                if self._hot_keys:
                    # salted sub-rows (negative sids) have no metadata
                    # row to fold a slot into
                    keep2 = ps2 >= 0
                    pk2, ps2 = pk2[keep2], ps2[keep2]
                    d_slots = d_slots[keep2]
                if len(pk2):
                    fk.append(pk2)
                    fs.append(ps2)
                    fl.append(d_slots)
            if fk:
                self.meta.note_slots(np.concatenate(fk),
                                     np.concatenate(fs),
                                     np.concatenate(fl))
        # absorbed host slots reusable now that the kernel moved the values;
        # record tombstones so delta snapshots drop the absorbed rows
        self._freed_ns.append(
            np.asarray(g.absorbed_sids, dtype=np.int64))
        if self._track_ns:
            self._drop_spilled(g.absorbed_sids)
            for p in range(self.P):
                self.indexes[p].free_namespaces(g.absorbed_sids)
        else:
            # registry-free: the absorbed rows' slots are in hand (the
            # src half of each shard's combined lookup)
            for p, (_, s_slots) in enumerate(per_shard):
                if p not in pairs:
                    continue
                src_sids = pairs[p][1][len(s_slots):]
                if self._paged:
                    self._free_rows_paged(p, s_slots, src_sids)
                else:
                    self.indexes[p].free_slots(s_slots)
                    self._dirty[p, s_slots] = False

    # ---------------------------------------------------- hot-key splitting

    def register_hot_key(self, key_id: int, salts: int = 8,
                         allow_inexact: bool = False) -> int:
        """Two-stage aggregation for one dominating key: salt its
        records into ``salts`` sub-keys, pre-aggregated on their OWN
        shards as ordinary (salted-key, negative-namespace) rows, and
        folded back into the main row's result at fire / query time in
        a fixed order (main row, then salts ascending — the same fold
        discipline the exchange applies within a shard).

        Exactness: min/max and integer sums commute freely, so salting
        is bit-identical to the unsalted oracle. Floating-point sums
        reassociate; pass ``allow_inexact=True`` to accept that —
        streams whose values are integer-valued floats (e.g. counters
        held in float32, exact below 2**24) remain bit-identical in
        practice. Requires the paged spill layout (the split rows ride
        the registry-free slot machinery). Returns the clamped salt
        count actually applied."""
        if not self._paged:
            raise ValueError(
                "hot-key splitting requires the paged spill layout "
                "(spill_layout='pages' with max_device_slots > 0)")
        salts = max(2, min(int(salts), MAX_SALTS))
        exact = all(
            l.reduce in ("min", "max") or np.dtype(l.dtype).kind in "iub"
            for l in self.agg.leaves)
        if not exact and not allow_inexact:
            raise ValueError(
                "splitting a float sum reassociates the fold; pass "
                "allow_inexact=True if the stream tolerates it (exact "
                "for integer-valued floats below the mantissa limit)")
        self._hot_keys[int(key_id)] = salts
        # the serving shadow must re-route the split key through the
        # live combined fold (one lookup answers main + salts)
        self._rep_rebuild = True
        return salts

    def hot_key_stats(self) -> Dict[str, object]:
        return {
            "keys": dict(self._hot_keys),
            "salted_records": int(self._hot_salted_records),
            "salted_fires": int(self._hot_salted_fires),
        }

    def _hot_key_array(self) -> np.ndarray:
        return np.fromiter(self._hot_keys, dtype=np.int64,
                           count=len(self._hot_keys))

    def _salt_hot_records(self, keys, ts, sess_key, sess_sid,
                          rec_sess, rec_slots, rec_shards):
        """Ingest diversion: re-point hot keys' records at their salted
        sub-rows. The salt is derived from the record TIMESTAMP
        (splitmix64 mod n_salts) so a replay salts identically — no
        RNG, no per-batch state."""
        hot = self._hot_key_array()
        hot_sess = np.isin(sess_key, hot) & (sess_sid >= 0)
        ridx = np.nonzero(hot_sess[rec_sess])[0]
        if not len(ridx):
            return rec_slots, rec_shards
        rk = keys[ridx]
        rs = sess_sid[rec_sess[ridx]]
        nsalts = np.zeros(len(ridx), dtype=np.uint64)
        for hk, hv in self._hot_keys.items():
            nsalts[rk == hk] = np.uint64(hv)
        salt = (_splitmix64(ts[ridx].astype(np.uint64))
                % nsalts).astype(np.int64)
        skey = _salted_keys(rk, salt)
        sns = _salted_ns(rs, salt)
        # sids are globally unique, so the salted namespace alone
        # identifies the (session, salt) pair: resolve each unique
        # sub-row once, scatter the slot to every diverted record
        uns, inv = np.unique(sns, return_inverse=True)
        first_pos = np.zeros(len(uns), dtype=np.int64)
        first_pos[inv[::-1]] = np.arange(len(sns) - 1, -1, -1)
        ukey = skey[first_pos]
        shards_u = self._route(ukey)
        per = {}
        for p in np.unique(shards_u).tolist():
            selp = np.nonzero(shards_u == p)[0]
            per[p] = (ukey[selp], uns[selp])
        resolved = self._resolve_slots_paged(per)
        slots_u = np.zeros(len(uns), dtype=np.int32)
        for p in per:
            selp = np.nonzero(shards_u == p)[0]
            slots_u[selp] = resolved[p]
            self._dirty[p, resolved[p]] = True
        if not rec_slots.flags.writeable:
            rec_slots = rec_slots.copy()
        if not rec_shards.flags.writeable:
            rec_shards = rec_shards.copy()
        rec_slots[ridx] = slots_u[inv]
        rec_shards[ridx] = shards_u[inv]
        self._hot_salted_records += len(ridx)
        return rec_slots, rec_shards

    def _expand_hot_merges(self, gk, ds, ss):
        """Session merges of a split key carry their salted sub-rows
        along: (skey(k,t), ssid(src,t)) folds into (skey(k,t),
        ssid(dst,t)) — same salted key, hence the same shard, so the
        merge kernel's no-cross-shard invariant holds. Missing sub-rows
        resolve to identity (a no-op merge)."""
        sel = np.nonzero(np.isin(gk, self._hot_key_array()))[0]
        if not len(sel):
            return gk, ds, ss
        ek, ed, es = [gk], [ds], [ss]
        freed = []
        for i in sel.tolist():
            k = int(gk[i])
            n = self._hot_keys[k]
            salts = np.arange(n, dtype=np.int64)
            kk = np.full(n, k, dtype=np.int64)
            ek.append(_salted_keys(kk, salts))
            ed.append(_salted_ns(np.full(n, int(ds[i]),
                                         dtype=np.int64), salts))
            sns = _salted_ns(np.full(n, int(ss[i]),
                                     dtype=np.int64), salts)
            es.append(sns)
            freed.append(sns)
        # absorbed sub-rows die with their session: tombstones so delta
        # snapshots drop them (mirrors g.absorbed_sids for main rows)
        self._freed_ns.append(np.concatenate(freed))
        return (np.concatenate(ek), np.concatenate(ed),
                np.concatenate(es))

    def _fire_hot_fold(self, hk, hs) -> List[np.ndarray]:
        """RAW folded leaves for hot fired sessions. The device delta
        fire FINISHES on device (nonlinear), so a split session cannot
        fire there — its sub-rows must fold BEFORE the finish. Resident
        physical rows come back through one gather + one reset (slots
        return to identity before reuse); paged rows extract from the
        page tier (tombstoning them); absent rows are identity. The
        fold runs per leaf with the exchange's combine op in array
        order: main row first, then salts ascending."""
        from flink_tpu.ops.segment_ops import HOST_COMBINE
        from flink_tpu.state.paged_spill import (
            reload_rows_for,
            sorted_match,
        )

        leaves = self.agg.leaves
        leaf_dtypes = [l.dtype for l in leaves]
        nh = len(hk)
        pks, pns, gids = [], [], []
        for i in range(nh):
            k, s = int(hk[i]), int(hs[i])
            n = self._hot_keys[k]
            salts = np.arange(n, dtype=np.int64)
            pks.append(np.concatenate((
                np.asarray([k], dtype=np.int64),
                _salted_keys(np.full(n, k, dtype=np.int64), salts))))
            pns.append(np.concatenate((
                np.asarray([s], dtype=np.int64),
                _salted_ns(np.full(n, s, dtype=np.int64), salts))))
            gids.append(np.full(n + 1, i, dtype=np.int64))
        pk = np.concatenate(pks)
        pn = np.concatenate(pns)
        gid = np.concatenate(gids)
        vals = [np.full(len(pk), l.identity, dtype=l.dtype)
                for l in leaves]
        shards = self._route(pk)
        lanes: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        g_max = 0
        for p in range(self.P):
            selp = np.nonzero(shards == p)[0]
            if not len(selp):
                continue
            idx = self.indexes[p]
            ks, ns = pk[selp], pn[selp]
            slots = idx.lookup(ks, ns)
            hit = slots >= 0
            if hit.any():
                rslots = slots[hit].astype(np.int32)
                lanes[p] = (selp[hit], rslots)
                g_max = max(g_max, len(rslots))
                idx.free_slots(rslots, keys=ks[hit], nss=ns[hit])
                self._dirty[p, rslots] = False
            miss = ~hit
            if miss.any() and len(self._pmaps[p]):
                rl = reload_rows_for(self.spills[p], self._pmaps[p],
                                     ns[miss], leaf_dtypes)
                if rl is not None:
                    _, rns, _, rvals = rl
                    ro = np.argsort(rns)
                    found, pos = sorted_match(rns[ro], ns[miss])
                    src = ro[pos[found]]
                    dstp = selp[miss][found]
                    for i in range(len(leaves)):
                        vals[i][dstp] = rvals[i][src]
        if g_max:
            G = pad_bucket_size(g_max, minimum=64)
            block = np.zeros((self.P, G), dtype=np.int32)
            for p, (_, rslots) in lanes.items():
                block[p, : len(rslots)] = rslots
            gathered = self._gather_step(self.accs,
                                         self._put_sharded(block))
            g_host = self._harvest_get(list(gathered), "hot_fire")
            # freed slots must hold identity before reuse (padded
            # lanes target reserved slot 0: harmless)
            self.accs = self._reset_step(self.accs,
                                         self._put_sharded(block))
            for p, (selp_hit, rslots) in lanes.items():
                for i in range(len(leaves)):
                    vals[i][selp_hit] = g_host[i][p][: len(rslots)]
        # salted namespaces die with the fire: delta tombstones
        self._freed_ns.append(pn[pn < 0])
        out = [np.full(nh, l.identity, dtype=l.dtype) for l in leaves]
        for i, l in enumerate(leaves):
            # np.ufunc.at is unbuffered: repeated gids fold in ARRAY
            # order — main first, salts ascending (the documented order)
            HOST_COMBINE[l.reduce].at(out[i], gid, vals[i])
        return out

    def _expand_hot_query(self, keys_r, sids):
        """Physical-row expansion for point lookups: every logical row
        of a split key reads main + all salted sub-rows, folded back by
        ``gid`` (index into the logical rows)."""
        pk: List[int] = []
        pn: List[int] = []
        gid: List[int] = []
        for j in range(len(keys_r)):
            k, s = int(keys_r[j]), int(sids[j])
            pk.append(k)
            pn.append(s)
            gid.append(j)
            n = self._hot_keys.get(k)
            if n and s >= 0:
                salts = np.arange(n, dtype=np.int64)
                pk.extend(_salted_keys(
                    np.full(n, k, dtype=np.int64), salts).tolist())
                pn.extend(_salted_ns(
                    np.full(n, s, dtype=np.int64), salts).tolist())
                gid.extend([j] * n)
        return (np.asarray(pk, dtype=np.int64),
                np.asarray(pn, dtype=np.int64),
                np.asarray(gid, dtype=np.int64))

    def _rep_publish_split(self, p, keys, nss):
        """Serving-plane filter: salted sub-rows never publish (their
        partials are meaningless alone); a hot key's MAIN rows publish
        as COLD entries so replica lookups route through the live
        engine's combined fold — a split key still answers ONE lookup."""
        if not self._hot_keys:
            return None
        nss = np.asarray(nss, dtype=np.int64)
        drop = nss < 0
        coldm = np.isin(np.asarray(keys, dtype=np.int64),
                        self._hot_key_array()) & ~drop
        return drop, coldm

    # ------------------------------------------------------------------ fire

    #: fires may be dispatched async (on_watermark(async_ok=True)
    #: returns PendingFire handles) — the pipelined driver overlaps the
    #: device fire + D2H copy with the next batches' host bucketing and
    #: harvests coalesced, in dispatch order (no reordering)
    supports_async_fires = True

    def on_watermark(self, watermark: int,
                     async_ok: bool = False) -> List[RecordBatch]:
        self._wd_boundary()
        with flight.fire_span(watermark):
            out = self._on_watermark_inner(watermark, async_ok)
        # replica publish AFTER this boundary's fires/frees (outside
        # the fire span — serving-plane work, budgeted under its own
        # serving.replica_publish span)
        self._publish_replica(watermark)
        return out

    # -------------------------------------------------- replica hooks

    def _rep_extra(self, p: int, keys: np.ndarray, nss: np.ndarray):
        """The session END per published (key, sid) row — the result
        key of the serving composition ({session_end -> columns}).
        One interval-list scan per KEY (not per row): this runs on the
        task thread inside the boundary publish, where the fire-
        deadline budget lives."""
        out = np.zeros(len(keys), dtype=np.int64)
        sessions = self.meta.sessions
        by_key: Dict[int, List[int]] = {}
        for j in range(len(keys)):
            by_key.setdefault(int(keys[j]), []).append(j)
        for key, idxs in by_key.items():
            ivs = sessions.get(key, ())
            if not ivs:
                continue
            end_of = {int(iv[2]): int(iv[1]) for iv in ivs}
            for j in idxs:
                out[j] = end_of.get(int(nss[j]), 0)
        return out

    def _rep_probe_cold(self, p: int, keys: np.ndarray,
                        nss: np.ndarray) -> np.ndarray:
        """A session that left the resident set is COLD iff its sid is
        still mapped in the shard's page tier (paged layout) or its
        namespace is spilled (registry layout); otherwise it fired/
        merged away and the index entry drops."""
        if self._paged:
            return self._pmaps[p].spilled_mask(
                np.asarray(nss, dtype=np.int64))
        return super()._rep_probe_cold(p, keys, nss)

    def _on_watermark_inner(self, watermark: int,
                            async_ok: bool = False) -> List[RecordBatch]:
        pop = self.meta.pop_fired_ex(watermark)
        keys, starts, ends, sids = pop.keys, pop.starts, pop.ends, pop.sids
        hint = pop.slot_hint
        if not len(keys):
            return []
        if self._spill_active:
            # a catch-up fire can exceed the device budget; chunking keeps
            # each fire's working set under it — fired slots free
            # immediately, so chunks reuse the space. The hybrid (paged)
            # fire touches the device only for already-RESIDENT rows, so
            # its device working set is bounded by the table itself and
            # the chunk merely bounds host-side assembly — chunking
            # per half-budget there would re-read the same pages once
            # per chunk for nothing.
            chunk = max(self.max_device_slots // 2, 1024)
            if self._paged:
                chunk = max(chunk, 1 << 20)
            if len(keys) > chunk:
                out: List[RecordBatch] = []
                for a in range(0, len(keys), chunk):
                    out.extend(self._fire_sessions(
                        keys[a:a + chunk], starts[a:a + chunk],
                        ends[a:a + chunk], sids[a:a + chunk],
                        async_ok=async_ok,
                        slot_hint=(None if hint is None
                                   else hint[a:a + chunk])))
                return out
        return self._fire_sessions(keys, starts, ends, sids,
                                   async_ok=async_ok, slot_hint=hint)

    def _fire_sessions(self, keys, starts, ends, sids,
                       async_ok: bool = False,
                       slot_hint=None) -> List[RecordBatch]:
        chaos.fault_point("mesh.session_fire", sessions=len(keys))
        k_arr = np.asarray(keys, dtype=np.int64)
        sid_arr = np.asarray(sids, dtype=np.int64)
        shards = self._route(k_arr)
        per_shard_sel: List[np.ndarray] = [
            np.nonzero(shards == p)[0] for p in range(self.P)]
        if self._paged:
            return self._fire_sessions_hybrid(
                k_arr, np.asarray(starts, dtype=np.int64),
                np.asarray(ends, dtype=np.int64), sid_arr,
                per_shard_sel, async_ok, slot_hint)
        resolved: Dict[int, np.ndarray] = {}
        if self._spill_active:
            touched = {p: np.unique(sid_arr[sel])
                       for p, sel in enumerate(per_shard_sel)
                       if len(sel)}
            self._ensure_resident(touched)
            for p in touched:
                sel = per_shard_sel[p]
                self._reserve(p, k_arr[sel], sid_arr[sel])
        for p, sel in enumerate(per_shard_sel):
            if len(sel):
                resolved[p] = self.indexes[p].lookup_or_insert(
                    k_arr[sel], sid_arr[sel])
        w_max = 0
        per_shard_slots: List[np.ndarray] = []
        for p, sel in enumerate(per_shard_sel):
            if len(sel) == 0:
                per_shard_slots.append(np.empty(0, np.int32))
                continue
            per_shard_slots.append(resolved[p].astype(np.int32))
            w_max = max(w_max, len(sel))
        W = sticky_bucket(w_max, self._fire_bucket, minimum=64)
        self._fire_bucket = W
        sm = np.zeros((self.P, W, 1), dtype=np.int32)
        rb = np.zeros((self.P, W), dtype=np.int32)
        for p, slots in enumerate(per_shard_slots):
            sm[p, : len(slots), 0] = slots
            rb[p, : len(slots)] = slots
        # delta harvest: fire + reset of exactly the closing sessions'
        # slots in ONE fused program (build_delta_fire_step); the fire
        # outputs are fresh buffers, so a deferred (async) host read
        # never races the donated reset
        self.accs, fire_out = self._delta_fire_step(
            self.accs, self._put_sharded(sm), self._put_sharded(rb))
        # free the fired slots' index entries (host bookkeeping)
        self._freed_ns.append(sid_arr)
        for p, slots in enumerate(per_shard_slots):
            if len(slots):
                self._dirty[p, slots] = False
            if self._track_ns:
                self.indexes[p].free_namespaces(
                    [int(sid_arr[i]) for i in per_shard_sel[p]])
            elif len(slots):
                # registry-free: slot-addressed free (the fire resolved
                # the rows, so no registry walk is needed)
                if self._paged:
                    self._free_rows_paged(p, slots,
                                          sid_arr[per_shard_sel[p]])
                else:
                    self.indexes[p].free_slots(slots)
        # assemble the output batch in shard order
        st_arr = np.asarray(starts, dtype=np.int64)
        en_arr = np.asarray(ends, dtype=np.int64)
        out_idx = np.concatenate([s for s in per_shard_sel if len(s)])
        cols = {
            KEY_ID_FIELD: k_arr[out_idx],
            WINDOW_START_FIELD: st_arr[out_idx],
            WINDOW_END_FIELD: en_arr[out_idx],
            TIMESTAMP_FIELD: en_arr[out_idx] - 1,
        }
        per_shard_counts = [len(s) for s in per_shard_sel]
        names = sorted(fire_out.keys())

        def build(host: List[np.ndarray]) -> RecordBatch:
            full = dict(cols)
            for name, arr in zip(names, host):
                chunks = [arr[p][:m]
                          for p, m in enumerate(per_shard_counts) if m]
                full[name] = np.concatenate(chunks)
            return RecordBatch(full)

        if async_ok:
            from flink_tpu.runtime.pending import PendingFire

            return [PendingFire([fire_out[n] for n in names], build,
                                watchdog=self._watchdog)]
        # sync path still batches all columns into ONE device_get
        return [build(self._harvest_get(
            [fire_out[n] for n in names]))]

    def _fire_sessions_hybrid(self, k_arr, st_arr, en_arr, sid_arr,
                              per_shard_sel, async_ok: bool,
                              slot_hint=None) -> List[RecordBatch]:
        """Paged-layout fire: RESIDENT sessions merge+finish on device
        (one fire kernel over the whole mesh), COLD sessions fire
        straight from page storage — their accumulators are already on
        the host, and a fired session frees immediately, so reloading
        it into the device table (the old path) bought nothing and cost
        everything: at the thrashing benchmark shape ~90% of fires were
        cold, and every reload evicted resident rows that later fired
        cold themselves (reload->evict churn: rows_evicted tracked
        rows_reloaded 1:1). Extraction tombstones the page rows (see
        paged_spill.reload_rows_for) — no device traffic at all."""
        from flink_tpu.state.paged_spill import (
            reload_rows_for,
            sorted_match,
        )

        leaves = self.agg.leaves
        n = len(k_arr)
        self._freed_ns.append(sid_arr)
        leaf_dtypes = [l.dtype for l in leaves]
        # hot (split) sessions cannot finish on device — fold their
        # physical rows on the host first and route the folded values
        # through the cold host-finish below. The ORIGINAL per-shard
        # selection keeps the output ordering; the loop skips hot rows.
        per_shard_out = per_shard_sel
        hot_pos = None
        hot_vals = None
        if self._hot_keys:
            hmask = np.isin(k_arr, self._hot_key_array())
            if hmask.any():
                hot_pos = np.nonzero(hmask)[0]
                hot_vals = self._fire_hot_fold(k_arr[hot_pos],
                                               sid_arr[hot_pos])
                self._hot_salted_fires += len(hot_pos)
                per_shard_sel = [s[~hmask[s]] for s in per_shard_sel]
        res_pos: List[np.ndarray] = []   # positions fired on device
        res_slots: List[np.ndarray] = []
        cold_chunks: List[np.ndarray] = []  # positions fired from pages
        cold_vals: List[List[np.ndarray]] = [[] for _ in leaves]
        w_max = 0
        for p, sel in enumerate(per_shard_sel):
            if len(sel) == 0:
                res_pos.append(np.empty(0, dtype=np.int64))
                res_slots.append(np.empty(0, dtype=np.int32))
                continue
            # per-shard attribution: this shard's fire-path host work
            # (slot resolve + cold page extraction) lands on its own
            # Perfetto track — "shard 3 is slow" reads off the trace
            _t_shard = time.perf_counter()
            idx = self.indexes[p]
            ks, ss = k_arr[sel], sid_arr[sel]
            if slot_hint is not None:
                # the pop carried each fired session's FOLDED device
                # slot out of its metadata row — verified folds replace
                # the per-fire hash probe; only stale folds (evicted
                # since the fold) pay the read-only lookup
                slots = resolve_slot_hints(idx, ks, ss, slot_hint[sel])
            else:
                slots = idx.lookup(ks, ss)  # read-only: no insert/evict
            hit = slots >= 0
            rslots = slots[hit].astype(np.int32)
            res_pos.append(sel[hit])
            res_slots.append(rslots)
            w_max = max(w_max, len(rslots))
            cold = ~hit
            if cold.any():
                cpos = sel[cold]
                # identity where no state exists (matching the old
                # path's fire of a freshly-inserted identity row)
                vals_p = [np.full(len(cpos), l.identity, dtype=l.dtype)
                          for l in leaves]
                rl = reload_rows_for(self.spills[p], self._pmaps[p],
                                     ss[cold], leaf_dtypes) \
                    if len(self._pmaps[p]) else None
                if rl is not None:
                    _, rns, _, rvals = rl
                    # align extracted rows (unordered) to their fired
                    # positions; sids are unique, misses keep identity
                    order = np.argsort(rns)
                    found, pos = sorted_match(rns[order], ss[cold])
                    src = order[pos[found]]
                    for i in range(len(leaves)):
                        vals_p[i][found] = rvals[i][src]
                cold_chunks.append(cpos)
                for i in range(len(leaves)):
                    cold_vals[i].append(vals_p[i])
            # slot-addressed free of the resident fired rows (their
            # cold siblings were unmapped by the extraction above); the
            # pair columns are in hand from the pop, so the free skips
            # the per-slot metadata gathers
            if len(rslots):
                idx.free_slots(rslots, keys=ks[hit], nss=ss[hit])
                self._dirty[p, rslots] = False
            flight.instant("fire.shard", shard=p,
                           duration_s=time.perf_counter() - _t_shard)
        # device part: fire + reset over resident rows only, fused into
        # ONE delta-harvest program (the fire outputs are fresh buffers,
        # so async reads never race the donated reset)
        fire_out = None
        if w_max:
            W = sticky_bucket(w_max, self._fire_bucket, minimum=64)
            self._fire_bucket = W
            sm = np.zeros((self.P, W, 1), dtype=np.int32)
            rb = np.zeros((self.P, W), dtype=np.int32)
            for p, rslots in enumerate(res_slots):
                m = len(rslots)
                sm[p, :m, 0] = rslots
                rb[p, :m] = rslots
            self.accs, fire_out = self._delta_fire_step(
                self.accs, self._put_sharded(sm), self._put_sharded(rb))
        # host finish over the COLD positions only (the resident
        # majority's finish already ran inside the device fire kernel)
        names = sorted(self.agg.output_names)
        if hot_pos is not None:
            # folded hot sessions finish with the cold rows (identical
            # host finish; their values scatter back by position)
            cold_chunks.append(hot_pos)
            for i in range(len(leaves)):
                cold_vals[i].append(hot_vals[i])
        if cold_chunks:
            cold_pos = np.concatenate(cold_chunks)
            finished = self.agg.finish(tuple(
                np.concatenate(c) for c in cold_vals))
            cold_out = {name: np.asarray(col)
                        for name, col in finished.items()}
        else:
            cold_pos = None
            cold_out = {}
        out_idx = np.concatenate([s for s in per_shard_out if len(s)])
        cols = {
            KEY_ID_FIELD: k_arr[out_idx],
            WINDOW_START_FIELD: st_arr[out_idx],
            WINDOW_END_FIELD: en_arr[out_idx],
            TIMESTAMP_FIELD: en_arr[out_idx] - 1,
        }

        def build(host: List[np.ndarray]) -> RecordBatch:
            full = dict(cols)
            for i, name in enumerate(names):
                if cold_pos is not None:
                    vals = np.empty(n, dtype=cold_out[name].dtype)
                    vals[cold_pos] = cold_out[name]
                else:
                    vals = np.empty(n, dtype=host[i].dtype)
                if host:
                    arr = host[i]
                    for p, rpos in enumerate(res_pos):
                        m = len(rpos)
                        if m:
                            vals[rpos] = arr[p][:m]
                full[name] = vals[out_idx]
            return RecordBatch(full)

        arrays = [fire_out[nm] for nm in names] if fire_out else []
        if async_ok:
            from flink_tpu.runtime.pending import PendingFire

            return [PendingFire(arrays, build,
                                watchdog=self._watchdog)]
        # sync path still batches all columns into ONE device_get
        return [build(self._harvest_get(arrays))]

    # ---------------------------------------------------------- point query

    def query_sessions(self, key_id: int) -> Dict[int, Dict[str, float]]:
        """{session_end -> result columns} for a key's live sessions —
        a batch of one (all reads route through :meth:`query_batch`)."""
        return self.query_batch(
            np.asarray([key_id], dtype=np.int64))[0]

    def query_batch(self, key_ids) -> List[Dict[int, Dict[str, float]]]:
        """Batched point lookup: one ``{session_end -> result columns}``
        dict per requested key, request order. The keys' live sessions
        come from the global host metadata; ALL resident accumulators of
        the batch come back through ONE gather program + ONE batched
        device read (the serving-plane cost model — a per-key fire paid
        one dispatch + one D2H per request), cold sessions answer from
        their shards' page tiers. Read-only — no residency change."""
        key_ids = np.asarray(key_ids, dtype=np.int64)
        n = len(key_ids)
        results: List[Dict[int, Dict[str, float]]] = [
            {} for _ in range(n)]
        if n == 0:
            return results
        rows: List[Tuple[int, int, int]] = []  # (request row, sid, end)
        for r in range(n):
            for iv in self.meta.sessions.get(int(key_ids[r]), ()):
                rows.append((r, int(iv[2]), int(iv[1])))
        if not rows:
            return results
        m = len(rows)
        rr = np.asarray([t[0] for t in rows], dtype=np.int64)
        sids = np.asarray([t[1] for t in rows], dtype=np.int64)
        keys_r = key_ids[rr]
        if self._hot_keys:
            # split keys read main + all salted sub-rows; gid folds the
            # physical rows back to their logical row below
            pk, pn, gid = self._expand_hot_query(keys_r, sids)
        else:
            pk, pn, gid = keys_r, sids, None
        mp = len(pk)
        shards = self._route(pk)
        leaves = self.agg.leaves
        leaf_rows = [np.full(mp, l.identity, dtype=l.dtype)
                     for l in leaves]
        have = np.zeros(mp, dtype=bool)
        lanes: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        g_max = 0
        cold: Dict[int, np.ndarray] = {}
        for p in range(self.P):
            sel = np.nonzero(shards == p)[0]
            if not len(sel):
                continue
            slots = self.indexes[p].lookup(pk[sel], pn[sel])
            hit = slots >= 0
            if hit.any():
                lanes[p] = (sel[hit], slots[hit].astype(np.int32))
                g_max = max(g_max, int(hit.sum()))
            if (~hit).any() and self._spill_active:
                cold[p] = sel[~hit]
        if g_max:
            G = pad_bucket_size(g_max, minimum=64)
            block = np.zeros((self.P, G), dtype=np.int32)
            for p, (_, hs) in lanes.items():
                block[p, : len(hs)] = hs
            gathered = self._gather_step(self.accs,
                                         self._put_sharded(block))
            # ONE batched D2H
            g_host = self._harvest_get(gathered, "serving_lookup")
            for p, (sel_hit, hs) in lanes.items():
                for i in range(len(leaves)):
                    leaf_rows[i][sel_hit] = g_host[i][p][: len(hs)]
                have[sel_hit] = True
        # cold sessions: read their rows out of the page tier (host-only,
        # one peek per touched page — see read_spilled_rows)
        from flink_tpu.state.paged_spill import read_spilled_rows

        def _take_row(j, entry, src):
            for i, l in enumerate(leaves):
                leaf_rows[i][j] = np.asarray(
                    entry[f"leaf_{i}"], dtype=l.dtype)[src]
            have[j] = True

        for p, sel_cold in cold.items():
            read_spilled_rows(
                self.spills[p],
                self._pmaps[p] if self._paged else None, self._paged,
                [(j, int(pk[j]), int(pn[j]))
                 for j in sel_cold.tolist()],
                _take_row)
        if gid is not None:
            from flink_tpu.ops.segment_ops import HOST_COMBINE

            # fold physical rows into their logical row — array order
            # is main first, salts ascending (the documented order);
            # not-found rows hold identity and fold as no-ops
            folded = [np.full(m, l.identity, dtype=l.dtype)
                      for l in leaves]
            for i, l in enumerate(leaves):
                HOST_COMBINE[l.reduce].at(folded[i], gid, leaf_rows[i])
            hv = np.zeros(m, dtype=bool)
            np.logical_or.at(hv, gid, have)
            leaf_rows, have = folded, hv
        # one host finish over every found row at once
        finished = self.agg.finish(tuple(leaf_rows))
        cols = {name: np.asarray(col) for name, col in finished.items()}
        for j, (r, _sid, end) in enumerate(rows):
            if have[j]:
                results[r][end] = {name: col[j].item()
                                   for name, col in cols.items()}
        return results

    # -------------------------------------------------------------- snapshot

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        """Same logical format as SessionWindower.snapshot — restorable
        across engines and mesh sizes (re-sharded by key group)."""
        if mode == "delta":
            out = {"table": self._snapshot_delta(),
                   **self.meta.snapshot()}
            if self._hot_keys:
                out["hot_keys"] = {int(k): int(v)
                                   for k, v in self._hot_keys.items()}
            return out
        accs_host = jax.device_get(list(self.accs))  # ONE batched D2H
        parts = []
        for p in range(self.P):
            idx = self.indexes[p]
            used = idx.used_slots()
            key_ids = idx.slot_key[used]
            parts.append({
                "key_id": key_ids,
                "namespace": idx.slot_ns[used],
                "key_group": assign_key_groups(key_ids,
                                               self.max_parallelism),
                **{f"leaf_{i}": accs_host[i][p][used]
                   for i in range(len(self.accs))},
            })
        # spilled sessions are part of the logical state
        parts.extend(self._spill_snapshot_parts())
        merged = {
            k: np.concatenate([pt[k] for pt in parts]) for k in parts[0]
        } if parts else {}
        if mode != "savepoint":
            self._dirty[:] = False
            self._freed_ns.clear()
            for sp in self.spills:
                sp.clear_dirty()
        out = {"table": merged, **self.meta.snapshot()}
        if self._hot_keys:
            # the salted rows above are physical state; the registry
            # travels with them so a restore folds them correctly
            out["hot_keys"] = {int(k): int(v)
                               for k, v in self._hot_keys.items()}
        return out

    def _snapshot_delta(self) -> Dict[str, np.ndarray]:
        """Dirty rows + freed-session tombstones (same format as
        SlotTable.snapshot_delta / MeshWindowEngine._snapshot_delta)."""
        per_shard = []
        g_max = 0
        for p in range(self.P):
            used = self.indexes[p].slot_used
            dirty = np.nonzero(self._dirty[p][:len(used)]
                               & used)[0].astype(np.int32)
            per_shard.append(dirty)
            g_max = max(g_max, len(dirty))
        freed = (np.unique(np.concatenate(self._freed_ns))
                 if self._freed_ns else np.empty(0, dtype=np.int64))
        if g_max == 0:
            out = {
                "__delta__": np.asarray(True),
                "key_id": np.empty(0, dtype=np.int64),
                "namespace": np.empty(0, dtype=np.int64),
                "key_group": np.empty(0, dtype=np.int32),
                "freed_namespaces": freed,
                **{f"leaf_{i}": np.empty(0, dtype=l.dtype)
                   for i, l in enumerate(self.agg.leaves)},
            }
        else:
            G = sticky_bucket(g_max, self._gather_bucket)
            self._gather_bucket = G
            block = np.zeros((self.P, G), dtype=np.int32)
            for p, dirty in enumerate(per_shard):
                block[p, :len(dirty)] = dirty
            gathered = self._gather_step(self.accs,
                                         self._put_sharded(block))
            leaves_host = jax.device_get(list(gathered))  # ONE batched D2H
            key_cols, ns_cols = [], []
            leaf_cols = [[] for _ in leaves_host]
            for p, dirty in enumerate(per_shard):
                mm = len(dirty)
                if mm == 0:
                    continue
                idx = self.indexes[p]
                key_cols.append(idx.slot_key[dirty])
                ns_cols.append(idx.slot_ns[dirty])
                for i, lh in enumerate(leaves_host):
                    leaf_cols[i].append(lh[p][:mm])
            key_ids = np.concatenate(key_cols)
            out = {
                "__delta__": np.asarray(True),
                "key_id": key_ids,
                "namespace": np.concatenate(ns_cols),
                "key_group": assign_key_groups(key_ids,
                                               self.max_parallelism),
                "freed_namespaces": freed,
                **{f"leaf_{i}": np.concatenate(cols)
                   for i, cols in enumerate(leaf_cols)},
            }
        self._spill_delta_append(out)
        self._dirty[:] = False
        self._freed_ns.clear()
        return out

    def restore(self, snap: Dict[str, object],
                key_group_filter=None) -> None:
        """Restore, re-sharding by key group — accepts single-device
        SessionWindower snapshots and mesh snapshots of any mesh size."""
        table = snap.get("table", {})
        hk = snap.get("hot_keys")
        if hk:
            # the snapshot carries salted physical rows — the registry
            # must be live BEFORE any fire/query folds them
            for k, v in hk.items():
                self._hot_keys[int(k)] = int(v)
        key_ids = np.asarray(table.get("key_id", []), dtype=np.int64)
        namespaces = np.asarray(table.get("namespace", []), dtype=np.int64)
        if len(key_ids):
            if key_group_filter is not None:
                groups = assign_key_groups(key_ids, self.max_parallelism)
                keep = np.isin(groups, np.asarray(sorted(key_group_filter)))
                key_ids, namespaces = key_ids[keep], namespaces[keep]
                leaves = [np.asarray(table[f"leaf_{i}"])[keep]
                          for i in range(len(self.agg.leaves))]
            else:
                leaves = [np.asarray(table[f"leaf_{i}"])
                          for i in range(len(self.agg.leaves))]
        if self._spill_active and len(key_ids):
            if self._paged:
                self._paged_restore_rows(key_ids, namespaces, leaves)
            else:
                self._spill_restore_rows(key_ids, namespaces, leaves)
        elif len(key_ids):
            shards = self._route(key_ids)
            # inserts first — growth must settle before the host copy
            # (same contract as MeshWindowEngine.restore)
            per_shard_slots: Dict[int, np.ndarray] = {}
            for p in range(self.P):
                mask = shards == p
                if mask.any():
                    per_shard_slots[p] = self.indexes[p].lookup_or_insert(
                        key_ids[mask], namespaces[mask])
            # one batched D2H read, then writable copies (restore
            # mutates them in place before re-uploading)
            accs_host = [np.array(a)
                         for a in jax.device_get(list(self.accs))]
            for p, slots in per_shard_slots.items():
                mask = shards == p
                for acc, vals in zip(accs_host, leaves):
                    acc[p][slots] = vals[mask]
            self.accs = tuple(
                jax.device_put(jnp.asarray(a), self._sharding)
                for a in accs_host)
        self._dirty[:] = False
        self._freed_ns.clear()
        for sp in self.spills:
            sp.clear_dirty()
        # restored values bypass the scatter sites — the replica shadow
        # is stale wholesale; republish at the next boundary
        self._rep_rebuild = True
        self.meta.restore(snap, key_group_filter=key_group_filter,
                          max_parallelism=self.max_parallelism)

    # ------------------------------------------------ partial-failover hooks

    def _drop_meta_key_groups(self, groups) -> None:
        # a lost shard's session intervals die with its state rows —
        # the checkpoint unit (restore_key_groups) brings both back
        self.meta.drop_key_groups(groups, self.max_parallelism)

    def _merge_restored_meta(self, snap, groups) -> None:
        self.meta.merge_restore(snap, groups, self.max_parallelism)

    def _filter_meta_snapshot(self, snap, groups):
        from flink_tpu.windowing.session_meta import SessionIntervalSet

        out = SessionIntervalSet.filter_snapshot(
            snap, groups, self.max_parallelism)
        hk = snap.get("hot_keys")
        if hk:
            # every unit carries the full split registry (tiny) — any
            # subset of units restores with the folds intact
            out["hot_keys"] = dict(hk)
        return out

    def _merge_meta_snapshots(self, units):
        _NEG = -(1 << 62)
        sessions: Dict[int, list] = {}
        hot: Dict[int, int] = {}
        for u in units:
            for k, ivs in u.get("sessions", {}).items():
                sessions[int(k)] = list(ivs)  # ranges are disjoint
            for k, v in (u.get("hot_keys") or {}).items():
                hot[int(k)] = max(hot.get(int(k), 0), int(v))
        out = {
            "sessions": sessions,
            "next_sid": max((int(u.get("next_sid", 1)) for u in units),
                            default=1),
            # the OLDEST unit's staleness horizon: its range's records
            # replay from its position and must not be judged stale
            "max_fired_watermark": min(
                (u.get("max_fired_watermark", _NEG) for u in units),
                default=_NEG),
        }
        if hot:
            out["hot_keys"] = hot
        return out

    # -------------------------------------------- native-plane degradation

    def _meta_fallback(self, err) -> None:
        """Swap the native metadata plane for the bit-identical Python
        plane after a runtime sweep failure — once, loudly (warning +
        ``flink_tpu.native.native_fallbacks()``), preserving the live
        interval state via the plane-independent snapshot format."""
        from flink_tpu.native import note_fallback
        from flink_tpu.windowing.session_meta import SessionIntervalSet

        note_fallback(
            f"native session sweep failed at runtime "
            f"({type(err).__name__}: {err}) — engine degraded to the "
            "Python metadata plane")
        py = SessionIntervalSet(self.gap, self.allowed_lateness)
        py.restore(self.meta.snapshot())
        py.late_records_dropped = self.meta.late_records_dropped
        self.meta = py
