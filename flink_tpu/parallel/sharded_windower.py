"""Mesh-sharded windowed keyed aggregation.

The multi-device form of ``flink_tpu.windowing.windower.SliceSharedWindower``:
state lives in ``[num_shards, capacity]`` device arrays with the leading axis
sharded over the key-group mesh axis; every step (scatter / fire / reset) is
ONE jitted ``shard_map`` program over the whole mesh. Records are routed to
their owning shard by the reference's key-group formula
(reference: KeyGroupRangeAssignment.java:124-127 via
flink_tpu.state.keygroups) — the same contract that makes checkpoints
re-shardable.

Scaling contract (SURVEY.md §2.9): shard count == mesh size == the
"parallelism" of the keyed operator; max_parallelism == number of key groups.
Cross-shard communication: none during scatter (records are bucketed to their
owner on the host, the device_put with a sharded layout IS the shuffle);
window fire is shard-local because every key's slices live on one shard
(keyed state locality, same as the reference). The collectives
(all_to_all/psum in flink_tpu.parallel.shuffle) appear when chaining keyed
stages or doing global two-phase aggregation.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.chaos import injection as chaos
from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.ops.segment_ops import (
    SCATTER_METHOD,
    MERGE_FN,
    pad_bucket_size,
    sticky_bucket,
)
from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
from flink_tpu.parallel.shuffle import (
    bucket_by_shard,
    build_exchange_scatter,
    group_shard_table,
    shard_records,
    stage_device_exchange,
)
from flink_tpu.state.keygroups import assign_key_groups
from flink_tpu.state.slot_table import (
    HostSlotIndex,
    resolve_slices_sharded,
    resolve_slot_hints,
)
from flink_tpu.windowing.aggregates import AggregateFunction
from flink_tpu.windowing.assigners import WindowAssigner
from flink_tpu.windowing.bookkeeping import SliceBookkeeper
from flink_tpu.windowing.windower import WINDOW_END_FIELD, WINDOW_START_FIELD


# Compiled step programs cached by (mesh devices, aggregate layout) so
# repeated engines (warmup + measured runs, restarted jobs) AND
# concurrent jobs on one mesh share executables — the cache lives in the
# tenancy layer's SharedProgramCache (per-job hit/miss attribution; see
# flink_tpu/tenancy/program_cache.py).
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

# Tiny non-donated slice dispatched after everything queued so far: its
# readiness proves the device consumed every earlier host buffer (the
# mesh form of SlotTable.make_fence). jit caches per input sharding.
_FENCE_STEP = jax.jit(lambda a: a[:1, :1])


class MeshSpillSupport:
    """Per-shard spill tier shared by the mesh window and mesh session
    engines: LRU namespace eviction under a per-device slot budget, batched
    reload, and the bookkeeping both engines need. Hosts must provide
    ``P, indexes, spills, agg, accs, _dirty, _ns_touch, _put_sharded`` and
    the ``_gather_step/_reset_step/_put_step`` programs."""

    max_device_slots: int = 0
    #: bytes of padded fire slot matrices handed to the device so far
    #: (one watermark advance's growth is its ``fire.dispatch`` span's
    #: work)
    _fire_matrix_bytes = 0
    #: (MemoryManager, owner) — managed accounting of the [P, capacity]
    #: device footprint (flink_tpu/core/memory.py); None = unmanaged
    _memory = None
    #: the ingest data plane: "device" routes records through the fused
    #: in-program exchange (one flat device_put + all_to_all + scatter
    #: in ONE compiled program), "host" through the [P, B] bucketing +
    #: sharded device_put (the explicit fallback — see parallel.shuffle)
    shuffle_mode: str = "device"
    #: the (hosts, local) factorization of the mesh, when it spans
    #: hosts/processes: device-mode ingest then runs the TWO-LEVEL
    #: ICI/DCN exchange (parallel/exchange2.py) instead of the flat
    #: single-axis program; None (or a 1-host topology) keeps the flat
    #: fast path — every engine on a single-process mesh is unchanged
    host_topology = None
    #: intra- vs cross-host row accounting for the two-level exchange
    #: (the tests' vacuity guard + the NOTES traffic split)
    _exchange2_traffic = None
    #: live non-contiguous shard->key-group assignment installed by
    #: reassign_key_groups(); None = the contiguous formula (the common
    #: case — every routing site goes through _route so a rebalanced
    #: table threads the whole data plane without per-site branching)
    _assignment = None
    #: (assignment, (P, max_parallelism, key_group_range), table) of the
    #: last :meth:`_group_shard_table`
    _group_shards = None
    #: hot-range rebalances applied (counterpart of reshards_completed)
    rebalances_completed: int = 0
    #: report dict of the most recent reassign_key_groups()
    last_rebalance = None

    @staticmethod
    def _check_shuffle_mode(mode: str) -> str:
        if mode not in ("host", "device"):
            raise ValueError(
                f"shuffle_mode must be 'host' or 'device', got {mode!r}")
        return mode

    def _route(self, key_ids) -> np.ndarray:
        """key id -> owning shard, THE engine routing decision: the
        contiguous ``shard_records`` formula, or the live assignment
        table after a hot-range rebalance. Every internal routing site
        (ingest, merges, fires, queries, spill restore, checkpoint
        restore, handoff redistribution) goes through here so an
        installed table re-routes the whole data plane at once."""
        if self._assignment is not None:
            return self._assignment.shard_of_keys(
                key_ids, self.max_parallelism).astype(np.int64)
        return shard_records(key_ids, self.P, self.max_parallelism,
                             self.key_group_range)

    def _group_shard_table(self) -> np.ndarray:
        """:meth:`_route` as the int32 key group -> shard table a native
        sweep routes by (-1: a group this mesh does not own), built anew
        when what ``_route`` reads has changed (a reshard, a rebalance)."""
        assignment, rest = self._assignment, (
            self.P, self.max_parallelism, self.key_group_range)
        cached = self._group_shards
        if cached is None or cached[0] is not assignment \
                or cached[1] != rest:
            cached = self._group_shards = (
                assignment, rest, group_shard_table(*rest, assignment))
        return cached[2]

    def _set_host_topology(self, topology) -> None:
        if topology is not None:
            topology.check_covers(self.P)
        self.host_topology = topology
        if topology is not None and self._exchange2_traffic is None:
            from flink_tpu.parallel.exchange2 import ExchangeTraffic

            self._exchange2_traffic = ExchangeTraffic()

    def _two_level_active(self) -> bool:
        from flink_tpu.parallel.exchange2 import two_level_active

        return two_level_active(self.host_topology, self.shuffle_mode)

    def exchange2_traffic(self) -> Dict[str, int]:
        """Two-level exchange traffic split (zeros when flat)."""
        from flink_tpu.parallel.exchange2 import ExchangeTraffic

        return ExchangeTraffic.dict_of(self._exchange2_traffic)

    def _reserve_rows(self, rows: int) -> None:
        if self._memory is not None:
            manager, owner = self._memory
            manager.reserve(owner, rows * sum(
                np.dtype(leaf.dtype).itemsize
                for leaf in self.agg.leaves))

    def release_memory(self) -> None:
        if self._memory is not None:
            manager, owner = self._memory
            manager.release_all(owner)

    def _init_spill(self, spill_dir: Optional[str],
                    spill_host_max_bytes: int) -> None:
        from flink_tpu.state.paged_spill import PagedSpillMap
        from flink_tpu.state.slot_table import SpillTier

        #: kept for reshard(): the rebuilt mesh plane re-creates its
        #: spill tiers from the same configuration
        self._spill_dir = spill_dir
        self._spill_host_max_bytes = spill_host_max_bytes
        #: one spill tier per shard (keys move between shards only
        #: through reshard(), so spilled namespaces are shard-local like
        #: the device rows)
        self.spills = [
            SpillTier(
                f"{spill_dir.rstrip('/')}/shard-{p}" if spill_dir else None,
                spill_host_max_bytes // self.P
                if spill_host_max_bytes else 0)
            for p in range(self.P)
        ]
        self._ns_touch: List[Dict[int, int]] = [{} for _ in range(self.P)]
        self._touch_clock = 0
        self._reload_bucket = 0
        #: namespace-layout spill traffic (the paged layout counts on its
        #: PagedSpillMaps instead); survives reshard — a job-lifetime
        #: counter must not reset when the mesh resizes
        if not hasattr(self, "_ns_counters"):
            self._ns_counters = PagedSpillMap.zero_counters()
        self._init_pipeline(getattr(self, "max_dispatch_ahead", 2))

    # ------------------------------------------------- host/device pipelining

    def _init_pipeline(self, depth: int) -> None:
        """Double-buffered dispatch-ahead: the host preps (and buckets)
        batch k+1 while the device still runs batch k. ``depth`` bounds
        how many dispatched-but-unfenced batches may be in flight; the
        shuffle pool rotates the same number of buffer generations, so a
        staging buffer is only rewritten after the dispatch that read it
        has provably completed (fence discipline — device_put from a
        host buffer is NOT synchronous on a real accelerator link)."""
        from collections import deque

        from flink_tpu.parallel.shuffle import ShuffleBufferPool

        self._pipeline_depth = max(int(depth or 1), 1)
        self._shuffle_pool = ShuffleBufferPool(
            generations=self._pipeline_depth)
        self._dispatch_fences = deque()
        #: monotonically increasing per-engine batch sequence — the
        #: flight recorder's batch_id attribution (survives reshard)
        if not hasattr(self, "_flight_batch"):
            self._flight_batch = 0

    def _flight_ingest(self):
        """Open the ``batch.ingest`` span for one ``process_batch`` and
        advance the engine's batch sequence (sub-spans and instants
        opened below it inherit the batch id from the ambient thread
        context)."""
        self._flight_batch += 1
        return flight.ingest_span(self._flight_batch)

    # ------------------------------------------------------------- watchdog

    #: device watchdog (runtime/watchdog.py) — None keeps every hook a
    #: single attribute check (the default; harness/executor attach one)
    _watchdog = None

    def attach_watchdog(self, wd) -> None:
        """Wrap this engine's device interactions (dispatch fences,
        eviction/fire harvests, batched device_get reads, serving
        lookups) in the watchdog's deadline-tracked sections, and run
        its shard-health probe at batch boundaries."""
        self._watchdog = wd
        if wd is not None:
            wd.rebind(self.P,
                      [d.id for d in self.mesh.devices.flat])
            # host-granular escalation needs the (hosts, local) map
            wd.set_topology(self.host_topology)

    def _wd_section(self, op: str, shard: int = -1):
        wd = self._watchdog
        if wd is None:
            from flink_tpu.runtime.watchdog import NULL_SECTION

            return NULL_SECTION
        return wd.section(op, shard)

    def _wd_boundary(self) -> None:
        """Batch-boundary health probe: the one point a shard may be
        DECLARED dead (engine state is consistent at a known source
        position here — see watchdog.boundary_probe)."""
        wd = self._watchdog
        if wd is not None:
            wd.boundary_probe()

    def _ingest_subbatch(self, batch) -> None:
        """Recursive ingest of a SPLIT sub-batch (working-set bounding):
        the watchdog is detached for the inner call — a shard declared
        dead between sub-batches would leave the step half-absorbed on
        the survivors, which is not a consistent failover point. The
        boundary probe stays at the OUTER batch boundary."""
        wd = self._watchdog
        self._watchdog = None
        try:
            self.process_batch(batch)
        finally:
            self._watchdog = wd

    def _harvest_get(self, tree, op: str = "fire_harvest"):
        """The watchdog-sectioned form of the batched-D2H harvest (ONE
        ``jax.device_get`` per harvest point — the TRC01 discipline)."""
        with flight.span("fire.harvest") as span, self._wd_section(op):
            host = jax.device_get(tree)
            span.work = sum(np.asarray(a).nbytes
                            for a in jax.tree_util.tree_leaves(host))
            return host

    # ---------------------------------------------------- read replica
    # (tenancy/replica.py — the boundary-published serving plane)

    #: armed by the tenancy layer (session cluster / tests); None keeps
    #: every hook a single attribute check on the ingest path
    _replica = None
    #: set where the replica's shadow of the slot metadata goes stale
    #: wholesale (restore, reshard, shard loss) — the next publish
    #: rebuilds the plane and republishes every resident row
    _rep_rebuild = False

    def arm_replica(self, plane=None):
        """Attach (or build) the read-replica plane this engine
        publishes into at watermark boundaries. Must run on the task
        thread (single-owner), before or between batches."""
        from flink_tpu.tenancy.replica import ReplicaPlane

        if plane is None:
            plane = ReplicaPlane(self.mesh, self.agg.leaves,
                                 self.capacity)
        plane.warm_tiers()
        self._replica = plane
        self._rep_cold_pending: Dict[int, list] = {}
        self._rep_rebuild = True
        return plane

    def _rep_note_cold(self, p: int, keys, nss) -> None:
        """Record rows leaving residency (evictions) so a row created
        AND evicted within one publish interval still reaches the
        replica index as a cold entry at the next boundary."""
        if self._replica is not None:
            self._rep_cold_pending.setdefault(p, []).append(
                (np.asarray(keys, dtype=np.int64).copy(),
                 np.asarray(nss, dtype=np.int64).copy()))

    def _rep_mark(self, p: int, slots) -> None:
        """Note value-changing scatters for the next publish delta
        (residency/identity changes are derived by the publish diff
        instead — see _publish_replica). While a rebuild is pending
        (reshard/restore/growth changed the plane shape under the
        shadow) marks are moot — the rebuild republishes everything."""
        rep = self._replica
        if rep is not None and not self._rep_rebuild \
                and not rep.needs_rebuild(self.P, self.capacity):
            rep.mark_dirty(p, slots)

    def _rep_extra(self, p: int, keys: np.ndarray,
                   nss: np.ndarray):
        """Per-row adapter payload published with the index entries
        (sessions: the session END; windows: none — the namespace IS
        the slice end)."""
        return None

    def _rep_publish_split(self, p: int, keys: np.ndarray,
                           nss: np.ndarray):
        """Hook: ``(drop_mask, cold_mask)`` over the publish upserts, or
        None (default — publish everything resident). The session
        engine's hot-key splitting uses it to keep PARTIAL rows out of
        the serving index: salted sub-rows are dropped outright (their
        synthetic keys are never looked up), and a split key's main row
        is entered COLD so the lookup routes through ``cold_fetch`` to
        the live engine's combined fold — a split key still answers one
        lookup, with the full value."""
        return None

    def _rep_probe_cold(self, p: int, keys: np.ndarray,
                        nss: np.ndarray) -> np.ndarray:
        """For pairs that left the resident set since the last publish:
        True = the row serves from the page tier (evicted), False =
        freed (fired/expired — drop from the index). Namespace-layout
        default: a namespace present in the shard's spill tier is cold
        (eviction moves whole namespaces)."""
        nsset = set(int(x) for x in self.spills[p].namespaces) \
            if self._spill_active else set()
        return np.asarray([int(ns) in nsset for ns in nss], dtype=bool)

    def _publish_replica(self, watermark: int) -> None:
        """Publish the boundary delta into the replica plane: diff the
        engine's per-shard slot metadata against the replica's shadow
        (plus the scatter-site dirty marks), hand the changed slots to
        ONE device-to-device copy program, and seal the next
        generation. Runs at the END of on_watermark — the fires and
        frees of this boundary are already applied, so the sealed view
        is exactly the engine state a checkpoint cut here would
        capture."""
        rep = self._replica
        if rep is None:
            return
        if rep.min_interval_s and not self._rep_rebuild:
            s = rep.sealed
            if s is not None and (time.monotonic() - s.published_at
                                  < rep.min_interval_s):
                # batch this boundary into the next publish: the dirty
                # marks keep accumulating, the diff/copy cost is paid
                # once per interval, and the cache invalidation rate is
                # bounded (staleness <= the interval, by construction)
                return
        with flight.span("serving.replica_publish",
                         watermark=int(watermark)):
            include_spilled = False
            if self._rep_rebuild or rep.needs_rebuild(self.P,
                                                      self.capacity):
                rep.rebuild(self.mesh, self.capacity)
                rep.warm_tiers()
                self._rep_cold_pending = {}
                self._rep_rebuild = False
                # the rebuild's full republish covers resident rows;
                # rows already cold (restored/re-homed pages) must
                # re-enter the index too — enumerated below
                include_spilled = self._spill_active
            per_shard = {}
            for p in range(self.P):
                idx = self.indexes[p]
                used = idx.slot_used
                L = len(used)
                cur_used = np.asarray(used[:L], dtype=bool)
                cur_key = np.asarray(idx.slot_key[:L])
                cur_ns = np.asarray(idx.slot_ns[:L])
                r_used = rep.rep_used[p][:L]
                r_key = rep.rep_key[p][:L]
                r_ns = rep.rep_ns[p][:L]
                moved = (cur_key != r_key) | (cur_ns != r_ns)
                ident_change = cur_used & (~r_used | moved)
                up = np.nonzero(ident_change
                                | (rep.rep_dirty[p][:L] & cur_used))[0]
                gone = np.nonzero(r_used & (~cur_used | moved))[0]
                cold: List[Tuple[int, int]] = []
                freed: List[Tuple[int, int]] = []
                if len(gone):
                    g_keys = r_key[gone].copy()
                    g_ns = r_ns[gone].copy()
                    # a pair re-homed to a NEW slot is covered by its
                    # upsert there; only pairs no longer resident at
                    # all need the cold/freed split
                    miss = idx.lookup(g_keys, g_ns) < 0
                    if miss.any():
                        mk, mn = g_keys[miss], g_ns[miss]
                        is_cold = self._rep_probe_cold(p, mk, mn)
                        for j in range(len(mk)):
                            if is_cold[j]:
                                cold.append((int(mk[j]), int(mn[j]),
                                             None))
                            else:
                                freed.append((int(mk[j]), int(mn[j])))
                # rows created AND evicted since the last publish were
                # never resident at a boundary — the eviction sites
                # recorded them; enter them cold (skipping any that
                # reloaded back to residency, covered by the diff)
                pend = self._rep_cold_pending.get(p)
                if pend:
                    pk = np.concatenate([a for a, _ in pend])
                    pn = np.concatenate([b for _, b in pend])
                    nonres = idx.lookup(pk, pn) < 0
                    if nonres.any():
                        ck, cn = pk[nonres], pn[nonres]
                        still = self._rep_probe_cold(p, ck, cn)
                        cx = self._rep_extra(p, ck, cn)
                        for j in range(len(ck)):
                            if still[j]:
                                cold.append((
                                    int(ck[j]), int(cn[j]),
                                    None if cx is None else cx[j]))
                    # cleared after the publish SUCCEEDS (torn-publish
                    # re-derivability — see below)
                up_keys = cur_key[up].copy()
                up_ns = cur_ns[up].copy()
                split = self._rep_publish_split(p, up_keys, up_ns)
                if split is not None:
                    drop, coldm = split
                    if coldm.any():
                        cks, cns = up_keys[coldm], up_ns[coldm]
                        cx = self._rep_extra(p, cks, cns)
                        for j in range(len(cks)):
                            cold.append((int(cks[j]), int(cns[j]),
                                         None if cx is None else cx[j]))
                    keep = ~(drop | coldm)
                    if not keep.all():
                        up = up[keep]
                        up_keys = up_keys[keep]
                        up_ns = up_ns[keep]
                per_shard[p] = {
                    "up_slots": up.astype(np.int32),
                    "up_keys": up_keys,
                    "up_ns": up_ns,
                    "up_extra": self._rep_extra(p, up_keys, up_ns),
                    "cold": cold,
                    "freed": freed,
                    "fresh": bool(ident_change.any()),
                }
                per_shard[p]["_shadow"] = (L, cur_used, cur_key, cur_ns)
            if include_spilled:
                cold0 = per_shard[0]["cold"]
                for part in self._spill_snapshot_parts():
                    ck = np.asarray(part["key_id"], dtype=np.int64)
                    cn = np.asarray(part["namespace"], dtype=np.int64)
                    split = self._rep_publish_split(0, ck, cn)
                    if split is not None:
                        keep = ~split[0]  # spilled rows are already cold
                        ck, cn = ck[keep], cn[keep]
                    cx = self._rep_extra(0, ck, cn)
                    for j in range(len(ck)):
                        cold0.append((int(ck[j]), int(cn[j]),
                                      None if cx is None else cx[j]))
                if cold0:
                    per_shard[0]["fresh"] = True
            # the metadata shadow, dirty marks and pending cold events
            # update ONLY after the publish succeeds: a fault inside
            # the publish (serving.replica_publish chaos, a device
            # error) must leave the delta re-derivable — otherwise the
            # torn boundary's rows silently never reach the replica
            rep.publish(self.accs, per_shard, int(watermark))
            for p, d in per_shard.items():
                L, cur_used, cur_key, cur_ns = d.pop("_shadow")
                rep.rep_used[p][:L] = cur_used
                rep.rep_used[p][L:] = False
                rep.rep_key[p][:L] = cur_key
                rep.rep_ns[p][:L] = cur_ns
                rep.rep_dirty[p][:] = False
                self._rep_cold_pending[p] = []

    def make_fence(self):
        """A tiny non-donated device value enqueued AFTER everything
        dispatched so far — used by the engine's own dispatch-ahead
        bound and by the task loop's pipelining fences
        (runtime/operators.py)."""
        return _FENCE_STEP(self.accs[0])

    def _await_dispatch_slot(self) -> None:
        """Block until < depth dispatches are outstanding. MUST run
        before this batch's staging buffers are (re)written."""
        if len(self._dispatch_fences) < self._pipeline_depth:
            return
        with flight.span("device.fence_wait"), \
                self._wd_section("fence_drain"):
            while len(self._dispatch_fences) >= self._pipeline_depth:
                # flint: disable=TRC01 -- the depth-bounded fence drain
                # IS the dispatch-ahead backpressure point: it blocks
                # only when the host ran a full pipeline depth ahead of
                # the device
                self._dispatch_fences.popleft().block_until_ready()

    def _push_dispatch_fence(self) -> None:
        # chaos: a fence failure mid-dispatch-ahead — the batch's device
        # work is enqueued but its completion proof is lost, which in a
        # real stack is a device reset/preemption: the engine dies here
        # with up to `depth` batches in flight (the hardest restore case)
        chaos.fault_point("mesh.dispatch_fence",
                          in_flight=len(self._dispatch_fences))
        # fence creation dispatches a (tiny) device program — an inline
        # device interaction, attributed as such in the trace
        with flight.span("device.dispatch"), \
                self._wd_section("dispatch_fence"):
            self._dispatch_fences.append(self.make_fence())

    @property
    def _spill_active(self) -> bool:
        return self.max_device_slots > 0

    def _any_spilled(self, slice_ends) -> bool:
        return self._spill_active and any(
            int(se) in self.spills[p]
            for p in range(self.P) for se in slice_ends)

    def _touch(self, p: int, namespaces) -> None:
        self._touch_clock += 1
        clock = self._touch_clock
        touch = self._ns_touch[p]
        for ns in namespaces:
            touch[int(ns)] = clock

    def _make_headroom(self, p: int, needed: int, protect: set) -> None:
        while self.indexes[p].free_headroom() < needed:
            self._evict_cold(p, protect)

    def _reserve(self, p: int, keys: np.ndarray, nss: np.ndarray) -> None:
        """Ensure shard ``p`` can absorb the genuinely NEW (key, ns)
        pairs among (keys, nss): under ample headroom this is one cheap
        over-counting check; otherwise a read-only probe counts the
        misses and cold namespaces are evicted to make room, protecting
        the namespaces this batch touches."""
        if not self._spill_active:
            return
        from flink_tpu.state.slot_table import unique_pairs

        uk, un, _ = unique_pairs(np.asarray(keys, dtype=np.int64),
                                 np.asarray(nss, dtype=np.int64))
        if self.indexes[p].free_headroom() >= len(uk):
            return
        needed = int((self.indexes[p].lookup(uk, un) < 0).sum())
        if needed:
            self._make_headroom(
                p, needed, protect={int(x) for x in np.unique(un)})

    def _evict_cold(self, p: int, protect: set) -> None:
        """Evict shard ``p``'s least-recently-touched namespaces to its
        spill tier until a workable fraction of the shard's slots is free —
        one gather + one reset kernel for the whole eviction batch (the
        other shards' rows in the [P, G] blocks are identity no-ops)."""
        from flink_tpu.state.slot_table import SlotTableFullError

        idx = self.indexes[p]
        target_free = max(idx.capacity // 8, 1024)
        touch = self._ns_touch[p]
        candidates = sorted(
            (ns for ns in idx.namespaces if int(ns) not in protect),
            key=lambda ns: touch.get(int(ns), 0))
        if not candidates:
            raise SlotTableFullError(
                f"shard {p}: device slot budget exhausted and every "
                "namespace in the current batch is protected — raise "
                "state.slot-table.max-device-slots or reduce batch size")
        chosen: List[Tuple[int, np.ndarray]] = []
        freed = 0
        for ns in candidates:
            if freed >= target_free:
                break
            slots = idx.slots_for_namespace(int(ns))
            chosen.append((int(ns), slots))
            freed += len(slots)
        empty = [ns for ns, s in chosen if len(s) == 0]
        if empty:
            idx.free_namespaces(empty)
        chosen = [(ns, s) for ns, s in chosen if len(s) > 0]
        if not chosen:
            return
        all_slots = np.concatenate([s for _, s in chosen])
        n = len(all_slots)
        G = sticky_bucket(n, self._gather_bucket)
        self._gather_bucket = G
        block = np.zeros((self.P, G), dtype=np.int32)
        block[p, :n] = all_slots
        gathered = self._gather_step(self.accs, self._put_sharded(block))
        # ONE batched D2H read for all leaves (per-array np.asarray pays
        # one link round-trip per leaf — see runtime/pending.py)
        leaves_host = [g[p][:n]
                       for g in self._harvest_get(gathered,
                                                  "evict_harvest")]
        off = 0
        for ns, slots in chosen:
            m = len(slots)
            entry = {
                "key_id": np.asarray(idx.slot_key[slots]),
                **{f"leaf_{i}": leaves_host[i][off:off + m]
                   for i in range(len(leaves_host))},
            }
            self.spills[p].put(ns, entry,
                               dirty=bool(self._dirty[p, slots].any()))
            # replica: never-published rows going cold (see _evict_cohorts)
            self._rep_note_cold(p, entry["key_id"],
                                np.full(m, int(ns), dtype=np.int64))
            off += m
            self._ns_touch[p].pop(ns, None)
        self._ns_counters["pages_evicted"] += len(chosen)
        self._ns_counters["rows_evicted"] += n
        idx.free_namespaces([ns for ns, _ in chosen])
        self._dirty[p, all_slots] = False
        R = sticky_bucket(n, getattr(self, "_reset_bucket", 0))
        self._reset_bucket = R
        rb = np.zeros((self.P, R), dtype=np.int32)
        rb[p, :n] = all_slots
        self.accs = self._reset_step(self.accs, self._put_sharded(rb))

    def _ensure_resident(self, per_shard: Dict[int, np.ndarray]) -> None:
        """Reload any spilled namespaces among each shard's touched set
        back onto the device — ALL shards' reloads batch into one insert
        pass + ONE put kernel."""
        if not self._spill_active:
            return
        entries: Dict[int, List[Tuple[int, Dict[str, np.ndarray]]]] = {}
        rows: Dict[int, int] = {}
        for p, nss in per_shard.items():
            sp = self.spills[p]
            if len(sp) == 0:
                continue
            es = []
            for ns in nss:
                ns = int(ns)
                if ns in sp:
                    e = sp.pop(ns)
                    if e is not None and len(e["key_id"]):
                        es.append((ns, e))
            if es:
                entries[p] = es
                rows[p] = sum(len(e["key_id"]) for _, e in es)
        if not entries:
            return
        self._ns_counters["pages_reloaded"] += sum(
            len(es) for es in entries.values())
        self._ns_counters["rows_reloaded"] += sum(rows.values())
        # headroom first, for every shard (evictions dispatch their own
        # kernels; slots resolved after growth/eviction settle)
        for p, need in rows.items():
            self._make_headroom(
                p, need, protect={int(n) for n in per_shard[p]})
        B = sticky_bucket(max(rows.values()), self._reload_bucket)
        self._reload_bucket = B
        slot_block = np.zeros((self.P, B), dtype=np.int32)
        val_blocks = [np.full((self.P, B), l.identity, dtype=l.dtype)
                      for l in self.agg.leaves]
        for p, es in entries.items():
            keys = np.concatenate([
                np.asarray(e["key_id"], dtype=np.int64) for _, e in es])
            nss = np.concatenate([
                np.full(len(e["key_id"]), ns, dtype=np.int64)
                for ns, e in es])
            n = len(keys)
            slots = self.indexes[p].lookup_or_insert(keys, nss)
            slot_block[p, :n] = slots
            for i, l in enumerate(self.agg.leaves):
                # assemble straight into the staged block row (one
                # concatenate per leaf, no intermediate copy)
                np.concatenate(
                    [np.asarray(e[f"leaf_{i}"], dtype=l.dtype)
                     for _, e in es],
                    out=val_blocks[i][p, :n])
            # reloaded rows keep their dirtiness: rows dirty at spill time
            # have not been in any snapshot since
            was_dirty = np.concatenate([
                np.full(len(e["key_id"]),
                        bool(e.get("__was_dirty__", False)), dtype=bool)
                for _, e in es])
            self._dirty[p, slots] = was_dirty
            self._touch(p, [ns for ns, _ in es])
        self.accs = self._put_step(
            self.accs, self._put_sharded(slot_block),
            tuple(self._put_sharded(v) for v in val_blocks))

    def _drop_spilled(self, ends, freed_touch: bool = True) -> None:
        """Discard spilled namespaces (fully fired/expired elsewhere)."""
        if not self._spill_active:
            return
        for p in range(self.P):
            sp = self.spills[p]
            if len(sp):
                for e in ends:
                    if int(e) in sp:
                        sp.drop(int(e))
            if freed_touch:
                touch = self._ns_touch[p]
                for e in ends:
                    touch.pop(int(e), None)

    def _spill_snapshot_parts(self) -> List[Dict[str, np.ndarray]]:
        """Logical-snapshot rows for every spilled namespace. Paged
        entries (the mesh session engine) carry their own ``ns`` column
        and one entry spans many sessions; dead rows are dropped."""
        parts: List[Dict[str, np.ndarray]] = []
        pmaps = getattr(self, "_pmaps", None)
        for p in range(self.P):
            sp = self.spills[p]
            for ns in sp.namespaces:
                entry = sp.peek(int(ns))
                if entry is None:
                    continue
                ekeys = np.asarray(entry["key_id"], dtype=np.int64)
                if "ns" in entry:  # paged entry: per-row namespaces
                    rns = np.asarray(entry["ns"], dtype=np.int64)
                    # lazy tombstones: only rows still mapped to this
                    # page are logical state (paged_spill)
                    alive = pmaps[p].live_row_mask(int(ns), rns)
                    ekeys, rns = ekeys[alive], rns[alive]
                    sel = alive
                else:
                    rns = np.full(len(ekeys), int(ns), dtype=np.int64)
                    sel = slice(None)
                if len(ekeys) == 0:
                    continue
                parts.append({
                    "key_id": ekeys,
                    "namespace": rns,
                    "key_group": assign_key_groups(
                        ekeys, self.max_parallelism),
                    **{f"leaf_{i}": np.asarray(
                        entry[f"leaf_{i}"],
                        dtype=self.agg.leaves[i].dtype)[sel]
                       for i in range(len(self.agg.leaves))},
                })
        return parts

    def _spill_delta_append(self, out: Dict[str, np.ndarray]) -> None:
        """Append spilled-but-dirty namespaces to a delta snapshot and
        clear their dirtiness. For paged entries only the dirty ROWS of
        a dirty page travel (pages are immutable once spilled, so the
        per-row dirty column captured at eviction stays authoritative)."""
        if not self._spill_active:
            return
        pmaps = getattr(self, "_pmaps", None)
        for p in range(self.P):
            sp = self.spills[p]
            for ns in sp.dirty_namespaces():
                entry = sp.peek(int(ns))
                if entry is None:
                    continue
                ekeys = np.asarray(entry["key_id"], dtype=np.int64)
                if "ns" in entry:  # paged entry
                    rns_all = np.asarray(entry["ns"], dtype=np.int64)
                    # dirty AND live: a tombstoned row is resident again
                    # (its device copy travels) or freed
                    sel = (np.asarray(entry["dirty"], dtype=bool)
                           & pmaps[p].live_row_mask(int(ns), rns_all))
                    ekeys = ekeys[sel]
                    rns = rns_all[sel]
                else:
                    sel = slice(None)
                    rns = np.full(len(ekeys), int(ns), dtype=np.int64)
                if len(ekeys) == 0:
                    continue
                out["key_id"] = np.concatenate([out["key_id"], ekeys])
                out["namespace"] = np.concatenate([out["namespace"], rns])
                out["key_group"] = np.concatenate([
                    out["key_group"],
                    assign_key_groups(ekeys, self.max_parallelism)])
                for i, l in enumerate(self.agg.leaves):
                    out[f"leaf_{i}"] = np.concatenate([
                        out[f"leaf_{i}"],
                        np.asarray(entry[f"leaf_{i}"],
                                   dtype=l.dtype)[sel]])
            sp.clear_dirty()

    def _spill_restore_rows(self, key_ids: np.ndarray,
                            namespaces: np.ndarray,
                            leaves: List[np.ndarray]) -> None:
        """Spill-enabled restore: rows land in each shard's spill tier
        grouped by namespace and reload lazily on first access — a
        snapshot far larger than the HBM budget restores with bounded
        device memory (same contract as SlotTable.restore)."""
        shards = self._route(key_ids)
        for p in range(self.P):
            mask = shards == p
            if not mask.any():
                continue
            ns_p = namespaces[mask]
            keys_p = key_ids[mask]
            leaves_p = [l[mask] for l in leaves]
            order = np.argsort(ns_p, kind="stable")
            s_ns, s_keys = ns_p[order], keys_p[order]
            s_leaves = [l[order] for l in leaves_p]
            bounds = np.nonzero(np.diff(s_ns))[0] + 1
            starts = np.concatenate(([0], bounds))
            ends = np.concatenate((bounds, [len(s_ns)]))
            sp = self.spills[p]
            for a, b in zip(starts.tolist(), ends.tolist()):
                ns = int(s_ns[a])
                entry = {"key_id": s_keys[a:b],
                         **{f"leaf_{i}": s_leaves[i][a:b]
                            for i in range(len(s_leaves))}}
                if ns in sp:
                    sp.drop(ns)
                sp.put(ns, entry, dirty=False)

    # -------------------------------------------------------- observability

    def spill_counters(self) -> Dict[str, int]:
        """Spill traffic summed over shards (namespace layout counts on
        the engine; the paged layout overrides and sums its maps)."""
        from flink_tpu.state.paged_spill import PagedSpillMap

        out = PagedSpillMap.zero_counters()
        for k, v in getattr(self, "_ns_counters", {}).items():
            out[k] += v
        return out

    def shard_resident_rows(self) -> List[int]:
        """Device-resident rows per shard — the key-imbalance signal the
        autoscaler reads before trusting a hot shard to mean overload."""
        return [int(idx.slot_used.sum()) for idx in self.indexes]

    def key_imbalance(self) -> float:
        """max/mean resident rows per shard (1.0 = perfectly balanced).

        A hot shard with high imbalance is a SKEW problem, not a
        capacity problem: fewer shards would concentrate the same keys
        harder, so the scaling policy refuses to scale down on it.
        The formula lives in autoscale.policy (one definition for the
        gauge and for the guard that acts on it)."""
        from flink_tpu.autoscale.policy import key_imbalance

        return key_imbalance(self.shard_resident_rows())

    # ---------------------------------------------------------- tenant quota

    def enforce_resident_budget(self, max_total_rows: int) -> int:
        """Quota backstop (flink_tpu.tenancy.quotas): evict this
        engine's OWN coldest rows until its device-resident total is at
        most ``max_total_rows`` — rows land in the engine's private
        spill tier, exactly like steady-state eviction. Structural
        isolation: the method only walks ``self``'s shards, so one
        job's enforcement can never reclaim another job's rows.
        Returns rows shed. Raises when no spill tier is configured
        (nowhere to shed to — the ledger counts a quota violation)."""
        from flink_tpu.state.slot_table import SlotTableFullError

        if not self._spill_active:
            raise RuntimeError(
                "engine has no spill tier — a resident-row quota needs "
                "state.slot-table.max-device-slots (+ spill dir) so "
                "over-budget rows have somewhere to go")
        max_total_rows = max(int(max_total_rows), 0)
        if getattr(self, "_paged", False):
            # the current batch's rows carry the live clock; the backstop
            # runs between scheduling quanta, so advancing it makes every
            # resident row evictable
            self._touch_clock += 1
        per = self.shard_resident_rows()
        shed = 0
        while sum(per) > max_total_rows:
            p = int(np.argmax(np.asarray(per)))
            if per[p] <= 0:
                break
            try:
                if getattr(self, "_paged", False):
                    self._evict_cold_paged(p)
                else:
                    self._evict_cold(p, protect=set())
            except SlotTableFullError:
                break
            new = self.shard_resident_rows()
            freed = sum(per) - sum(new)
            if freed <= 0:
                break
            shed += freed
            per = new
        return shed

    # ------------------------------------------------- live rescale (reshard)

    #: live rescales completed since engine construction
    reshards_completed: int = 0
    #: report dict of the most recent reshard (None until the first)
    last_reshard: Optional[Dict[str, object]] = None

    def _make_shard_indexes(self) -> List:
        """Fresh per-shard host indexes at the CURRENT self.P/capacity
        (shared by __init__ and the reshard rebuild)."""
        from flink_tpu.state.slot_table import make_slot_index

        return [
            make_slot_index(
                self.capacity, growable=True,
                on_grow=lambda old, new: self._shard_index_grew(new),
                max_capacity=self.max_device_slots,
                track_namespaces=getattr(self, "_track_ns", True),
                full_hint=("state spills to host beyond "
                           "state.slot-table.max-device-slots"
                           if self.max_device_slots
                           else "raise state.slot-table.capacity"))
            for _ in range(self.P)
        ]

    def reshard(self, new_shards: int, devices=None) -> Dict[str, object]:
        """LIVE key-group migration to a new mesh size — no checkpoint
        round-trip, no stop-and-redeploy.

        Rescaling *is* key-group-range reassignment (reference:
        KeyGroupRangeAssignment.java — the same group->subtask formula
        the data path routes by): the engine drains its dispatch-ahead
        fences, lifts every logical row (device-resident AND spilled)
        off the old mesh with its dirtiness and recency intact, rebuilds
        the [P, capacity] plane over a mesh of ``new_shards`` devices,
        and lands the rows on their new owners — resident rows through
        ONE batched put program (the cross-shard reload machinery),
        cold rows straight into the new shards' spill tiers. Window/
        session metadata (bookkeeper / interval set) is global host
        state and never moves. Delta-snapshot bookkeeping survives: rows
        dirty before the reshard are still dirty after, and freed-
        namespace tombstones carry over, so the next incremental
        checkpoint is exactly what it would have been.

        Callers must have harvested in-flight async fires first (their
        device buffers reference the pre-reshard arrays); the operator
        wrapper (WindowAggOperator.reshard) enforces this.

        NOT exception-atomic: a failure mid-handoff (e.g. an injected
        ``rescale.handoff`` chaos fault) leaves the engine unusable —
        the failover path is checkpoint-restore-at-new-parallelism,
        exactly how the chaos harness recovers.
        """
        new_shards = int(new_shards)
        if new_shards < 1:
            raise ValueError(f"new_shards must be >= 1, got {new_shards}")
        if new_shards == self.P and devices is None:
            return {"from": self.P, "to": self.P, "rows_moved": 0,
                    "resident_rows": 0, "spilled_rows": 0,
                    "seconds": 0.0, "noop": True}
        if self.max_parallelism < new_shards:
            raise ValueError(
                f"cannot reshard to {new_shards} shards: max_parallelism "
                f"{self.max_parallelism} bounds the shard count (the "
                "key-group space cannot be split finer)")
        if self.key_group_range is not None:
            first, last = self.key_group_range
            span = int(last) - int(first) + 1
            if span < new_shards:
                raise ValueError(
                    f"cannot reshard to {new_shards} shards: this engine "
                    f"owns only {span} key groups "
                    f"[{int(first)}, {int(last)}]")
        if devices is None and new_shards > len(jax.devices()):
            raise ValueError(
                f"cannot reshard to {new_shards} shards: only "
                f"{len(jax.devices())} devices exist")
        t0 = time.perf_counter()
        with flight.span("reshard.handoff"):
            # quiesce: prove the device consumed every staged host buffer
            # before the staging pool and the accumulator plane are
            # replaced
            while self._dispatch_fences:
                # flint: disable=TRC01 -- reshard quiesce: the mesh plane
                # is about to be torn down, every in-flight dispatch must
                # land
                self._dispatch_fences.popleft().block_until_ready()
            chaos.fault_point("rescale.handoff", stage="drain",
                              from_shards=self.P, to_shards=new_shards)
            rows = self._collect_handoff()
            old_p = self.P
            # a live rebalanced assignment is defined over the OLD shard
            # count: changing P resets to the contiguous layout (the
            # lifted rows re-route below; the rebalancer re-detects on
            # the new mesh if the skew persists)
            self._assignment = None
            self._rebuild_mesh_plane(new_shards, devices)
            # the hardest crash point: old state lifted, new plane empty
            # — recovery is restore-from-checkpoint (the engine object is
            # dead)
            chaos.fault_point("rescale.handoff", stage="commit",
                              from_shards=old_p, to_shards=new_shards)
            resident_rows, spilled_rows = self._redistribute_handoff(rows)
        self.reshards_completed += 1
        self.last_reshard = {
            "from": old_p, "to": new_shards,
            "rows_moved": int(len(rows["key_id"])),
            "resident_rows": resident_rows,
            "spilled_rows": spilled_rows,
            "seconds": time.perf_counter() - t0,
        }
        return self.last_reshard

    def reassign_key_groups(self, assignment) -> Dict[str, object]:
        """LIVE hot-range rebalance: move key groups BETWEEN shards at a
        batch boundary without changing P — the skew response the
        rescale path cannot provide (more shards under a hot range just
        concentrates the same keys).

        Same handoff discipline as :meth:`reshard` (drain fences ->
        lift rows -> rebuild plane -> redistribute by the NEW routing),
        with its own chaos fault point (``rebalance.handoff``) at the
        same two stages. The full row lift is acceptable because the
        rebalance policy's cooldown makes moves rare; the win is
        steady-state throughput, not handoff latency.

        NOT exception-atomic, like reshard: a crash mid-handoff is
        recovered by checkpoint restore (the restoring engine routes by
        ITS OWN assignment — snapshots are key-id addressed and carry no
        assignment, so restore after a crash-at-commit is well-defined).
        """
        from flink_tpu.state.keygroups import KeyGroupAssignment

        if not isinstance(assignment, KeyGroupAssignment):
            raise TypeError(
                f"expected KeyGroupAssignment, got {type(assignment).__name__}")
        if assignment.num_shards != self.P:
            raise ValueError(
                f"assignment is for {assignment.num_shards} shards, "
                f"engine has {self.P} — rebalance moves groups, "
                "reshard() changes P")
        if self.key_group_range is not None:
            first = int(self.key_group_range[0])
            span = int(self.key_group_range[1]) - first + 1
        else:
            first, span = 0, self.max_parallelism
        if assignment.first != first or assignment.span != span:
            raise ValueError(
                f"assignment covers groups [{assignment.first}, "
                f"{assignment.first + assignment.span - 1}], engine owns "
                f"[{first}, {first + span - 1}]")
        cur = self._assignment if self._assignment is not None else \
            KeyGroupAssignment.contiguous(self.P, self.max_parallelism,
                                          self.key_group_range)
        moved = np.nonzero(assignment.table != cur.table)[0]
        if len(moved) == 0:
            return {"groups_moved": 0, "rows_moved": 0,
                    "resident_rows": 0, "spilled_rows": 0,
                    "seconds": 0.0, "noop": True}
        t0 = time.perf_counter()
        with flight.span("reshard.handoff"):
            while self._dispatch_fences:
                # flint: disable=TRC01 -- rebalance quiesce: the mesh
                # plane is about to be torn down, every in-flight
                # dispatch must land
                self._dispatch_fences.popleft().block_until_ready()
            chaos.fault_point("rebalance.handoff", stage="drain",
                              groups_moved=len(moved))
            rows = self._collect_handoff()
            # install the table BEFORE redistribution: _route must send
            # the lifted rows to their NEW owners
            self._assignment = None if assignment.is_contiguous \
                else assignment
            self._rebuild_mesh_plane(self.P)
            chaos.fault_point("rebalance.handoff", stage="commit",
                              groups_moved=len(moved))
            resident_rows, spilled_rows = self._redistribute_handoff(rows)
        self.rebalances_completed += 1
        self.last_rebalance = {
            "groups_moved": int(len(moved)),
            "rows_moved": int(len(rows["key_id"])),
            "resident_rows": resident_rows,
            "spilled_rows": spilled_rows,
            "seconds": time.perf_counter() - t0,
        }
        return self.last_rebalance

    @property
    def key_group_assignment(self):
        """The EFFECTIVE assignment (explicit table, or the contiguous
        default) — what serving-side ``host_of_key_group`` routing must
        follow after a rebalance."""
        from flink_tpu.state.keygroups import KeyGroupAssignment

        if self._assignment is not None:
            return self._assignment
        return KeyGroupAssignment.contiguous(
            self.P, self.max_parallelism, self.key_group_range)

    def _collect_handoff(self, skip_shards=()) -> Dict[str, np.ndarray]:
        """Lift every logical row off the current mesh: key/namespace/
        leaf columns plus the handoff metadata restore does not need —
        per-row dirtiness (delta-snapshot correctness), recency clocks
        (who stays resident on a scale-down), and residency.

        ``skip_shards``: shards whose state must NOT be read (a lost
        device — its plane slice and spill tier are gone; partial
        failover restores that range from its checkpoint unit instead).
        """
        leaves = self.agg.leaves
        paged = bool(getattr(self, "_paged", False))
        accs_host = jax.device_get(list(self.accs))  # ONE batched D2H
        keys: List[np.ndarray] = []
        nss: List[np.ndarray] = []
        dirty: List[np.ndarray] = []
        touch: List[np.ndarray] = []
        resident: List[np.ndarray] = []
        leaf_cols: List[List[np.ndarray]] = [[] for _ in leaves]
        skip = set(skip_shards)
        for p in range(self.P):
            if p in skip:
                continue
            idx = self.indexes[p]
            used = idx.used_slots()
            if len(used):
                u_ns = np.asarray(idx.slot_ns[used], dtype=np.int64)
                keys.append(np.asarray(idx.slot_key[used],
                                       dtype=np.int64))
                nss.append(u_ns)
                dirty.append(np.asarray(self._dirty[p][used], dtype=bool))
                if paged:
                    touch.append(self._slot_touch[p][used].copy())
                else:
                    nt = self._ns_touch[p]
                    touch.append(np.asarray(
                        [nt.get(int(x), 0) for x in u_ns],
                        dtype=np.int64))
                resident.append(np.ones(len(used), dtype=bool))
                for i in range(len(leaves)):
                    leaf_cols[i].append(accs_host[i][p][used])
            sp = self.spills[p]
            if len(sp) == 0:
                continue
            dirty_set = set(sp.dirty_namespaces())
            pmap = self._pmaps[p] if paged else None
            for ns in sp.namespaces:
                entry = sp.peek(int(ns))
                if entry is None:
                    continue
                ekeys = np.asarray(entry["key_id"], dtype=np.int64)
                if "ns" in entry:  # paged page: per-row ns + tombstones
                    rns = np.asarray(entry["ns"], dtype=np.int64)
                    alive = pmap.live_row_mask(int(ns), rns)
                    if not alive.any():
                        continue
                    ekeys, rns = ekeys[alive], rns[alive]
                    # only rows not shipped by a snapshot since their
                    # eviction are still dirty (tier flag gates, the
                    # per-row column refines — same rule as
                    # _spill_delta_append)
                    row_dirty = (
                        np.asarray(entry["dirty"], dtype=bool)[alive]
                        if int(ns) in dirty_set
                        else np.zeros(len(ekeys), dtype=bool))
                    sel = alive
                else:
                    rns = np.full(len(ekeys), int(ns), dtype=np.int64)
                    row_dirty = np.full(len(ekeys), int(ns) in dirty_set,
                                        dtype=bool)
                    sel = slice(None)
                if len(ekeys) == 0:
                    continue
                keys.append(ekeys)
                nss.append(rns)
                dirty.append(row_dirty)
                touch.append(np.zeros(len(ekeys), dtype=np.int64))
                resident.append(np.zeros(len(ekeys), dtype=bool))
                for i, l in enumerate(leaves):
                    leaf_cols[i].append(
                        np.asarray(entry[f"leaf_{i}"],
                                   dtype=l.dtype)[sel])
        if not keys:
            return {
                "key_id": np.empty(0, dtype=np.int64),
                "namespace": np.empty(0, dtype=np.int64),
                "dirty": np.empty(0, dtype=bool),
                "touch": np.empty(0, dtype=np.int64),
                "resident": np.empty(0, dtype=bool),
                **{f"leaf_{i}": np.empty(0, dtype=l.dtype)
                   for i, l in enumerate(leaves)},
            }
        return {
            "key_id": np.concatenate(keys),
            "namespace": np.concatenate(nss),
            "dirty": np.concatenate(dirty),
            "touch": np.concatenate(touch),
            "resident": np.concatenate(resident),
            **{f"leaf_{i}": np.concatenate(leaf_cols[i])
               for i in range(len(leaves))},
        }

    def _rebuild_mesh_plane(self, new_shards: int, devices=None) -> None:
        """Re-point the engine at a fresh [new_shards, capacity] plane:
        new mesh, indexes, spill tiers, identity accumulators and step
        programs. Job-lifetime state survives: the recency clock, the
        namespace-layout spill counters, and the delta tombstones
        (_freed_ns) are NOT reset — only the per-mesh containers are."""
        from flink_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(new_shards, devices=devices)
        self.release_memory()
        self.mesh = mesh
        self.P = int(mesh.devices.size)
        t = self.host_topology
        if t is not None and t.num_shards != self.P:
            # a reshard / partial failover changed the device count:
            # the (hosts, local) factorization no longer describes the
            # mesh — fall back to the flat single-axis exchange (the
            # evacuated mesh is host-local until a re-plan re-declares
            # a topology)
            self.host_topology = None
        # the replica's metadata shadow describes the OLD plane — the
        # next boundary publish rebuilds it over the new mesh
        self._rep_rebuild = True
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        if hasattr(self, "_replicated"):
            self._replicated = NamedSharding(mesh, P())
        clock = getattr(self, "_touch_clock", 0)
        # the old tiers' fs-resident pages would otherwise be orphaned
        # on disk (collect only peeks) — reclaim them before rebinding
        for sp in self.spills:
            for ns in list(sp.namespaces):
                sp.discard(int(ns))
        if getattr(self, "_paged", False):
            # fold the outgoing maps' lifetime counters into the
            # engine-held dict spill_counters() also sums — a rescale
            # must not zero the job's monotonic spill gauges
            for pm in self._pmaps:
                for k, v in pm.counters().items():
                    self._ns_counters[k] += v
        self.indexes = self._make_shard_indexes()
        self._init_spill(self._spill_dir, self._spill_host_max_bytes)
        self._touch_clock = clock  # recency survives the move
        if getattr(self, "_paged", False):
            self._init_paged()
        self._reserve_rows(self.P * self.capacity)
        self.accs = tuple(
            jax.device_put(
                jnp.full((self.P, self.capacity), leaf.identity,
                         dtype=leaf.dtype),
                self._sharding)
            for leaf in self.agg.leaves)
        self._build_steps()
        self._dirty = np.zeros((self.P, self.capacity), dtype=bool)
        # sticky bucket sizes are per-mesh-shape dispatch amortizers
        self._gather_bucket = 0
        self._reset_bucket = 0
        self._fire_bucket = 0
        self._merge_bucket = 0

    def _redistribute_handoff(
            self, rows: Dict[str, np.ndarray]) -> Tuple[int, int]:
        """Land the collected rows on their new owners (the same
        key-group formula the data path routes by). Returns
        (resident_rows, spilled_rows).

        Residency policy under a device budget: previously-resident rows
        stay resident while they fit; on a scale-down the hottest rows
        (by carried recency clock) win and the overflow lands in the new
        shard's spill tier. The namespace layout decides per NAMESPACE
        (its eviction unit — a namespace split between device and tier
        would double-apply on the next reload), the paged layout per ROW
        (its pages already span namespaces)."""
        leaves = self.agg.leaves
        keys = rows["key_id"]
        nss = rows["namespace"]
        n = len(keys)
        if n == 0:
            return 0, 0
        paged = bool(getattr(self, "_paged", False))
        shards = self._route(keys)
        stay = rows["resident"].copy()
        if self._spill_active:
            # slot 0 is the reserved identity row — usable capacity is
            # one short of the budget
            budget = self.max_device_slots - 1
            if paged:
                for p in range(self.P):
                    sel = np.nonzero(stay & (shards == p))[0]
                    if len(sel) > budget:
                        order = np.argsort(rows["touch"][sel],
                                           kind="stable")
                        stay[sel[order[: len(sel) - budget]]] = False
            else:
                for p in range(self.P):
                    sel = np.nonzero(shards == p)[0]
                    if not len(sel):
                        continue
                    uniq, inv = np.unique(nss[sel], return_inverse=True)
                    grp_res = np.zeros(len(uniq), dtype=bool)
                    np.logical_or.at(grp_res, inv, rows["resident"][sel])
                    grp_touch = np.zeros(len(uniq), dtype=np.int64)
                    np.maximum.at(grp_touch, inv, rows["touch"][sel])
                    grp_rows = np.bincount(inv, minlength=len(uniq))
                    stay_grp = np.zeros(len(uniq), dtype=bool)
                    free = budget
                    cand = np.nonzero(grp_res)[0]
                    for g in cand[np.argsort(-grp_touch[cand],
                                             kind="stable")].tolist():
                        if grp_rows[g] <= free:
                            stay_grp[g] = True
                            free -= int(grp_rows[g])
                    stay[sel] = stay_grp[inv]
        # resident rows: resolve every slot FIRST (inserts may grow the
        # plane; growth must settle before the host blocks are built),
        # then land all shards' values in ONE batched put program
        per_shard: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for p in range(self.P):
            sel = np.nonzero(stay & (shards == p))[0]
            if len(sel):
                per_shard[p] = (sel, self.indexes[p].lookup_or_insert(
                    keys[sel], nss[sel]))
        if per_shard:
            B = sticky_bucket(
                max(len(sel) for sel, _ in per_shard.values()),
                self._reload_bucket)
            self._reload_bucket = B
            slot_block = np.zeros((self.P, B), dtype=np.int32)
            val_blocks = [np.full((self.P, B), l.identity, dtype=l.dtype)
                          for l in leaves]
            for p, (sel, slots) in per_shard.items():
                m = len(sel)
                slot_block[p, :m] = slots
                for i in range(len(leaves)):
                    val_blocks[i][p, :m] = rows[f"leaf_{i}"][sel]
                self._dirty[p, slots] = rows["dirty"][sel]
                if paged:
                    self._slot_touch[p][slots] = rows["touch"][sel]
                elif self._spill_active:
                    self._touch(p, np.unique(nss[sel]).tolist())
            self.accs = self._put_step(
                self.accs, self._put_sharded(slot_block),
                tuple(self._put_sharded(v) for v in val_blocks))
        # cold rows re-home into the new shards' spill tiers, dirtiness
        # intact (pages for the paged layout, per-ns entries otherwise)
        cold_total = 0
        cold = ~stay
        if cold.any():
            for p in range(self.P):
                sel = np.nonzero(cold & (shards == p))[0]
                if not len(sel):
                    continue
                cold_total += len(sel)
                c_keys, c_nss = keys[sel], nss[sel]
                c_dirty = rows["dirty"][sel]
                c_leaves = [rows[f"leaf_{i}"][sel]
                            for i in range(len(leaves))]
                if paged:
                    from flink_tpu.state.paged_spill import (
                        restore_into_pages,
                    )

                    restore_into_pages(
                        self.spills[p], self._pmaps[p], c_keys, c_nss,
                        c_leaves,
                        page_rows=max(self.indexes[p].capacity // 8,
                                      1024),
                        dirty=c_dirty)
                else:
                    order = np.argsort(c_nss, kind="stable")
                    s_ns, s_keys = c_nss[order], c_keys[order]
                    s_dirty = c_dirty[order]
                    s_leaves = [l[order] for l in c_leaves]
                    bounds = np.nonzero(np.diff(s_ns))[0] + 1
                    starts = np.concatenate(([0], bounds))
                    stops = np.concatenate((bounds, [len(s_ns)]))
                    sp = self.spills[p]
                    for a, b in zip(starts.tolist(), stops.tolist()):
                        ns = int(s_ns[a])
                        entry = {"key_id": s_keys[a:b],
                                 **{f"leaf_{i}": s_leaves[i][a:b]
                                    for i in range(len(leaves))}}
                        sp.put(ns, entry,
                               dirty=bool(s_dirty[a:b].any()))
        return int(stay.sum()), cold_total


    # ---------------------------------------------- partial failover (shard)

    #: report dict of the most recent shard loss (None until the first)
    last_shard_loss: Optional[Dict[str, object]] = None

    def shard_key_groups(self) -> List[Tuple[int, int]]:
        """GLOBAL ``(first, last)`` inclusive key groups per shard —
        the unit of failure/recovery, and the split shard-granular
        checkpoints key their units by (the exact inverse of
        ``shard_records``' routing formula). Undefined under a live
        rebalanced assignment (a shard's groups are no longer ONE
        range) — use :meth:`shard_key_group_runs` there."""
        from flink_tpu.state.keygroups import shard_key_group_ranges

        if self._assignment is not None:
            raise ValueError(
                "shard->key-group ownership is non-contiguous under a "
                "live rebalanced assignment — shard_key_group_runs() "
                "gives the per-run decomposition")
        return shard_key_group_ranges(self.P, self.max_parallelism,
                                      self.key_group_range)

    def shard_key_group_runs(self) -> List[Tuple[int, int, int]]:
        """GLOBAL ``(first, last, shard)`` maximal same-shard runs in
        key-group order — the checkpoint-unit granularity that stays
        well-defined under a rebalanced assignment (contiguous layout:
        exactly one run per shard)."""
        if self._assignment is not None:
            return self._assignment.runs()
        return [(g0, g1, p) for p, (g0, g1)
                in enumerate(self.shard_key_groups())]

    def lose_shard(self, dead: int) -> Tuple[int, int]:
        """Simulated device loss of shard ``dead``: its resident plane
        slice, spill tier and key-range metadata are gone WHOLESALE
        (the TaskManager-loss failure domain). Survivors' fences drain,
        their rows lift intact (dirtiness + recency preserved — the
        reshard machinery), the mesh rebuilds over the remaining
        ``P - 1`` devices, and the survivors' rows land on their new
        owners. Returns the DEAD shard's (first, last) key groups — the
        caller then restores exactly that range from its checkpoint
        unit (:meth:`restore_key_groups`) and replays only that range's
        records from the unit's source position.

        Like ``reshard``, not exception-atomic: a failure mid-evacuation
        falls back to whole-job checkpoint restore.
        """
        return self.lose_shards([dead])

    def lose_shards(self, dead) -> Tuple[int, int]:
        """Multi-shard loss in ONE evacuation — the HOST failure
        domain: a lost process takes its whole contiguous slice of
        shards (``HostTopology.shards_of_host``), survivors evacuate
        once, the mesh rebuilds over ``P - k`` devices, and the caller
        restores the dead shards' ``k`` checkpoint units. The dead
        shards must be CONTIGUOUS in flat shard order (hosts are, by
        construction — host-major layout), so the merged key-group
        span ``(first, last)`` returned covers exactly their units and
        the bounded replay is one contiguous range."""
        if self._assignment is not None:
            raise ValueError(
                "partial failover under a live rebalanced assignment is "
                "not supported: a dead shard's groups are no longer one "
                "contiguous range, so the bounded contiguous replay "
                "contract does not hold — whole-job restore applies")
        dead_set = sorted({int(d) for d in dead})
        if not dead_set:
            raise ValueError("no shards to lose")
        for d in dead_set:
            if not (0 <= d < self.P):
                raise ValueError(
                    f"no shard {d} on a {self.P}-shard mesh")
        if dead_set != list(range(dead_set[0], dead_set[-1] + 1)):
            raise ValueError(
                f"dead shards must be contiguous (a host's slice), "
                f"got {dead_set}")
        if len(dead_set) >= self.P:
            raise ValueError(
                "cannot partially fail over the whole mesh — "
                "whole-job restore applies")
        t0 = time.perf_counter()
        ranges = self.shard_key_groups()
        dead_range = (int(ranges[dead_set[0]][0]),
                      int(ranges[dead_set[-1]][1]))
        # quiesce the SURVIVORS: every in-flight dispatch must land
        # before the plane is torn down (the dead shards' fences are
        # moot — their state is discarded unread below)
        while self._dispatch_fences:
            # flint: disable=TRC01 -- failover quiesce: the mesh plane
            # is about to be rebuilt, in-flight dispatches must land
            self._dispatch_fences.popleft().block_until_ready()
        rows = self._collect_handoff(skip_shards=set(dead_set))
        devices = [d for i, d in enumerate(self.mesh.devices.flat)
                   if i not in dead_set]
        old_p = self.P
        self._rebuild_mesh_plane(old_p - len(dead_set),
                                 devices=devices)
        resident_rows, spilled_rows = self._redistribute_handoff(rows)
        # the dead ranges' host metadata dies with their shards (engine
        # hook: session intervals for the window engines' global book
        # there is nothing per-key to drop)
        self._drop_meta_key_groups(
            range(dead_range[0], dead_range[1] + 1))
        wd = self._watchdog
        if wd is not None:
            # survivors renumber 0..P-k-1; the dead device ids stay in
            # the watchdog's quarantine HISTORY for budget accounting
            wd.rebind(self.P,
                      [d.id for d in self.mesh.devices.flat])
        self.last_shard_loss = {
            "dead_shard": dead_set[0], "dead_shards": dead_set,
            "from": old_p, "to": self.P,
            "key_groups": dead_range,
            "survivor_rows": int(len(rows["key_id"])),
            "resident_rows": resident_rows,
            "spilled_rows": spilled_rows,
            "seconds": time.perf_counter() - t0,
        }
        return dead_range

    def restore_key_groups(self, snap: Dict[str, object],
                           groups) -> int:
        """Partial restore INTO a live engine: land only ``groups``'
        rows (survivors untouched) and merge the unit's metadata (the
        engine hook rolls watermark/staleness guards back to the
        checkpoint so the range's replayed records are accepted).
        Restored rows are CLEAN — they are in the checkpoint, so the
        next delta must not re-ship them; survivors keep their genuine
        dirtiness. Returns rows restored."""
        # restored values bypass the scatter sites: the replica shadow
        # cannot tell them apart — republish wholesale
        self._rep_rebuild = True
        table = snap.get("table", {}) or {}
        key_ids = np.asarray(table.get("key_id", []), dtype=np.int64)
        gset = np.asarray(sorted(int(g) for g in groups),
                          dtype=np.int64)
        n_restored = 0
        if len(key_ids):
            kg = table.get("key_group")
            kg = (np.asarray(kg, dtype=np.int64) if kg is not None
                  else assign_key_groups(key_ids, self.max_parallelism))
            keep = np.isin(kg, gset)
            key_ids = key_ids[keep]
            namespaces = np.asarray(table["namespace"],
                                    dtype=np.int64)[keep]
            leaves = [np.asarray(table[f"leaf_{i}"])[keep]
                      for i in range(len(self.agg.leaves))]
            n_restored = int(len(key_ids))
        if n_restored:
            shards = self._route(key_ids)
            if getattr(self, "_paged", False):
                from flink_tpu.state.paged_spill import (
                    restore_into_pages,
                )

                for p in range(self.P):
                    mask = shards == p
                    if not mask.any():
                        continue
                    # APPEND: the survivors' pages must stay intact;
                    # the restored namespaces (per-session sids) were
                    # never held by the surviving tiers
                    restore_into_pages(
                        self.spills[p], self._pmaps[p], key_ids[mask],
                        namespaces[mask], [l[mask] for l in leaves],
                        page_rows=max(self.indexes[p].capacity // 8,
                                      1024),
                        append=True)
            else:
                # land resident: resolve all slots first (growth must
                # settle), then ONE batched put program for all shards
                per_shard: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
                for p in range(self.P):
                    mask = shards == p
                    if not mask.any():
                        continue
                    if self._spill_active:
                        self._reserve(p, key_ids[mask],
                                      namespaces[mask])
                    slots = self.indexes[p].lookup_or_insert(
                        key_ids[mask], namespaces[mask])
                    per_shard[p] = (np.nonzero(mask)[0], slots)
                B = sticky_bucket(
                    max(len(s) for _, s in per_shard.values()),
                    self._reload_bucket)
                self._reload_bucket = B
                slot_block = np.zeros((self.P, B), dtype=np.int32)
                val_blocks = [
                    np.full((self.P, B), l.identity, dtype=l.dtype)
                    for l in self.agg.leaves]
                for p, (sel, slots) in per_shard.items():
                    m = len(sel)
                    slot_block[p, :m] = slots
                    for i in range(len(val_blocks)):
                        val_blocks[i][p, :m] = leaves[i][sel]
                    # restored rows are the checkpoint's — clean
                    self._dirty[p, slots] = False
                    if self._spill_active:
                        self._touch(p, np.unique(
                            namespaces[sel]).tolist())
                self.accs = self._put_step(
                    self.accs, self._put_sharded(slot_block),
                    tuple(self._put_sharded(v) for v in val_blocks))
        self._merge_restored_meta(snap, groups)
        return n_restored

    # engine hooks (window engines: global book; session engines:
    # per-key interval metadata) ------------------------------------------

    def _drop_meta_key_groups(self, groups) -> None:
        """Discard the host metadata owned by ``groups`` (no-op for
        engines whose lifecycle metadata carries no per-key state)."""

    def _merge_restored_meta(self, snap: Dict[str, object],
                             groups) -> None:
        """Fold a checkpoint unit's metadata for ``groups`` into the
        live engine (partial failover)."""

    def _filter_meta_snapshot(self, snap: Dict[str, object],
                              groups) -> Dict[str, object]:
        """The non-table part of ``snap`` restricted to ``groups`` —
        default: global metadata replicates whole into every unit."""
        return {k: v for k, v in snap.items() if k != "table"}

    def _merge_meta_snapshots(self, units: List[Dict[str, object]]
                              ) -> Dict[str, object]:
        """Merge units' metadata for a whole-job restore assembled from
        (possibly different-age) shard units."""
        raise NotImplementedError

    #: delta/tombstone fields that replicate whole into every unit —
    #: applying another range's tombstones to a unit's base is a no-op
    #: (the base holds no rows of that range), so replication is safe
    #: and keeps each unit independently restorable
    _UNIT_PASSTHROUGH = ("__delta__", "freed_namespaces",
                         "tombstone_key_id", "tombstone_namespace")

    def snapshot_sharded(self, mode: str = "full"
                         ) -> Dict[Tuple[int, int], Dict[str, object]]:
        """One independently-restorable unit per shard: the logical
        snapshot split by the current shards' key-group ranges (rows by
        their ``key_group`` column — the delta machinery keeps
        increments per-shard through the same split), plus each unit's
        slice of the metadata. The union of the units is exactly
        ``snapshot(mode)``."""
        snap = self.snapshot(mode)
        table = snap.get("table", {}) or {}
        kg = np.asarray(table.get("key_group", ()), dtype=np.int64)
        units: Dict[Tuple[int, int], Dict[str, object]] = {}
        # one unit per maximal same-shard RUN: under the contiguous
        # layout that is exactly one unit per shard (unchanged); under a
        # rebalanced assignment a shard contributes one unit per run it
        # owns, and the union of units is still exactly snapshot(mode)
        for g0, g1, _p in self.shard_key_group_runs():
            if len(kg):
                mask = (kg >= g0) & (kg <= g1)
                unit_table = {
                    k: (v if k in self._UNIT_PASSTHROUGH
                        else np.asarray(v)[mask])
                    for k, v in table.items()
                }
            else:
                unit_table = dict(table)
            units[(int(g0), int(g1))] = {
                "table": unit_table,
                **self._filter_meta_snapshot(
                    snap, range(int(g0), int(g1) + 1)),
            }
        return units

    def merge_unit_snapshots(self, units: List[Dict[str, object]]
                             ) -> Dict[str, object]:
        """Reassemble one engine snapshot from shard units (whole-job
        restore; units may come from DIFFERENT checkpoints when a torn
        unit fell back to an older complete one — the caller replays
        each range from its own unit's source position)."""
        tables = [u.get("table", {}) or {} for u in units]
        tables = [t for t in tables if t]
        merged: Dict[str, object] = {}
        if tables:
            cols = set().union(*(set(t) for t in tables))
            for k in sorted(cols):
                parts = [np.asarray(t[k]) for t in tables if k in t]
                if k == "__delta__":
                    merged[k] = np.asarray(True)
                elif k == "freed_namespaces":
                    merged[k] = (np.unique(np.concatenate(parts))
                                 if parts else np.empty(0,
                                                        dtype=np.int64))
                else:
                    # tombstone_key_id / tombstone_namespace are ROW-
                    # PAIRED parallel columns (apply_table_delta packs
                    # them): a per-column unique would break the pair
                    # correspondence — plain concatenation keeps it
                    # (duplicate pairs apply idempotently)
                    merged[k] = (np.concatenate(parts) if parts
                                 else np.empty(0, dtype=np.int64))
        return {"table": merged, **self._merge_meta_snapshots(units)}


class MeshPagedSpillSupport(MeshSpillSupport):
    """Paged (cohort) spill for session-shaped mesh state — the mesh form
    of the single-device ``spill_layout="pages"`` machinery
    (flink_tpu.state.paged_spill, shared): per shard, the unit of
    movement is an eviction cohort of the coldest rows (slot-granular
    touch clocks, not namespace recency), reloads extract exactly the
    requested rows by stored row index and leave LAZY TOMBSTONES in
    their pages (space comes back via threshold compaction, never
    read-path rewrites), and the host index runs registry-free
    (``track_namespaces=False`` — one row per session id makes the
    per-namespace registry O(live sessions) Python per batch).

    Device traffic stays batched across shards: all shards' page reloads
    land in ONE put program, and all shards short on headroom evict in
    ONE gather + ONE reset program per round (the other shards' rows
    identity no-ops)."""

    def _init_paged(self) -> None:
        from flink_tpu.state.paged_spill import PagedSpillMap

        #: one membership map (+ counters) per shard — spilled pages are
        #: shard-local like the device rows
        self._pmaps = [PagedSpillMap() for _ in range(self.P)]
        # latency tier: fire-path extractions queue their page sweeps
        # (reap/compact) instead of running them inline — the engine
        # drains the queue on its next ingest step, keeping the fire
        # span a bounded delta (space reclamation is time-insensitive)
        for pm in self._pmaps:
            pm.defer_sweeps = True
        #: [P, capacity] per-slot touch clocks (the paged analog of the
        #: namespace recency map)
        self._slot_touch = np.zeros((self.P, self.capacity),
                                    dtype=np.int64)

    def _drain_deferred_sweeps(self) -> None:
        """Run the page sweeps queued by fire-path extractions (ingest
        boundary — see PagedSpillMap.defer_sweeps)."""
        from flink_tpu.state.paged_spill import run_deferred_sweeps

        for p, pm in enumerate(self._pmaps):
            if pm.deferred_pages:
                run_deferred_sweeps(self.spills[p], pm)

    def _paged_grow(self, new_capacity: int) -> None:
        if new_capacity <= self._slot_touch.shape[1]:
            return
        grown = np.zeros((self.P, new_capacity), dtype=np.int64)
        grown[:, : self._slot_touch.shape[1]] = self._slot_touch
        self._slot_touch = grown

    def spill_counters(self) -> Dict[str, int]:
        """Spill traffic summed over shards (zeros when unbudgeted);
        the namespace-layout engine counters ride along so a
        spill_layout="namespaces" session engine still reports."""
        out = super().spill_counters()
        for pm in getattr(self, "_pmaps", ()):
            for k, v in pm.counters().items():
                out[k] += v
        return out

    def _resolve_slots_paged(
            self, per_shard: Dict[int, Tuple[np.ndarray, np.ndarray]],
            fresh: Optional[Dict[int, np.ndarray]] = None,
            hints: Optional[Dict[int, np.ndarray]] = None,
    ) -> Dict[int, np.ndarray]:
        """Batched slot resolution over shards with page reload and
        cohort eviction: resident rows of THIS batch get a fresh clock
        (protecting them from the eviction the batch itself triggers),
        missing pairs reload by page (ONE put program for all shards),
        then only the still-missing pairs insert.

        ``fresh``: optional per-shard bool masks marking pairs the
        caller KNOWS were allocated this batch (fresh session ids from
        the monotonic allocator) — they cannot be resident or paged, so
        they skip both the index probe and the page query and go
        straight to insert. At high-cardinality shapes most of a
        batch's sessions are fresh, and the skipped page query is a
        sorted-match over the full spilled-row map.

        ``hints``: per-shard folded device slots from the native
        session-metadata plane (-1 unknown). A hint is VERIFIED against
        the shard index's metadata views (``verify_slot_hints``) before
        use — verified rows skip the hash probe entirely, stale folds
        fall back to it, so the state evolution is identical to the
        hint-free path (same hits, same misses, same insert order).

        Callers pass session-shaped pairs (one row per globally-unique
        sid), so no dedup pass runs here and the insert probe is
        restricted to the pre-lookup's misses — the resident-majority
        steady state pays ONE native hash probe per row. Duplicate
        pairs stay correct (the insert dedups); they only overcount the
        eviction headroom."""
        from flink_tpu.state.paged_spill import reload_rows_for

        self._touch_clock += 1
        clock = self._touch_clock
        leaf_dtypes = [l.dtype for l in self.agg.leaves]
        reloads: Dict[int, Tuple[np.ndarray, List[np.ndarray]]] = {}
        extracted: Dict[int, Tuple] = {}
        out: Dict[int, np.ndarray] = {}
        missing_by_shard: Dict[int, np.ndarray] = {}
        needs: Dict[int, int] = {}
        for p, (keys, nss) in per_shard.items():
            keys = np.asarray(keys, dtype=np.int64)
            nss = np.asarray(nss, dtype=np.int64)
            idx = self.indexes[p]
            fr = fresh.get(p) if fresh is not None else None
            hint = hints.get(p) if hints is not None else None
            if hint is not None:
                pre = resolve_slot_hints(idx, keys, nss, hint, skip=fr)
            elif fr is not None and fr.any():
                pre = np.full(len(keys), -1, dtype=np.int32)
                probe = ~fr
                if probe.any():
                    pre[probe] = idx.lookup(keys[probe], nss[probe])
            else:
                pre = idx.lookup(keys, nss)
                fr = None
            hit = pre >= 0
            self._slot_touch[p][pre[hit]] = clock
            missing = ~hit
            n_missing = int(missing.sum())
            if n_missing:
                if len(self._pmaps[p]):
                    # pure host work: rows leave their pages by index
                    # (lazy tombstones — see paged_spill); fresh pairs
                    # are never spilled, so only the non-fresh misses
                    # query the page map
                    q = missing if fr is None else (missing & ~fr)
                    rl = reload_rows_for(self.spills[p], self._pmaps[p],
                                         nss[q], leaf_dtypes) \
                        if q.any() else None
                    if rl is not None:
                        extracted[p] = rl
                missing_by_shard[p] = missing
                needs[p] = n_missing
            out[p] = pre
            per_shard[p] = (keys, nss)
        # one batched eviction round covers every shard short on
        # headroom (one gather + one reset, not one pair per shard)
        if needs:
            self._make_headroom_paged_multi(needs)
        for p, rl in extracted.items():
            rkeys, rns, rdirty, rvals = rl
            rslots = self.indexes[p].lookup_or_insert(rkeys, rns)
            # reloaded rows keep their dirtiness (not snapshotted
            # since) and take the current clock — the cohort is
            # likely about to fire
            self._dirty[p, rslots] = rdirty
            self._slot_touch[p][rslots] = clock
            reloads[p] = (rslots.astype(np.int32), rvals)
        if reloads:
            B = sticky_bucket(max(len(r[0]) for r in reloads.values()),
                              self._reload_bucket)
            self._reload_bucket = B
            slot_block = np.zeros((self.P, B), dtype=np.int32)
            val_blocks = [np.full((self.P, B), l.identity, dtype=l.dtype)
                          for l in self.agg.leaves]
            for p, (rslots, rvals) in reloads.items():
                n = len(rslots)
                slot_block[p, :n] = rslots
                for i in range(len(val_blocks)):
                    val_blocks[i][p, :n] = rvals[i]
            with flight.span("device.dispatch"):
                self.accs = self._put_step(
                    self.accs, self._put_sharded(slot_block),
                    tuple(self._put_sharded(v) for v in val_blocks))
        for p, missing in missing_by_shard.items():
            keys, nss = per_shard[p]
            # insert ONLY the pre-lookup misses (reloaded rows resolve
            # as hits here; genuinely fresh sids insert)
            slots = out[p]
            slots[missing] = self.indexes[p].lookup_or_insert(
                keys[missing], nss[missing])
            self._slot_touch[p][slots[missing]] = clock
        return out

    def _make_headroom_paged(self, p: int, needed: int) -> None:
        self._make_headroom_paged_multi({p: needed})

    def _make_headroom_paged_multi(self, needs: Dict[int, int]) -> None:
        """Evict cold cohorts for EVERY shard short on headroom in one
        round: however many shards must evict, the batch costs one
        gather + one reset program (per-shard eviction paid a dispatch
        + device sync per shard — at the thrashing shape most batches
        evict on ~6 of 8 shards, so batching cuts the eviction syncs
        ~6x)."""
        pending = {p: n for p, n in needs.items()
                   if self.indexes[p].free_headroom() < n}
        while pending:
            self._evict_cohorts({p: self._choose_eviction_cohort(p)
                                 for p in pending})
            pending = {p: n for p, n in pending.items()
                       if self.indexes[p].free_headroom() < n}

    def _evict_cold_paged(self, p: int) -> None:
        """Single-shard form (kept for tests/direct callers)."""
        self._evict_cohorts({p: self._choose_eviction_cohort(p)})

    def _choose_eviction_cohort(self, p: int) -> np.ndarray:
        """Shard ``p``'s coldest slots (touch < current clock) — the
        rows this round's page will carry."""
        from flink_tpu.state.slot_table import SlotTableFullError

        idx = self.indexes[p]
        used = idx.used_slots()
        touch = self._slot_touch[p][used]
        evictable = used[touch < self._touch_clock]
        if len(evictable) == 0:
            raise SlotTableFullError(
                f"shard {p}: device slot budget exhausted and every "
                "resident row was touched by the current batch — raise "
                "state.slot-table.max-device-slots or reduce batch size")
        # a quarter of the table per round: every round pays one
        # gather + one D2H sync + a cohort-choice pass over the used
        # set, so fewer/larger cohorts amortize the fixed costs; the
        # lazy-tombstone tier keeps over-eviction cheap (a re-touched
        # row reloads by index, no page rewrite)
        target = min(max(idx.capacity // 4, 1024), len(evictable))
        if target < len(evictable):
            et = self._slot_touch[p][evictable]
            sel = np.argpartition(et, target - 1)[:target]
            chosen = evictable[sel]
        else:
            chosen = evictable
        return np.asarray(chosen, dtype=np.int32)

    def _evict_cohorts(self, cohorts: Dict[int, np.ndarray]) -> None:
        """Move each shard's chosen cohort to its spill tier as one
        page — ONE gather + ONE reset program for all shards (rows of
        non-evicting shards are identity no-ops)."""
        from flink_tpu.state.paged_spill import spill_page

        n_max = max(len(c) for c in cohorts.values())
        G = sticky_bucket(n_max, self._gather_bucket)
        self._gather_bucket = G
        block = np.zeros((self.P, G), dtype=np.int32)
        for p, chosen in cohorts.items():
            block[p, : len(chosen)] = chosen
        with flight.span("device.dispatch"):
            gathered = self._gather_step(self.accs,
                                         self._put_sharded(block))
            # ONE batched D2H
            gathered_host = self._harvest_get(gathered, "evict_harvest")
        for p, chosen in cohorts.items():
            idx = self.indexes[p]
            n = len(chosen)
            entry = {
                "key_id": np.asarray(idx.slot_key[chosen]),
                "ns": np.asarray(idx.slot_ns[chosen]),
                "dirty": self._dirty[p, chosen].copy(),
                **{f"leaf_{i}": g[p][:n]
                   for i, g in enumerate(gathered_host)},
            }
            # replica: a row evicted before it was ever published
            # resident must still enter the index cold at the next
            # boundary (the publish drains these events)
            self._rep_note_cold(p, entry["key_id"], entry["ns"])
            spill_page(self.spills[p], self._pmaps[p], entry)
            idx.free_slots(chosen)
            self._dirty[p, chosen] = False
        R = sticky_bucket(n_max, getattr(self, "_reset_bucket", 0))
        self._reset_bucket = R
        rb = np.zeros((self.P, R), dtype=np.int32)
        for p, chosen in cohorts.items():
            rb[p, : len(chosen)] = chosen
        with flight.span("device.dispatch"):
            self.accs = self._reset_step(self.accs,
                                         self._put_sharded(rb))

    def _free_rows_paged(self, p: int, slots: np.ndarray,
                         nss) -> None:
        """Slot-addressed free for the registry-free index (the caller
        resolved the rows this batch); spilled copies — rare, resolves
        reload first — are marked dead and their empty pages reaped."""
        from flink_tpu.state.paged_spill import drop_spilled_sessions

        if self._spill_active and len(self._pmaps[p]):
            drop_spilled_sessions(self.spills[p], self._pmaps[p],
                                  np.asarray(nss, dtype=np.int64))
        slots = np.asarray(slots, dtype=np.int32)
        if len(slots):
            self.indexes[p].free_slots(slots)
            self._dirty[p, slots] = False

    def _paged_restore_rows(self, key_ids: np.ndarray,
                            namespaces: np.ndarray,
                            leaves: List[np.ndarray]) -> None:
        """Paged restore: rows land in each shard's spill tier as
        page-sized entries and reload lazily by page."""
        from flink_tpu.state.paged_spill import restore_into_pages

        shards = self._route(key_ids)
        for p in range(self.P):
            mask = shards == p
            if not mask.any():
                if len(self._pmaps[p]):
                    restore_into_pages(  # clears stale pages
                        self.spills[p], self._pmaps[p],
                        np.empty(0, dtype=np.int64),
                        np.empty(0, dtype=np.int64),
                        [np.empty(0, dtype=l.dtype)
                         for l in self.agg.leaves], 1024)
                continue
            restore_into_pages(
                self.spills[p], self._pmaps[p], key_ids[mask],
                namespaces[mask], [l[mask] for l in leaves],
                page_rows=max(self.indexes[p].capacity // 8, 1024))


class MeshWindowEngine(MeshSpillSupport):
    """Windowed keyed aggregation sharded over a 1-D device mesh."""

    def __init__(
        self,
        assigner: WindowAssigner,
        agg: AggregateFunction,
        mesh: Mesh,
        capacity_per_shard: int = 1 << 16,
        max_parallelism: int = 128,
        allowed_lateness: int = 0,
        fire_projector=None,
        max_device_slots: int = 0,
        spill_dir: Optional[str] = None,
        spill_host_max_bytes: int = 0,
        key_group_range: Optional[Tuple[int, int]] = None,
        memory=None,
        max_dispatch_ahead: int = 2,
        shuffle_mode: str = "device",
        host_topology=None,
    ) -> None:
        self.assigner = assigner
        self.agg = agg
        self.shuffle_mode = self._check_shuffle_mode(shuffle_mode)
        #: dispatch-ahead depth (double-buffered by default; see
        #: MeshSpillSupport._init_pipeline)
        self.max_dispatch_ahead = max(int(max_dispatch_ahead or 1), 1)
        #: (first, last) inclusive GLOBAL key groups this engine owns; the
        #: mesh shards within the range (mesh x stage — see shard_records)
        self.key_group_range = key_group_range
        #: (MemoryManager, owner) — the [P, capacity] accumulator
        #: footprint is managed like the single-device table's
        self._memory = memory
        #: host-side (cross-shard) fired-row reduction; the single-device
        #: engine fuses this into the fire kernel, here it runs after the
        #: per-shard results are assembled (the per-shard transfer is
        #: already bounded by the fire bucket)
        self.fire_projector = fire_projector
        self.mesh = mesh
        self.P = int(mesh.devices.size)
        self._set_host_topology(host_topology)
        #: per-SHARD HBM slot budget — the raw
        #: state.slot-table.max-device-slots value, which is PER DEVICE
        #: (each shard owns one chip's HBM, so total capacity scales with
        #: the mesh while each chip stays bounded): beyond it, cold
        #: namespaces spill to the per-shard host/fs tier and reload on
        #: access (reference: RocksDBKeyedStateBackend.java — RocksDB
        #: state was never bounded by memory either)
        self.max_device_slots = int(max_device_slots or 0)
        self.capacity = max(int(capacity_per_shard), 1024)
        if self.max_device_slots:
            self.max_device_slots = max(self.max_device_slots, 1024)
            self.capacity = min(self.capacity, self.max_device_slots)
        self.max_parallelism = max_parallelism
        self.allowed_lateness = allowed_lateness
        if max_parallelism < self.P:
            raise ValueError(
                f"max_parallelism {max_parallelism} < mesh size {self.P}")

        # growable per-shard indexes: hot-key skew concentrating (key,
        # slice) pairs on one shard grows the table instead of killing the
        # job (SURVEY hard-part (e)); device arrays stay uniform [P, cap]
        # sized to the LARGEST shard index (SPMD shape requirement)
        self.indexes = self._make_shard_indexes()
        self._init_spill(spill_dir, spill_host_max_bytes)
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        self._replicated = NamedSharding(mesh, P())
        self._reserve_rows(self.P * self.capacity)
        self.accs: Tuple[jnp.ndarray, ...] = tuple(
            jax.device_put(
                jnp.full((self.P, self.capacity), leaf.identity,
                         dtype=leaf.dtype),
                self._sharding)
            for leaf in agg.leaves
        )
        self._build_steps()
        # window lifecycle metadata is global: watermarks and window ends are
        # aligned across shards
        self.book = SliceBookkeeper(assigner, allowed_lateness)
        # incremental-snapshot bookkeeping, the mesh form of
        # SlotTable._dirty: a [P, capacity] host bitmap of slots touched
        # since the last snapshot + namespaces freed since (tombstones)
        self._dirty = np.zeros((self.P, self.capacity), dtype=bool)
        #: freed-namespace tombstone chunks (int64 arrays, deduped at
        #: snapshot time)
        self._freed_ns: List[np.ndarray] = []
        self._gather_bucket = 0

    @property
    def late_records_dropped(self) -> int:
        return self.book.late_records_dropped

    # -------------------------------------------------------- jitted programs

    def _build_steps(self) -> None:
        (self._scatter_step, self._fire_step, self._reset_step,
         self._gather_step, self._put_step, self._merge_step,
         self._valued_scatter_step) = build_mesh_steps(self.mesh, self.agg)
        # the fused exchange+scatter pair (device shuffle mode); built
        # through the shared program cache regardless of mode so a
        # mode flip or a second tenant never pays a family build
        self._exchange_scatter_step = build_exchange_scatter(
            self.mesh, self.agg, valued=False)
        self._exchange_valued_step = build_exchange_scatter(
            self.mesh, self.agg, valued=True)
        if self._two_level_active():
            from flink_tpu.parallel.exchange2 import (
                build_exchange2_steps,
            )

            self._exchange2_steps = build_exchange2_steps(
                self.mesh, self.host_topology, self.agg, valued=False)
            self._exchange2_valued = build_exchange2_steps(
                self.mesh, self.host_topology, self.agg, valued=True)

    def _shard_index_grew(self, new_capacity: int) -> None:
        """One shard's index outgrew the device column count: widen the
        [P, capacity] arrays (all shards — SPMD shapes are uniform; the
        other shards' indexes keep their smaller capacities and simply
        address a prefix)."""
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


    def _put_sharded(self, host_block: np.ndarray) -> jnp.ndarray:
        return jax.device_put(host_block, self._sharding)

    # ---------------------------------------------------------------- ingest

    def _ns_group_plan(self, key_ids: np.ndarray,
                       slice_ends: np.ndarray) -> Optional[List[List[int]]]:
        """When one batch's touched-namespace working set exceeds the
        per-shard budget, plan namespace groups so only one group must be
        resident at a time (the mesh form of SlotTable.upsert's chunking;
        a single namespace whose per-shard key set alone exceeds the
        budget is the irreducible limit and fails loudly downstream).

        Cost of a namespace = max over shards of (resident rows + spilled
        rows + this batch's new pairs) — the slots it needs while its
        group is being scattered. Returns None when no chunking is needed.
        """
        from flink_tpu.state.slot_table import unique_pairs

        pk, pns, _ = unique_pairs(
            np.asarray(key_ids, dtype=np.int64),
            np.asarray(slice_ends, dtype=np.int64))
        uniq_ns = np.unique(pns)
        if len(uniq_ns) <= 1:
            return None
        budget = max(self.max_device_slots // 2, 1024)
        pshards = self._route(pk)
        costs: Dict[int, int] = {}
        for ns in uniq_ns.tolist():
            ns = int(ns)
            sel = pns == ns
            per_shard_new = np.bincount(pshards[sel], minlength=self.P)
            worst = 0
            for p in range(self.P):
                worst = max(
                    worst,
                    len(self.indexes[p].slots_for_namespace(ns))
                    + self.spills[p].rows(ns)
                    + int(per_shard_new[p]))
            costs[ns] = worst
        if sum(costs.values()) <= budget:
            return None
        groups: List[List[int]] = []
        cur: List[int] = []
        cur_cost = 0
        for ns in sorted(costs):
            c = costs[ns]
            if cur and cur_cost + c > budget:
                groups.append(cur)
                cur, cur_cost = [], 0
            cur.append(ns)
            cur_cost += c
        groups.append(cur)
        return groups if len(groups) > 1 else None

    def process_batch(self, batch: RecordBatch) -> None:
        if len(batch) == 0:
            return
        with self._flight_ingest() as ingest:
            if self._process_batch_inner(batch) is not False:
                ingest.work = len(batch)

    def _process_batch_inner(self, batch: RecordBatch):
        """Returns False where the batch was handed on as sub-batches
        (each its own ``batch.ingest``, stating its own events)."""
        n = len(batch)
        # batch boundary: the engine is consistent at a known source
        # position — the one point the watchdog may declare a shard dead
        self._wd_boundary()
        if (self.shuffle_mode == "device" and not self._spill_active
                and self._replica is None):
            # one native sweep over timestamps, keys and all the shards'
            # indexes, where the engine and the batch allow it: nothing
            # here wants a shard's records as one run (spill's residency
            # and reserve and the replica's marks do), the indexes are
            # native slice-partitioned ones and no record is late; else
            # the path below, with the same results
            with flight.span("prep.resolve") as resolve:
                capacity = self.capacity
                swept = resolve_slices_sharded(
                    self.indexes, batch.key_ids, batch.timestamps,
                    self._group_shard_table(), self.assigner.offset,
                    self.assigner.slice_width,
                    self.book.oldest_live_slice_end(), dirty=self._dirty)
                if swept is not None:
                    shards, rec_slots, uniq, _, resolve.work = swept
                    if self.capacity != capacity:
                        # an index grew inside the sweep: the slots past
                        # the old capacity had no place in the old map
                        self._dirty[shards, rec_slots] = True
                    self.book.register_slices(uniq, uniq=uniq)
                    flight.instant("resolve.sweep", work=n)
            if swept is not None:
                values, leaves, partial = self._values_of(batch)
                self._dispatch_device(shards, rec_slots, values, leaves,
                                      partial)
                return
        key_ids = batch.key_ids
        slice_ends = self.assigner.assign_slice_ends(batch.timestamps)
        if self._spill_active and n > 1:
            groups = self._ns_group_plan(key_ids, slice_ends)
            if groups is not None:
                for g in groups:
                    mask = np.isin(slice_ends, np.asarray(g))
                    if mask.any():
                        self._ingest_subbatch(batch.filter(mask))
                return False
        with flight.span("prep.resolve"):
            live = self.book.live_mask(slice_ends)
            if live is not None:
                key_ids, slice_ends = key_ids[live], slice_ends[live]
                batch = batch.filter(live)
                if len(batch) == 0:
                    return
            self.book.register_slices(slice_ends)

            # route to owning shard, bucket into [P, B] blocks
            shards = self._route(key_ids)
        values, leaves, partial = self._values_of(batch)
        if self.shuffle_mode == "device":
            self._process_batch_device(key_ids, slice_ends, shards,
                                       values, leaves, partial)
            return
        # pipelining: wait for a dispatch slot BEFORE rewriting the
        # pooled staging buffers, then bucket while the device still
        # runs the previous batches
        self._await_dispatch_slot()
        self._shuffle_pool.flip()
        counts, blocked = bucket_by_shard(
            shards, self.P,
            columns=[key_ids, slice_ends,
                     *[np.asarray(v, dtype=l.dtype)
                       for v, l in zip(values, leaves)]],
            fills=[0, 0, *[l.identity for l in leaves]],
            pool=self._shuffle_pool,
        )
        key_block, ns_block = blocked[0], blocked[1]
        value_blocks = blocked[2:]

        if self._spill_active:
            # reload spilled namespaces this batch touches (batched across
            # shards), then refresh recency
            touched = {p: np.unique(ns_block[p, :int(counts[p])])
                       for p in range(self.P) if int(counts[p])}
            self._ensure_resident(touched)
            for p, nss in touched.items():
                self._touch(p, nss.tolist())

        # per-shard slot assignment (host)
        B = key_block.shape[1]
        slot_block = np.zeros((self.P, B), dtype=np.int32)
        with flight.span("prep.resolve") as resolve:
            inserted = self._pairs_inserted()
            for p in range(self.P):
                c = int(counts[p])
                if not c:
                    continue
                self._reserve(p, key_block[p, :c], ns_block[p, :c])
                slot_block[p, :c] = self.indexes[p].lookup_or_insert(
                    key_block[p, :c], ns_block[p, :c])
                self._dirty[p, slot_block[p, :c]] = True
                self._rep_mark(p, slot_block[p, :c])
            resolve.work = self._pairs_inserted() - inserted

        step = self._valued_scatter_step if partial else self._scatter_step
        with flight.span("device.dispatch"):
            self.accs = step(
                self.accs,
                self._put_sharded(slot_block),
                tuple(self._put_sharded(v) for v in value_blocks),
            )
        self._push_dispatch_fence()

    def _values_of(self, batch: RecordBatch):
        """``(values, their leaves, partial)`` of a batch for the
        scatter."""
        from flink_tpu.runtime.local_agg import (
            is_partial_batch,
            partial_leaf_values,
        )

        if is_partial_batch(batch):
            # locally pre-aggregated rows (two-phase agg): one explicit
            # value per ACC leaf, folded with the valued scatter (the
            # mesh form of SlotTable.upsert_valued)
            return partial_leaf_values(batch, self.agg), self.agg.leaves, True
        return self.agg.map_input(batch), self.agg.input_leaves, False

    def _pairs_inserted(self) -> int:
        """(key, slice) pairs the shards' host indexes have given a slot
        so far (one batch's growth is its ``prep.resolve`` work)."""
        return sum(idx.pairs_inserted for idx in self.indexes)

    def _process_batch_device(self, key_ids, slice_ends, shards, values,
                              leaves, partial: bool) -> None:
        """Device-shuffle ingest: the host resolves slots (the index is
        host state) but never sorts or blocks the record columns — flat
        padded columns go up in ONE device_put and the fused
        exchange+scatter program (segment sort + all_to_all + scatter,
        one XLA program) routes them to their owner shards."""
        n = len(key_ids)
        with flight.span("prep.resolve") as resolve:
            inserted = self._pairs_inserted()
            # per-shard grouping for the HOST index work only: one stable
            # argsort over the destinations, contiguous slices per shard
            order = np.argsort(shards, kind="stable")
            counts = np.bincount(shards, minlength=self.P)
            offsets = np.zeros(self.P + 1, dtype=np.int64)
            np.cumsum(counts, out=offsets[1:])
            s_keys = key_ids[order]
            s_ns = slice_ends[order]
            if self._spill_active:
                touched = {
                    p: np.unique(s_ns[offsets[p]:offsets[p + 1]])
                    for p in range(self.P) if counts[p]}
                self._ensure_resident(touched)
                for p, nss in touched.items():
                    self._touch(p, nss.tolist())
            slots_sorted = np.empty(n, dtype=np.int32)
            for p in range(self.P):
                a, b = int(offsets[p]), int(offsets[p + 1])
                if a == b:
                    continue
                self._reserve(p, s_keys[a:b], s_ns[a:b])
                slots = self.indexes[p].lookup_or_insert(
                    s_keys[a:b], s_ns[a:b])
                slots_sorted[a:b] = slots
                self._dirty[p, slots] = True
                self._rep_mark(p, slots)
            rec_slots = np.empty(n, dtype=np.int32)
            rec_slots[order] = slots_sorted
            resolve.work = self._pairs_inserted() - inserted
        self._dispatch_device(shards, rec_slots, values, leaves, partial)

    def _dispatch_device(self, shards, rec_slots, values, leaves,
                         partial: bool) -> None:
        """Stage a batch's per-record (shard, slot) and values and hand
        them to the fused exchange+scatter program."""
        n = len(rec_slots)
        # pipelining: claim a dispatch slot BEFORE rewriting the pooled
        # flat staging buffers (their previous consumer must have
        # finished — the same fence discipline as the host blocks)
        self._await_dispatch_slot()
        self._shuffle_pool.flip()
        columns = [rec_slots,
                   *[np.asarray(v, dtype=l.dtype)
                     for v, l in zip(values, leaves)]]
        fills = [0, *[l.identity for l in leaves]]
        if self._two_level_active():
            # pod mesh: the two-level ICI/DCN exchange — stage 1 routes
            # by destination local index over the intra-host axis,
            # stage 2 batches the cross-host residue over the hosts
            # axis and scatters in global stream order (bit-identical
            # to the flat program; two dispatches so the recorder can
            # attribute ICI vs DCN time)
            from flink_tpu.parallel.exchange2 import (
                stage_two_level_exchange,
            )

            with flight.span("prep.stage") as stage:
                dst, staged, w1, w2 = stage_two_level_exchange(
                    shards, self.host_topology, columns=columns,
                    fills=fills, pool=self._shuffle_pool,
                    traffic=self._exchange2_traffic)
                stage.work = dst.nbytes + sum(c.nbytes for c in staged)
            s1, s2 = (self._exchange2_valued if partial
                      else self._exchange2_steps)
            # bytes of one record's exchanged columns: each stage sends
            # a [destinations, width] block of them per source shard
            row = sum(c.nbytes for c in staged) // len(dst)
            topo = self.host_topology
            hosts, local = topo.num_hosts, topo.local_devices
            with flight.span("exchange.stage1") as x1, \
                    flight.span("device.dispatch"):
                put = jax.device_put((dst, *staged), self._sharding)
                inter = s1(put[0], put[1], tuple(put[2:]), w1)
                x1.work = self.P * local * w1 * row
            with flight.span("exchange.stage2") as x2, \
                    flight.span("device.dispatch"):
                self.accs = s2(self.accs, inter[0], inter[1],
                               tuple(inter[2:]), w2)
                x2.work = self.P * hosts * w2 * row
        else:
            with flight.span("prep.stage") as stage:
                dst, staged, width = stage_device_exchange(
                    shards, self.P,
                    columns=columns,
                    fills=fills,
                    pool=self._shuffle_pool,
                )
                stage.work = dst.nbytes + sum(c.nbytes for c in staged)
            with flight.span("device.dispatch") as dispatch:
                # ONE host->device hop for the whole batch: every flat
                # column in a single device_put against the key-group
                # sharding
                put = jax.device_put((dst, *staged), self._sharding)
                step = (self._exchange_valued_step if partial
                        else self._exchange_scatter_step)
                self.accs = step(self.accs, put[0], put[1],
                                 tuple(put[2:]), width)
                # through all_to_all: every shard sends each of the P
                # destinations a [width] bucket of every exchanged
                # column (the block shapes at dispatch; no pass over
                # the batch)
                dispatch.work = self.P * self.P * width * (
                    sum(c.nbytes for c in staged) // len(dst))
        # "crash mid-batch after the fused dispatch": the scatter is in
        # flight on the device queue, the host dies before the fence —
        # the hardest restore case for the device data plane
        chaos.fault_point("shuffle.device_exchange", records=n)
        self._push_dispatch_fence()

    # ------------------------------------------------------------------ fire

    #: fires may be dispatched async (on_watermark(async_ok=True)
    #: returns PendingFire handles): the fire kernel and its D2H copies
    #: overlap the next ingest step's host prep, and the harvest is ONE
    #: batched device_get once the copies land — the mesh window form
    #: of the session engine's overlapped fire harvests (latency tier)
    supports_async_fires = True

    def on_watermark(self, watermark: int,
                     async_ok: bool = False) -> List[RecordBatch]:
        self._wd_boundary()
        with flight.fire_span(watermark) as fire:
            staged = self._fire_matrix_bytes
            out = self._on_watermark_inner(watermark, async_ok)
            fire.work = self._fire_matrix_bytes - staged
        # replica publish AFTER the fires/frees of this boundary (and
        # outside the fire span — it is serving-plane work, budgeted
        # under its own serving.replica_publish span)
        self._publish_replica(watermark)
        return out

    def _on_watermark_inner(self, watermark: int,
                            async_ok: bool = False) -> List[RecordBatch]:
        out: List[RecordBatch] = []
        while True:
            w_end = self.book.next_window(watermark)
            if w_end is None:
                break
            batch = self._fire_window(w_end, async_ok=async_ok)
            if batch is not None and (not hasattr(batch, "__len__")
                                      or len(batch) > 0):
                out.append(batch)
            self.book.mark_fired(w_end)
        expired = self.book.expired_slices(watermark)
        if expired:
            # the donated reset is device-queue-ordered BEHIND the fire
            # kernels dispatched above, so a deferred (async) host read
            # of the fire outputs never races the frees
            with flight.span("slice.retire", faults=True) as retire:
                retire.work = self._free_slices(expired)
        return out

    def _fire_window(self, window_end: int,
                     async_ok: bool = False) -> Optional[RecordBatch]:
        chaos.fault_point("mesh.window_fire", window_end=window_end)
        slice_ends = self.assigner.slice_ends_for_window(window_end)
        if self._any_spilled(slice_ends):
            # hybrid fire: resident slices merge on device (one kernel),
            # spilled slices merge on host — the device budget stays
            # independent of the window's slice count (the mesh form of
            # SlotTable.fire_hybrid). Host-merged values are already on
            # the host, so there is nothing to defer: stays synchronous
            # inside an async on_watermark.
            return self._fire_window_hybrid(window_end, slice_ends)
        per_shard_mats: List[np.ndarray] = []
        per_shard_keys: List[np.ndarray] = []
        w_max = 0
        for p in range(self.P):
            # each shard's index carries its (keys, slot matrix) from
            # one window to the next; work: the cells this call resolved
            with flight.span("fire.shard", shard=p) as resolve:
                keys, mat, resolve.work = self.indexes[p].slice_matrix(
                    slice_ends)
            per_shard_mats.append(mat)
            per_shard_keys.append(keys)
            w_max = max(w_max, len(keys))
        if w_max == 0:
            return None
        W = sticky_bucket(w_max, getattr(self, "_fire_bucket", 0), minimum=64)
        self._fire_bucket = W
        # each shard's matrix is as wide as its fullest row needs
        # (``slice_matrix``): the block takes the widest
        width = max(mat.shape[1] for mat in per_shard_mats)
        sm = np.zeros((self.P, W, width), dtype=np.int32)
        for p, mat in enumerate(per_shard_mats):
            sm[p, : len(mat), : mat.shape[1]] = mat
        self._fire_matrix_bytes += sm.nbytes
        fire_out = self._fire_step(self.accs, self._put_sharded(sm))
        names = sorted(fire_out.keys())
        projector = self.fire_projector
        w_start = self.assigner.window_start(window_end)
        per_keys = per_shard_keys  # host arrays, stable after dispatch

        def build(host: List[np.ndarray]) -> Optional[RecordBatch]:
            key_cols: List[np.ndarray] = []
            res_cols: Dict[str, List[np.ndarray]] = {n: [] for n in names}
            for p in range(len(per_keys)):
                m = len(per_keys[p])
                if m == 0:
                    continue
                key_cols.append(per_keys[p])
                for name, arr in zip(names, host):
                    res_cols[name].append(arr[p][:m])
            keys = np.concatenate(key_cols)
            merged = {name: np.concatenate(chunks)
                      for name, chunks in res_cols.items()}
            if projector is not None:
                keys, merged = projector.project_host(keys, merged)
            m = len(keys)
            cols = {
                KEY_ID_FIELD: keys,
                WINDOW_START_FIELD: np.full(m, w_start, dtype=np.int64),
                WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
                TIMESTAMP_FIELD: np.full(m, window_end - 1,
                                         dtype=np.int64),
            }
            cols.update(merged)
            return RecordBatch(cols)

        if async_ok:
            from flink_tpu.runtime.pending import PendingFire

            # overlapped fire harvest: the kernel + D2H copies run while
            # the task loop keeps ingesting; the harvest is one batched
            # device_get when the copies land (runtime/pending.py)
            return PendingFire([fire_out[n] for n in names], build,
                               watchdog=self._watchdog)
        # sync path still batches all columns into ONE device_get
        return build(self._harvest_get([fire_out[n] for n in names]))

    def _fire_window_hybrid(self, window_end: int,
                            slice_ends) -> Optional[RecordBatch]:
        from flink_tpu.ops.segment_ops import HOST_COMBINE

        leaves = self.agg.leaves
        # device part: per-shard slot matrices over RESIDENT slices (the
        # index only knows resident namespaces), merged raw on device
        per_shard_mats: List[np.ndarray] = []
        per_shard_keys: List[np.ndarray] = []
        w_max = 0
        for p in range(self.P):
            keys, mat, _ = self.indexes[p].slice_matrix(slice_ends)
            per_shard_mats.append(mat)
            per_shard_keys.append(keys)
            w_max = max(w_max, len(keys))
        key_chunks: List[np.ndarray] = []
        leaf_chunks: List[List[np.ndarray]] = [[] for _ in leaves]
        if w_max:
            W = sticky_bucket(w_max, getattr(self, "_fire_bucket", 0),
                              minimum=64)
            self._fire_bucket = W
            width = max(mat.shape[1] for mat in per_shard_mats)
            sm = np.zeros((self.P, W, width), dtype=np.int32)
            for p, mat in enumerate(per_shard_mats):
                sm[p, : len(mat), : mat.shape[1]] = mat
            merged = self._merge_step(self.accs, self._put_sharded(sm))
            merged_host = self._harvest_get(merged)  # ONE batched D2H
            for p in range(self.P):
                m = len(per_shard_keys[p])
                if m == 0:
                    continue
                key_chunks.append(per_shard_keys[p])
                for i in range(len(leaves)):
                    leaf_chunks[i].append(merged_host[i][p][:m])
        # host part: spilled slices of this window, every shard
        for p in range(self.P):
            sp = self.spills[p]
            for se in slice_ends:
                entry = sp.peek(int(se))
                if entry is None or len(entry["key_id"]) == 0:
                    continue
                key_chunks.append(
                    np.asarray(entry["key_id"], dtype=np.int64))
                for i, l in enumerate(leaves):
                    leaf_chunks[i].append(
                        np.asarray(entry[f"leaf_{i}"], dtype=l.dtype))
        if not key_chunks:
            return None
        all_keys = np.concatenate(key_chunks)
        uniq, inv = np.unique(all_keys, return_inverse=True)
        out_leaves = []
        for i, l in enumerate(leaves):
            acc = np.full(len(uniq), l.identity, dtype=l.dtype)
            HOST_COMBINE[l.reduce].at(acc, inv,
                                      np.concatenate(leaf_chunks[i]))
            out_leaves.append(acc)
        finished = self.agg.finish(tuple(out_leaves))
        merged_cols = {name: np.asarray(col)
                       for name, col in finished.items()}
        keys = uniq
        if self.fire_projector is not None:
            keys, merged_cols = self.fire_projector.project_host(
                keys, merged_cols)
        m = len(keys)
        if m == 0:
            return None
        cols = {
            KEY_ID_FIELD: keys,
            WINDOW_START_FIELD: np.full(
                m, self.assigner.window_start(window_end), dtype=np.int64),
            WINDOW_END_FIELD: np.full(m, window_end, dtype=np.int64),
            TIMESTAMP_FIELD: np.full(m, window_end - 1, dtype=np.int64),
        }
        cols.update(merged_cols)
        return RecordBatch(cols)

    def _free_slices(self, ends: List[int]) -> int:
        """Erase the expired slices' pairs from every shard's host index
        and reset their device rows; returns the pairs erased."""
        f_max = 0
        freed: List[Optional[np.ndarray]] = []
        self._freed_ns.append(np.asarray(list(ends), dtype=np.int64))
        self._drop_spilled(ends)
        before = sum(idx.pairs_dropped for idx in self.indexes)
        for p in range(self.P):
            slots = self.indexes[p].free_namespaces(ends)
            freed.append(slots)
            if slots is not None:
                self._dirty[p, slots] = False
                f_max = max(f_max, len(slots))
        dropped = sum(idx.pairs_dropped for idx in self.indexes) - before
        if dropped:
            # pairs that left with their slice's whole table (the native
            # index's drop; none on the Python index)
            flight.instant("retire.drop", work=dropped)
        if f_max == 0:
            return 0
        F = sticky_bucket(f_max, getattr(self, "_reset_bucket", 0))
        self._reset_bucket = F
        block = np.zeros((self.P, F), dtype=np.int32)
        for p, slots in enumerate(freed):
            if slots is not None:
                block[p, : len(slots)] = slots
        self.accs = self._reset_step(self.accs, self._put_sharded(block))
        return sum(len(slots) for slots in freed if slots is not None)

    # ---------------------------------------------------------- point query

    def query_windows(self, key_id: int) -> Dict[int, Dict[str, float]]:
        """Queryable-state point lookup — a batch of one (the serving
        plane routes ALL reads through :meth:`query_batch`)."""
        return self.query_batch(
            np.asarray([key_id], dtype=np.int64))[0]

    def query_batch(self, key_ids) -> List[Dict[int, Dict[str, float]]]:
        """Batched point lookup, mesh form: every requested key routes to
        its owning shard (the key-group formula the data path uses), the
        whole batch's resident slice accumulators come back through ONE
        gather program + ONE batched device read, spilled slices answer
        from their shards' host tiers, and window results compose on host
        (slice sharing, as SlotTable.query_windows). Read-only — no
        residency change, no sticky-bucket mutation. One result dict
        ({window_end -> columns}) per requested key, request order."""
        from flink_tpu.windowing.windower import compose_windows

        key_ids = np.asarray(key_ids, dtype=np.int64)
        n = len(key_ids)
        if n == 0:
            return []
        leaves = self.agg.leaves
        shards = self._route(key_ids)
        #: per request row: slice end -> per-leaf 1-element raw values
        slice_vals: List[Dict[int, Tuple[np.ndarray, ...]]] = [
            {} for _ in range(n)]
        # resident probe: (requested keys on shard) x (live namespaces),
        # all shards' hits land in one [P, G] gather block
        lanes: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
        g_max = 0
        for p in range(self.P):
            rows_p = np.nonzero(shards == p)[0]
            if not len(rows_p):
                continue
            idx = self.indexes[p]
            live_ns = np.asarray([int(x) for x in idx.namespaces],
                                 dtype=np.int64)
            if not len(live_ns):
                continue
            pk = np.repeat(key_ids[rows_p], len(live_ns))
            pn = np.tile(live_ns, len(rows_p))
            prow = np.repeat(rows_p, len(live_ns))
            slots = idx.lookup(pk, pn)
            hit = slots >= 0
            if hit.any():
                lanes[p] = (slots[hit].astype(np.int32), prow[hit],
                            pn[hit])
                g_max = max(g_max, int(hit.sum()))
        if lanes:
            G = pad_bucket_size(g_max, minimum=64)
            block = np.zeros((self.P, G), dtype=np.int32)
            for p, (hs, _, _) in lanes.items():
                block[p, : len(hs)] = hs
            gathered = self._gather_step(self.accs,
                                         self._put_sharded(block))
            # ONE batched D2H
            g_host = self._harvest_get(gathered, "serving_lookup")
            for p, (hs, prow, pn) in lanes.items():
                shard_leaves = [g[p] for g in g_host]
                for j in range(len(hs)):
                    slice_vals[int(prow[j])][int(pn[j])] = tuple(
                        g[j:j + 1] for g in shard_leaves)
        if self._spill_active:
            for p in range(self.P):
                rows_p = np.nonzero(shards == p)[0]
                if not len(rows_p):
                    continue
                sp = self.spills[p]
                if len(sp) == 0:
                    continue
                want = key_ids[rows_p]
                for ns in sp.namespaces:
                    entry = sp.peek(int(ns))
                    if entry is None:
                        continue
                    ek = np.asarray(entry["key_id"], dtype=np.int64)
                    if not len(ek):
                        continue
                    order = np.argsort(ek, kind="stable")
                    pos = np.searchsorted(ek[order], want)
                    pos = np.minimum(pos, len(ek) - 1)
                    ok = ek[order][pos] == want
                    for j in np.nonzero(ok)[0].tolist():
                        src = int(order[pos[j]])
                        slice_vals[int(rows_p[j])][int(ns)] = tuple(
                            np.asarray(entry[f"leaf_{i}"],
                                       dtype=l.dtype)[src:src + 1]
                            for i, l in enumerate(leaves))
        results: List[Dict[int, Dict[str, float]]] = []
        for r in range(n):
            sv = slice_vals[r]
            results.append(compose_windows(self.assigner, self.agg, sv)
                           if sv else {})
        return results

    # -------------------------------------------------------------- snapshot

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        """Logical snapshot merged over shards, re-shardable by key group.

        mode: "full" (new incremental base), "delta" (dirty rows +
        tombstones only), "savepoint" (full, preserving dirty tracking) —
        the same contract as SliceSharedWindower.snapshot, so mesh and
        single-device checkpoints are mutually restorable."""
        if mode == "delta":
            return {"table": self._snapshot_delta(), **self.book.snapshot()}
        accs_host = jax.device_get(list(self.accs))  # ONE batched D2H
        parts = []
        for p in range(self.P):
            idx = self.indexes[p]
            used = idx.used_slots()
            key_ids = idx.slot_key[used]
            parts.append({
                "key_id": key_ids,
                "namespace": idx.slot_ns[used],
                "key_group": assign_key_groups(key_ids, self.max_parallelism),
                **{f"leaf_{i}": accs_host[i][p][used]
                   for i in range(len(self.accs))},
            })
        # spilled namespaces are part of the logical state
        parts.extend(self._spill_snapshot_parts())
        merged = {
            k: np.concatenate([pt[k] for pt in parts]) for k in parts[0]
        } if parts else {}
        if mode != "savepoint":
            self._dirty[:] = False
            self._freed_ns.clear()
            for sp in self.spills:
                sp.clear_dirty()
        return {"table": merged, **self.book.snapshot()}

    def _snapshot_delta(self) -> Dict[str, np.ndarray]:
        """Dirty rows gathered off the device in ONE sharded program +
        freed-namespace tombstones (same format as SlotTable.snapshot_delta)."""
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
            empty = {f"leaf_{i}": np.empty(0, dtype=l.dtype)
                     for i, l in enumerate(self.agg.leaves)}
            out = {
                "__delta__": np.asarray(True),
                "key_id": np.empty(0, dtype=np.int64),
                "namespace": np.empty(0, dtype=np.int64),
                "key_group": np.empty(0, dtype=np.int32),
                "freed_namespaces": freed,
                **empty,
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
                m = len(dirty)
                if m == 0:
                    continue
                idx = self.indexes[p]
                key_cols.append(idx.slot_key[dirty])
                ns_cols.append(idx.slot_ns[dirty])
                for i, lh in enumerate(leaves_host):
                    leaf_cols[i].append(lh[p][:m])
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
        """Restore, re-sharding by key group (works across mesh sizes).

        ``key_group_filter``: keep only rows in these GLOBAL key groups
        (subtask-expansion restore — the mesh x stage composition
        restores the merged logical snapshot into each subtask's owned
        range)."""
        table = snap["table"]
        key_ids = np.asarray(table["key_id"], dtype=np.int64)
        namespaces = np.asarray(table["namespace"], dtype=np.int64)
        leaves = [np.asarray(table[f"leaf_{i}"])
                  for i in range(len(self.agg.leaves))]
        if key_group_filter is not None and len(key_ids):
            groups = assign_key_groups(key_ids, self.max_parallelism)
            mask = np.isin(groups, np.asarray(sorted(key_group_filter)))
            key_ids, namespaces = key_ids[mask], namespaces[mask]
            leaves = [v[mask] for v in leaves]
        if self._spill_active and len(key_ids):
            self._spill_restore_rows(key_ids, namespaces, leaves)
        elif len(key_ids):
            shards = self._route(key_ids)
            # resolve ALL slots first: inserts may grow the table
            # (on_grow widens self.accs / self.capacity), so the host
            # copy must be taken only after growth has settled
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
        # restored state IS the new incremental base
        self._dirty[:] = False
        self._freed_ns.clear()
        for sp in self.spills:
            sp.clear_dirty()
        # restored VALUES bypass the scatter sites — the replica shadow
        # is stale wholesale; republish everything at the next boundary
        self._rep_rebuild = True
        self.book.restore(snap)

    # ------------------------------------------------ partial-failover hooks

    def _merge_restored_meta(self, snap, groups) -> None:
        # window lifecycle metadata is global: the book merge re-opens
        # the windows the restored range must re-fire during replay
        self.book.merge_restore(snap)

    def _merge_meta_snapshots(self, units):
        _NEG = -(1 << 62)
        pending = sorted({int(w) for u in units
                          for w in u.get("pending", ())})
        slw: Dict[int, int] = {}
        for u in units:
            slw.update(dict(u.get("slice_last_window", {})))
        return {
            "pending": pending,
            "slice_last_window": slw,
            # the OLDEST unit decides: its range's records replay from
            # its position and must pass the late-record guard exactly
            # as they originally did
            "watermark": min((u.get("watermark", _NEG) for u in units),
                             default=_NEG),
            "max_fired_end": min(
                (u.get("max_fired_end", _NEG) for u in units),
                default=_NEG),
            "late_records_dropped": max(
                (u.get("late_records_dropped", 0) for u in units),
                default=0),
        }


def build_mesh_steps(mesh: Mesh, agg: AggregateFunction):
    """(scatter, fire, reset, gather, put, merge) shard_map step programs
    over a [P, capacity] sharded slot table — shared by the mesh window and
    mesh session engines (cached per (devices, aggregate layout)).

    ``put`` overwrites slots with explicit per-leaf values (spill reload);
    ``merge`` is fire without the finish — raw merged leaves come back to
    the host so spilled slices can be combined there (the mesh form of
    SlotTable.fire_hybrid)."""
    cache_key = (tuple(d.id for d in mesh.devices.flat), agg.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "mesh-steps", cache_key, lambda: _build_mesh_steps(mesh, agg))


def _build_mesh_steps(mesh: Mesh, agg: AggregateFunction):
    leaves = agg.leaves
    methods = tuple(SCATTER_METHOD[l.reduce] for l in agg.leaves)
    merges = tuple(MERGE_FN[l.reduce] for l in agg.leaves)
    idents = tuple(l.identity for l in agg.leaves)
    finish = agg.finish
    n_leaves = len(agg.leaves)
    n_inputs = len(agg.input_leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter_step(accs, slots, values):
        # accs: ([P, cap], ...) sharded; slots: [P, B]; values: one
        # [P, B] block per *input* leaf (const leaves broadcast on device)
        def local(*args):
            accs_l = args[:n_leaves]          # each [1, cap]
            slots_l = args[n_leaves]          # [1, B]
            vals_l = iter(args[n_leaves + 1:])  # each [1, B]
            # .at[...].op() returns the full [1, cap] block
            out = []
            for a, m, l in zip(accs_l, methods, leaves):
                if l.const is not None:
                    # padded lanes target identity slot 0 — keep it pure
                    v = jnp.where(
                        slots_l[0] == 0,
                        jnp.asarray(l.identity, dtype=l.dtype),
                        jnp.asarray(l.const, dtype=l.dtype))
                else:
                    v = next(vals_l)[0]
                out.append(getattr(a.at[0, slots_l[0]], m)(v))
            return tuple(out)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 1 + n_inputs),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slots, *values)

    # hoisted so the jitted closures capture only plain values, never
    # an engine (the step cache outlives engines; a capture would pin
    # the first engine's device arrays in memory for the process)
    names = sorted(agg.output_names)

    @jax.jit
    def fire_step(accs, slot_matrix):
        # slot_matrix: [P, W, k] sharded -> result cols each [P, W]
        def local(*args):
            accs_l = args[:n_leaves]          # [1, cap]
            sm = args[n_leaves][0]            # [W, k]
            merged = tuple(
                m(a[0][sm], axis=1) for a, m in zip(accs_l, merges))
            out = finish(merged)              # dict name -> [W]
            return tuple(out[name][None] for name in names)

        outs = shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * len(names),
        )(*accs, slot_matrix)
        return dict(zip(names, outs))

    @partial(jax.jit, donate_argnums=(0,))
    def reset_step(accs, slots):
        def local(*args):
            accs_l = args[:n_leaves]
            slots_l = args[n_leaves]
            return tuple(
                a.at[0, slots_l[0]].set(i)
                for a, i in zip(accs_l, idents)
            )

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slots)

    @jax.jit
    def gather_step(accs, slots):
        # slots: [P, G] sharded -> per-leaf [P, G] raw accumulator
        # values (delta-snapshot / point-query readback)
        def local(*args):
            accs_l = args[:n_leaves]
            slots_l = args[n_leaves]
            return tuple(a[0][slots_l[0]][None] for a in accs_l)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slots)

    @partial(jax.jit, donate_argnums=(0,))
    def put_step(accs, slots, values):
        # slots: [P, B]; values: one [P, B] block per LEAF — overwrite
        # semantics (spill reload into slots just reset to identity).
        # Padded lanes target slot 0 with identity values: harmless.
        def local(*args):
            accs_l = args[:n_leaves]
            slots_l = args[n_leaves]
            vals_l = args[n_leaves + 1:]
            return tuple(a.at[0, slots_l[0]].set(v[0])
                         for a, v in zip(accs_l, vals_l))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (2 * n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slots, *values)

    @jax.jit
    def merge_step(accs, slot_matrix):
        # slot_matrix: [P, W, k] sharded -> per-leaf [P, W] RAW merged
        # accumulators (no finish) for host-side hybrid-fire composition
        def local(*args):
            accs_l = args[:n_leaves]
            sm = args[n_leaves][0]
            return tuple(
                m(a[0][sm], axis=1)[None]
                for a, m in zip(accs_l, merges))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slot_matrix)

    @partial(jax.jit, donate_argnums=(0,))
    def valued_scatter_step(accs, slots, values):
        # slots: [P, B]; values: one explicit [P, B] block per ACC LEAF
        # (locally pre-aggregated partials, flink_tpu/runtime/local_agg) —
        # folded with each leaf's own reduce; no const shortcut (a
        # partial COUNT is the combined count, not 1). The mesh form of
        # SlotTable.scatter_valued; decomposability guarantees the
        # per-leaf reduce merges partials exactly.
        def local(*args):
            accs_l = args[:n_leaves]
            slots_l = args[n_leaves]
            vals_l = args[n_leaves + 1:]
            return tuple(
                getattr(a.at[0, slots_l[0]], m)(v[0])
                for a, m, v in zip(accs_l, methods, vals_l))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (2 * n_leaves + 1),
            out_specs=(P(KEY_AXIS),) * n_leaves,
        )(*accs, slots, *values)

    return (scatter_step, fire_step, reset_step, gather_step,
            put_step, merge_step, valued_scatter_step)


def build_delta_fire_step(mesh: Mesh, agg: AggregateFunction):
    """The delta-harvest program: fire + reset FUSED into one compiled
    program — ``merge+finish`` over each closing row's slots, then the
    fired slots reset to identity, in a single dispatch (the separate
    fire_step + reset_step pair paid two). The merged reads are data-
    dependencies of the donated writes, so XLA orders them correctly;
    the fire outputs are fresh buffers, safe for deferred (async)
    harvest. Cached in the shared PROGRAM_CACHE per (devices, aggregate
    layout) — family "delta-fire", 0 steady-state compiles (shapes ride
    the same sticky fire buckets as the unfused pair)."""
    cache_key = (tuple(d.id for d in mesh.devices.flat), agg.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "delta-fire", cache_key, lambda: _build_delta_fire_step(mesh, agg))


def _build_delta_fire_step(mesh: Mesh, agg: AggregateFunction):
    merges = tuple(MERGE_FN[l.reduce] for l in agg.leaves)
    idents = tuple(l.identity for l in agg.leaves)
    finish = agg.finish
    n_leaves = len(agg.leaves)
    names = sorted(agg.output_names)

    @partial(jax.jit, donate_argnums=(0,))
    def delta_fire_step(accs, slot_matrix, reset_slots):
        # slot_matrix: [P, W, k] sharded; reset_slots: [P, W] (padded
        # lanes target the reserved identity slot 0 — reset is a no-op
        # there). Returns (new accs, {name -> [P, W] result columns}).
        def local(*args):
            accs_l = args[:n_leaves]
            sm = args[n_leaves][0]       # [W, k]
            rs = args[n_leaves + 1][0]   # [W]
            merged = tuple(
                m(a[0][sm], axis=1) for a, m in zip(accs_l, merges))
            out = finish(merged)
            fresh = tuple(
                a.at[0, rs].set(jnp.asarray(i, dtype=a.dtype))
                for a, i in zip(accs_l, idents))
            return fresh + tuple(out[name][None] for name in names)

        outs = shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 2),
            out_specs=(P(KEY_AXIS),) * (n_leaves + len(names)),
        )(*accs, slot_matrix, reset_slots)
        return tuple(outs[:n_leaves]), dict(zip(names, outs[n_leaves:]))

    return delta_fire_step

