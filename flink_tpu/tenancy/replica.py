"""Read-replica serving plane: double-buffered boundary-published state.

The queryable-state cost model before this module: every lookup batch
paid a fresh gather + ``device_get`` against the LIVE state plane,
serialized behind the owning job's batch boundaries (the control-queue
detour — reads had to wait for the single-owner task loop because the
live plane mutates under them). At serving QPS that serialization IS
the latency.

This module decouples readers from ingest with a device-resident READ
REPLICA of the hot slot rows:

- **Publish at boundaries.** At every fire/watermark boundary the
  owning engine publishes a bounded DELTA of rows changed since the
  last publish into the replica plane — one compiled device-to-device
  copy program (no D2H), riding the same sticky-bucket shape
  discipline as the engines' own steps and cached in the shared
  :data:`~flink_tpu.tenancy.program_cache.PROGRAM_CACHE` (family
  ``replica-pub``), so multi-tenant zero-recompile holds.
- **Double buffering / snapshot isolation.** A publish builds the next
  generation FUNCTIONALLY from the sealed one (``rep.at[slots].set``
  without donation — the sealed arrays are never written) and seals it
  with one atomic reference swap. Readers always resolve against the
  generation they grabbed: they see exactly the state at that
  generation's boundary, never a torn mid-batch view, and never
  contend with ingest.
- **Index without copies in steady state.** Each generation carries a
  host index ``key_id -> {namespace -> (shard, slot, extra)}``. Value
  -only publishes (the steady state of a stable key set) reuse the
  sealed index object untouched; structural publishes (new rows,
  frees, residency flips) copy the outer dict and copy-on-write only
  the touched keys' inner dicts.
- **Cold rows stay serveable.** A row the engine evicted serves from
  the page tier *through the replica path*: its index entry flips to
  ``slot == -1`` at the next publish and lookups detour those keys to
  the owning task loop (pages are single-owner host state), counted in
  ``cold_rows_served``. A row's page value cannot change while it is
  cold, so the detour still answers with boundary state.

The engines drive this through ``MeshSpillSupport.arm_replica`` /
``_publish_replica`` (parallel/sharded_windower.py): the publish delta
is derived by comparing the engine's per-shard slot metadata against
the replica's shadow of it (``rep_key/rep_ns/rep_used``), plus a
``rep_dirty`` bitmap set at the scatter sites — eviction, reload,
fires and slot reuse all surface as metadata differences, so the
delta needs no per-site bookkeeping beyond the scatters.

reference: the L6/L4 queryable-state survey (PAPER.md) — serve reads
off the keyed backend, decoupled from the task thread; the shape is
the read-replica + staleness-bounded cache every feature store builds.
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from flink_tpu.chaos import injection as chaos
from flink_tpu.observe import flight_recorder as flight
from flink_tpu.ops.segment_ops import sticky_bucket
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

#: index entry slot value for a row serving from the page tier
COLD_SLOT = -1


def build_replica_steps(mesh, dtypes: Tuple[str, ...]):
    """(publish_step, gather_step) for a replica plane of per-leaf
    ``[P, capacity]`` arrays with the given dtype layout. Cached in the
    shared PROGRAM_CACHE per (device ids, dtypes) — keyed on WHAT they
    compute, never on which job's replica runs them (the tenancy
    zero-recompile contract, same as build_mesh_steps)."""
    cache_key = (tuple(d.id for d in mesh.devices.flat), tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "replica-pub", cache_key, lambda: _build_replica_steps(mesh))


def _build_replica_steps(mesh):
    import jax
    import jax.numpy as jnp

    from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
    from jax.sharding import PartitionSpec as P

    @jax.jit
    def publish_step(rep, live, slots):
        # rep/live: per-leaf [P, cap] sharded; slots: [P, D]. NO
        # donation: the input rep arrays ARE the sealed generation
        # readers are resolving against — the output is a fresh buffer
        # set (the double buffer). Padded lanes carry slot 0: copying
        # live slot 0 over rep slot 0 is safe because any slot whose
        # value changed since the last publish is in the delta — an
        # unchanged slot's copy is a no-op by value.
        n = len(rep)

        def local(*args):
            rep_l = args[:n]
            live_l = args[n:2 * n]
            sl = args[2 * n][0]
            return tuple(r.at[0, sl].set(a[0][sl])
                         for r, a in zip(rep_l, live_l))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (2 * n + 1),
            out_specs=(P(KEY_AXIS),) * n,
        )(*rep, *live, slots)

    @jax.jit
    def gather_step(rep, slots):
        # slots: [P, G] -> per-leaf [P, G] replica values (the serving
        # read program — identical shape contract to the engines'
        # gather_step, over the sealed plane instead of the live one)
        n = len(rep)

        def local(*args):
            rep_l = args[:n]
            sl = args[n][0]
            return tuple(r[0][sl][None] for r in rep_l)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n + 1),
            out_specs=(P(KEY_AXIS),) * n,
        )(*rep, slots)

    return publish_step, gather_step


class ReplicaGeneration:
    """One sealed, immutable snapshot view. ``accs`` are the replica's
    device arrays (never written after seal), ``index`` maps
    ``key_id -> {namespace -> (shard, slot, extra)}`` (``slot ==
    COLD_SLOT`` serves from the page tier), ``extra`` is the owning
    adapter's per-row payload (session end; join ``(ts, host cols)``)."""

    __slots__ = ("gen", "boundary_wm", "published_at", "accs", "index",
                 "num_shards")

    def __init__(self, gen: int, boundary_wm: int, published_at: float,
                 accs, index: Dict[int, Dict[int, tuple]],
                 num_shards: int) -> None:
        self.gen = gen
        self.boundary_wm = boundary_wm
        self.published_at = published_at
        self.accs = accs
        self.index = index
        self.num_shards = num_shards


class ReplicaPlane:
    """The double-buffered replica one engine publishes into.

    Single-writer: every mutating method runs on the engine's task
    thread (single-owner discipline). Readers (serving worker threads)
    only ever touch :attr:`sealed` — an atomic reference to an
    immutable :class:`ReplicaGeneration` — and the compiled gather
    program, both safe concurrently with a publish in progress."""

    def __init__(self, mesh, leaves, capacity: int) -> None:
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_tpu.parallel.mesh import KEY_AXIS

        self.mesh = mesh
        self.P = int(mesh.devices.size)
        self.capacity = int(capacity)
        self.leaves = tuple(leaves)
        self._dtypes = tuple(np.dtype(l.dtype).name for l in self.leaves)
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        self._publish_step, self._gather_step = build_replica_steps(
            mesh, self._dtypes)
        # the engine-metadata shadow the publish delta diffs against
        self.rep_key = np.zeros((self.P, self.capacity), dtype=np.int64)
        self.rep_ns = np.zeros((self.P, self.capacity), dtype=np.int64)
        self.rep_used = np.zeros((self.P, self.capacity), dtype=bool)
        #: rows whose VALUE changed since the last publish (set by the
        #: engines' scatter sites; residency/identity changes are
        #: derived from the metadata diff instead)
        self.rep_dirty = np.zeros((self.P, self.capacity), dtype=bool)
        self._accs = self._identity_accs()
        self._pub_bucket = 0
        self._gather_bucket = 0
        self._gen = 0
        #: the sealed generation readers resolve against (atomic swap)
        self.sealed: Optional[ReplicaGeneration] = None
        #: set by rebuild(): the next publish must not carry the sealed
        #: index forward (and must seal even if the state is empty)
        self._index_reset = False
        #: minimum seconds between publishes (0 = every boundary).
        #: Batching boundaries under one publish bounds BOTH the
        #: per-boundary metadata-diff cost and the hot-row cache's
        #: invalidation rate — staleness stays bounded by the interval.
        self.min_interval_s = 0.0
        #: set by the serving adapter (attach_cache): called on the
        #: TASK thread after each seal with (generation, per_shard,
        #: host_leaves) — the publish HARVEST: the delta rows' values
        #: come host-side in ONE batched device_get so the hot-row
        #: cache re-primes without any lookup ever touching the device
        self.on_publish = None
        #: called when the plane rebuilds (restore/reshard/loss): the
        #: cache must drop this operator's entries — a rolled-back
        #: value would otherwise serve stale forever
        self.on_rebuild = None
        # ---- counters (read by serving gauges / the serving tests)
        self.publishes = 0
        self.rows_published = 0
        self.rows_freed = 0
        self.cold_flips = 0
        self.lookups_served = 0
        self.cold_rows_served = 0

    def _identity_accs(self):
        import jax
        import jax.numpy as jnp

        return tuple(
            jax.device_put(
                jnp.full((self.P, self.capacity), l.identity,
                         dtype=l.dtype),
                self._sharding)
            for l in self.leaves)

    def warm_tiers(self) -> None:
        """Compile the publish/gather programs at EVERY pow2 block tier
        up to the plane capacity — deterministic zero-recompile under
        the sentinel: the tiers a live run's deltas/miss batches walk
        are data-dependent, so a measured phase could otherwise hit a
        tier the warm phase never saw. Shapes compiled here are cached
        per (program fn, shape) by jax itself, and the fns are shared
        through the PROGRAM_CACHE, so a SECOND plane on the same mesh/
        dtype layout pays nothing (multi-tenant zero-recompile)."""
        import jax

        from flink_tpu.ops.segment_ops import pad_bucket_size

        top = pad_bucket_size(self.capacity, minimum=64)
        D = 64
        while True:
            block = jax.device_put(
                np.zeros((self.P, D), dtype=np.int32), self._sharding)
            # discard outputs: this is shape warmup, not a publish
            self._publish_step(self._accs, self._accs, block)
            self._gather_step(self._accs, block)
            if D >= top:
                break
            D <<= 1

    # ----------------------------------------------------------- publishing

    def needs_rebuild(self, P: int, capacity: int) -> bool:
        return P != self.P or capacity != self.capacity

    def rebuild(self, mesh, capacity: int) -> None:
        """Reset the plane over a (possibly) new mesh/capacity — after
        restore, reshard, shard loss or index growth. The next publish
        diffs against an empty shadow, i.e. republishes every resident
        row (the bounded-full publish); the generation counter keeps
        advancing so caches tagged with older generations invalidate."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_tpu.parallel.mesh import KEY_AXIS

        self.mesh = mesh
        self.P = int(mesh.devices.size)
        self.capacity = int(capacity)
        self._sharding = NamedSharding(mesh, P(KEY_AXIS))
        self._publish_step, self._gather_step = build_replica_steps(
            mesh, self._dtypes)
        self.rep_key = np.zeros((self.P, self.capacity), dtype=np.int64)
        self.rep_ns = np.zeros((self.P, self.capacity), dtype=np.int64)
        self.rep_used = np.zeros((self.P, self.capacity), dtype=bool)
        self.rep_dirty = np.zeros((self.P, self.capacity), dtype=bool)
        self._accs = self._identity_accs()
        self._pub_bucket = 0
        self._gather_bucket = 0
        # readers keep serving the last sealed generation of the OLD
        # plane until the first publish on the new one seals; that
        # publish must build its index FROM SCRATCH — carrying the
        # sealed index forward would keep entries for keys that do not
        # exist in the rebuilt (restored) state, and their stale slots
        # could address OTHER keys' rows after the republish
        self._index_reset = True
        if self.on_rebuild is not None:
            self.on_rebuild()

    def mark_dirty(self, p: int, slots) -> None:
        self.rep_dirty[p, slots] = True

    def publish(self, live_accs, per_shard: Dict[int, dict],
                boundary_wm: int) -> bool:
        """Build + seal the next generation. ``per_shard[p]`` carries::

            up_slots   int32 slots to (re)publish on shard p
            up_keys    their key ids
            up_ns      their namespaces
            up_extra   per-row adapter payloads (or None)
            cold       [(key, ns, extra)] flipped to (or inserted as)
                       page-tier serving; extra None keeps the
                       existing entry's payload
            freed      [(key, ns)] dropped from the index

        Returns True when a new generation was sealed (False = no
        changes; the sealed boundary watermark still advances, so
        staleness gauges and caches read "up to date")."""
        import jax

        structural = self._index_reset
        d_max = 0
        for p, d in per_shard.items():
            d_max = max(d_max, len(d["up_slots"]))
            if d["cold"] or d["freed"] or d.get("fresh"):
                structural = True
            elif d["up_extra"] is not None and len(d["up_slots"]):
                # extras travel WITH values (a session's END extends as
                # it absorbs) — a value-only publish must still rewrite
                # those entries, which needs the COW index
                structural = True
        if d_max == 0 and not structural:
            s = self.sealed
            if s is not None:
                # metadata-only advance: same state, newer boundary —
                # mutating these two scalars on the sealed object is
                # benign (readers never derive row addressing from them)
                s.boundary_wm = boundary_wm
                s.published_at = time.monotonic()
            return False
        chaos.fault_point("serving.replica_publish", generation=self._gen + 1)
        # ---- device delta: one program, no D2H on the publish itself
        harvest = None
        if d_max:
            D = sticky_bucket(d_max, self._pub_bucket, minimum=64)
            self._pub_bucket = D
            block = np.zeros((self.P, D), dtype=np.int32)
            for p, d in per_shard.items():
                n = len(d["up_slots"])
                if n:
                    block[p, :n] = d["up_slots"]
            dev_block = jax.device_put(block, self._sharding)
            self._accs = self._publish_step(self._accs, live_accs,
                                            dev_block)
            if self.on_publish is not None:
                # the publish HARVEST: the delta rows' values, ONE
                # gather + ONE device_get (the delta-checkpoint cost
                # model) — the cache prime below is what lets hot-key
                # lookups skip the device entirely
                harvest = jax.device_get(
                    list(self._gather_step(self._accs, dev_block)))
        # ---- host index (COW: outer copy only on structural change;
        # a rebuild starts from {} — see rebuild())
        sealed = self.sealed
        index = ({} if self._index_reset
                 else sealed.index if sealed is not None else {})
        new_index = dict(index) if structural or sealed is None else index
        touched: Dict[int, Dict[int, tuple]] = {}

        def inner(key: int) -> Dict[int, tuple]:
            d = touched.get(key)
            if d is None:
                d = dict(new_index.get(key, ()))
                touched[key] = d
                new_index[key] = d
            return d

        rows = 0
        for p, d in per_shard.items():
            keys, nss = d["up_keys"], d["up_ns"]
            extra = d["up_extra"]
            slots = d["up_slots"]
            n = len(slots)
            rows += n
            if structural or sealed is None:
                for j in range(n):
                    inner(int(keys[j]))[int(nss[j])] = (
                        p, int(slots[j]),
                        extra[j] if extra is not None else None)
            else:
                # value-only publish: every pair already has an entry at
                # the same (shard, slot) — the index object is reused
                # untouched and readers of the new generation see the
                # same addressing over the new arrays
                pass
            for key, ns, c_extra in d["cold"]:
                ki = inner(int(key))
                ent = ki.get(int(ns))
                if ent is not None:
                    ki[int(ns)] = (
                        ent[0], COLD_SLOT,
                        ent[2] if c_extra is None else c_extra)
                else:
                    # a row created AND evicted within one publish
                    # interval was never resident at a boundary — it
                    # enters the index cold directly (its page value
                    # IS its boundary value)
                    ki[int(ns)] = (p, COLD_SLOT, c_extra)
                self.cold_flips += 1
            for key, ns in d["freed"]:
                ki = inner(int(key))
                ki.pop(int(ns), None)
                if not ki:
                    new_index.pop(int(key), None)
                self.rows_freed += 1
        self._gen += 1
        self.rows_published += rows
        self.publishes += 1
        # the pre-publish index: the adapters' prime needs the OLD
        # result addressing (a session's end MOVES as it absorbs — the
        # stale entry is found here). A rebuild publish starts from
        # nothing: its caches were invalidated, nothing maps back.
        prev_index = {} if self._index_reset else index
        self._index_reset = False
        self.sealed = ReplicaGeneration(
            self._gen, boundary_wm, time.monotonic(), self._accs,
            new_index, self.P)
        if self.on_publish is not None:
            # AFTER the seal: a prime tags entries with the new
            # generation, so it must not run while probes still
            # resolve the old one (they would read fresh tags as
            # future and miss)
            self.on_publish(self._gen, per_shard, harvest, prev_index)
        return True

    # -------------------------------------------------------------- reading

    def generation(self) -> int:
        s = self.sealed
        return s.gen if s is not None else 0

    def staleness_ms(self) -> float:
        s = self.sealed
        if s is None:
            return 0.0
        return (time.monotonic() - s.published_at) * 1e3

    def gather_rows(self, gen: ReplicaGeneration,
                    rows: List[Tuple[int, int]]) -> List[tuple]:
        """Read resident replica rows ``(shard, slot)`` back as per-row
        leaf tuples: ONE gather program + ONE ``jax.device_get`` for the
        whole batch (the serving cost model), against the sealed
        generation's immutable arrays — safe from any thread."""
        import jax

        if not rows:
            return []
        g_max = 0
        lanes: Dict[int, List[int]] = {}
        order: List[Tuple[int, int]] = []  # (shard, lane)
        for p, s in rows:
            lane = lanes.setdefault(p, [])
            order.append((p, len(lane)))
            lane.append(s)
            g_max = max(g_max, len(lane))
        G = sticky_bucket(g_max, self._gather_bucket, minimum=64)
        self._gather_bucket = G
        block = np.zeros((self.P, G), dtype=np.int32)
        for p, lane in lanes.items():
            block[p, :len(lane)] = lane
        gathered = self._gather_step(
            gen.accs, jax.device_put(block, self._sharding))
        host = jax.device_get(list(gathered))  # ONE batched D2H
        return [tuple(h[p][j] for h in host) for p, j in order]

    def counters(self) -> Dict[str, int]:
        return {
            "publishes": int(self.publishes),
            "rows_published": int(self.rows_published),
            "rows_freed": int(self.rows_freed),
            "cold_flips": int(self.cold_flips),
            "lookups_served": int(self.lookups_served),
            "cold_rows_served": int(self.cold_rows_served),
        }


# --------------------------------------------------------------- adapters


class ReplicaAdapter:
    """The serving plane's view of one operator's replica: everything a
    worker thread may touch without the task loop. Subclasses compose
    engine-specific results (windows/sessions) from gathered rows.

    ``cold_fetch(key_ids)`` — posts ONE live query batch for keys whose
    entries are cold (page-tier state is single-owner host state, so
    the read detours through the owning job's control queue exactly
    like the legacy path; bound by the session cluster)."""

    def __init__(self, plane: ReplicaPlane, agg) -> None:
        self.plane = plane
        self.agg = agg
        self.cold_fetch = None  # bound by ServingPlane.bind_replica
        self._cache = None
        self._cache_job = None
        self._cache_op = None

    # -- publish-harvest cache feed

    def attach_cache(self, cache, job: str, operator: str) -> None:
        """Wire the hot-row cache into the publish harvest: every
        boundary publish folds its delta into the cached entries it
        touches (on the task thread — ONE batched D2H per publish), so
        a hot key's lookups never touch the device between misses."""
        self._cache = cache
        self._cache_job = job
        self._cache_op = operator
        self.plane.on_publish = self._on_publish
        self.plane.on_rebuild = self._on_rebuild

    def _on_rebuild(self) -> None:
        if self._cache is not None:
            self._cache.invalidate_op(self._cache_job, self._cache_op)

    def prime_value_ns(self, ns: int, extra):
        """Result-dict key for an upserted row, or None when the
        cached composition cannot be updated incrementally (the key's
        entry is dropped and the next lookup re-resolves)."""
        return None

    def prime_free_ns(self, ns: int, extra):
        """Result-dict key removed by a freed row (``extra`` is the
        row's payload in the PRE-publish index, when it had one), or
        None to drop the key's entry instead."""
        return None

    def _prime_rows(self, keys_f: np.ndarray, ns_f: np.ndarray,
                    extra_f: np.ndarray, prev_index):
        """Map the delta rows to cache updates: ``(rns, valid,
        removals, kill)`` where ``rns[j]`` is row j's RESULT namespace
        (valid[j] False = no incremental update), ``removals`` is a
        list of ``(kid, result_ns)`` stale entries to delete in the
        SAME batched prime, and ``kill`` is kids whose cached entry
        drops outright. Default: per-row :meth:`prime_value_ns`."""
        n = len(keys_f)
        rns = np.zeros(n, dtype=np.int64)
        valid = np.zeros(n, dtype=bool)
        kill: set = set()
        for j in range(n):
            r = self.prime_value_ns(int(ns_f[j]), extra_f[j])
            if r is None:
                kill.add(int(keys_f[j]))
            else:
                rns[j] = int(r)
                valid[j] = True
        return rns, valid, [], kill

    def _on_publish(self, gen: int, per_shard: Dict[int, dict],
                    harvest, prev_index) -> None:
        """The publish-harvest cache feed, batch-first: flatten the
        delta rows once, finish the value columns ONCE, map rows to
        result namespaces, and fold the whole boundary into the cache
        as ONE :class:`~flink_tpu.tenancy.hot_cache.PrimeDelta` (one
        GIL-released C call on the native plane; one locked pass on
        the Python fallback) — the publish used to pay one ``put()``
        per touched key on the task thread, inside the fire-deadline
        budget."""
        cache = self._cache
        if cache is None:
            return
        from flink_tpu.tenancy.hot_cache import PrimeDelta

        job, op = self._cache_job, self._cache_op
        leaves = self.agg.leaves
        # flatten the delta rows across shards, finish ONCE
        keys_l, ns_l, extra_l, val_cols = [], [], [], None
        if harvest is not None:
            chunks: List[List[np.ndarray]] = [[] for _ in leaves]
            for p, d in per_shard.items():
                n = len(d["up_slots"])
                if not n:
                    continue
                keys_l.append(d["up_keys"])
                ns_l.append(d["up_ns"])
                extra_l.append(
                    d["up_extra"] if d["up_extra"] is not None
                    else np.zeros(n, dtype=np.int64))
                for i in range(len(leaves)):
                    chunks[i].append(harvest[i][p][:n])
            if keys_l:
                finished = self.agg.finish(tuple(
                    np.concatenate(chunks[i]).astype(l.dtype,
                                                     copy=False)
                    for i, l in enumerate(leaves)))
                val_cols = [(name, np.asarray(col))
                            for name, col in finished.items()]
        if keys_l:
            keys_f = np.concatenate(keys_l)
            ns_f = np.concatenate(ns_l)
            extra_f = np.concatenate(extra_l)
        else:
            keys_f = ns_f = extra_f = np.zeros(0, dtype=np.int64)
        removals: List[Tuple[int, int]] = []
        kill: set = set()
        if val_cols is not None:
            rns, valid, removals, kill = self._prime_rows(
                keys_f, ns_f, extra_f, prev_index)
        else:
            rns = np.zeros(0, dtype=np.int64)
            valid = np.zeros(len(keys_f), dtype=bool)
        for d in per_shard.values():
            for key, ns in d["freed"]:
                prev = prev_index.get(int(key), {}).get(int(ns))
                r = self.prime_free_ns(
                    int(ns), prev[2] if prev is not None else None)
                if r is None:
                    kill.add(int(key))
                else:
                    removals.append((int(key), int(r)))
        # ---- group per kid into the flat delta
        if valid.any():
            u_kids = keys_f[valid]
            u_rns = rns[valid] if len(rns) == len(keys_f) else rns
            order = np.argsort(u_kids, kind="stable")
            u_kids = u_kids[order]
            u_rns = u_rns[order]
            u_cols = [(name, col[valid][order])
                      for name, col in val_cols]
            uniq, starts = np.unique(u_kids, return_index=True)
            ends = np.append(starts[1:], len(u_kids))
        else:
            u_rns = np.zeros(0, dtype=np.int64)
            u_cols = [(name, col[:0]) for name, col in (val_cols or [])]
            uniq = np.zeros(0, dtype=np.int64)
            starts = ends = np.zeros(0, dtype=np.int64)
        upd_of = {int(uniq[i]): (int(starts[i]), int(ends[i]))
                  for i in range(len(uniq))}
        rem_of: Dict[int, List[int]] = {}
        for kid, r in removals:
            if kid not in kill:
                rem_of.setdefault(kid, []).append(r)
        all_kids = sorted(set(upd_of) - kill | set(rem_of) | kill)
        if not all_kids:
            return
        index = self.plane.sealed.index if self.plane.sealed else {}
        keys_a = np.asarray(all_kids, dtype=np.int64)
        uoff = np.zeros(len(all_kids) + 1, dtype=np.int64)
        u_take: List[int] = []
        roff = np.zeros(len(all_kids) + 1, dtype=np.int64)
        r_ns: List[int] = []
        flags = np.zeros(len(all_kids), dtype=np.uint8)
        for i, kid in enumerate(all_kids):
            if kid in kill:
                flags[i] = 2
                uoff[i + 1] = uoff[i]
                roff[i + 1] = roff[i]
                continue
            lo_hi = upd_of.get(kid)
            if lo_hi is not None:
                u_take.extend(range(lo_hi[0], lo_hi[1]))
                # the delta covered EVERY published row of the key ->
                # the update IS its complete composed state, safe to
                # INSERT: first-touch lookups of hot keys never touch
                # the device
                if lo_hi[1] - lo_hi[0] == len(index.get(kid, ())):
                    flags[i] |= 1
            uoff[i + 1] = uoff[i] + (
                lo_hi[1] - lo_hi[0] if lo_hi is not None else 0)
            rem = rem_of.get(kid, ())
            r_ns.extend(rem)
            roff[i + 1] = roff[i] + len(rem)
        take = np.asarray(u_take, dtype=np.int64)
        cache.prime_batch(job, op, gen, PrimeDelta(
            keys=keys_a, uoff=uoff,
            u_ns=u_rns[take] if len(take) else u_rns[:0],
            u_cols=[(name, col[take] if len(take) else col[:0])
                    for name, col in u_cols],
            roff=roff,
            r_ns=np.asarray(r_ns, dtype=np.int64),
            flags=flags))

    # -- key plumbing (worker threads)

    def key_id(self, key) -> int:
        if isinstance(key, (int, np.integer)):
            return int(key)  # integer keys ARE their identity
        from flink_tpu.state.keygroups import hash_keys_to_i64

        return int(hash_keys_to_i64(np.asarray([key]))[0])

    def shard_of(self, key_id: int) -> int:
        gen = self.plane.sealed
        n = gen.num_shards if gen is not None else self.plane.P
        return key_id % n if n else 0

    def generation(self) -> int:
        return self.plane.generation()

    def ready(self) -> bool:
        return self.plane.sealed is not None

    # -- the lookup itself

    def lookup_batch(self, keys: List[Any]) -> Tuple[List[dict], int]:
        """One result dict per key (the operator's query_state_batch
        shape), resolved against ONE sealed generation; returns
        ``(results, generation)`` so the hot-row cache can tag them."""
        from flink_tpu.state.keygroups import hash_keys_to_i64

        gen = self.plane.sealed
        if gen is None:
            raise RuntimeError("replica not published yet")
        key_ids = hash_keys_to_i64(np.asarray(keys))
        n = len(key_ids)
        rows: List[Tuple[int, int]] = []
        row_of: List[List[Tuple[int, int, Any]]] = [[] for _ in range(n)]
        cold_of: List[List[Tuple[int, Any]]] = [[] for _ in range(n)]
        for r in range(n):
            entries = gen.index.get(int(key_ids[r]))
            if not entries:
                continue
            for ns, (p, slot, extra) in entries.items():
                if slot == COLD_SLOT:
                    cold_of[r].append((int(ns), extra))
                else:
                    row_of[r].append((int(ns), len(rows), extra))
                    rows.append((p, slot))
        vals = self.plane.gather_rows(gen, rows)
        cold_vals: Dict[int, dict] = {}
        cold_rows = [r for r in range(n) if cold_of[r]]
        if cold_rows:
            if self.cold_fetch is None:
                raise RuntimeError(
                    "replica has cold rows but no cold_fetch is bound")
            fetched = self.cold_fetch([keys[r] for r in cold_rows])
            for r, res in zip(cold_rows, fetched):
                cold_vals[r] = res
                self.plane.cold_rows_served += len(cold_of[r])
        out = self.compose_all(row_of, vals, cold_of, cold_vals)
        self.plane.lookups_served += n
        return out, gen.gen

    def compose_all(self, row_of, vals, cold_of,
                    cold_vals: Dict[int, dict]) -> List[dict]:
        """Compose every requested key's result. Default: one
        :meth:`compose` call per key; adapters override with a
        vectorized pass where the window/namespace mapping allows."""
        return [self.compose(row_of[r], vals, cold_of[r],
                             cold_vals.get(r))
                for r in range(len(row_of))]

    def compose(self, entries, vals, cold_entries, cold_result) -> dict:
        raise NotImplementedError


class SessionReplicaAdapter(ReplicaAdapter):
    """Session engine: an index entry's ``extra`` is the session END;
    a key's result is ``{session_end -> finished columns}``.

    Sessions PRIME instead of invalidating: a session's result key —
    its END — moves as the session absorbs, so each publish upserts
    the row under the NEW end and deletes the stale-end entry in the
    SAME batched prime (the old end read from the PRE-publish index,
    where the (key, sid) row still carries it). The hottest workload
    class — a session absorbing across many boundaries — stays on the
    hit path instead of structurally missing at every boundary."""

    def _prime_rows(self, keys_f, ns_f, extra_f, prev_index):
        n = len(keys_f)
        rns = np.asarray(extra_f, dtype=np.int64)  # the NEW ends
        valid = np.ones(n, dtype=bool)
        removals: List[Tuple[int, int]] = []
        # one prev-index probe per KEY (rows grouped), not per row
        by_key: Dict[int, List[int]] = {}
        for j in range(n):
            by_key.setdefault(int(keys_f[j]), []).append(j)
        for kid, idxs in by_key.items():
            prev = prev_index.get(kid)
            if not prev:
                continue
            for j in idxs:
                ent = prev.get(int(ns_f[j]))
                if ent is not None and ent[2] is not None \
                        and int(ent[2]) != int(rns[j]):
                    # the session's end MOVED: the entry cached under
                    # the old end is stale — delete it in this prime
                    removals.append((kid, int(ent[2])))
        return rns, valid, removals, set()

    def prime_free_ns(self, ns: int, extra):
        # a freed (fired/merged-away) session removes its END entry;
        # ``extra`` is the pre-publish index payload = the old end.
        # A freed row with no recorded end cannot be mapped — drop the
        # key's entry (the safe fallback the old invalidate path took).
        return int(extra) if extra is not None else None

    def compose(self, entries, vals, cold_entries, cold_result) -> dict:
        out: Dict[int, Dict[str, float]] = {}
        if entries:
            leaves = [np.asarray([vals[j][i] for _, j, _ in entries],
                                 dtype=l.dtype)
                      for i, l in enumerate(self.agg.leaves)]
            finished = self.agg.finish(tuple(leaves))
            cols = {name: np.asarray(col)
                    for name, col in finished.items()}
            for r, (_ns, _j, end) in enumerate(entries):
                out[int(end)] = {name: col[r].item()
                                 for name, col in cols.items()}
        if cold_result is not None:
            # take ONLY the sessions the sealed index flagged cold out
            # of the live detour's full map (their entry extra is the
            # session end) — a cold row cannot change while cold, so
            # its live value IS its boundary value; sessions born after
            # the boundary are not in the sealed index and stay out
            for _sid, end in cold_entries:
                colsd = cold_result.get(int(end))
                if colsd is not None:
                    out[int(end)] = colsd
        return out


class JoinSideReplicaAdapter(ReplicaAdapter):
    """One join side table's replica view: rows are immutable, the
    index maps ``key -> {rid -> (shard, slot, (ts, host_col_values))}``
    and a key's result is the live ``query_side_batch`` shape — a list
    of ``{"ts", "rid", <col>: v}`` dicts sorted by (ts, rid). Device
    columns gather from the sealed plane; device-ineligible columns
    ride the published ``extra`` payload; cold rows detour through
    ``cold_fetch`` (their page value IS their boundary value — join
    rows never change after insert)."""

    def __init__(self, plane: ReplicaPlane, side) -> None:
        super().__init__(plane, agg=None)
        self.schema = list(side.schema)
        self.device_cols = list(side.device_cols)
        self.host_cols = list(side.host_cols)

    def compose(self, entries, vals, cold_entries, cold_result) -> list:
        rows: List[dict] = []
        names = [nm for nm, _ in self.schema]
        for rid, j, extra in entries:
            ts, host_vals = extra
            row = {"ts": int(ts), "rid": int(rid)}
            for gi, i in enumerate(self.device_cols):
                row[names[i]] = np.asarray(vals[j][gi]).item()
            for hi, i in enumerate(self.host_cols):
                v = host_vals[hi]
                row[names[i]] = v.item() if hasattr(v, "item") else v
            rows.append(row)
        if cold_result is not None:
            want = {int(rid) for rid, _ in cold_entries}
            for row in cold_result:
                if int(row["rid"]) in want:
                    rows.append(dict(row))
        rows.sort(key=lambda d: (d["ts"], d["rid"]))
        return rows


class WindowReplicaAdapter(ReplicaAdapter):
    """Window engine: entries are per-SLICE accumulator rows
    (namespace == slice end); results compose host-side through the
    same ``compose_windows`` the live query path uses. A window with at
    least one COLD slice answers from the live detour (raw slice values
    are not recoverable from a composed window result) — those slices
    are boundary-stable by definition of cold, and the detour is the
    exact legacy read path."""

    def __init__(self, plane: ReplicaPlane, agg, assigner) -> None:
        super().__init__(plane, agg)
        self.assigner = assigner
        #: None = unknown, probed on first lookup: does every slice map
        #: to exactly ONE window that is exactly that slice (tumbling)?
        #: Then composition is a single vectorized finish over all rows
        #: instead of a per-key per-window host merge loop.
        self._one_to_one: Optional[bool] = None

    def _probe_one_to_one(self, ns: int) -> bool:
        if self._one_to_one is None:
            a = self.assigner
            self._one_to_one = (
                [int(w) for w in a.window_ends_for_slice(int(ns))]
                == [int(ns)]
                and [int(s) for s in a.slice_ends_for_window(int(ns))]
                == [int(ns)])
        return self._one_to_one

    def prime_value_ns(self, ns: int, extra):
        # tumbling-style: the slice end IS the window end, a stable
        # result key — the cached entry updates in place. Sliding/
        # cumulative shapes fall back to drop-and-re-resolve (a slice
        # feeds k windows; incremental re-compose isn't worth it).
        return ns if self._probe_one_to_one(ns) else None

    def prime_free_ns(self, ns: int, extra):
        return ns if self._probe_one_to_one(ns) else None

    def _prime_rows(self, keys_f, ns_f, extra_f, prev_index):
        # vectorized: ONE assigner probe decides the whole batch —
        # tumbling-style rows prime under their own namespace, other
        # shapes drop every touched key (the base class would have
        # made the same per-row decision n times)
        n = len(keys_f)
        if n and self._probe_one_to_one(int(ns_f[0])):
            return (np.asarray(ns_f, dtype=np.int64),
                    np.ones(n, dtype=bool), [], set())
        return (np.zeros(n, dtype=np.int64), np.zeros(n, dtype=bool),
                [], {int(k) for k in keys_f})

    def compose_all(self, row_of, vals, cold_of, cold_vals):
        # vectorized fast path (the serving hot loop): tumbling-style
        # assigners finish EVERY gathered row in one pass — the per-key
        # compose_windows loop is only needed for sliding/cumulative
        # shapes (slice sharing) and for keys with cold slices
        some = next((row_of[r][0][0] for r in range(len(row_of))
                     if row_of[r]), None)
        if some is None or not self._probe_one_to_one(some):
            return super().compose_all(row_of, vals, cold_of, cold_vals)
        leaves = [np.asarray([v[i] for v in vals], dtype=l.dtype)
                  for i, l in enumerate(self.agg.leaves)]
        finished = self.agg.finish(tuple(leaves))
        cols = [(name, np.asarray(col)) for name, col in
                finished.items()]
        out: List[dict] = []
        for r in range(len(row_of)):
            if cold_of[r]:
                out.append(self.compose(row_of[r], vals, cold_of[r],
                                        cold_vals.get(r)))
                continue
            res: Dict[int, Dict[str, float]] = {}
            for ns, j, _extra in row_of[r]:
                res[ns] = {name: col[j].item() for name, col in cols}
            out.append(res)
        return out

    def compose(self, entries, vals, cold_entries, cold_result) -> dict:
        from flink_tpu.windowing.windower import compose_windows

        slice_vals: Dict[int, tuple] = {}
        for ns, j, _extra in entries:
            slice_vals[int(ns)] = tuple(
                np.asarray([v], dtype=l.dtype)
                for v, l in zip(vals[j], self.agg.leaves))
        out = compose_windows(self.assigner, self.agg, slice_vals) \
            if slice_vals else {}
        if cold_result is not None:
            cold_windows = sorted({
                int(w) for ns, _ in cold_entries
                for w in self.assigner.window_ends_for_slice(int(ns))})
            for w in cold_windows:
                colsd = cold_result.get(w)
                if colsd is not None:
                    out[w] = colsd
                else:
                    out.pop(w, None)
        return out
