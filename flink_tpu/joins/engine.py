"""Device-native two-input join engines over dual keyed slot tables.

The two-input form of the mesh window/session engines: both inputs ride
the keyBy data plane (``parallel.shuffle`` — device-mode fused exchange
or host bucketing) co-partitioned onto the SAME mesh axis by the same
key-group routing, so a key's left and right rows always share a shard
and every probe is shard-local. Per batch the device runs at most three
programs — the ingest put/exchange, the banded probe, and (under
budget pressure) one eviction gather — all cached in the shared
``PROGRAM_CACHE`` and shape-bounded by the ``pad_bucket_size`` /
``sticky_bucket`` tier discipline, so steady state compiles nothing
(gated by ``tests/test_device_joins.py`` and the join tier walk of
``tests/test_stateplane.py``).

- :class:`MeshIntervalJoinEngine` — keyed interval join (left row at
  ``t`` matches right rows in ``[t+lower, t+upper]``,
  reference: IntervalJoinOperator.java): a banded segment-intersection
  over the two sorted row tables. A new batch probes the OTHER side's
  table before inserting into its own (pair emitted by whichever side
  arrives second — the host operator's structural dedup), with the band
  ``[lo, lo+cnt)`` resolved on host metadata and the candidates
  gathered/intersected/emitted by ONE compiled program per batch.
- :class:`MeshTemporalJoinEngine` — event-time temporal join (``FOR
  SYSTEM_TIME AS OF``, reference: TemporalRowTimeJoinOperator.java):
  the right side is a VERSIONED state plane (version boundaries are the
  per-key sorted ``ts`` column of its slot table); left rows wait for
  the combined watermark, then one searchsorted-style gather program
  per batch picks each row's latest version at-or-before its time (the
  ``W == 1`` band). Version state compacts to the reference's
  cleanupState contract on every watermark.

``backend="host"`` runs the numpy oracle: identical metadata code,
identical emission order, value movement in host arrays — the
bit-identity pin for the device path (including under forced paged
eviction and mid-stream ``reshard()``).
"""

from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from flink_tpu.chaos import injection as chaos
from flink_tpu.core.records import KEY_ID_FIELD, TIMESTAMP_FIELD, RecordBatch
from flink_tpu.joins.side_table import (
    JoinSideTable,
    pair_lower_bound,
)
from flink_tpu.ops.segment_ops import pad_bucket_size, sticky_bucket
from flink_tpu.state.keygroups import assign_key_groups
from flink_tpu.state.paged_spill import restore_into_pages

_NEG = -(1 << 62)

SIDE_NAMES = ("left", "right")

# tiny non-donated slice enqueued after everything dispatched so far —
# its readiness proves the device consumed every earlier staging buffer
# (the join engines' form of the mesh engines' fence; jit caches per
# input sharding)
_FENCE_STEP = jax.jit(lambda a: a[:1, :1])


def _suffixed_names(left_names: Sequence[str],
                    right_names: Sequence[str],
                    suffixes: Tuple[str, str]
                    ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """Output column name per input column, matching the host join
    operators' ``_merge_columns`` convention: a name present on both
    sides gets the side suffix, everything else passes through."""
    overlap = set(left_names) & set(right_names)
    lmap = {n: (n + suffixes[0] if n in overlap else n)
            for n in left_names}
    rmap = {n: (n + suffixes[1] if n in overlap else n)
            for n in right_names}
    return lmap, rmap


class JoinEngineBase:
    """Shared machinery of the two-input engines: the dual side tables,
    the data-plane staging, eviction, probing, checkpoints, partial
    restore, live reshard and the watchdog plumbing."""

    #: subclasses set: ("interval", lower, upper) or ("temporal",)
    kind: str = ""

    def __init__(self, mesh=None, num_shards: int = 1,
                 capacity_per_shard: int = 1 << 16,
                 max_parallelism: int = 128,
                 max_device_slots: int = 0,
                 spill_dir: Optional[str] = None,
                 spill_host_max_bytes: int = 0,
                 key_group_range: Optional[Tuple[int, int]] = None,
                 backend: str = "device",
                 shuffle_mode: str = "device",
                 host_topology=None,
                 suffixes: Tuple[str, str] = ("_l", "_r")) -> None:
        if backend not in ("device", "host"):
            raise ValueError(
                f"backend must be 'device' or 'host', got {backend!r}")
        if shuffle_mode not in ("device", "host"):
            raise ValueError(
                f"shuffle_mode must be 'device' or 'host', got "
                f"{shuffle_mode!r}")
        self.backend = backend
        self.shuffle_mode = shuffle_mode
        self.mesh = None
        if backend == "device":
            if mesh is None:
                from flink_tpu.parallel.mesh import make_mesh

                mesh = make_mesh(num_shards)
            self.mesh = mesh
            self.P = int(mesh.devices.size)
        else:
            self.P = int(num_shards)
        self.capacity = max(int(capacity_per_shard), 256)
        self.max_device_slots = int(max_device_slots or 0)
        if self.max_device_slots:
            self.capacity = min(self.capacity,
                                max(self.max_device_slots, 256))
        self.max_parallelism = int(max_parallelism)
        if self.max_parallelism < self.P:
            raise ValueError(
                f"max_parallelism {max_parallelism} < shard count "
                f"{self.P}")
        self.key_group_range = key_group_range
        self.spill_dir = spill_dir
        self.spill_host_max_bytes = int(spill_host_max_bytes or 0)
        self.suffixes = tuple(suffixes)
        #: per-side state (created lazily at the side's first batch —
        #: the value schema is observed, like the table-runtime's
        #: late-bound row types)
        self.sides: List[Optional[JoinSideTable]] = [None, None]
        self._planes: List[Optional[tuple]] = [None, None]
        self._next_rid = 1
        #: flight-recorder batch sequence (the join engines' analog of
        #: MeshSpillSupport._flight_batch)
        self._flight_batch = 0
        # sticky compile-shape tiers (per side where shapes differ)
        self._put_bucket = [0, 0]
        self._mirror_bucket = [0, 0]
        self._probe_bucket = [0, 0]
        self._band_bucket = [0, 0]
        self._gather_bucket = 0
        if backend == "device":
            from jax.sharding import NamedSharding, PartitionSpec
            from flink_tpu.parallel.mesh import KEY_AXIS
            from flink_tpu.parallel.shuffle import ShuffleBufferPool

            self._sharding = NamedSharding(self.mesh,
                                           PartitionSpec(KEY_AXIS))
            self._pool = ShuffleBufferPool(generations=2)
            self._fences: List = []
        #: (hosts, local) factorization when the mesh spans processes:
        #: device-mode ingest then runs the two-level ICI/DCN exchange
        #: (the join twin of the mesh engines' pod path)
        self.host_topology = None
        self._exchange2_traffic = None
        if host_topology is not None:
            if backend != "device":
                raise ValueError(
                    "host_topology requires the device backend")
            host_topology.check_covers(self.P)
            from flink_tpu.parallel.exchange2 import ExchangeTraffic

            self.host_topology = host_topology
            self._exchange2_traffic = ExchangeTraffic()

    def _two_level_active(self) -> bool:
        from flink_tpu.parallel.exchange2 import two_level_active

        return two_level_active(self.host_topology, self.shuffle_mode)

    def exchange2_traffic(self) -> Dict[str, int]:
        from flink_tpu.parallel.exchange2 import ExchangeTraffic

        return ExchangeTraffic.dict_of(self._exchange2_traffic)

    # ------------------------------------------------------------- watchdog

    _watchdog = None

    def attach_watchdog(self, wd) -> None:
        self._watchdog = wd
        if wd is not None and self.mesh is not None:
            wd.rebind(self.P, [d.id for d in self.mesh.devices.flat])
            wd.set_topology(self.host_topology)

    def _wd_section(self, op: str, shard: int = -1):
        wd = self._watchdog
        if wd is None:
            from flink_tpu.runtime.watchdog import NULL_SECTION

            return NULL_SECTION
        return wd.section(op, shard)

    def _wd_boundary(self) -> None:
        wd = self._watchdog
        if wd is not None:
            wd.boundary_probe()

    def _harvest_get(self, tree, op: str = "join_probe_harvest"):
        """ONE batched D2H per harvest point (the TRC01 discipline)."""
        import jax

        from flink_tpu.observe import flight_recorder as flight

        with flight.span("fire.harvest"), self._wd_section(op):
            return jax.device_get(tree)

    def _flight_ingest(self):
        """Open the ``batch.ingest`` flight span for one
        ``process_batch`` (the one contract, shared with the mesh
        engines — see flight_recorder.ingest_span)."""
        from flink_tpu.observe import flight_recorder as flight

        self._flight_batch += 1
        return flight.ingest_span(self._flight_batch)

    def _flight_fire(self, watermark: int):
        """Open the ``fire.dispatch`` flight span for one
        ``on_watermark`` (see flight_recorder.fire_span)."""
        from flink_tpu.observe import flight_recorder as flight

        return flight.fire_span(watermark)

    # ----------------------------------------------------------- data plane

    def _drain_fences(self) -> None:
        if self.backend != "device":
            return
        while self._fences:
            # flint: disable=TRC01 -- the depth-bounded fence drain is
            # the ingest backpressure point: it blocks only when the
            # host ran a full staging generation ahead of the device
            self._fences.pop(0).block_until_ready()

    def _push_fence(self) -> None:
        import jax

        planes = self._planes[0] or self._planes[1]
        if planes is None:
            return
        with self._wd_section("dispatch_fence"):
            self._fences.append(_FENCE_STEP(planes[0]))
        # one staging generation may be in flight; the next must wait
        if len(self._fences) > 1:
            with self._wd_section("fence_drain"):
                # flint: disable=TRC01 -- see _drain_fences: this is
                # the designed double-buffer backpressure point
                self._fences.pop(0).block_until_ready()

    def _ensure_side(self, side_idx: int, batch: RecordBatch
                     ) -> JoinSideTable:
        side = self.sides[side_idx]
        if side is not None:
            return side
        schema = sorted(
            (n, np.asarray(batch[n]).dtype) for n in batch.names()
            if n not in (KEY_ID_FIELD, TIMESTAMP_FIELD))
        return self._init_side(side_idx, schema)

    def _init_side(self, side_idx: int, schema) -> JoinSideTable:
        sdir = (f"{self.spill_dir.rstrip('/')}/{SIDE_NAMES[side_idx]}"
                if self.spill_dir else None)
        side = JoinSideTable(
            self.P, self.capacity, schema,
            max_device_slots=self.max_device_slots,
            spill_dir=sdir,
            # the operator's host page-memory budget splits across the
            # two sides (each side then splits per shard)
            spill_host_max_bytes=self.spill_host_max_bytes // 2,
            backend=self.backend)
        self.sides[side_idx] = side
        if self.backend == "device":
            import jax
            import jax.numpy as jnp

            self._planes[side_idx] = tuple(
                jax.device_put(
                    jnp.zeros((self.P, side.capacity),
                              dtype=side.schema[i][1]),
                    self._sharding)
                for i in side.device_cols)
        return side

    def _check_schema(self, side: JoinSideTable,
                      batch: RecordBatch, side_idx: int) -> None:
        names = set(batch.names()) - {KEY_ID_FIELD, TIMESTAMP_FIELD}
        declared = {n for n, _ in side.schema}
        if names != declared:
            raise RuntimeError(
                f"{SIDE_NAMES[side_idx]} join input changed columns "
                f"mid-stream: {sorted(declared)} -> {sorted(names)}")

    def _shards_of(self, keys: np.ndarray) -> np.ndarray:
        from flink_tpu.parallel.shuffle import shard_records

        return shard_records(keys, self.P, self.max_parallelism,
                             self.key_group_range)

    # --------------------------------------------------------------- ingest

    def _ingest(self, side_idx: int, keys: np.ndarray, ts: np.ndarray,
                values: List[np.ndarray], shards=None) -> None:
        """Insert rows into ``side_idx``'s table: route, make headroom,
        allocate slots, merge metadata, move values (device put /
        fused exchange / host shadow). ``values`` in schema order;
        ``shards`` lets a caller that already routed these keys (the
        probe path) skip the second routing pass."""
        side = self.sides[side_idx]
        n = len(keys)
        if n == 0:
            return
        if shards is None:
            shards = self._shards_of(keys)
        # chaos: the two-input data plane. Payload kinds (drop /
        # duplicate) mutate one shard's rows BEFORE any state mutation
        # — a bucket lost or replayed in flight; raise/delay fire at
        # the post-dispatch site below (crash mid-batch with the put
        # on the device queue — the hardest restore case)
        if chaos.armed():
            mutations: Dict[int, str] = {}
            for p in np.unique(shards).tolist():
                rule = chaos.payload_action(
                    "join.exchange",
                    kinds=("drop", "duplicate", "delay"),
                    shard=int(p), side=side_idx)
                if rule is not None and rule.kind in ("drop",
                                                      "duplicate"):
                    mutations[int(p)] = rule.kind
            for p, mkind in mutations.items():
                sel = shards == p
                if mkind == "drop":
                    keep = ~sel
                    keys, ts, shards = keys[keep], ts[keep], shards[keep]
                    values = [v[keep] for v in values]
                else:
                    keys = np.concatenate([keys, keys[sel]])
                    ts = np.concatenate([ts, ts[sel]])
                    shards = np.concatenate([shards, shards[sel]])
                    values = [np.concatenate([v, v[sel]])
                              for v in values]
            n = len(keys)
            if n == 0:
                return
        self._ingest_rows(side_idx, keys, ts, values, shards)
        chaos.fault_point("join.exchange", records=n, side=side_idx)

    def _ingest_rows(self, side_idx: int, keys, ts, values,
                     shards) -> None:
        """Route/allocate/insert, bisecting when one batch's per-shard
        rows exceed the plane (the working-set bound: rows of the SAME
        chunk cannot evict each other — same discipline as the session
        engine's batch split)."""
        side = self.sides[side_idx]
        n = len(keys)
        counts = np.bincount(shards, minlength=self.P)
        if side.spill_active and int(counts.max()) > side.capacity - 1 \
                and n > 1:
            half = n // 2
            self._ingest_rows(side_idx, keys[:half], ts[:half],
                              [v[:half] for v in values],
                              shards[:half])
            self._ingest_rows(side_idx, keys[half:], ts[half:],
                              [v[half:] for v in values],
                              shards[half:])
            return
        rids = np.arange(self._next_rid, self._next_rid + n,
                         dtype=np.int64)
        self._next_rid += n
        if side.spill_active:
            self._make_headroom(side_idx, counts)
        else:
            need = int(counts.max()) if n else 0
            while any(side.free_headroom(p) < counts[p]
                      for p in range(self.P)):
                self._grow_side(side_idx, max(
                    side.capacity * 2,
                    pad_bucket_size(side.capacity + need)))
        slots = np.zeros(n, dtype=np.int32)
        order = np.argsort(shards, kind="stable")
        offs = np.concatenate(([0], np.cumsum(counts)))
        for p in np.nonzero(counts)[0].tolist():
            sel = order[offs[p]:offs[p + 1]]
            sl = side.allocate(p, len(sel))
            slots[sel] = sl
            side.meta[p].merge_rows(
                keys[sel], ts[sel], rids[sel], sl,
                np.ones(len(sel), dtype=bool))
            for i in side.shadow:
                side.shadow[i][p][sl] = np.asarray(
                    values[i], dtype=side.schema[i][1])[sel]
        if self.backend == "device" and side.device_cols:
            self._device_put_rows(side_idx, shards, slots, values)

    def _device_put_rows(self, side_idx: int, shards, slots,
                         values) -> None:
        import jax

        from flink_tpu.parallel.shuffle import (
            bucket_by_shard,
            stage_device_exchange,
        )
        from flink_tpu.joins.kernels import (
            build_join_exchange_put,
            build_join_put,
        )

        side = self.sides[side_idx]
        planes = self._planes[side_idx]
        cols = [np.asarray(slots, dtype=np.int32)] + [
            np.asarray(values[i], dtype=side.schema[i][1])
            for i in side.device_cols]
        fills = [0] + [side.schema[i][1].type(0)
                       for i in side.device_cols]
        self._pool.flip()
        if self._two_level_active():
            # pod mesh: two-level ICI/DCN exchange then the plane
            # write — stream order preserved, so the last-write-wins
            # semantics stay bit-identical to the flat exchange
            from flink_tpu.observe import flight_recorder as flight
            from flink_tpu.parallel.exchange2 import (
                build_join_exchange2_steps,
                stage_two_level_exchange,
            )

            dst, staged, w1, w2 = stage_two_level_exchange(
                shards, self.host_topology, columns=cols, fills=fills,
                pool=self._pool, traffic=self._exchange2_traffic)
            s1, s2 = build_join_exchange2_steps(
                self.mesh, self.host_topology, side.dtypes_key())
            with self._wd_section("join_ingest"):
                with flight.span("exchange.stage1"):
                    put = jax.device_put((dst, *staged),
                                         self._sharding)
                    inter = s1(put[0], put[1], tuple(put[2:]), w1)
                with flight.span("exchange.stage2"):
                    self._planes[side_idx] = s2(
                        planes, inter[0], inter[1], tuple(inter[2:]),
                        w2)
        elif self.shuffle_mode == "device":
            dst, staged, width = stage_device_exchange(
                shards, self.P, columns=cols, fills=fills,
                pool=self._pool)
            prog = build_join_exchange_put(self.mesh,
                                           side.dtypes_key())
            with self._wd_section("join_ingest"):
                put = jax.device_put((dst, *staged), self._sharding)
                self._planes[side_idx] = prog(
                    planes, put[0], put[1], tuple(put[2:]), width)
        else:
            counts, blocked = bucket_by_shard(
                shards, self.P, columns=cols, fills=fills,
                pool=self._pool)
            prog = build_join_put(self.mesh, side.dtypes_key())
            with self._wd_section("join_ingest"):
                put = jax.device_put(tuple(blocked), self._sharding)
                self._planes[side_idx] = prog(
                    planes, put[0], tuple(put[1:]))
        self._push_fence()

    # ------------------------------------------------------------- eviction

    def _make_headroom(self, side_idx: int, needed: np.ndarray) -> None:
        """Evict the coldest (oldest-ts) rows of every shard that
        cannot absorb its share of the batch — cohorts gathered in ONE
        program + ONE batched D2H across shards."""
        side = self.sides[side_idx]
        cohorts: Dict[int, np.ndarray] = {}
        for p in range(self.P):
            if side.free_headroom(p) >= int(needed[p]):
                continue
            pos = side.choose_eviction(
                p, int(needed[p]) - side.free_headroom(p))
            cohorts[p] = pos
        if not cohorts:
            return
        # host backend: shadow_values already carries every column —
        # the gather would be a duplicate copy immediately discarded
        vals = (self._gather_rows(side_idx, {
            p: side.meta[p].slot[pos] for p, pos in cohorts.items()})
            if self.backend == "device" and side.device_cols else None)
        for p, pos in cohorts.items():
            columns = side.shadow_values(p, pos)
            if vals is not None:
                for j, i in enumerate(side.device_cols):
                    columns[i] = vals[p][j]
            side.evict_rows(p, pos, columns)

    def _gather_rows(self, side_idx: int,
                     per_shard_slots: Dict[int, np.ndarray]
                     ) -> Dict[int, List[np.ndarray]]:
        """Device-column values at the given slots, per shard: one
        gather program + ONE device_get for all shards. Host backend
        reads the shadow store."""
        side = self.sides[side_idx]
        out: Dict[int, List[np.ndarray]] = {}
        if self.backend == "host" or not side.device_cols:
            for p, slots in per_shard_slots.items():
                sc = np.clip(slots, 0, None)
                out[p] = [side.shadow[i][p][sc]
                          for i in side.device_cols]
            return out
        from flink_tpu.joins.kernels import build_join_gather
        import jax

        g_max = max(len(s) for s in per_shard_slots.values())
        G = sticky_bucket(g_max, self._gather_bucket)
        self._gather_bucket = G
        block = np.zeros((self.P, G), dtype=np.int32)
        for p, slots in per_shard_slots.items():
            block[p, :len(slots)] = slots
        prog = build_join_gather(self.mesh, side.dtypes_key())
        with self._wd_section("evict_gather"):
            gathered = prog(self._planes[side_idx],
                            jax.device_put(block, self._sharding))
        host = self._harvest_get(gathered, "evict_harvest")
        for p, slots in per_shard_slots.items():
            out[p] = [h[p][:len(slots)] for h in host]
        return out

    def _grow_side(self, side_idx: int, new_capacity: int) -> None:
        side = self.sides[side_idx]
        old = side.capacity
        if new_capacity <= old:
            return
        side.grow(new_capacity)
        if self.backend == "device" and side.device_cols:
            import jax
            import jax.numpy as jnp

            host = self._harvest_get(list(self._planes[side_idx]),
                                     "grow_harvest")
            grown = []
            for h, i in zip(host, side.device_cols):
                wide = np.zeros((self.P, new_capacity),
                                dtype=side.schema[i][1])
                wide[:, :old] = h
                grown.append(jax.device_put(jnp.asarray(wide),
                                            self._sharding))
            self._planes[side_idx] = tuple(grown)

    # --------------------------------------------------------------- probes

    def _probe_banded(self, store_idx: int,
                      per_shard: Dict[int, Tuple[np.ndarray,
                                                 np.ndarray,
                                                 np.ndarray]],
                      band) -> Dict[int, dict]:
        """The banded probe: ``per_shard[p] = (orig_rows, keys, ts)``;
        ``band(meta, keys, ts) -> (lo, cnt)`` resolves each probe's
        candidate band over the shard's sorted metadata. Returns per
        shard the flattened match structure and the stored side's value
        columns (device-gathered for resident candidates, page-served
        for cold ones) — identical content and order in both backends.
        """
        side = self.sides[store_idx]
        bounds: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        w_max = 0
        b_max = 0
        s_max = 0
        total = 0
        for p, (_, pk, pt) in per_shard.items():
            m = side.meta[p]
            lo, cnt = band(m, pk, pt)
            bounds[p] = (lo, cnt)
            if len(cnt):
                w_max = max(w_max, int(cnt.max()))
                total += int(cnt.sum())
            b_max = max(b_max, len(pk))
            s_max = max(s_max, len(m))
        gathered_host = None
        W = 0
        if total and w_max:
            W = sticky_bucket(w_max, self._band_bucket[store_idx],
                              minimum=8)
            self._band_bucket[store_idx] = W
        if (self.backend == "device" and side.device_cols and total
                and W):
            gathered_host = self._dispatch_probe(
                store_idx, per_shard, bounds, W, b_max, s_max)
        out: Dict[int, dict] = {}
        for p, (orig, pk, pt) in per_shard.items():
            lo, cnt = bounds[p]
            t = int(cnt.sum()) if len(cnt) else 0
            if t == 0:
                continue
            m = side.meta[p]
            l_rep = np.repeat(np.arange(len(pk), dtype=np.int64), cnt)
            off = (np.arange(t, dtype=np.int64)
                   - np.repeat(np.cumsum(cnt) - cnt, cnt))
            cand = lo[l_rep] + off
            cslot = m.slot[cand]
            resident = cslot >= 0
            cols: List[np.ndarray] = []
            for i, (_, dt) in enumerate(side.schema):
                if i in side.shadow:
                    cols.append(side.shadow[i][p]
                                [np.clip(cslot, 0, None)].copy())
                else:
                    g = gathered_host[side.device_cols.index(i)][p]
                    cols.append(np.ascontiguousarray(
                        g[l_rep, off]).astype(dt, copy=False))
            cold = np.nonzero(~resident)[0]
            if len(cold):
                side.fill_cold(
                    p,
                    [(int(j), int(pk[l_rep[j]]), int(m.rid[cand[j]]))
                     for j in cold.tolist()],
                    cols, np.arange(t, dtype=np.int64))
            out[p] = {
                "orig": orig, "l_rep": l_rep, "cand": cand,
                "cols": cols, "ts": m.ts[cand], "resident": resident,
            }
        return out

    def _dispatch_probe(self, store_idx, per_shard, bounds, W,
                        b_max, s_max):
        """Stage the sorted-order slot mirror + band bounds and run the
        banded-probe program; ONE batched D2H for every output column."""
        import jax

        from flink_tpu.joins.kernels import build_banded_probe

        side = self.sides[store_idx]
        S = sticky_bucket(max(s_max, 1),
                          self._mirror_bucket[store_idx])
        self._mirror_bucket[store_idx] = S
        B = sticky_bucket(max(b_max, 1),
                          self._probe_bucket[store_idx], minimum=64)
        self._probe_bucket[store_idx] = B
        # NO pool flip here: the probe is synchronous — its harvest
        # (device_get below) completes before this method returns, so
        # its tagged buffers are free to rewrite next batch. Flipping
        # would advance the generation a second time per batch and
        # break the INGEST path's double-buffer (its fence drains one
        # generation behind).
        mirror = self._pool.get((self.P, S), np.int32, -1,
                                tag=("probe", "mirror", store_idx))
        lo_b = self._pool.get((self.P, B), np.int32, 0,
                              tag=("probe", "lo", store_idx))
        cnt_b = self._pool.get((self.P, B), np.int32, 0,
                               tag=("probe", "cnt", store_idx))
        for p, (_, pk, _pt) in per_shard.items():
            m = side.meta[p]
            mirror[p, :len(m)] = m.slot
            lo, cnt = bounds[p]
            lo_b[p, :len(pk)] = lo
            cnt_b[p, :len(pk)] = cnt
        prog = build_banded_probe(self.mesh, side.dtypes_key())
        with self._wd_section("join_probe"):
            put = jax.device_put((mirror, lo_b, cnt_b),
                                 self._sharding)
            outs = prog(self._planes[store_idx], put[0], put[1],
                        put[2], W)
        return self._harvest_get(outs)

    # ------------------------------------------------------ match assembly

    def _assemble(self, probe_idx: int, probe_cols: Dict[str,
                                                         np.ndarray],
                  probe_ts: np.ndarray,
                  probe_keys: np.ndarray,
                  probed: Dict[int, dict],
                  out_ts) -> Optional[RecordBatch]:
        """One output batch from the per-shard probe results:
        shard-major, probe stream order within shard, band order within
        probe — deterministic and backend-identical. ``out_ts(lt, rt)``
        computes the emitted timestamp column."""
        store_idx = 1 - probe_idx
        store = self.sides[store_idx]
        if not probed:
            return None
        store_names = [n for n, _ in store.schema]
        probe_names = sorted(probe_cols)
        # _suffixed_names takes (left, right); the probe side is left
        # only when it is input 0
        if probe_idx == 0:
            pmap_names, smap_names = _suffixed_names(
                probe_names, store_names, self.suffixes)
        else:
            smap_names, pmap_names = _suffixed_names(
                store_names, probe_names, self.suffixes)
        chunks: List[Dict[str, np.ndarray]] = []
        for p in sorted(probed):
            r = probed[p]
            rows = r["orig"][r["l_rep"]]
            cols: Dict[str, np.ndarray] = {
                KEY_ID_FIELD: probe_keys[rows]}
            for n in probe_names:
                cols[pmap_names[n]] = probe_cols[n][rows]
            for i, n in enumerate(store_names):
                cols[smap_names[n]] = r["cols"][i]
            lt = probe_ts[rows]
            rt = r["ts"]
            cols[TIMESTAMP_FIELD] = out_ts(lt, rt)
            chunks.append(cols)
        if not chunks:
            return None
        merged = {k: (np.concatenate([c[k] for c in chunks])
                      if len(chunks) > 1 else chunks[0][k])
                  for k in chunks[0]}
        return RecordBatch(merged)

    # ------------------------------------------------------------ snapshots

    def _side_snapshot(self, side_idx: int) -> Dict[str, object]:
        side = self.sides[side_idx]
        if side is None:
            return {"table": {}, "schema": []}
        device_values = None
        if self.backend == "device" and side.device_cols:
            host = self._harvest_get(list(self._planes[side_idx]),
                                     "snapshot_harvest")
            device_values = [
                {i: host[j][p]
                 for j, i in enumerate(side.device_cols)}
                for p in range(self.P)]
        else:
            device_values = [{} for _ in range(self.P)]
        return {
            "table": side.snapshot_rows(self.max_parallelism,
                                        device_values),
            "schema": [(n, dt.str) for n, dt in side.schema],
        }

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        self._drain_fences()
        return {
            "kind": self.kind,
            "left": self._side_snapshot(0),
            "right": self._side_snapshot(1),
            "next_rid": int(self._next_rid),
            **self._meta_snapshot(),
        }

    def _meta_snapshot(self) -> Dict[str, object]:
        return {}

    def _restore_meta(self, snap: Dict[str, object]) -> None:
        pass

    def restore(self, snap: Dict[str, object],
                key_group_filter=None) -> None:
        for side_idx, name in ((0, "left"), (1, "right")):
            s = snap.get(name) or {}
            table = s.get("table") or {}
            schema = [(n, np.dtype(d)) for n, d in
                      s.get("schema", [])]
            self.sides[side_idx] = None
            self._planes[side_idx] = None
            if not schema:
                continue
            self._init_side(side_idx, schema)
            self._restore_rows(side_idx, table, key_group_filter)
        self._next_rid = max(int(snap.get("next_rid", 1)),
                             self._next_rid)
        self._restore_meta(snap)

    def _restore_rows(self, side_idx: int, table: Dict[str, object],
                      key_group_filter) -> None:
        side = self.sides[side_idx]
        keys = np.asarray(table.get("key_id", ()), dtype=np.int64)
        if not len(keys):
            return
        rids = np.asarray(table["namespace"], dtype=np.int64)
        ts = np.asarray(table["ts"], dtype=np.int64)
        dirty = np.asarray(table.get("dirty",
                                     np.zeros(len(keys), bool)),
                           dtype=bool)
        leaves = [np.asarray(table[f"leaf_{i}"],
                             dtype=side.schema[i][1])
                  for i in range(len(side.schema))]
        if key_group_filter is not None:
            kg = table.get("key_group")
            kg = (np.asarray(kg, dtype=np.int64) if kg is not None
                  else assign_key_groups(keys, self.max_parallelism))
            keep = np.isin(kg, np.asarray(sorted(
                int(g) for g in key_group_filter)))
            keys, rids, ts, dirty = (keys[keep], rids[keep],
                                     ts[keep], dirty[keep])
            leaves = [lv[keep] for lv in leaves]
        if not len(keys):
            return
        self._next_rid = max(self._next_rid, int(rids.max()) + 1)
        shards = self._shards_of(keys)
        if not side.spill_active:
            # an engine that grew during the run must be able to
            # restore its own snapshot: grow exactly like ingest does
            counts = np.bincount(shards, minlength=self.P)
            need = int(counts.max())
            while any(side.free_headroom(p) < counts[p]
                      for p in range(self.P)):
                self._grow_side(side_idx, max(
                    side.capacity * 2,
                    pad_bucket_size(side.capacity + need)))
        put_slots: Dict[int, np.ndarray] = {}
        put_sel: Dict[int, np.ndarray] = {}
        for p in range(self.P):
            sel = np.nonzero(shards == p)[0]
            if not len(sel):
                continue
            # newest rows stay resident (they expire last and are the
            # likeliest band candidates); the rest re-home as pages
            order = sel[np.argsort(-ts[sel], kind="stable")]
            n_res = min(len(order), side.free_headroom(p))
            res, cold = order[:n_res], order[n_res:]
            slots = side.allocate(p, n_res)
            slot_col = np.full(len(sel), -1, dtype=np.int32)
            if len(cold):
                restore_into_pages(
                    side.spills[p], side.pmaps[p], keys[cold],
                    rids[cold], [lv[cold] for lv in leaves],
                    page_rows=max(side.capacity // 8, 256),
                    dirty=dirty[cold], append=True)
            # metadata rows for everything (cold rows carry slot -1);
            # keep (res-first) ordering irrelevant — merge sorts
            both = np.concatenate([res, cold]).astype(np.int64)
            slot_col[:n_res] = slots
            side.meta[p].merge_rows(keys[both], ts[both], rids[both],
                                    slot_col, dirty[both])
            for i in side.shadow:
                side.shadow[i][p][slots] = leaves[i][res]
            if len(res):
                put_slots[p] = slots
                put_sel[p] = res
        if self.backend == "device" and side.device_cols and put_slots:
            import jax

            from flink_tpu.joins.kernels import build_join_put

            B = sticky_bucket(max(len(s) for s in put_slots.values()),
                              self._put_bucket[side_idx])
            self._put_bucket[side_idx] = B
            slot_block = np.zeros((self.P, B), dtype=np.int32)
            val_blocks = [np.zeros((self.P, B),
                                   dtype=side.schema[i][1])
                          for i in side.device_cols]
            for p, slots in put_slots.items():
                m = len(slots)
                slot_block[p, :m] = slots
                for j, i in enumerate(side.device_cols):
                    val_blocks[j][p, :m] = leaves[i][put_sel[p]]
            prog = build_join_put(self.mesh, side.dtypes_key())
            with self._wd_section("restore_put"):
                put = jax.device_put(
                    (slot_block, *val_blocks), self._sharding)
                self._planes[side_idx] = prog(
                    self._planes[side_idx], put[0], tuple(put[1:]))

    # ---------------------------------------------- shard-granular units

    def shard_key_groups(self) -> List[Tuple[int, int]]:
        from flink_tpu.state.keygroups import shard_key_group_ranges

        return shard_key_group_ranges(self.P, self.max_parallelism,
                                      self.key_group_range)

    def snapshot_sharded(self, mode: str = "full"
                         ) -> Dict[Tuple[int, int], Dict[str, object]]:
        """One independently-restorable unit per shard's key-group
        range — both sides' rows split by their ``key_group`` column,
        scalar metadata replicated (monotonic-max / watermark-min on
        merge). The union of the units is exactly ``snapshot()``."""
        snap = self.snapshot(mode)
        units: Dict[Tuple[int, int], Dict[str, object]] = {}
        for g0, g1 in self.shard_key_groups():
            unit = {"kind": snap["kind"],
                    "next_rid": snap["next_rid"],
                    **{k: v for k, v in snap.items()
                       if k not in ("kind", "left", "right",
                                    "next_rid")}}
            for name in ("left", "right"):
                s = snap[name]
                table = s.get("table") or {}
                kg = np.asarray(table.get("key_group", ()),
                                dtype=np.int64)
                if len(kg):
                    mask = (kg >= g0) & (kg <= g1)
                    unit[name] = {
                        "table": {k: np.asarray(v)[mask]
                                  for k, v in table.items()},
                        "schema": s.get("schema", []),
                    }
                else:
                    unit[name] = {"table": dict(table),
                                  "schema": s.get("schema", [])}
            units[(int(g0), int(g1))] = unit
        return units

    def merge_unit_snapshots(self, units: List[Dict[str, object]]
                             ) -> Dict[str, object]:
        merged: Dict[str, object] = {
            "kind": self.kind,
            "next_rid": max((int(u.get("next_rid", 1))
                             for u in units), default=1),
            **self._merge_meta_units(units),
        }
        for name in ("left", "right"):
            tables = [u.get(name, {}).get("table") or {}
                      for u in units]
            tables = [t for t in tables if t and len(
                np.asarray(t.get("key_id", ())))]
            schema = next((u[name]["schema"] for u in units
                           if u.get(name, {}).get("schema")), [])
            if not tables:
                merged[name] = {"table": {}, "schema": schema}
                continue
            cols = sorted(set().union(*(set(t) for t in tables)))
            table = {k: np.concatenate([np.asarray(t[k])
                                        for t in tables])
                     for k in cols}
            order = np.argsort(table["namespace"], kind="stable")
            merged[name] = {
                "table": {k: v[order] for k, v in table.items()},
                "schema": schema,
            }
        return merged

    def _merge_meta_units(self, units) -> Dict[str, object]:
        return {}

    # ------------------------------------------------------------- reshard

    def reshard(self, new_shards: int, devices=None) -> Dict[str, object]:
        """LIVE key-group migration to a new mesh size: every logical
        row (resident + paged, dirtiness intact) lifts off the old
        plane, the mesh rebuilds, and rows land on their new owners —
        the join form of ``MeshSpillSupport.reshard``."""
        new_shards = int(new_shards)
        if new_shards < 1:
            raise ValueError("new_shards must be >= 1")
        t0 = time.perf_counter()
        self._drain_fences()
        chaos.fault_point("rescale.handoff", stage="drain",
                          shards=new_shards)
        snaps = [self._side_snapshot(i) for i in (0, 1)]
        rows_moved = sum(
            len(np.asarray((s.get("table") or {}).get("key_id", ())))
            for s in snaps)
        if self.backend == "device":
            from flink_tpu.parallel.mesh import make_mesh
            from jax.sharding import NamedSharding, PartitionSpec
            from flink_tpu.parallel.mesh import KEY_AXIS

            self.mesh = make_mesh(new_shards, devices=devices)
            self.P = int(self.mesh.devices.size)
            self._sharding = NamedSharding(self.mesh,
                                           PartitionSpec(KEY_AXIS))
        else:
            self.P = new_shards
        t = self.host_topology
        if t is not None and t.num_shards != self.P:
            # the (hosts, local) factorization no longer covers the
            # resized mesh — drop to the flat single-axis exchange
            self.host_topology = None
        if self.max_parallelism < self.P:
            raise ValueError(
                f"cannot reshard to {new_shards}: max_parallelism "
                f"{self.max_parallelism}")
        chaos.fault_point("rescale.handoff", stage="commit",
                          shards=new_shards)
        old_counters = [
            self.sides[i].spill_counters() if self.sides[i] else None
            for i in (0, 1)]
        for side_idx, s in enumerate(snaps):
            schema = [(n, np.dtype(d))
                      for n, d in s.get("schema", [])]
            self.sides[side_idx] = None
            self._planes[side_idx] = None
            if not schema:
                continue
            self._init_side(side_idx, schema)
            # job-lifetime spill counters survive the mesh resize
            if old_counters[side_idx]:
                c = old_counters[side_idx]
                pm = self.sides[side_idx].pmaps[0]
                pm.pages_evicted += c["pages_evicted"]
                pm.rows_evicted += c["rows_evicted"]
                pm.pages_reloaded += c["pages_reloaded"]
                pm.rows_reloaded += c["rows_reloaded"]
                pm.rows_compacted += c["rows_compacted"]
                self.sides[side_idx].cold_rows_served = \
                    c["cold_rows_served"]
            # lifted rows keep their dirtiness: _restore_rows carries
            # the snapshot's dirty column into metadata and pages
            self._restore_rows(side_idx, s.get("table") or {}, None)
        wd = self._watchdog
        if wd is not None and self.mesh is not None:
            wd.rebind(self.P,
                      [d.id for d in self.mesh.devices.flat])
        return {"shards": self.P, "rows_moved": rows_moved,
                "seconds": time.perf_counter() - t0}

    # ------------------------------------------------------------ counters

    def spill_counters(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for side_idx, name in ((0, "left"), (1, "right")):
            side = self.sides[side_idx]
            if side is None:
                continue
            for k, v in side.spill_counters().items():
                out[k] = out.get(k, 0) + v
        return out

    def shard_resident_rows(self) -> List[int]:
        totals = [0] * self.P
        for side in self.sides:
            if side is None:
                continue
            for p, n in enumerate(side.resident_rows()):
                totals[p] += n
        return totals

    # --------------------------------------------------- read replica
    # (tenancy/replica.py — publish hooks for the join side tables;
    # rows are immutable after insert, so the boundary delta is pure
    # identity churn: inserts, evictions, prunes)

    #: per-side ReplicaPlane (armed by arm_side_replica)
    _side_replicas = (None, None)

    def arm_side_replica(self, side_idx: int):
        """Arm a read replica over one side table (device backend; the
        side must have seen its first batch — the value schema is
        late-bound). Returns a
        :class:`~flink_tpu.tenancy.replica.JoinSideReplicaAdapter`."""
        from flink_tpu.tenancy.replica import (
            JoinSideReplicaAdapter,
            ReplicaPlane,
        )

        side = self.sides[side_idx]
        if side is None:
            raise RuntimeError(
                "side table not initialized yet — the value schema is "
                "observed at the side's first batch")
        if self.backend != "device":
            raise RuntimeError(
                "side replicas ride the device value planes; the host "
                "oracle backend serves reads directly")

        class _Leaf:
            def __init__(self, dtype):
                self.dtype = dtype
                self.identity = np.dtype(dtype).type(0)

        plane = ReplicaPlane(
            self.mesh, [_Leaf(side.schema[i][1])
                        for i in side.device_cols], side.capacity)
        plane.warm_tiers()
        reps = list(self._side_replicas)
        reps[side_idx] = plane
        self._side_replicas = tuple(reps)
        return JoinSideReplicaAdapter(plane, side)

    def _publish_side_replicas(self, watermark: int) -> None:
        for side_idx in (0, 1):
            rep = self._side_replicas[side_idx]
            side = self.sides[side_idx]
            if rep is None or side is None:
                continue
            from flink_tpu.observe import flight_recorder as flight

            with flight.span("serving.replica_publish",
                             watermark=int(watermark)):
                self._publish_one_side(rep, side, side_idx,
                                       int(watermark))

    def _publish_one_side(self, rep, side, side_idx: int,
                          watermark: int) -> None:
        """The join form of the boundary publish: derive per-slot
        metadata from the sorted row metadata, diff against the
        replica's shadow (rows are immutable — identity changes ARE
        the delta), split disappeared rows into cold (still mapped in
        the page tier) vs pruned, and hand the changed slots to the
        shared publish program."""
        if not hasattr(self, "_rep_last_rid"):
            self._rep_last_rid = [0, 0]
        if rep.needs_rebuild(self.P, side.capacity):
            rep.rebuild(self.mesh, side.capacity)
            rep.warm_tiers()
            # a rebuild's republish covers resident rows; resetting the
            # rid watermark makes every COLD row re-enter the index too
            self._rep_last_rid[side_idx] = 0
        last_rid = self._rep_last_rid[side_idx]
        per_shard = {}
        for p in range(self.P):
            m = side.meta[p]
            cap = side.capacity
            cur_used = np.zeros(cap, dtype=bool)
            cur_key = np.zeros(cap, dtype=np.int64)
            cur_rid = np.zeros(cap, dtype=np.int64)
            cur_ts = np.zeros(cap, dtype=np.int64)
            res = np.nonzero(m.slot >= 0)[0]
            slots_res = m.slot[res]
            cur_used[slots_res] = True
            cur_key[slots_res] = m.key[res]
            cur_rid[slots_res] = m.rid[res]
            cur_ts[slots_res] = m.ts[res]
            r_used = rep.rep_used[p]
            r_key = rep.rep_key[p]
            r_rid = rep.rep_ns[p]
            moved = (cur_key != r_key) | (cur_rid != r_rid)
            ident_change = cur_used & (~r_used | moved)
            up = np.nonzero(ident_change)[0]
            gone = np.nonzero(r_used & (~cur_used | moved))[0]
            cold: List[Tuple[int, int]] = []
            freed: List[Tuple[int, int]] = []
            if len(gone):
                from flink_tpu.joins.side_table import _rid_positions

                g_keys = r_key[gone].copy()
                g_rids = r_rid[gone].copy()
                # still resident at another slot? covered by its upsert
                found, src = _rid_positions(m.rid, g_rids)
                still = np.zeros(len(g_rids), dtype=bool)
                still[found] = m.slot[src] >= 0
                miss = ~still
                if miss.any():
                    mk, mr = g_keys[miss], g_rids[miss]
                    is_cold = side.pmaps[p].spilled_mask(
                        np.asarray(mr, dtype=np.int64))
                    for j in range(len(mk)):
                        if is_cold[j]:
                            cold.append((int(mk[j]), int(mr[j]), None))
                        else:
                            freed.append((int(mk[j]), int(mr[j])))
            # rows created AND evicted since the last publish (never
            # resident at a boundary): rids are allocation-monotonic,
            # so "new" is one vectorized compare
            new_cold = np.nonzero((m.slot < 0) & (m.rid > last_rid))[0]
            for pos in new_cold.tolist():
                cold.append((int(m.key[pos]), int(m.rid[pos]),
                             (int(m.ts[pos]), None)))
            # extra payload: (ts, host-shadow column values) per row —
            # device-ineligible columns never ride the device plane
            extra = None
            if len(up):
                host_cols = [side.shadow[i][p][up]
                             for i in side.host_cols]
                extra = [
                    (int(cur_ts[s]),
                     tuple(hc[j] for hc in host_cols))
                    for j, s in enumerate(up)]
            per_shard[p] = {
                "up_slots": up.astype(np.int32),
                "up_keys": cur_key[up].copy(),
                "up_ns": cur_rid[up].copy(),
                "up_extra": extra,
                "cold": cold,
                "freed": freed,
                "fresh": bool(ident_change.any()),
            }
            per_shard[p]["_shadow"] = (cur_used, cur_key, cur_rid)
        # shadow + rid watermark update ONLY after the publish succeeds
        # (a torn publish must leave the delta re-derivable)
        rep.publish(self._planes[side_idx] or (), per_shard, watermark)
        for p, d in per_shard.items():
            cur_used, cur_key, cur_rid = d.pop("_shadow")
            rep.rep_used[p][:] = cur_used
            rep.rep_key[p][:] = cur_key
            rep.rep_ns[p][:] = cur_rid
        self._rep_last_rid[side_idx] = self._next_rid - 1

    def query_side_batch(self, side_idx: int, key_ids
                         ) -> List[List[dict]]:
        """LIVE point lookup against one side table: per requested key,
        the side's buffered rows as ``[{"ts", "rid", <col>: v}, ...]``
        sorted by (ts, rid) — resident rows through ONE gather + ONE
        device read, cold rows from their shards' page tiers
        (``cold_rows_served`` counted). The replica staleness tests pin
        the replica path bit-identical to this at every published
        boundary (via a checkpoint round-trip)."""
        side_idx = int(side_idx)
        side = self.sides[side_idx]
        key_ids = np.asarray(key_ids, dtype=np.int64)
        n = len(key_ids)
        results: List[List[dict]] = [[] for _ in range(n)]
        if side is None or n == 0:
            return results
        shards = self._shards_of(key_ids)
        #: (request row, meta position) per matched row, per shard
        rows_of: Dict[int, List[Tuple[int, int]]] = {}
        for p in np.unique(shards).tolist():
            m = side.meta[p]
            if not len(m):
                continue
            sel = np.nonzero(shards == p)[0]
            lo = pair_lower_bound(m.key, m.ts, key_ids[sel],
                                  np.full(len(sel), -(1 << 62)))
            hi = pair_lower_bound(m.key, m.ts, key_ids[sel],
                                  np.full(len(sel), (1 << 62)))
            lanes = []
            for j, r in enumerate(sel.tolist()):
                for pos in range(int(lo[j]), int(hi[j])):
                    lanes.append((r, pos))
            if lanes:
                rows_of[int(p)] = lanes
        # resident values: one gather + one batched D2H for all shards
        gathered = self._gather_rows(side_idx, {
            p: np.clip(side.meta[p].slot[[pos for _, pos in lanes]],
                       0, None)
            for p, lanes in rows_of.items()}) if rows_of else {}
        names = [nm for nm, _ in side.schema]
        for p, lanes in rows_of.items():
            m = side.meta[p]
            cold_wants: List[Tuple[int, int, int]] = []
            sinks = [np.zeros(len(lanes), dtype=dt)
                     for _, dt in side.schema]
            rows_arr = np.arange(len(lanes))
            for j, (r, pos) in enumerate(lanes):
                if m.slot[pos] < 0:
                    cold_wants.append((j, int(m.key[pos]),
                                       int(m.rid[pos])))
                else:
                    for i in side.shadow:
                        sinks[i][j] = side.shadow[i][p][m.slot[pos]]
                    gi = 0
                    for i in side.device_cols:
                        sinks[i][j] = gathered[p][gi][j]
                        gi += 1
            if cold_wants:
                side.fill_cold(p, cold_wants, sinks, rows_arr)
            for j, (r, pos) in enumerate(lanes):
                row = {"ts": int(m.ts[pos]), "rid": int(m.rid[pos])}
                for i, nm in enumerate(names):
                    row[nm] = sinks[i][j].item()
                results[r].append(row)
        for r in range(n):
            results[r].sort(key=lambda d: (d["ts"], d["rid"]))
        return results


class MeshIntervalJoinEngine(JoinEngineBase):
    """Keyed interval join over the dual slot tables (INNER)."""

    kind = "interval"

    def __init__(self, lower: int, upper: int, **kw) -> None:
        if lower > upper:
            raise ValueError(f"lower {lower} > upper {upper}")
        super().__init__(**kw)
        self.lower = int(lower)
        self.upper = int(upper)

    # band of STORED rows matching a probe at time t: the stored side's
    # admissible window depends on which side probes —
    #   probe = left  -> stored right rows in [t+lower, t+upper]
    #   probe = right -> stored left rows with t in [lts+lower,
    #   lts+upper], i.e. lts in [t-upper, t-lower]
    def _band_for(self, probe_idx: int):
        if probe_idx == 0:
            blo, bhi = self.lower, self.upper
        else:
            blo, bhi = -self.upper, -self.lower

        def band(m, pk, pt):
            lo = pair_lower_bound(m.key, m.ts, pk, pt + blo)
            hi = pair_lower_bound(m.key, m.ts, pk, pt + bhi + 1)
            return lo, (hi - lo).astype(np.int64)

        return band

    def process_batch(self, batch: RecordBatch,
                      input_index: int = 0) -> List[RecordBatch]:
        if len(batch) == 0:
            return []
        with self._flight_ingest():
            return self._process_batch_inner(batch, int(input_index))

    def _process_batch_inner(self, batch: RecordBatch,
                             side_idx: int) -> List[RecordBatch]:
        self._wd_boundary()
        side = self._ensure_side(side_idx, batch)
        self._check_schema(side, batch, side_idx)
        keys = np.asarray(batch.key_ids, dtype=np.int64)
        ts = np.asarray(batch.timestamps, dtype=np.int64)
        values = [np.asarray(batch[n]) for n, _ in side.schema]
        out: List[RecordBatch] = []
        store_idx = 1 - side_idx
        store = self.sides[store_idx]
        shards = self._shards_of(keys)
        if store is not None and store.num_rows():
            per_shard: Dict[int, Tuple[np.ndarray, np.ndarray,
                                       np.ndarray]] = {}
            for p in np.unique(shards).tolist():
                sel = np.nonzero(shards == p)[0]
                per_shard[int(p)] = (sel, keys[sel], ts[sel])
            probed = self._probe_banded(store_idx, per_shard,
                                        self._band_for(side_idx))
            probe_cols = {n: np.asarray(batch[n])
                          for n in batch.names()
                          if n not in (KEY_ID_FIELD,
                                       TIMESTAMP_FIELD)}
            m = self._assemble(side_idx, probe_cols, ts, keys, probed,
                               out_ts=np.maximum)
            if m is not None and len(m):
                out.append(m)
        # insert AFTER the probe: a pair is emitted by whichever side
        # arrives second (never joins its own batch — the structural
        # dedup of the reference operator)
        self._ingest(side_idx, keys, ts, values, shards=shards)
        return out

    def on_watermark(self, watermark: int) -> List[RecordBatch]:
        """Prune expired rows: a left row at t is dead once the
        watermark passes ``t + upper``; a right row at t once it passes
        ``t - lower`` (no right-side probe can still reach it)."""
        with self._flight_fire(watermark):
            self._wd_boundary()
            if self.sides[0] is not None:
                self.sides[0].prune(int(watermark) - self.upper)
            if self.sides[1] is not None:
                self.sides[1].prune(int(watermark) + self.lower)
        # replica publish AFTER the prunes of this boundary
        self._publish_side_replicas(int(watermark))
        return []

    def _meta_snapshot(self) -> Dict[str, object]:
        return {"lower": self.lower, "upper": self.upper}

    def _merge_meta_units(self, units) -> Dict[str, object]:
        return {"lower": self.lower, "upper": self.upper}


class MeshTemporalJoinEngine(JoinEngineBase):
    """Event-time temporal join against the versioned right plane."""

    kind = "temporal"

    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        #: pending left rows (host columnar, drained per watermark:
        #: they are transient ordering state, not keyed state — the
        #: versioned RIGHT side is the device-resident plane)
        self._pending: List[RecordBatch] = []
        self._emitted_wm = _NEG
        self.late_left_dropped = 0

    def process_batch(self, batch: RecordBatch,
                      input_index: int = 0) -> List[RecordBatch]:
        if len(batch) == 0:
            return []
        with self._flight_ingest():
            return self._process_batch_inner(batch, int(input_index))

    def _process_batch_inner(self, batch: RecordBatch,
                             input_index: int) -> List[RecordBatch]:
        self._wd_boundary()
        if int(input_index) == 0:
            late = np.asarray(batch.timestamps,
                              dtype=np.int64) <= self._emitted_wm
            if late.any():
                self.late_left_dropped += int(late.sum())
                batch = batch.filter(~late)
            if len(batch):
                self._pending.append(batch)
            return []
        side = self._ensure_side(1, batch)
        self._check_schema(side, batch, 1)
        self._ingest(1, np.asarray(batch.key_ids, dtype=np.int64),
                     np.asarray(batch.timestamps, dtype=np.int64),
                     [np.asarray(batch[n]) for n, _ in side.schema])
        return []

    @staticmethod
    def _version_band(m, pk, pt):
        """Latest version at-or-before each probe time: the ``W == 1``
        band ``[ub(k, t) - 1]`` where the candidate's key matches."""
        hi = pair_lower_bound(m.key, m.ts, pk, pt + 1)
        pos = hi - 1
        ok = pos >= 0
        ok[ok] &= m.key[pos[ok]] == pk[ok]
        return np.maximum(pos, 0), ok.astype(np.int64)

    def on_watermark(self, watermark: int) -> List[RecordBatch]:
        with self._flight_fire(watermark):
            out = self._on_watermark_inner(int(watermark))
        # replica publish AFTER this boundary's probes/compaction
        self._publish_side_replicas(int(watermark))
        return out

    def _on_watermark_inner(self, watermark: int) -> List[RecordBatch]:
        self._wd_boundary()
        out: List[RecordBatch] = []
        if self._pending:
            left = (self._pending[0] if len(self._pending) == 1
                    else RecordBatch.concat(self._pending))
            ready_mask = left.timestamps <= watermark
            ready = left.filter(ready_mask)
            if len(ready) and self.sides[1] is not None \
                    and self.sides[1].num_rows():
                # sort once by (key, ts): the reference's per-key
                # ordered probe, vectorized — and the left side must
                # know its schema even when it never stores rows
                self._ensure_side(0, ready)
                order = np.lexsort((ready.timestamps, ready.key_ids))
                ready = ready.take(order)
                keys = np.asarray(ready.key_ids, dtype=np.int64)
                ts = np.asarray(ready.timestamps, dtype=np.int64)
                shards = self._shards_of(keys)
                per_shard = {}
                for p in np.unique(shards).tolist():
                    sel = np.nonzero(shards == p)[0]
                    per_shard[int(p)] = (sel, keys[sel], ts[sel])
                # a crash/stall at the versioned-plane lookup: the
                # probe is read-only and the pending left buffer is
                # still intact, so recovery replays this watermark
                # consistently
                chaos.fault_point("join.versioned_lookup",
                                  probes=len(ready))
                probed = self._probe_banded(1, per_shard,
                                            self._version_band)
                probe_cols = {n: np.asarray(ready[n])
                              for n in ready.names()
                              if n not in (KEY_ID_FIELD,
                                           TIMESTAMP_FIELD)}
                m = self._assemble(0, probe_cols, ts, keys, probed,
                                   out_ts=lambda lt, rt: lt)
                if m is not None and len(m):
                    out.append(m)
            elif len(ready) and self.sides[0] is None:
                self._ensure_side(0, ready)
            # buffer mutation AFTER the probe: a crash mid-probe
            # replays with the pending rows intact
            keep = ~ready_mask
            self._pending = ([left.filter(keep)] if keep.any()
                             else [])
        self._emitted_wm = max(self._emitted_wm, watermark)
        self._compact_versions(watermark)
        return out

    def _compact_versions(self, watermark: int) -> None:
        """Keep versions newer than the watermark plus each key's
        single latest at-or-before it (the cleanupState contract)."""
        side = self.sides[1]
        if side is None:
            return
        for p in range(self.P):
            m = side.meta[p]
            if not len(m):
                continue
            future = m.ts > watermark
            last_of_prefix = np.r_[
                (m.key[1:] != m.key[:-1]) | future[1:], True] & ~future
            dead = ~(future | last_of_prefix)
            if dead.any():
                side.drop_positions(p, np.nonzero(dead)[0])

    def _meta_snapshot(self) -> Dict[str, object]:
        pend = (RecordBatch.concat(self._pending)
                if self._pending else None)
        return {
            "emitted_wm": int(self._emitted_wm),
            "late_left_dropped": int(self.late_left_dropped),
            "pending": (dict(pend.columns) if pend is not None
                        else None),
        }

    def _restore_meta(self, snap: Dict[str, object]) -> None:
        self._emitted_wm = int(snap.get("emitted_wm", _NEG))
        self.late_left_dropped = int(snap.get("late_left_dropped", 0))
        pend = snap.get("pending")
        self._pending = (
            [RecordBatch({k: np.asarray(v) for k, v in pend.items()})]
            if pend else [])

    def _merge_meta_units(self, units) -> Dict[str, object]:
        pend_tabs = [u.get("pending") for u in units
                     if u.get("pending")]
        pending = None
        if pend_tabs:
            merged = {
                k: np.concatenate([np.asarray(t[k])
                                   for t in pend_tabs])
                for k in pend_tabs[0]}
            pending = merged
        return {
            # the OLDEST unit's horizon: its range replays from its
            # position and must not be judged late
            "emitted_wm": min((int(u.get("emitted_wm", _NEG))
                               for u in units), default=_NEG),
            "late_left_dropped": max(
                (int(u.get("late_left_dropped", 0)) for u in units),
                default=0),
            "pending": pending,
        }

    def snapshot_sharded(self, mode: str = "full"):
        units = super().snapshot_sharded(mode)
        # pending left rows split by key group like table rows — each
        # unit replays only its own range
        for (g0, g1), unit in units.items():
            pend = unit.get("pending")
            if not pend:
                continue
            kid = np.asarray(pend[KEY_ID_FIELD], dtype=np.int64)
            kg = assign_key_groups(kid, self.max_parallelism)
            mask = (kg >= g0) & (kg <= g1)
            unit["pending"] = {k: np.asarray(v)[mask]
                               for k, v in pend.items()}
        return units


class UnsupportedJoinColumn(TypeError):
    """A column the window-join engine cannot carry as int32 words
    (strings, objects): the operator falls back to the host join."""


def fine_bucket(n: int, cached: int = 0, minimum: int = 256) -> int:
    """``n`` rounded up to its three leading bits (at most a quarter of
    padding, where a power of two may double it), or ``cached`` where
    that covers ``n`` within ``sticky_bucket``'s fourfold: a steady
    stream, and the smaller batches that end it, keep one compiled
    shape."""
    n = max(int(n), int(minimum))
    q = 1 << max(n.bit_length() - 3, 0)
    need = -(-n // q) * q
    if need <= cached <= 4 * need:
        return cached
    return need


class _Column(NamedTuple):
    """How one input column rides the device: ``words`` int32 planes (1
    or 2 by its width; 0 for the key column of an integer key, which is
    the key itself)."""

    name: str
    dtype: np.dtype
    words: int


#: the key id: two words, the first two planes of a side
_KEY = _Column(KEY_ID_FIELD, np.dtype(np.int64), 2)


def _column_of(name: str, dtype: np.dtype, key_field: Optional[str]
               ) -> _Column:
    if name == KEY_ID_FIELD:
        return _KEY
    if name == key_field and dtype.kind in "iu":
        # hash_keys_to_i64 passes integers through: the key IS the column
        return _Column(name, dtype, 0)
    if dtype.kind not in "iufb" or dtype.itemsize not in (1, 2, 4, 8):
        raise UnsupportedJoinColumn(
            f"column {name!r} of dtype {dtype} cannot ride the device "
            "window join (fixed-width numbers and booleans only)")
    return _Column(name, dtype, 2 if dtype.itemsize == 8 else 1)


def _to_words(values: np.ndarray, col: _Column) -> List[np.ndarray]:
    v = np.ascontiguousarray(values, dtype=col.dtype)
    if col.words == 2:
        w = v.view(np.int32).reshape(-1, 2)
        return [w[:, 0], w[:, 1]]
    if col.dtype.itemsize == 4:
        return [v.view(np.int32)]
    return [v.astype(np.int32)]


def _from_words(words: List[np.ndarray], dtype: np.dtype) -> np.ndarray:
    if len(words) == 2:
        both = np.empty((len(words[0]), 2), dtype=np.int32)
        both[:, 0], both[:, 1] = words
        return both.view(dtype).reshape(-1)
    if dtype.itemsize == 4:
        return words[0].view(dtype)
    return words[0].astype(dtype)


class _Fire:
    """One dispatched window fire, until its harvest: what a re-run
    needs, and the slices that leave with it."""

    __slots__ = ("window_end", "pages", "out_rows", "retire", "done",
                 "pending")

    def __init__(self, window_end: int, pages, out_rows: int) -> None:
        self.window_end = window_end
        self.pages = pages
        self.out_rows = out_rows
        #: (side, detached slice) to release once harvested
        self.retire: List[Tuple[int, list]] = []
        self.done = False
        #: the ``PendingFire`` of an asynchronous fire until its harvest
        self.pending = None


#: a staged chunk's lanes per shard, in the side's ingest buckets
_CHUNK_BUCKETS = 8
#: batches a side stages before its chunks go up behind the match
_STAGE_BATCHES = 64


class _Stage:
    """One side's rows on their way to the device: ``[P, 1 + words,
    lanes]`` blocks (slot first) that each shard fills from the front,
    batch after batch, one put a block. A batch's own put is one block of
    its ingest bucket; the rows staged while a match runs fill blocks of
    ``_CHUNK_BUCKETS`` buckets, fixed when the stage opens, so a flush is
    a few puts of one compiled shape however many batches were staged."""

    __slots__ = ("width", "lanes", "blocks", "fill", "batches")

    def __init__(self, shards: int, width: int, lanes: int) -> None:
        self.width = width
        self.lanes = lanes
        self.blocks: List[np.ndarray] = []
        self.fill = [0] * shards
        self.batches = 0

    def add(self, slots, words, shards, counts) -> None:
        """A batch's slots and words; ``counts`` its rows per shard,
        ``shards`` each row's (None: one shard)."""
        cols = [slots] + words
        if shards is not None:
            order = np.argsort(shards, kind="stable")
            cols = [c[order] for c in cols]
        at = 0
        for p, c in enumerate(counts):
            if c:
                self._write(p, [col[at:at + c] for col in cols])
            at += c
        self.batches += 1

    def _write(self, p: int, cols) -> None:
        n, done = len(cols[0]), 0
        while done < n:
            blk, off = divmod(self.fill[p], self.lanes)
            if blk == len(self.blocks):
                self.blocks.append(np.empty(
                    (len(self.fill), self.width, self.lanes), np.int32))
            take = min(n - done, self.lanes - off)
            block = self.blocks[blk]
            for w, col in enumerate(cols):
                block[p, w, off:off + take] = col[done:done + take]
            done += take
            self.fill[p] += take

    def padded(self, drop_slot: int) -> List[np.ndarray]:
        """The blocks, each shard's lanes past its rows given the slot
        ``drop_slot``, which the scatter drops (and words it never
        reads): written now, at the put, because a ``_grow`` makes the
        old capacity a live slot."""
        for i, block in enumerate(self.blocks):
            for p, f in enumerate(self.fill):
                block[p, 0, min(max(f - i * self.lanes, 0),
                                self.lanes):] = drop_slot
        return self.blocks


class MeshWindowJoinEngine(JoinEngineBase):
    """INNER equi-join of two keyed streams per event-time window
    (``JoinedStreams.where().equal_to().window().apply()``, reference:
    JoinedStreams.java / CoGroupedStreams.java) on the device.

    Semantics are the host ``WindowJoinOperator``'s: every pair of a
    window once, when the watermark passes its end, stamped ``end - 1``
    and carrying ``window_start`` / ``window_end``; records behind a
    fired window's retention dropped and counted (``SliceBookkeeper``).

    - **Ingest** (``process_batch``; the side is ``input_side``, which
      the operator sets before each call): the batch's slices, its key
      groups' shards, slots from each (shard, slice)'s pages
      (:class:`~flink_tpu.joins.side_table.WindowPages`), and one scatter
      of its int32 words into the side's planes — host work in the
      batch's rows only: no live row is read, merged or sorted. While an
      asynchronous fire of this engine runs on the device, a batch is
      indexed as ever but its rows wait in a host stage per side
      (:class:`_Stage`, counted by the instant ``join.staged``) instead of
      queueing a put behind the match; ``flush`` sends each side's stage
      as a few puts of one fixed width — when the match lands (the
      operator's poll, before the harvest), before any match is
      dispatched, before the planes or the index are read or grown, and
      at ``_STAGE_BATCHES`` staged batches.
    - **Fire** (``on_watermark``): one program over the pages of the
      window's slices finds every pair (``kernels.window_pairs``). The
      host sizes the output from the window's row counts (and the last
      fire's pairs) without waiting for the device; the pair count comes
      back with the pairs, and a window with more pairs than its output
      holds runs again, at its harvest, wide enough.
    - **Retire**: an expired slice leaves the index as its page list; its
      pages go back to the free list when the last fire that reads them
      has been harvested.
    """

    kind = "window"
    supports_async_fires = True

    def __init__(self, assigner, key_fields=(None, None),
                 suffixes: Tuple[str, str] = ("_l", "_r"), **kw) -> None:
        super().__init__(suffixes=suffixes, **kw)
        if self.backend != "device" or self.max_device_slots:
            raise ValueError(
                "the window join runs on the device backend without a "
                "spill budget; the host WindowJoinOperator is the other "
                "path")
        from flink_tpu.joins.side_table import WindowPages
        from flink_tpu.windowing.bookkeeping import SliceBookkeeper

        self.assigner = assigner
        self.key_fields = tuple(key_fields)
        self.book = SliceBookkeeper(assigner)
        # a slice takes whole pages: about a thousandth of the capacity
        # each, so that many small slices fit as well as a few large ones
        self.page_rows = int(min(max(
            1 << max((self.capacity // 1024).bit_length() - 1, 0), 128),
            1 << 14))
        pages = -(-self.capacity // self.page_rows)
        self.capacity = pages * self.page_rows
        self.index = [WindowPages(self.P, pages, self.page_rows)
                      for _ in SIDE_NAMES]
        self.columns: List[Optional[List[_Column]]] = [None, None]
        #: the side ``process_batch`` ingests into
        self.input_side = 0
        self._ingest_bucket = [0, 0]
        self._page_bucket = [0, 0]
        self._out_bucket = 0
        self._last_pairs = 0
        #: the newest fire not yet harvested: slices that expire while
        #: it is in flight leave with it
        self._in_flight: Optional[_Fire] = None
        #: per side, the rows staged while a match runs (None: none)
        self._stage: List[Optional[_Stage]] = [None, None]
        #: asynchronous fires dispatched and not yet harvested
        self._fires_out = 0

    # ------------------------------------------------------------ columns

    @property
    def accs(self) -> tuple:
        """Every word plane of both sides: the state on the device."""
        return tuple(a for planes in self._planes if planes
                     for a in planes)

    @property
    def late_records_dropped(self) -> int:
        return self.book.late_records_dropped

    def _columns(self, side: int, batch: RecordBatch) -> List[_Column]:
        cols = self.columns[side]
        names = [n for n in batch.names() if n != TIMESTAMP_FIELD]
        if cols is None:
            # the planes hold the key's two words first, then each value
            # column's in the batch's order
            key_field = self.key_fields[side]
            cols = [_column_of(n, np.asarray(batch[n]).dtype, key_field)
                    for n in names]
            self.columns[side] = cols
            self._planes[side] = self._zeros(
                sum(c.words for c in cols), self.capacity)
        elif set(names) != {c.name for c in cols}:
            raise RuntimeError(
                f"{SIDE_NAMES[side]} join input changed columns "
                f"mid-stream: {sorted(c.name for c in cols)} -> "
                f"{sorted(names)}")
        return cols

    def _zeros(self, n_words: int, capacity: int) -> tuple:
        import jax.numpy as jnp

        return tuple(jax.device_put(jnp.zeros((self.P, capacity),
                                              jnp.int32), self._sharding)
                     for _ in range(n_words))

    # ------------------------------------------------------------- ingest

    def process_batch(self, batch: RecordBatch) -> bool:
        """Index the batch and put its rows up — or, while a match of
        this engine runs, stage them. True where something was dispatched
        (the batch's put or a flush): a fence then has work to stand
        behind."""
        side = self.input_side
        if len(batch) == 0:
            return False
        from flink_tpu.observe import flight_recorder as flight

        with self._flight_ingest():
            cols = self._columns(side, batch)
            with flight.span("join.index") as s:
                ends = self.assigner.assign_slice_ends(batch.timestamps)
                live = self.book.live_mask(ends)
                if live is not None:
                    ends, batch = ends[live], batch.filter(live)
                if len(batch) == 0:
                    return False
                if ends[0] == ends[-1] and (ends == ends[0]).all():
                    # one slice: the batch of an in-order stream but the
                    # one that crosses a slice's end
                    uniq, slice_of = ends[:1], None
                else:
                    uniq, slice_of = np.unique(ends, return_inverse=True)
                self.book.register_slices(ends, uniq)
                shards = (self._shards_of(batch.key_ids) if self.P > 1
                          else None)
                slots = self._allocate(side, len(batch), shards, uniq,
                                       slice_of)
                s.work = len(batch)
                staging = self.match_running()
                if staging:
                    flight.instant("join.staged", work=len(batch))
            if staging:
                return self._stage_rows(side, shards, slots, batch, cols)
            # rows staged behind a match that has landed go first
            self.flush()
            with flight.span("device.dispatch"):
                self._put(side, shards, slots, batch, cols)
        return True

    def match_running(self) -> bool:
        """Whether the one asynchronous fire of this engine not yet
        harvested is still on the device: a put dispatched now would wait
        behind it. False while two are out: the device is then a window
        behind, and the batches' puts and fences hold the loop back."""
        fire = self._in_flight
        return (self._fires_out == 1 and fire.pending is not None
                and not fire.pending.ready())

    @property
    def staged(self) -> bool:
        """Whether rows wait in the host stage."""
        return self._stage != [None, None]

    def _allocate(self, side: int, n: int, shards, uniq, slice_of
                  ) -> np.ndarray:
        """Slots for the batch's ``n`` rows: grouped by (shard, slice),
        each group from its slice's pages, in row order within the group.
        ``uniq`` are the batch's slice ends, ``slice_of`` each row's place
        in them (None: one slice)."""
        index = self.index[side]
        if shards is None and slice_of is None:
            groups = [(0, int(uniq[0]), None)]
        else:
            if slice_of is None:
                slice_of = np.zeros(n, dtype=np.int64)
            group_of = slice_of if shards is None else \
                shards.astype(np.int64) * len(uniq) + slice_of
            gids, inverse = np.unique(group_of, return_inverse=True)
            order = np.argsort(inverse, kind="stable")
            bounds = np.searchsorted(inverse[order],
                                     np.arange(len(gids) + 1))
            groups = []
            for g in range(len(gids)):
                rows = order[bounds[g]:bounds[g + 1]]
                p = 0 if shards is None else int(shards[rows[0]])
                groups.append((p, int(uniq[slice_of[rows[0]]]), rows))
        demand: Dict[int, Dict[int, int]] = {}
        for p, se, rows in groups:
            demand.setdefault(p, {})[se] = n if rows is None else len(rows)
        short = max(index.pages_short(p, d) for p, d in demand.items())
        if short:
            self._grow(index.num_pages + max(short, index.num_pages))
        if groups[0][2] is None:
            return index.insert(0, groups[0][1], n)
        slots = np.empty(n, dtype=np.int32)
        for p, se, rows in groups:
            slots[rows] = index.insert(p, se, len(rows))
        return slots

    def _grow(self, num_pages: int) -> None:
        """Widen both sides' planes to ``num_pages`` pages a shard (on the
        device; rare: capacity is sized for the live rows)."""
        import jax.numpy as jnp

        self.flush()
        capacity = num_pages * self.page_rows
        for side in range(len(SIDE_NAMES)):
            self.index[side].grow(num_pages)
            if self._planes[side] is not None:
                self._planes[side] = tuple(
                    jax.device_put(jnp.pad(pl, ((0, 0), (0, capacity
                                                         - self.capacity))),
                                   self._sharding)
                    for pl in self._planes[side])
        self.capacity = capacity

    def _words(self, side: int, shards, batch, cols):
        """The batch's int32 word columns (the key's two first), its rows
        per shard, and the side's sticky ingest bucket for them."""
        words = _to_words(batch[KEY_ID_FIELD], _KEY) + [
            w for c in cols if c.words and c.name != KEY_ID_FIELD
            for w in _to_words(batch[c.name], c)]
        counts = [len(batch)] if shards is None else \
            np.bincount(shards, minlength=self.P).tolist()
        b = self._ingest_bucket[side] = fine_bucket(
            max(counts), self._ingest_bucket[side])
        return words, counts, b

    def _put(self, side: int, shards, slots, batch, cols) -> None:
        """One scatter of the batch's words to their slots: a ``[P, 1 +
        words, B]`` block, slot first, up in one ``device_put``."""
        words, counts, b = self._words(side, shards, batch, cols)
        block = _Stage(self.P, 1 + len(words), b)
        block.add(slots, words, shards, counts)
        self._put_stage(side, block)

    def _stage_rows(self, side: int, shards, slots, batch, cols) -> bool:
        """The batch's rows into its side's stage, whose chunks are
        ``_CHUNK_BUCKETS`` ingest buckets wide; at ``_STAGE_BATCHES``
        staged batches every stage goes up. True where it did."""
        words, counts, b = self._words(side, shards, batch, cols)
        stage = self._stage[side]
        if stage is None:
            stage = self._stage[side] = _Stage(self.P, 1 + len(words),
                                               _CHUNK_BUCKETS * b)
        stage.add(slots, words, shards, counts)
        return stage.batches >= _STAGE_BATCHES and self.flush()

    def _put_stage(self, side: int, stage: _Stage) -> None:
        from flink_tpu.joins.kernels import build_window_put

        prog = build_window_put(self.mesh, stage.width - 1)
        with self._wd_section("join_ingest"):
            for block in stage.padded(self.capacity):
                self._planes[side] = prog(
                    self._planes[side],
                    jax.device_put(block, self._sharding))

    def flush(self) -> bool:
        """Every staged row up, a chunk a put, both sides. True where any
        row was staged."""
        if not self.staged:
            return False
        from flink_tpu.observe import flight_recorder as flight

        stages, self._stage = self._stage, [None, None]
        with flight.span("device.dispatch"):
            for side, stage in enumerate(stages):
                if stage is not None:
                    self._put_stage(side, stage)
        return True

    def make_fence(self):
        """A tiny program behind everything dispatched so far: ready once
        the device has consumed every staged batch."""
        planes = self._planes[0] or self._planes[1]
        return _FENCE_STEP(planes[0]) if planes else None

    # --------------------------------------------------------------- fire

    def on_watermark(self, watermark: int, async_ok: bool = False) -> list:
        """Fire every window the watermark passed (a ``PendingFire`` each
        where ``async_ok``, else its rows), then retire expired slices."""
        out = []
        with self._flight_fire(watermark):
            while True:
                w_end = self.book.next_window(watermark)
                if w_end is None:
                    break
                fired = self._fire(w_end, async_ok)
                self.book.mark_fired(w_end)
                if fired is not None and (async_ok or len(fired)):
                    out.append(fired)
            expired = self.book.expired_slices(watermark)
            if expired:
                detached = [(side, self.index[side].detach(se))
                            for se in expired
                            for side in range(len(SIDE_NAMES))]
                holder = self._in_flight
                if holder is not None and not holder.done:
                    holder.retire.extend(detached)
                else:
                    self._retire(detached)
        return out

    def _retire(self, detached) -> None:
        from flink_tpu.observe import flight_recorder as flight

        with flight.span("join.retire") as s:
            s.work = sum(self.index[side].release(d)
                         for side, d in detached)

    def _fire(self, window_end: int, async_ok: bool):
        from flink_tpu.observe import flight_recorder as flight
        from flink_tpu.runtime.pending import PendingFire

        # the window's rows on the device before its match reads them
        self.flush()
        with flight.span("join.match") as s:
            slices = self.assigner.slice_ends_for_window(window_end)
            windows = [[self.index[side].window(p, slices)
                        for p in range(self.P)]
                       for side in range(len(SIDE_NAMES))]
            rows = [[sum(f) for _, f in w] for w in windows]
            flight.instant("join.rows", work=sum(map(sum, rows)))
            if not (sum(rows[0]) and sum(rows[1])) or None in self.columns:
                return None
            pages = [self._page_block(side, windows[side])
                     for side in range(len(SIDE_NAMES))]
            sized = max(max(max(a, b) for a, b in zip(*rows)),
                        self._last_pairs)
            out_rows = self._out_bucket = fine_bucket(sized,
                                                      self._out_bucket)
            fire = _Fire(window_end, pages, out_rows)
            arrays = self._match(pages, out_rows)
            s.work = out_rows
            self._in_flight = fire
            pf = PendingFire(arrays, lambda host, f=fire: self._build(f, host),
                             watchdog=self._watchdog)
        if not async_ok:
            return pf.harvest()
        fire.pending = pf
        self._fires_out += 1
        return pf

    def _page_block(self, side: int, window) -> tuple:
        """The window's ``[P, pages]`` page ids and rows per page, on the
        device (pages past a shard's own: page 0, no rows)."""
        width = max(len(pg) for pg, _ in window)
        b = self._page_bucket[side] = fine_bucket(
            width, self._page_bucket[side], minimum=8)
        ids = np.zeros((self.P, b), dtype=np.int32)
        fill = np.zeros((self.P, b), dtype=np.int32)
        for p, (pg, fl) in enumerate(window):
            ids[p, :len(pg)] = pg
            fill[p, :len(fl)] = fl
        return tuple(jax.device_put((ids, fill), self._sharding))

    def _match(self, pages, out_rows: int) -> list:
        from flink_tpu.joins.kernels import build_window_match

        prog = build_window_match(self.mesh, len(self._planes[0]),
                                  len(self._planes[1]), self.page_rows)
        with self._wd_section("join_match"):
            return list(prog(self._planes[0], self._planes[1],
                             pages[0][0], pages[0][1],
                             pages[1][0], pages[1][1], out_rows))

    def _build(self, fire: _Fire, host: List[np.ndarray]
               ) -> Optional[RecordBatch]:
        """The harvested fire's rows (the harvest's ``build``): re-run
        wider where a shard found more pairs than the output holds, then
        retire what waited for this fire."""
        from flink_tpu.observe import flight_recorder as flight

        pairs = host[0].astype(np.int64)
        if float(host[1].max()) >= _PAIRS_LIMIT:
            raise RuntimeError(
                f"window {fire.window_end}: a shard holds about "
                f"{float(host[1].max()):.0f} pairs, past the device window "
                f"join's {_PAIRS_LIMIT} (the match counts in int32)")
        if int(pairs.max()) > fire.out_rows:
            # the window holds more pairs than its rows sized: once more,
            # wide enough (its pages are still taken: they leave with it)
            fire.out_rows = self._out_bucket = fine_bucket(
                int(pairs.max()), self._out_bucket)
            host = [np.asarray(a) for a in self._harvest_get(
                self._match(fire.pages, fire.out_rows), "join_rematch")]
            pairs = host[0].astype(np.int64)
        self._last_pairs = int(pairs.max())
        flight.instant("join.pairs", work=int(pairs.sum()))
        cols = [np.concatenate([h[p, :pairs[p]] for p in range(self.P)])
                if self.P > 1 else h[0, :pairs[0]] for h in host[2:]]
        fire.done = True
        if fire.pending is not None:
            fire.pending = None
            self._fires_out -= 1
        if fire.retire:
            self._retire(fire.retire)
            fire.retire = []
        if not len(cols[0]):
            return None
        return self._output(fire.window_end, cols)

    def _output(self, window_end: int, words: List[np.ndarray]
                ) -> RecordBatch:
        """The host ``WindowJoinOperator``'s columns, names and all, from
        the fire's words."""
        from flink_tpu.runtime.join_operators import merged_names
        from flink_tpu.windowing.windower import (
            WINDOW_END_FIELD,
            WINDOW_START_FIELD,
        )

        key = _from_words(words[:2], np.dtype(np.int64))
        n_left = len(self._planes[0]) - 2
        values = [self._columns_of_words(0, key, words[2:2 + n_left]),
                  self._columns_of_words(1, key, words[2 + n_left:])]
        left_names = [c.name for c in self.columns[0]]
        right_names = [c.name for c in self.columns[1]]
        if self.key_fields[0] is not None \
                and self.key_fields[0] == self.key_fields[1]:
            # one join key column, unsuffixed, as the host operator has it
            right_names.remove(self.key_fields[1])
        cols = {}
        for side, src, name in merged_names(left_names, right_names,
                                            self.suffixes):
            cols[name] = values[side][src]
        m = len(key)
        cols[WINDOW_START_FIELD] = np.full(
            m, self.assigner.window_start(window_end), dtype=np.int64)
        cols[WINDOW_END_FIELD] = np.full(m, window_end, dtype=np.int64)
        cols[TIMESTAMP_FIELD] = np.full(m, window_end - 1, dtype=np.int64)
        return RecordBatch(cols)

    # ---------------------------------------------------------- snapshots

    def export_slices(self) -> Dict[int, Tuple[list, list]]:
        """Every live slice's rows, as the host ``WindowJoinOperator``
        buffers them: ``{slice_end: ([left columns], [right columns])}``
        (one dict of columns per side that holds rows; a row's timestamp
        comes back as its slice's last millisecond, the only thing of it
        the join reads)."""
        self.flush()
        planes = [None if p is None else self._harvest_get(
            list(p), "snapshot_harvest") for p in self._planes]
        out: Dict[int, Tuple[list, list]] = {}
        for side in range(len(SIDE_NAMES)):
            if planes[side] is None:
                continue
            index = self.index[side]
            for se in index.live_slices():
                parts = []
                for p in range(self.P):
                    pg, fl = index.window(p, [se])
                    if not pg:
                        continue
                    slots = np.concatenate([
                        np.arange(g * self.page_rows, g * self.page_rows + f)
                        for g, f in zip(pg, fl)])
                    parts.append([np.asarray(h[p])[slots]
                                  for h in planes[side]])
                words = [np.concatenate(ws) for ws in zip(*parts)]
                cols = self._columns_of_words(
                    side, _from_words(words[:2], np.dtype(np.int64)),
                    words[2:])
                cols[TIMESTAMP_FIELD] = np.full(len(words[0]), se - 1,
                                                dtype=np.int64)
                out.setdefault(se, ([], []))[side].append(cols)
        return out

    def _columns_of_words(self, side: int, key: np.ndarray, words
                          ) -> Dict[str, np.ndarray]:
        """One side's input columns from the key and that side's value
        words, in the order its planes hold them."""
        cols, at = {}, 0
        for c in self.columns[side]:
            if c.name == KEY_ID_FIELD:
                cols[c.name] = key
            elif c.words == 0:
                cols[c.name] = key.astype(c.dtype, copy=False)
            else:
                cols[c.name] = _from_words(words[at:at + c.words], c.dtype)
                at += c.words
        return cols

    def snapshot(self, mode: str = "full") -> Dict[str, object]:
        """The host ``WindowJoinOperator``'s snapshot, field for field:
        either path restores the other's."""
        return {"book": self.book.snapshot(), "buf": self.export_slices()}

    def restore(self, snap: Dict[str, object], key_group_filter=None
                ) -> None:
        from flink_tpu.runtime.join_operators import _filter_by_key_groups

        self.flush()
        self.book.restore(snap["book"])
        for se, sides in sorted(snap.get("buf", {}).items()):
            for side, parts in enumerate(sides):
                for cols in parts:
                    cols = {k: np.asarray(v) for k, v in cols.items()}
                    if key_group_filter is not None:
                        cols = _filter_by_key_groups(
                            cols, key_group_filter, self.max_parallelism)
                    self.input_side = side
                    self.process_batch(RecordBatch(cols))
        self.input_side = 0


#: pairs one shard's fire may hold: the match counts them in int32
_PAIRS_LIMIT = 1 << 30
