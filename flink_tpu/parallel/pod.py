"""The pod runtime: multi-process record routing over the DCN axis.

In pod mode every PROCESS owns a contiguous slice of the key-group
space (``host_key_group_ranges`` — the stable process -> range mapping)
and runs its own engine over its LOCAL devices; that engine's fused
device exchange IS the intra-host ICI stage. What a single process
cannot do is deliver a record whose key belongs to ANOTHER process:
that hop is this module — :class:`PodDataPlane` stages each process's
sub-batch onto its local devices, ``all_to_all``s the per-host buckets
over the ``hosts`` axis of the process-spanning mesh (the DCN stage —
the bytes move device-to-device, replacing the reference's Netty
shuffle for the inter-TaskManager hop), and hands each process exactly
the records its range owns, in GLOBAL STREAM ORDER (arrivals flatten
by (source host, source chunk, rank); chunks partition the stream
host-major) — so per-key processing order, and with it every float
fold downstream, matches a single-process run bit-for-bit.

Host-granular planes fall out of the process split: each process keeps
its own session-metadata plane, spill tier and per-range checkpoint
units (its engine's ``snapshot_sharded`` — PR 9's shard units), so a
lost process is "restore k units, replay one contiguous range"
(``tests/test_host_failover.py``'s ``TestTwoRealProcesses`` drives
exactly that scenario).

CPU bring-up: ``mesh.initialize_distributed`` enables the gloo
cross-process collectives; N processes x M virtual devices then run
the same program a v5e pod would. The plane also runs degenerate in
ONE process over a virtual topology (every "host" addressable), which
is how tier-1 tests cover the routing program.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from flink_tpu.ops.segment_ops import pad_bucket_size, sticky_bucket
from flink_tpu.parallel.mesh import (
    HOST_AXIS,
    KEY_AXIS,
    HostTopology,
    make_mesh,
    pod_mesh_view,
    shard_map,
)
from flink_tpu.state.keygroups import host_key_group_ranges
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE


def build_pod_route(mesh, topology: HostTopology,
                    dtypes: Tuple[str, ...]):
    """The DCN routing program: each shard buckets its flat record
    chunk by destination HOST (one-hot-cumsum ranks — stream order per
    destination) and ``all_to_all``s the ``[H, W]`` buckets over the
    hosts axis. Returns the received buckets flattened ``[H * W]`` per
    shard, destination column first (its received values mark lane
    validity: a real lane carries the receiving host's own id, padding
    carries ``H``). Cached in the shared PROGRAM_CACHE."""
    key = (tuple(d.id for d in mesh.devices.flat), topology.num_hosts,
           topology.local_devices, tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "pod-route", key, lambda: _build_pod_route(mesh, topology,
                                                   dtypes))


def _build_pod_route(mesh, topology: HostTopology, dtypes):
    from functools import partial

    import jax.numpy as jnp

    H = topology.num_hosts
    mesh2 = pod_mesh_view(mesh, topology)

    def _xc(block):
        if H == 1:
            return block
        return jax.lax.all_to_all(block, HOST_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(2,))
    def route(dst, cols, w):
        W = int(w)

        def local(*args):
            d = args[0]          # [C] destination HOST (H = padding)
            vals = args[1:]
            oh = jax.nn.one_hot(d, H, dtype=jnp.int32)
            rank = jnp.cumsum(oh, axis=0) - oh
            rank_d = jnp.take_along_axis(
                rank, jnp.clip(d, 0, H - 1)[:, None], axis=1)[:, 0]
            ok = (d < H) & (rank_d < W)
            flat = jnp.where(ok, d * W + rank_d, H * W)
            outs = [_xc(
                jnp.full((H * W,), H, dtype=jnp.int32)
                .at[flat].set(d, mode="drop")
                .reshape(H, W)).reshape(-1)]
            for v, dt in zip(vals, dtypes):
                trail = v.shape[1:]  # 64-bit columns ride as [C, 2]
                outs.append(_xc(
                    jnp.zeros((H * W,) + trail, dtype=dt)
                    .at[flat].set(v, mode="drop")
                    .reshape((H, W) + trail))
                    .reshape((H * W,) + trail))
            return tuple(outs)

        from flink_tpu.parallel.mesh import LOCAL_AXIS

        spec = P((HOST_AXIS, LOCAL_AXIS))
        return shard_map(
            local, mesh=mesh2,
            in_specs=(spec,) * (1 + len(cols)),
            out_specs=(spec,) * (1 + len(cols)),
        )(dst, *cols)

    return route


def _build_agree(mesh):
    from functools import partial

    import jax.numpy as jnp

    rep = NamedSharding(mesh, P())

    @partial(jax.jit, out_shardings=rep)
    def agree(x):  # [P, 2] int32 sharded -> [2] replicated
        return jnp.max(x, axis=0)

    return agree


class PodDataPlane:
    """Routes raw record columns to their owning process over the DCN
    axis of a process-spanning mesh.

    ``dtypes``: the record columns every exchange call carries (e.g.
    key ids, timestamps, values). 64-bit columns (int64 key
    identities, timestamps) ride the x32 device plane as int32 LANE
    PAIRS (``[n, 2]`` views) and reassemble bit-exactly on harvest —
    the same reason the join side tables shadow int64 host-side; here
    the values only transit, so the pair split is enough.
    """

    def __init__(self, topology: HostTopology,
                 dtypes: Sequence, mesh=None,
                 max_parallelism: int = 128,
                 min_bucket: int = 256) -> None:
        self.topology = topology
        self.mesh = mesh if mesh is not None else make_mesh(
            span="process")
        if topology.num_shards != int(self.mesh.devices.size):
            raise ValueError(
                f"topology {topology.num_hosts}x"
                f"{topology.local_devices} does not cover the "
                f"{int(self.mesh.devices.size)}-device mesh")
        self.dtypes = tuple(np.dtype(d) for d in dtypes)
        #: device-side carrier dtype per column: 64-bit columns travel
        #: as int32 lane pairs (x32 plane), everything else unchanged
        self._wire = tuple(
            (np.dtype(np.int32) if d.itemsize == 8 else d)
            for d in self.dtypes)
        self._pair = tuple(d.itemsize == 8 for d in self.dtypes)
        self.max_parallelism = int(max_parallelism)
        self.min_bucket = int(min_bucket)
        self._sharding = NamedSharding(self.mesh, P(KEY_AXIS))
        self._route = build_pod_route(
            self.mesh, topology,
            tuple(d.str for d in self._wire))
        self._chunk_bucket = 0
        self._w_bucket = 0
        self.host_ranges = host_key_group_ranges(
            topology.num_hosts, topology.local_devices,
            self.max_parallelism)
        self.my_host = (jax.process_index()
                        if jax.process_count() > 1 else 0)
        self._agree_fn = PROGRAM_CACHE.get_or_build(
            "pod-agree",
            (tuple(d.id for d in self.mesh.devices.flat),),
            lambda: _build_agree(self.mesh))
        #: rows that genuinely crossed a process boundary vs stayed
        #: home (the two-process test's vacuity guard)
        self.rows_cross_host = 0
        self.rows_intra_host = 0
        self.batches = 0

    # ------------------------------------------------------------ sizing

    def _agree(self, chunk_max: int, pair_max: int) -> Tuple[int, int]:
        """All processes must dispatch the SAME program shape (SPMD):
        agree on the global chunk length and bucket width. One tiny
        CACHED fixed-shape max-reduction per batch in multi-process
        mode (a fresh jit per call would trip the recompile sentinel);
        a no-op on one process."""
        if jax.process_count() > 1:
            L = self.topology.local_devices
            local = np.tile(
                np.array([[chunk_max, pair_max]], dtype=np.int32),
                (L, 1))
            arr = jax.make_array_from_process_local_data(
                self._sharding, local,
                (self.topology.num_shards, 2))
            both = np.asarray(jax.device_get(self._agree_fn(arr)))
            chunk_max = int(both[0])
            pair_max = int(both[1])
        C = sticky_bucket(chunk_max, self._chunk_bucket,
                          self.min_bucket)
        self._chunk_bucket = C
        W = sticky_bucket(min(pair_max, C), self._w_bucket,
                          self.min_bucket)
        self._w_bucket = min(W, C)
        return C, self._w_bucket

    # ---------------------------------------------------------- exchange

    def exchange(self, dst_host: np.ndarray,
                 columns: Sequence[np.ndarray],
                 chunk_bound: Optional[int] = None
                 ) -> Dict[int, List[np.ndarray]]:
        """Route this process's sub-batch: every record lands on its
        owning host, arrivals in GLOBAL stream order.

        Multi-process: each process passes ITS sub-batch (the global
        batch is the process-major concatenation) and receives
        ``{my_host: [col, ...]}``. Single-process (virtual topology):
        pass the WHOLE batch; every host's arrivals come back
        ``{host: [col, ...]}`` — the tier-1 test mode.

        ``chunk_bound``: a DETERMINISTIC upper bound on every process's
        per-chunk record count (e.g. ``ceil(max sub-batch / L)`` when
        the caller knows the global batch split). With it, no
        agreement collective runs — the bucket width is the chunk tier
        (a bounded overshoot); without it, one tiny cached max-
        reduction per batch agrees on exact shapes.
        """
        H = self.topology.num_hosts
        L = self.topology.local_devices
        dst_host = np.asarray(dst_host)
        n = len(dst_host)
        columns = [
            (np.ascontiguousarray(np.asarray(c, dtype=d))
             .view(np.int32).reshape(n, 2) if pair
             else np.asarray(c, dtype=d))
            for c, d, pair in zip(columns, self.dtypes, self._pair)]
        multi = jax.process_count() > 1
        local_chunks = L if multi else H * L
        per = -(-max(n, 1) // local_chunks)
        if chunk_bound is not None:
            # deterministic sizing: no collective, W = the chunk tier
            per = max(per, int(chunk_bound))
            C = sticky_bucket(per, self._chunk_bucket,
                              self.min_bucket)
            self._chunk_bucket = C
            self._w_bucket = W = C
        else:
            if n:
                chunk_of = np.minimum(
                    np.arange(n, dtype=np.int64) // per,
                    local_chunks - 1)
                pair_max = int(np.bincount(
                    chunk_of * (H + 1) + np.minimum(dst_host, H),
                    minlength=local_chunks * (H + 1))
                    .reshape(local_chunks, H + 1)[:, :H].max())
            else:
                pair_max = 0
            C, W = self._agree(per, pair_max)
        N_local = local_chunks * C
        dst_buf = np.full(N_local, H, dtype=np.int32)
        bufs = [np.zeros((N_local, 2) if pair else (N_local,),
                         dtype=w)
                for w, pair in zip(self._wire, self._pair)]
        if n:
            # re-chunk against the AGREED C: chunk j covers sub-batch
            # positions [j*C, (j+1)*C) — the contiguous split the
            # stream-order reconstruction assumes
            if per > C:
                raise AssertionError("agreed chunk below local need")
            for j in range(local_chunks):
                a, b = j * per, min((j + 1) * per, n)
                if a >= b:
                    break
                dst_buf[j * C:j * C + (b - a)] = dst_host[a:b]
                for buf, col in zip(bufs, columns):
                    buf[j * C:j * C + (b - a)] = col[a:b]
        src_host_of_chunk = (
            np.arange(local_chunks) // L if not multi
            else np.full(local_chunks, self.my_host))
        if n:
            cross = int((dst_host
                         != (src_host_of_chunk[np.minimum(
                             np.arange(n) // per,
                             local_chunks - 1)])).sum())
            self.rows_cross_host += cross
            self.rows_intra_host += n - cross
        self.batches += 1
        G = H * L * C
        if multi:
            arrs = [jax.make_array_from_process_local_data(
                self._sharding, b, (G,) + b.shape[1:])
                for b in [dst_buf] + bufs]
        else:
            arrs = [jax.device_put(b, self._sharding)
                    for b in [dst_buf] + bufs]
        out = self._route(arrs[0], tuple(arrs[1:]), W)
        # harvest THIS process's shards: ONE batched device_get of all
        # addressable pieces (the TRC01 discipline)
        shard_data: Dict[int, list] = {}
        for ci, o in enumerate(out):
            for s in o.addressable_shards:
                p = s.index[0].start // (H * W)
                shard_data.setdefault(p, [None] * len(out))[ci] = s.data
        flat_order = sorted(shard_data)
        fetched = jax.device_get(
            [shard_data[p] for p in flat_order])
        # reassemble in (source host, source chunk, rank) order =
        # global stream order restricted to each receiving host
        arrivals: Dict[int, List[np.ndarray]] = {}
        by_shard = dict(zip(flat_order, fetched))
        hosts = ({self.my_host} if multi
                 else set(range(H)))
        for h in sorted(hosts):
            parts: List[List[np.ndarray]] = [[] for _ in self.dtypes]
            for sh in range(H):
                for sl in range(L):
                    p = h * L + sl
                    cols_p = by_shard.get(p)
                    if cols_p is None:
                        continue
                    dcol = np.asarray(cols_p[0]).reshape(H, W)[sh]
                    valid = dcol < H
                    if not valid.any():
                        continue
                    m = int(valid.sum())  # ranks are contiguous
                    for ci, pair in enumerate(self._pair):
                        c = np.asarray(cols_p[ci + 1])
                        c = c.reshape((H, W) + c.shape[1:])[sh][:m]
                        parts[ci].append(c)
            cols_out: List[np.ndarray] = []
            for ps, d, pair in zip(parts, self.dtypes, self._pair):
                if not ps:
                    cols_out.append(np.empty(0, dtype=d))
                    continue
                c = np.ascontiguousarray(np.concatenate(ps))
                if pair:  # [m, 2] int32 lanes -> the 64-bit column
                    c = c.view(d).ravel()
                cols_out.append(c)
            arrivals[h] = cols_out
        return arrivals
