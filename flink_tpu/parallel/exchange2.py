"""The two-level (ICI/DCN) exchange: the pod-scale data plane.

A flat ``all_to_all`` over a process-spanning mesh treats every
(source, destination) shard pair uniformly — intra-host traffic that
could ride ICI pays the DCN latency of the slowest link, and the
payload fragments into ``P x P`` tiny blocks. The two-level program
family splits the keyBy exchange along the physical topology
(:class:`~flink_tpu.parallel.mesh.HostTopology`):

- **Stage 1 (ICI)**: each shard segment-sorts its flat record chunk by
  the destination's LOCAL index (one-hot-cumsum ranks, the same
  order-preserving discipline as the flat program) and ``all_to_all``s
  the ``[L, W1]`` buckets over the intra-host ``local`` axis. After
  stage 1 every record sits on the shard whose local index matches its
  destination's — intra-host records are home, cross-host records need
  only the host hop.
- **Stage 2 (DCN)**: the received rows (flattened in (source-local,
  rank) order — stream order restricted to the source host) bucket by
  destination HOST into ``[H, W2]`` and ``all_to_all`` over the
  ``hosts`` axis. Only the off-diagonal blocks cross the DCN; the
  genuinely cross-host residue is batched into one block per host pair
  instead of ``L x L`` fragments. The receive flattening (source-host,
  rank) is GLOBAL stream order (chunks partition the stream host-major),
  so the single scatter that follows folds every slot's records in
  stream order — float folds stay bit-identical to the flat exchange
  AND the host bucketing path.

Both stages are their own jitted programs (so the flight recorder can
attribute ICI vs DCN time as distinct span kinds) with their own
``pad_bucket_size`` tier (``W1`` = densest (chunk, dest-local) pair,
``W2`` = densest (source-host, dest-shard) pair) — steady-state
compiles stay 0 across the tier lattice. Cached in the shared
PROGRAM_CACHE keyed ``(device ids, topology, layout)`` — tenants and
rebuilt engines share the executables. The flat single-axis program
remains the single-host fast path (``HostTopology(1, P)`` never routes
here).

The chaos payload point ``exchange.dcn_send`` models a lossy DCN link:
drop/duplicate/delay per (src_host, dst_host) bucket, cross-host pairs
only — the intra-host stage rides ICI and has its own fault points.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flink_tpu.chaos import injection as chaos
from flink_tpu.ops.segment_ops import SCATTER_METHOD, pad_bucket_size
from flink_tpu.parallel.mesh import (
    HOST_AXIS,
    LOCAL_AXIS,
    HostTopology,
    pod_mesh_view,
    shard_map,
)
from flink_tpu.stateplane.rank import exchange_rank_flat
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE


class ExchangeTraffic:
    """Per-engine two-level traffic accounting: how many records stayed
    on ICI vs genuinely crossed the DCN (the tests' vacuity guard and
    the NOTES scaling-walk split read these)."""

    __slots__ = ("rows_intra_host", "rows_cross_host", "batches")

    def __init__(self) -> None:
        self.rows_intra_host = 0
        self.rows_cross_host = 0
        self.batches = 0

    def as_dict(self) -> Dict[str, int]:
        return {"rows_intra_host": self.rows_intra_host,
                "rows_cross_host": self.rows_cross_host,
                "exchange2_batches": self.batches}

    @staticmethod
    def dict_of(traffic) -> Dict[str, int]:
        """``traffic.as_dict()`` or the zero dict for engines running
        the flat exchange — ONE shape for every ``exchange2_traffic``
        accessor (engines must not re-inline the key set)."""
        if traffic is not None:
            return traffic.as_dict()
        return ExchangeTraffic().as_dict()


def two_level_active(topology, shuffle_mode: str) -> bool:
    """THE activation rule, shared by every engine: a multi-host
    factorization under the device data plane."""
    return (topology is not None and topology.num_hosts > 1
            and shuffle_mode == "device")


def stage_two_level_exchange(
    shard_of_record: np.ndarray,
    topology: HostTopology,
    columns: Sequence[np.ndarray],
    fills: Sequence,
    min_bucket: int = 256,
    pool=None,
    traffic: Optional[ExchangeTraffic] = None,
) -> Tuple[np.ndarray, List[np.ndarray], int, int]:
    """Stage flat record columns for the two-level exchange.

    Identical staging contract to
    :func:`~flink_tpu.parallel.shuffle.stage_device_exchange` (flat
    padded columns of length ``P * C``, padding lanes carry the
    out-of-range destination ``P``), plus the per-LEVEL bucket tiers:
    returns ``(dst, staged_columns, w1, w2)`` where ``w1`` bounds the
    densest (source chunk, destination local index) pair and ``w2`` the
    densest (source host, destination shard) pair — each level's
    compiled program allocates exactly its own bucket capacity.
    """
    from flink_tpu.parallel.shuffle import exchange_chunk_size

    H, L = topology.num_hosts, topology.local_devices
    num_shards = topology.num_shards
    shard_of_record = np.asarray(shard_of_record)
    n = len(shard_of_record)
    columns = [np.asarray(c) for c in columns]
    if chaos.armed():
        # DCN link faults: payload kinds per CROSS-host (src, dst) pair
        # (the intra-host stage is ICI — shuffle.device_exchange and the
        # engines' post-dispatch crash point cover it). The source host
        # of a record is its staging chunk's host; provisional chunking
        # from the pre-mutation length keeps the rule deterministic.
        C0 = exchange_chunk_size(n, num_shards, min_bucket)
        src_host = (np.arange(n, dtype=np.int64) // C0) // L
        dst_host = shard_of_record // L
        cross = src_host != dst_host
        if cross.any():
            pairs = np.unique(
                np.stack([src_host[cross], dst_host[cross]], axis=1),
                axis=0)
            drop_mask = np.zeros(n, dtype=bool)
            dup_mask = np.zeros(n, dtype=bool)
            for sh, dh in pairs.tolist():
                rule = chaos.payload_action(
                    "exchange.dcn_send",
                    kinds=("drop", "duplicate", "delay"),
                    src_host=int(sh), dst_host=int(dh))
                if rule is None:
                    continue
                sel = cross & (src_host == sh) & (dst_host == dh)
                if rule.kind == "drop":
                    drop_mask |= sel
                elif rule.kind == "duplicate":
                    dup_mask |= sel
            if drop_mask.any():
                # dropped rows re-route to the padding destination:
                # they vanish before the stage-1 collective, exactly a
                # lost DCN bucket (the oracle diff catches it)
                shard_of_record = np.where(drop_mask, num_shards,
                                           shard_of_record)
            if dup_mask.any():
                shard_of_record = np.concatenate(
                    [shard_of_record, shard_of_record[dup_mask]])
                columns = [np.concatenate([c, c[dup_mask]])
                           for c in columns]
                n = len(shard_of_record)
    C = exchange_chunk_size(n, num_shards, min_bucket)
    N = num_shards * C
    dst = (pool.get((N,), np.int32, num_shards, tag=("xchg2", "dst"))
           if pool is not None
           else np.full(N, num_shards, dtype=np.int32))
    dst[:n] = shard_of_record
    staged: List[np.ndarray] = []
    for ci, (col, fill) in enumerate(zip(columns, fills)):
        shape = (N,) + col.shape[1:]
        if pool is not None:
            buf = pool.get(shape, col.dtype, fill, tag=("xchg2", ci))
        else:
            buf = np.full(shape, fill, dtype=col.dtype)
        buf[:n] = col
        staged.append(buf)
    # per-level densest pairs, one bincount pass each over the real
    # records (padding lanes excluded structurally)
    if n:
        real = dst[:n]
        live = real < num_shards
        idx = np.nonzero(live)[0]
        d_live = real[idx].astype(np.int64)
        chunk_of = idx // C
        # W1: records of chunk c destined to local index l (any host)
        dl = d_live % L
        w1_max = int(np.bincount(chunk_of * L + dl,
                                 minlength=num_shards * L).max()) \
            if len(idx) else 0
        # W2: records of source host (c // L) destined to shard d
        sh = chunk_of // L
        w2_max = int(np.bincount(sh * num_shards + d_live,
                                 minlength=H * num_shards).max()) \
            if len(idx) else 0
        if traffic is not None:
            crossed = int((sh != d_live // L).sum())
            traffic.rows_cross_host += crossed
            traffic.rows_intra_host += int(len(idx)) - crossed
            traffic.batches += 1
    else:
        w1_max = w2_max = 0
        if traffic is not None:
            traffic.batches += 1
    w1 = min(pad_bucket_size(w1_max, minimum=min_bucket), C)
    # stage 2's input is the [L, W1] receive block: a (host, shard)
    # pair can at most fill it
    w2 = min(pad_bucket_size(w2_max, minimum=min_bucket), L * w1)
    return dst, staged, w1, w2


# ---------------------------------------------------------------------------
# program families
# ---------------------------------------------------------------------------


def _mesh_key(mesh) -> Tuple[int, ...]:
    return tuple(d.id for d in mesh.devices.flat)


def _stage1_route(mesh2, H: int, L: int, fill_specs):
    """Stage 1: route (dst, slot, values...) by destination LOCAL index
    over the intra-host axis. Returns per-column received buckets
    flattened ``[L * W1]`` in (source-local, rank) order."""
    num_shards = H * L

    def _xc_local(block):
        if L == 1:
            return block
        return jax.lax.all_to_all(block, LOCAL_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(3,))
    def stage1(dst, slots, values, w1):
        W1 = int(w1)

        def local(*args):
            d = args[0]                 # [C] global destination shard
            s = args[1]                 # [C] destination slot
            vals = args[2:]
            dl = jnp.where(d < num_shards,
                           jax.lax.rem(d, L), L)
            flat = exchange_rank_flat(dl, L, W1)
            outs = []
            # the destination shard rides the exchange (stage 2 needs
            # the host part); empty lanes carry the padding sentinel
            outs.append(_xc_local(
                jnp.full((L * W1,), num_shards, dtype=jnp.int32)
                .at[flat].set(d, mode="drop")
                .reshape(L, W1)).reshape(-1))
            outs.append(_xc_local(
                jnp.zeros((L * W1,), jnp.int32)
                .at[flat].set(s, mode="drop")
                .reshape(L, W1)).reshape(-1))
            for v, (dt, fill) in zip(vals, fill_specs):
                outs.append(_xc_local(
                    jnp.full((L * W1,), fill, dtype=dt)
                    .at[flat].set(v, mode="drop")
                    .reshape(L, W1)).reshape(-1))
            return tuple(outs)

        n_vals = len(values)
        spec = P((HOST_AXIS, LOCAL_AXIS))
        return shard_map(
            local, mesh=mesh2,
            in_specs=(spec,) * (2 + n_vals),
            out_specs=(spec,) * (2 + n_vals),
        )(dst, slots, *values)

    return stage1


def _stage2_rank(d2, H: int, L: int, num_shards: int, W2: int):
    """Shared stage-2 bucketing: destination-host rank-within-
    destination (the stateplane rank combinator) over the
    stage-1 receive order."""
    dh = jnp.where(d2 < num_shards, d2 // L, H)
    return exchange_rank_flat(dh, H, W2)


def build_exchange2_steps(mesh, topology: HostTopology, agg,
                          valued: bool = False):
    """The two-level exchange+scatter pair for the mesh engines'
    aggregate planes: ``(stage1, stage2)`` jitted programs. ``stage2``
    folds the received rows into the [P, capacity] accumulators with
    the same per-slot stream-order guarantee as
    ``build_exchange_scatter`` — bit-identical output, two dispatches.
    """
    key = (_mesh_key(mesh), topology.num_hosts,
           topology.local_devices, agg.cache_key(), bool(valued))
    return (
        PROGRAM_CACHE.get_or_build(
            "exchange2-stage1", key,
            lambda: _build_fold_stage1(mesh, topology, agg, valued)),
        PROGRAM_CACHE.get_or_build(
            "exchange2-stage2", key,
            lambda: _build_fold_stage2(mesh, topology, agg, valued)),
    )


def _exchanged_leaves(agg, valued: bool):
    """The leaves whose value columns ride the exchange — all of them
    in the valued (two-phase partial) variant, only the const-free ones
    otherwise (const leaves derive on device at the final fold)."""
    if valued:
        return list(agg.leaves)
    return [l for l in agg.leaves if l.const is None]


def _build_fold_stage1(mesh, topology: HostTopology, agg, valued: bool):
    H, L = topology.num_hosts, topology.local_devices
    mesh2 = pod_mesh_view(mesh, topology)
    fill_specs = tuple((np.dtype(l.dtype).str, l.identity)
                       for l in _exchanged_leaves(agg, valued))
    return _stage1_route(mesh2, H, L, fill_specs)


def _build_fold_stage2(mesh, topology: HostTopology, agg, valued: bool):
    H, L = topology.num_hosts, topology.local_devices
    num_shards = H * L
    mesh2 = pod_mesh_view(mesh, topology)
    leaves = agg.leaves
    methods = tuple(SCATTER_METHOD[l.reduce] for l in leaves)
    n_leaves = len(leaves)

    def _xc_hosts(block):
        if H == 1:
            return block
        return jax.lax.all_to_all(block, HOST_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
    def stage2(accs, dst2, slots2, vals2, w2):
        W2 = int(w2)

        def local(*args):
            accs_l = args[:n_leaves]     # each [1, cap]
            d2 = args[n_leaves]          # [L*W1] destination shard
            s2 = args[n_leaves + 1]      # [L*W1] destination slot
            vals_l = iter(args[n_leaves + 2:])
            flat = _stage2_rank(d2, H, L, num_shards, W2)
            recv_s = _xc_hosts(
                jnp.zeros((H * W2,), jnp.int32)
                .at[flat].set(s2, mode="drop")
                .reshape(H, W2)).reshape(-1)
            out = []
            for a, m, l in zip(accs_l, methods, leaves):
                if not valued and l.const is not None:
                    # empty bucket lanes hold slot 0 (the reserved
                    # identity slot) — keep it pure
                    v = jnp.where(
                        recv_s == 0,
                        jnp.asarray(l.identity, dtype=l.dtype),
                        jnp.asarray(l.const, dtype=l.dtype))
                else:
                    v = _xc_hosts(
                        jnp.full((H * W2,), l.identity, dtype=l.dtype)
                        .at[flat].set(next(vals_l), mode="drop")
                        .reshape(H, W2)).reshape(-1)
                out.append(getattr(a.at[0, recv_s], m)(v))
            return tuple(out)

        n_vals = len(vals2)
        spec = P((HOST_AXIS, LOCAL_AXIS))
        return shard_map(
            local, mesh=mesh2,
            in_specs=(spec,) * (n_leaves + 2 + n_vals),
            out_specs=(spec,) * n_leaves,
        )(*accs, dst2, slots2, *vals2)

    return stage2


def build_join_exchange2_steps(mesh, topology: HostTopology,
                               dtypes: Tuple[str, ...]):
    """The two-level variant of ``join-exchange-put``: stage 1 routes
    the (slot, value...) rows by destination local index, stage 2 hops
    the host axis and writes the received rows into the side table's
    plane (``.set`` — last write in stream order wins, identical to the
    flat join exchange)."""
    key = (_mesh_key(mesh), topology.num_hosts,
           topology.local_devices, tuple(dtypes))
    return (
        PROGRAM_CACHE.get_or_build(
            "join-exchange2-stage1", key,
            lambda: _build_join_stage1(mesh, topology, dtypes)),
        PROGRAM_CACHE.get_or_build(
            "join-exchange2-stage2", key,
            lambda: _build_join_stage2(mesh, topology, dtypes)),
    )


def _build_join_stage1(mesh, topology: HostTopology, dtypes):
    H, L = topology.num_hosts, topology.local_devices
    mesh2 = pod_mesh_view(mesh, topology)
    fill_specs = tuple((np.dtype(dt).str, 0) for dt in dtypes)
    return _stage1_route(mesh2, H, L, fill_specs)


def _build_join_stage2(mesh, topology: HostTopology, dtypes):
    H, L = topology.num_hosts, topology.local_devices
    num_shards = H * L
    mesh2 = pod_mesh_view(mesh, topology)
    n_cols = len(dtypes)

    def _xc_hosts(block):
        if H == 1:
            return block
        return jax.lax.all_to_all(block, HOST_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
    def stage2(planes, dst2, slots2, vals2, w2):
        W2 = int(w2)

        def local(*args):
            planes_l = args[:n_cols]
            d2 = args[n_cols]
            s2 = args[n_cols + 1]
            vs = args[n_cols + 2:]
            flat = _stage2_rank(d2, H, L, num_shards, W2)
            recv_s = _xc_hosts(
                jnp.zeros((H * W2,), jnp.int32)
                .at[flat].set(s2, mode="drop")
                .reshape(H, W2)).reshape(-1)
            out = []
            for pl, v in zip(planes_l, vs):
                rv = _xc_hosts(
                    jnp.zeros((H * W2,), pl.dtype)
                    .at[flat].set(v, mode="drop")
                    .reshape(H, W2)).reshape(-1)
                # empty lanes carry recv_s == 0: the reserved scratch
                # slot absorbs them
                out.append(pl.at[0, recv_s].set(rv))
            return tuple(out)

        spec = P((HOST_AXIS, LOCAL_AXIS))
        return shard_map(
            local, mesh=mesh2,
            in_specs=(spec,) * (2 * n_cols + 2),
            out_specs=(spec,) * n_cols,
        )(*planes, dst2, slots2, *vals2)

    return stage2
