"""The data plane: key-group repartitioning over the device mesh.

Replaces the reference's Netty shuffle (reference:
flink-runtime/.../io/network/ — RecordWriter.emit:105 -> KeyGroupStreamPartitioner
.selectChannel:55 -> PipelinedSubpartition -> Netty TCP with credit-based flow
control) with two TPU-native mechanisms:

1. **The in-program device exchange** (``shuffle.mode=device``, the
   default): a batch goes host->device ONCE as flat padded columns (one
   ``device_put`` of the whole column pytree against the key-group
   sharding), and a single jitted shard_map program segment-sorts each
   shard's chunk into per-destination buckets, exchanges them with
   ``all_to_all`` over the mesh axis, and feeds the segment-reduce
   scatter in the SAME program — ``keyBy -> window -> aggregate`` is one
   XLA program end to end (``build_exchange_scatter``). The collective
   runs over ICI on real hardware; there is no host argsort and no
   ``[num_shards, B]`` staging block.
2. **Host-side bucketing** (``shuffle.mode=host``, the explicit
   fallback): records are grouped by owning shard (key_group -> shard
   via the reference's operator-index formula) into a dense
   ``[num_shards, B]`` block that is laid out with the leading axis
   sharded over the mesh — the "shuffle" is then just a sharded
   device_put (``bucket_by_shard``).

``all_to_all`` also repartitions between chained keyed stages
(``make_all_to_all_repartition``), and **``psum``** handles two-phase
local/global aggregation (the MiniBatch local/global pattern, reference:
flink-table-runtime/.../aggregate/MiniBatchLocalGroupAggFunction.java /
MiniBatchGlobalGroupAggFunction.java).

Backpressure (credit-based flow control) maps to the bounded micro-batch
queue feeding the device — see flink_tpu.runtime.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.chaos import injection as chaos
from flink_tpu.ops.segment_ops import SCATTER_METHOD, pad_bucket_size
from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
from flink_tpu.stateplane.backends import backend_of
from flink_tpu.stateplane.rank import exchange_rank_flat
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE
from flink_tpu.state.keygroups import (
    assign_key_groups,
    key_group_to_operator_index,
)


class ShuffleBufferPool:
    """Reused host-side staging buffers for the [num_shards, B] blocks.

    Allocating (and zero/identity-filling) fresh blocks per batch per
    column was a measurable slice of the mesh engines' host prep; the
    pool hands back the same arrays across batches instead. Buffers
    rotate through ``generations`` slots and a caller ``flip()``s once
    per batch, so with dispatch-ahead <= generations the async
    ``device_put`` that consumed a buffer has completed before the
    buffer is written again (the double-buffer contract — the engines
    fence their dispatch depth to guarantee it).
    """

    def __init__(self, generations: int = 2) -> None:
        self.generations = max(int(generations), 1)
        self._gen = 0
        self._bufs: Dict[tuple, np.ndarray] = {}

    def flip(self) -> None:
        """Advance to the next buffer generation (call once per batch)."""
        self._gen = (self._gen + 1) % self.generations

    def get(self, shape: tuple, dtype, fill, tag=None) -> np.ndarray:
        """A [shape] buffer pre-filled with ``fill`` (fast memset on
        reuse, one allocation on first use per shape/dtype/generation).
        ``tag`` disambiguates same-shaped buffers used concurrently
        within one generation (e.g. two value columns of one batch)."""
        dtype = np.dtype(dtype)
        key = (self._gen, shape, dtype.str, tag)
        buf = self._bufs.get(key)
        if buf is None:
            buf = np.empty(shape, dtype=dtype)
            self._bufs[key] = buf
        buf.fill(fill)
        return buf


def bucket_by_shard(
    shard_of_record: np.ndarray,
    num_shards: int,
    columns: Sequence[np.ndarray],
    fills: Sequence,
    min_bucket: int = 256,
    pool: Optional[ShuffleBufferPool] = None,
    want_order: bool = False,
):
    """Group records into a dense [num_shards, B] block (host side).

    Returns ``(counts[num_shards], blocked_columns each [num_shards,
    B])`` — records of shard p occupy ``block[p, :counts[p]]`` in
    stream order. With ``want_order=True`` the applied permutation is
    returned as a third element; the engines pre-permute their columns
    and never need it, so the default return shape is explicit about
    that (no silently-discarded values at the call sites).

    Fully vectorized: one argsort for the permutation, then ONE fancy
    scatter per column through a precomputed flat index (record i of the
    sorted stream lands at row shard, column i - offsets[shard]) — no
    per-shard Python loop. With ``pool`` set the destination blocks are
    reused (pinned) buffers instead of per-batch allocations.
    """
    shard_of_record = np.asarray(shard_of_record)
    n = len(shard_of_record)
    counts = np.bincount(shard_of_record, minlength=num_shards)
    # chaos (armed-only — the disarmed path pays one module check):
    # per-shard bucket faults model a lossy exchange. drop re-fills the
    # shard's rows (they then scatter identities into slot 0, i.e. the
    # records vanish in flight), duplicate replays them (B is padded to
    # hold the copy), delay/raise apply inside payload_action.
    mutations: Dict[int, str] = {}
    if chaos.armed():
        chaos.fault_point("shuffle.bucket_prep", num_shards=num_shards)
        for p in np.nonzero(counts)[0].tolist():
            rule = chaos.payload_action("shuffle.bucket_send", shard=p)
            if rule is not None and rule.kind in ("drop", "duplicate"):
                mutations[p] = rule.kind
    eff_counts = counts
    if mutations:
        eff_counts = counts.copy()
        for p, kind in mutations.items():
            if kind == "duplicate":
                eff_counts[p] = counts[p] * 2
    B = pad_bucket_size(int(eff_counts.max()) if n else 0,
                        minimum=min_bucket)
    order = np.argsort(shard_of_record, kind="stable")
    offsets = np.zeros(num_shards + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    sorted_shard = shard_of_record[order]
    # flat destination of sorted record j: its shard's row, at column
    # j - offsets[shard] (its rank within the shard)
    flat_dst = (sorted_shard * B
                + np.arange(n, dtype=np.int64) - offsets[sorted_shard])
    blocked = []
    for ci, (col, fill) in enumerate(zip(columns, fills)):
        col = np.asarray(col)
        shape = (num_shards, B) + col.shape[1:]
        if pool is not None:
            block = pool.get(shape, col.dtype, fill, tag=("bucket", ci))
        else:
            block = np.full(shape, fill, dtype=col.dtype)
        block.reshape((num_shards * B,) + col.shape[1:])[flat_dst] = \
            col[order]
        blocked.append(block)
    if mutations:
        for p, kind in mutations.items():
            c = int(counts[p])
            for block, fill in zip(blocked, fills):
                if kind == "drop":
                    block[p, :c] = fill
                else:  # duplicate: replay the bucket's rows
                    block[p, c:2 * c] = block[p, :c]
            eff_counts[p] = 0 if kind == "drop" else 2 * c
        counts = eff_counts
    if want_order:
        return counts, blocked, order
    return counts, blocked


def shard_records(
    key_ids: np.ndarray,
    num_shards: int,
    max_parallelism: int,
    key_group_range=None,
    assignment=None,
) -> np.ndarray:
    """key id -> owning shard (the keyBy routing decision).

    reference: KeyGroupStreamPartitioner.java:55 selectChannel =
    operator index of the key's group.

    ``key_group_range`` = (first, last) inclusive global key groups this
    mesh owns (the mesh x stage composition: a keyed SUBTASK owns a range
    of the global key-group space and shards it across its private
    sub-mesh). The reference formula applied to the LOCAL group space —
    without the remap, a sub-range would collapse onto a couple of shards.

    ``assignment``: a :class:`flink_tpu.state.KeyGroupAssignment` — the
    explicit table a rebalanced plane routes by instead of the
    contiguous formula. Subsumes ``key_group_range`` (an assignment
    carries its own first/span).
    """
    return shard_of_key_groups(
        assign_key_groups(key_ids, max_parallelism), num_shards,
        max_parallelism, key_group_range, assignment)


def shard_of_key_groups(
    groups: np.ndarray,
    num_shards: int,
    max_parallelism: int,
    key_group_range=None,
    assignment=None,
) -> np.ndarray:
    """Global key group -> owning shard: :func:`shard_records` past the
    key's hash (its arguments, its three forms)."""
    if assignment is not None:
        return assignment.shard_of_groups(groups).astype(np.int64)
    if key_group_range is not None:
        first, last = key_group_range
        local = (np.asarray(groups, dtype=np.int64) - int(first))
        local_max = int(last) - int(first) + 1
        return ((local * num_shards) // local_max).astype(np.int64)
    return key_group_to_operator_index(groups, max_parallelism, num_shards)


def group_shard_table(
    num_shards: int,
    max_parallelism: int,
    key_group_range=None,
    assignment=None,
) -> np.ndarray:
    """:func:`shard_of_key_groups` of every key group, as the int32
    ``[max_parallelism]`` table a native sweep routes by
    (``resolve_slices_sharded``); -1 for a group outside the range this
    mesh owns."""
    groups = np.arange(max_parallelism, dtype=np.int64)
    if assignment is not None:
        first, last = assignment.first, assignment.first + assignment.span - 1
    else:
        first, last = key_group_range or (0, max_parallelism - 1)
    owned = (groups >= int(first)) & (groups <= int(last))
    table = np.full(max_parallelism, -1, dtype=np.int32)
    table[owned] = shard_of_key_groups(
        groups[owned], num_shards, max_parallelism, key_group_range,
        assignment)
    return table


# ---------------------------------------------------------------------------
# The in-program exchange (shuffle.mode=device)
# ---------------------------------------------------------------------------


def exchange_chunk_size(n: int, num_shards: int,
                        min_bucket: int = 256) -> int:
    """Per-shard flat-column chunk length for ``n`` records: the
    ``pad_bucket_size`` tier of ``ceil(n / num_shards)``, so the fused
    exchange program compiles once per tier (the same bounded shape set
    the host blocks use) and the staged length ``num_shards * C`` is
    always divisible by the mesh."""
    per = -(-max(int(n), 1) // num_shards)
    return pad_bucket_size(per, minimum=min_bucket)


def stage_device_exchange(
    shard_of_record: np.ndarray,
    num_shards: int,
    columns: Sequence[np.ndarray],
    fills: Sequence,
    min_bucket: int = 256,
    pool: Optional[ShuffleBufferPool] = None,
) -> Tuple[np.ndarray, List[np.ndarray], int]:
    """Stage flat record columns for the in-program exchange.

    Unlike :func:`bucket_by_shard` there is NO host argsort and NO
    [num_shards, B] scatter: each column is copied once into a padded
    flat buffer of length ``num_shards * C`` (``C`` =
    :func:`exchange_chunk_size` — a ``pad_bucket_size`` tier, so the
    fused program's shape set stays bounded) and the segment sort +
    exchange happen inside the compiled program. Padded lanes carry the
    out-of-range destination ``num_shards``; the program drops them
    before the collective.

    Returns ``(dst, staged_columns, bucket_width)``, columns all length
    ``num_shards * C``. ``bucket_width`` is the ``pad_bucket_size`` tier
    of the batch's densest (source chunk, destination) pair count — the
    static per-pair bucket capacity the fused program allocates. Sizing
    it to the worst case (``C``) would make every shard's received
    block ``num_shards`` times wider than the data; the O(n) host
    bincount buys the compiled program a ~P-fold smaller exchange
    payload at the cost of one more bounded shape dimension.

    The chaos payload point ``shuffle.device_exchange`` models a lossy
    exchange like ``shuffle.bucket_send`` does for the host path: drop
    re-routes one shard's records to the padding destination (they
    vanish before the collective), duplicate replays them.
    """
    shard_of_record = np.asarray(shard_of_record)
    n = len(shard_of_record)
    columns = [np.asarray(c) for c in columns]
    if chaos.armed():
        # payload kinds only — raise/delay fire at the engines'
        # post-dispatch fault point, so a "crash mid-batch" lands AFTER
        # the fused program was dispatched (the hardest restore case)
        mutations: Dict[int, str] = {}
        present = np.unique(shard_of_record) if n else ()
        for p in present:
            rule = chaos.payload_action(
                "shuffle.device_exchange",
                kinds=("drop", "duplicate", "delay"), shard=int(p))
            if rule is not None and rule.kind in ("drop", "duplicate"):
                mutations[int(p)] = rule.kind
        for p, kind in mutations.items():
            sel = shard_of_record == p
            if kind == "drop":
                shard_of_record = np.where(sel, num_shards,
                                           shard_of_record)
            else:  # duplicate: replay the shard's records
                shard_of_record = np.concatenate(
                    [shard_of_record, shard_of_record[sel]])
                columns = [np.concatenate([c, c[sel]]) for c in columns]
                n = len(shard_of_record)
    C = exchange_chunk_size(n, num_shards, min_bucket)
    N = num_shards * C
    dst = (pool.get((N,), np.int32, num_shards, tag=("xchg", "dst"))
           if pool is not None
           else np.full(N, num_shards, dtype=np.int32))
    dst[:n] = shard_of_record
    staged: List[np.ndarray] = []
    for ci, (col, fill) in enumerate(zip(columns, fills)):
        shape = (N,) + col.shape[1:]
        if pool is not None:
            buf = pool.get(shape, col.dtype, fill, tag=("xchg", ci))
        else:
            buf = np.full(shape, fill, dtype=col.dtype)
        buf[:n] = col
        staged.append(buf)
    # densest (source chunk, destination) pair: one flat bincount over
    # the real records (padding lanes land in the excluded column)
    if n:
        chunk_of = np.arange(n, dtype=np.int64) // C
        pair_max = int(np.bincount(
            chunk_of * (num_shards + 1)
            + np.minimum(dst[:n], num_shards),
            minlength=num_shards * (num_shards + 1))
            .reshape(num_shards, num_shards + 1)[:, :num_shards].max())
    else:
        pair_max = 0
    bucket_width = min(pad_bucket_size(pair_max, minimum=min_bucket), C)
    return dst, staged, bucket_width


def build_exchange_scatter(mesh: Mesh, agg, valued: bool = False):
    """The fused exchange+scatter program: ONE jitted shard_map over the
    whole mesh that (a) segment-sorts each shard's flat record chunk
    into per-destination buckets, (b) exchanges the buckets with
    ``all_to_all`` over the mesh axis, and (c) scatters the received
    rows into the [P, capacity] accumulator plane — the keyBy exchange
    and the aggregate step as one XLA program.

    ``valued=False`` folds raw input-leaf values (const leaves derive on
    device, like ``scatter_step``); ``valued=True`` folds explicit
    per-ACC-leaf partials (the two-phase local/global path, like
    ``valued_scatter_step``). Cached in the shared program cache per
    ``(device ids, aggregate layout, variant)`` — jobs and rebuilt
    engines share the executable (the multi-tenant zero-recompile
    contract), shapes one level down via jit + the pad_bucket_size
    tiers."""
    rank_backend = backend_of("exchange-rank")
    key = (tuple(d.id for d in mesh.devices.flat), agg.cache_key(),
           bool(valued), rank_backend)
    return PROGRAM_CACHE.get_or_build(
        "exchange-scatter", key,
        lambda: _build_exchange_scatter(mesh, agg, valued, rank_backend))


def _build_exchange_scatter(mesh: Mesh, agg, valued: bool,
                            rank_backend: str = "xla"):
    leaves = agg.leaves
    methods = tuple(SCATTER_METHOD[l.reduce] for l in leaves)
    n_leaves = len(leaves)
    num_shards = int(mesh.devices.size)
    # pallas_call has no shard_map replication rule — disable the check
    # for the pallas-ranked build only (the xla build stays byte-
    # identical in behavior to the pre-stateplane program)
    sm_kwargs = {"check_vma": False} if rank_backend == "pallas" else {}

    def _exchange(block):
        # [P, W] local block, dim0 = destination shard -> [P, W] with
        # dim0 = source shard (the ICI hop; identity on a 1-mesh)
        if num_shards == 1:
            return block
        return jax.lax.all_to_all(block, KEY_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
    def exchange_scatter(accs, dst, slots, values, bucket_width):
        W = int(bucket_width)

        def local(*args):
            accs_l = args[:n_leaves]         # each [1, cap]
            d = args[n_leaves]               # [C] destination shard
            s = args[n_leaves + 1]           # [C] destination slot
            vals_l = iter(args[n_leaves + 2:])
            # rank of record i within its destination = count of prior
            # same-destination records: preserves STREAM ORDER per
            # destination (chunks partition the stream contiguously, so
            # the received (source, rank) flattening is stream order —
            # the same order the host bucketing produces, which keeps
            # float folds bit-identical across modes). Padded / dropped
            # lanes (dst == num_shards) get the out-of-range flat
            # sentinel and are dropped by the scatter; the host sized W
            # to the batch's densest pair, so the rank < W guard only
            # bounds a miscount to a drop (-> oracle divergence)
            # instead of silent row corruption.
            flat = exchange_rank_flat(d, num_shards, W, rank_backend)
            recv_s = _exchange(
                jnp.zeros((num_shards * W,), jnp.int32)
                .at[flat].set(s, mode="drop")
                .reshape(num_shards, W)).reshape(-1)
            out = []
            for a, m, l in zip(accs_l, methods, leaves):
                if not valued and l.const is not None:
                    # bucket lanes that received no record hold slot 0
                    # (the reserved identity slot) — keep it pure
                    v = jnp.where(
                        recv_s == 0,
                        jnp.asarray(l.identity, dtype=l.dtype),
                        jnp.asarray(l.const, dtype=l.dtype))
                else:
                    v = _exchange(
                        jnp.full((num_shards * W,), l.identity,
                                 dtype=l.dtype)
                        .at[flat].set(next(vals_l), mode="drop")
                        .reshape(num_shards, W)).reshape(-1)
                out.append(getattr(a.at[0, recv_s], m)(v))
            return tuple(out)

        n_vals = len(values)
        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_leaves + 2 + n_vals),
            out_specs=(P(KEY_AXIS),) * n_leaves,
            **sm_kwargs,
        )(*accs, dst, slots, *values)

    return exchange_scatter


# ---------------------------------------------------------------------------
# Device-side collectives (used inside shard_map-ped steps)
# ---------------------------------------------------------------------------


def make_all_to_all_repartition(mesh: Mesh):
    """[P, P, B] block (dim0 = source shard sharded, dim1 = dest shard) ->
    redistributed so each shard holds the rows destined for it.

    This is the ICI replacement for the reference's network exchange between
    two keyed stages.
    """

    @jax.jit
    def repartition(block):
        def local(x):  # x: [1, P, B, ...]; dim1 indexed by destination shard
            # exchange blocks: after this, dim1 is indexed by SOURCE shard
            return jax.lax.all_to_all(x, KEY_AXIS, split_axis=1, concat_axis=1)

        return shard_map(
            local, mesh=mesh,
            in_specs=P(KEY_AXIS), out_specs=P(KEY_AXIS))(block)

    return repartition


def make_global_combine(mesh: Mesh, reduce: str = "sum"):
    """Two-phase aggregation: per-shard partials [P, ...] -> full reduction
    replicated on every shard (psum/pmax/pmin over ICI)."""

    op = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin}[reduce]

    local_reduce = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[reduce]

    @jax.jit
    def combine(partials):
        def local(x):  # [1, ...] per shard
            return op(local_reduce(x, axis=0), KEY_AXIS)

        return shard_map(
            local, mesh=mesh,
            in_specs=P(KEY_AXIS), out_specs=P())(partials)

    return combine
