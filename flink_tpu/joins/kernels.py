"""Compiled device programs for the two-input join engines.

Four program families, all cached in the shared
:data:`~flink_tpu.tenancy.program_cache.PROGRAM_CACHE` keyed on
``(device ids, plane dtype layout)`` — never on an engine or job
identity — so rebuilt engines, restarted jobs and concurrent tenants
share the executables (the multi-tenant zero-recompile contract), with
shapes handled one level down by jit + the ``pad_bucket_size`` /
``sticky_bucket`` tier discipline:

- **join-put**: scatter staged ``[P, B]`` row blocks (slot + value
  columns) into the side's ``[P, capacity]`` plane — the host-bucketed
  ingest path (``shuffle.mode=host``).
- **join-exchange-put**: the device-mode ingest: flat staged columns go
  up in ONE ``device_put``, and a single program segment-sorts each
  shard's chunk into per-destination buckets (the stateplane
  ``exchange-rank`` combinator, xla or pallas backend —
  stream order preserved per destination, same as the host path),
  ``all_to_all``-exchanges them over the mesh axis and scatters the
  received rows into the plane — keyBy exchange + state write as one
  XLA program, the join form of
  ``parallel/shuffle.build_exchange_scatter``.
- **join-gather**: plane rows at ``[P, G]`` slot blocks (eviction
  cohorts, snapshots, reshard lifts) — ONE batched D2H per harvest.
- **join-banded-probe**: the banded segment-intersection step. The host
  metadata (sorted ``(key, ts)`` per shard — int64 lives on the host,
  the x32 device plane never sees a key) resolves each probe's band
  ``[lo, lo+cnt)`` over the sorted row order; the program walks every
  probe's band positions, gathers the banded candidates' slots from the
  per-shard sorted-order mirror, masks out-of-band and non-resident
  (spilled, ``slot < 0``) lanes, and gathers the surviving candidates'
  value columns from the slot plane — emitting ``[P, B, W]`` joined
  value columns in band order. The temporal join is the ``W == 1``
  degenerate band (the latest version at-or-before the probe time).

Value columns ride the device plane only when their dtype survives the
x32 backend bit-exactly (float32/int32/bool — see
``side_table.DEVICE_ELIGIBLE``); wider columns stay in the host shadow
store so device and host modes remain bit-identical.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from flink_tpu.parallel.mesh import KEY_AXIS, shard_map
from flink_tpu.stateplane.backends import backend_of
from flink_tpu.stateplane.rank import exchange_rank_flat
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE


def _mesh_key(mesh: Mesh) -> Tuple[int, ...]:
    return tuple(d.id for d in mesh.devices.flat)


def build_join_put(mesh: Mesh, dtypes: Tuple[str, ...]):
    """``plane[p, slots] = values`` for [P, B] staged blocks. Padded
    lanes carry slot 0 (the reserved scratch slot) — writes there are
    structurally dead."""
    key = (_mesh_key(mesh), tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "join-put", key, lambda: _build_join_put(mesh, len(dtypes)))


def _build_join_put(mesh: Mesh, n_cols: int):
    @partial(jax.jit, donate_argnums=(0,))
    def put(planes, slots, values):
        def local(*args):
            planes_l = args[:n_cols]
            s = args[n_cols][0]
            vs = args[n_cols + 1:]
            return tuple(pl.at[0, s].set(v[0])
                         for pl, v in zip(planes_l, vs))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (2 * n_cols + 1),
            out_specs=(P(KEY_AXIS),) * n_cols,
        )(*planes, slots, *values)

    return put


def build_join_exchange_put(mesh: Mesh, dtypes: Tuple[str, ...]):
    """The fused device-mode ingest: segment-sort each shard's flat
    chunk into per-destination buckets, ``all_to_all`` them over the
    mesh axis, scatter the received (slot, values) rows into the plane
    — one compiled program from staged columns to state write."""
    rank_backend = backend_of("exchange-rank")
    key = (_mesh_key(mesh), tuple(dtypes), rank_backend)
    return PROGRAM_CACHE.get_or_build(
        "join-exchange-put", key,
        lambda: _build_join_exchange_put(mesh, len(dtypes), rank_backend))


def _build_join_exchange_put(mesh: Mesh, n_cols: int,
                             rank_backend: str = "xla"):
    num_shards = int(mesh.devices.size)
    sm_kwargs = {"check_vma": False} if rank_backend == "pallas" else {}

    def _exchange(block):
        if num_shards == 1:
            return block
        return jax.lax.all_to_all(block, KEY_AXIS,
                                  split_axis=0, concat_axis=0)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
    def exchange_put(planes, dst, slots, values, bucket_width):
        W = int(bucket_width)

        def local(*args):
            planes_l = args[:n_cols]
            d = args[n_cols]          # [C] destination shard
            s = args[n_cols + 1]      # [C] destination slot
            vs = args[n_cols + 2:]
            # rank within destination preserves stream order per
            # destination — the same (source, rank) flattening the
            # host bucketing produces (see build_exchange_scatter)
            flat = exchange_rank_flat(d, num_shards, W, rank_backend)
            recv_s = _exchange(
                jnp.zeros((num_shards * W,), jnp.int32)
                .at[flat].set(s, mode="drop")
                .reshape(num_shards, W)).reshape(-1)
            out = []
            for pl, v in zip(planes_l, vs):
                rv = _exchange(
                    jnp.zeros((num_shards * W,), pl.dtype)
                    .at[flat].set(v, mode="drop")
                    .reshape(num_shards, W)).reshape(-1)
                # empty bucket lanes carry recv_s == 0: the reserved
                # scratch slot absorbs them
                out.append(pl.at[0, recv_s].set(rv))
            return tuple(out)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (2 * n_cols + 2),
            out_specs=(P(KEY_AXIS),) * n_cols,
            **sm_kwargs,
        )(*planes, dst, slots, *values)

    return exchange_put


def build_join_gather(mesh: Mesh, dtypes: Tuple[str, ...]):
    """Plane rows at [P, G] slot blocks (evictions, snapshots, reshard
    lifts) — the caller does ONE batched ``device_get`` on the result."""
    key = (_mesh_key(mesh), tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "join-gather", key, lambda: _build_join_gather(mesh, len(dtypes)))


def _build_join_gather(mesh: Mesh, n_cols: int):
    @jax.jit
    def gather(planes, slots):
        def local(*args):
            planes_l = args[:n_cols]
            s = args[n_cols][0]
            return tuple(pl[0][s][None, :] for pl in planes_l)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_cols + 1),
            out_specs=(P(KEY_AXIS),) * n_cols,
        )(*planes, slots)

    return gather


def build_banded_probe(mesh: Mesh, dtypes: Tuple[str, ...]):
    """The banded segment-intersection program: for each probe, gather
    the band's candidate slots from the sorted-order mirror, intersect
    (in-band AND resident) and emit the candidates' value columns as
    ``[P, B, W]`` blocks in band order. Non-resident lanes emit zero;
    the host serves them from the paged spill tier and the in-band
    structure (``lo``/``cnt``) is identical on both sides by
    construction — the host computed it."""
    key = (_mesh_key(mesh), tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "join-banded-probe", key,
        lambda: _build_banded_probe(mesh, len(dtypes)))


def _build_banded_probe(mesh: Mesh, n_cols: int):
    @partial(jax.jit, static_argnums=(4,))
    def probe(planes, sorted_slots, lo, cnt, band_width):
        W = int(band_width)

        def local(*args):
            planes_l = args[:n_cols]
            ss = args[n_cols][0]       # [S] sorted-order slot mirror
            lo_l = args[n_cols + 1][0]  # [B]
            cnt_l = args[n_cols + 2][0]  # [B]
            S = ss.shape[0]
            j = jax.lax.broadcasted_iota(jnp.int32, (lo_l.shape[0], W), 1)
            pos = lo_l[:, None] + j                    # [B, W]
            inband = (j < cnt_l[:, None]) & (pos < S)
            cslot = ss[jnp.clip(pos, 0, S - 1)]        # [B, W]
            ok = inband & (cslot >= 0)
            sc = jnp.clip(cslot, 0, None)
            outs = []
            for pl in planes_l:
                g = pl[0][sc]                          # [B, W]
                outs.append(jnp.where(ok, g,
                                      jnp.zeros((), dtype=pl.dtype))
                            [None, :, :])
            return tuple(outs)

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_cols + 3),
            out_specs=(P(KEY_AXIS),) * n_cols,
        )(*planes, sorted_slots, lo, cnt)

    return probe
