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
  rank-within-destination combinator —
  stream order preserved per destination, same as the host path),
  ``all_to_all``-exchanges them over the mesh axis and scatters the
  received rows into the plane — keyBy exchange + state write as one
  XLA program, the join form of
  ``parallel/shuffle.build_exchange_scatter``.
- **join-gather**: plane rows at ``[P, G]`` slot blocks (eviction
  cohorts, snapshots, reshard lifts) — ONE batched D2H per harvest.
- **window-join-put** / **window-join-match**: the window join's two
  programs (:class:`~flink_tpu.joins.engine.MeshWindowJoinEngine`). A
  side's rows are int32 WORDS — the int64 key as two, each value column
  as one or two by its width — in ``[P, capacity]`` planes cut into
  pages; the put scatters a batch's words to the slots its slices' pages
  gave it. The match runs at a window's fire over the pages of its
  slices: it gathers both sides' words, sorts them together by (key,
  side) with their value words, counts each right row's partners in its
  key's run, and expands the pairs into ``out_rows``-wide output columns
  — every pair, many-to-many, exact on all 64 bits of the key. The host
  only sizes the output; the pair count comes back with it.
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
    key = (_mesh_key(mesh), tuple(dtypes))
    return PROGRAM_CACHE.get_or_build(
        "join-exchange-put", key,
        lambda: _build_join_exchange_put(mesh, len(dtypes)))


def _build_join_exchange_put(mesh: Mesh, n_cols: int):
    num_shards = int(mesh.devices.size)

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
            flat = exchange_rank_flat(d, num_shards, W)
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


def build_window_put(mesh: Mesh, n_words: int):
    """``planes[w][p, slot] = block[p, 1 + w]`` for the slots in
    ``block[p, 0]``: one batch of one side into its word planes. Lanes
    past a shard's rows carry the slot ``capacity``, which the scatter
    drops."""
    return PROGRAM_CACHE.get_or_build(
        "window-join-put", (_mesh_key(mesh), int(n_words)),
        lambda: _build_window_put(mesh, int(n_words)))


def _build_window_put(mesh: Mesh, n_words: int):
    @partial(jax.jit, donate_argnums=(0,))
    def put(planes, block):
        def local(*args):
            b = args[n_words][0]
            return tuple(pl.at[0, b[0]].set(b[1 + w], mode="drop")
                         for w, pl in enumerate(args[:n_words]))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_words + 1),
            out_specs=(P(KEY_AXIS),) * n_words,
        )(*planes, block)

    return put


def build_window_match(mesh: Mesh, left_words: int, right_words: int,
                       page_rows: int):
    """The window join's fire: see the module docstring. Takes both
    sides' word planes, each side's ``[P, pages]`` page ids and rows per
    page (0 past the window's pages) and the static ``out_rows``;
    returns the pairs per shard ``[P]`` (twice: int32 and a float sum)
    and, per output column ``[P, out_rows]``: the key's two words, the left row's value words, the
    right row's value words (the right key is the left's)."""
    key = (_mesh_key(mesh), int(left_words), int(right_words),
           int(page_rows))
    return PROGRAM_CACHE.get_or_build(
        "window-join-match", key,
        lambda: _build_window_match(mesh, int(left_words),
                                    int(right_words), int(page_rows)))


def _window_rows(planes, pages, fill, page_rows: int):
    """A window's words, page after page, and which lanes hold a row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (pages.shape[0], page_rows),
                                    1)
    valid = (lane < fill[:, None]).reshape(-1)
    words = [pl[0].reshape(-1, page_rows)[pages].reshape(-1)
             for pl in planes]
    return words, valid


#: a row's side, the sort's last key: within a key's run the left rows
#: come first, then the right rows, then padding lanes
_LEFT, _RIGHT, _PAD = 0, 1, 2


def window_pairs(left, left_valid, right, right_valid, out_rows: int):
    """Every (left, right) pair of equal keys, for the words of one
    window. ``left`` / ``right``: word columns, the key's two first;
    returns ``[pairs]`` and ``out_rows``-long columns: the key's two
    words, then the left's value words, then the right's (lanes past
    ``pairs`` hold anything).

    One sort of both sides by (key, side) that carries each row's value
    words (a left row's and a right row's share columns), so each key's
    run holds its left rows, then its right rows, then padding; a right
    row's partners are its run's left rows, counted by a prefix sum less
    the count before the run (a running maximum: the count only grows).
    The pairs are laid out right row after right row, each one's
    partners in a block; an output lane finds its right row as the last
    block start at or before it (a scatter and a running maximum) and
    its left row as the run's start plus its place in the block. After
    the sort every random read is indexed by those two vectors: two
    gathers, one per vector."""
    n_left, n_right = left[0].shape[0], right[0].shape[0]
    n = n_left + n_right
    n_lv, n_rv = len(left) - 2, len(right) - 2
    pos = jnp.arange(n, dtype=jnp.int32)
    side = jnp.concatenate([jnp.where(left_valid, _LEFT, _PAD),
                            jnp.where(right_valid, _RIGHT, _PAD)])
    k0 = jnp.concatenate([left[0], right[0]])
    k1 = jnp.concatenate([left[1], right[1]])
    zero_l = jnp.zeros((n_left,), jnp.int32)
    zero_r = jnp.zeros((n_right,), jnp.int32)
    words = [jnp.concatenate([left[2 + j] if j < n_lv else zero_l,
                              right[2 + j] if j < n_rv else zero_r])
             for j in range(max(n_lv, n_rv))]
    k1s, k0s, sides, *words = jax.lax.sort(
        (k1, k0, side.astype(jnp.int32), *words), num_keys=3,
        is_stable=False)
    new_run = jnp.concatenate([
        jnp.ones((1,), bool), (k1s[1:] != k1s[:-1]) | (k0s[1:] != k0s[:-1])])
    run_start = jax.lax.cummax(jnp.where(new_run, pos, 0))
    is_left = (sides == _LEFT).astype(jnp.int32)
    lefts_upto = jnp.cumsum(is_left)
    lefts_before_run = jax.lax.cummax(
        jnp.where(new_run, lefts_upto - is_left, 0))
    partners = jnp.where(sides == _RIGHT, lefts_upto - lefts_before_run, 0)
    block_end = jnp.cumsum(partners)
    block_start = block_end - partners
    pairs = block_end[-1]
    lane = jnp.arange(out_rows, dtype=jnp.int32)
    # the block starts rise with the rows (so the scatter needs no sort of
    # its own); a row without partners shares its start with the next
    # block's owner, which comes after it and so outweighs it
    starts = jnp.full((out_rows,), -1, jnp.int32).at[block_start].max(
        pos, indices_are_sorted=True, mode="drop")
    owner = jnp.maximum(jax.lax.cummax(starts), 0)
    # what one index reads is stacked and read by one gather: on the chip
    # a [c, n] gather costs about what one [n] gather does
    by_owner = jnp.stack([run_start - block_start] + words[:n_rv])[:, owner]
    left_at = jnp.clip(by_owner[0] + lane, 0, n - 1)
    by_left = jnp.stack([k0s, k1s] + words[:n_lv])[:, left_at]
    # the count in int32 wraps past 2**31 pairs; its float sum says so
    approx = jnp.sum(partners.astype(jnp.float32))
    return [pairs, approx] + list(by_left) + list(by_owner[1:])


def _build_window_match(mesh: Mesh, left_words: int, right_words: int,
                        page_rows: int):
    n_planes = left_words + right_words
    n_out = 4 + (left_words - 2) + (right_words - 2)

    @partial(jax.jit, static_argnums=(6,))
    def match(left_planes, right_planes, left_pages, left_fill,
              right_pages, right_fill, out_rows):
        def local(*args):
            lp, rp = args[:left_words], args[left_words:n_planes]
            lpg, lfl, rpg, rfl = (a[0] for a in args[n_planes:])
            left, lvalid = _window_rows(lp, lpg, lfl, page_rows)
            right, rvalid = _window_rows(rp, rpg, rfl, page_rows)
            return tuple(o[None] for o in window_pairs(
                left, lvalid, right, rvalid, int(out_rows)))

        return shard_map(
            local, mesh=mesh,
            in_specs=(P(KEY_AXIS),) * (n_planes + 4),
            out_specs=(P(KEY_AXIS),) * n_out,
        )(*left_planes, *right_planes, left_pages, left_fill,
          right_pages, right_fill)

    return match
