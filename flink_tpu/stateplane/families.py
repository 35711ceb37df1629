"""The canonical program-family library of the device state plane.

Before this module, ~eight homes (SlotTable, PaneTable, the two mesh
engines, the join side tables, the replica publisher, the two-level
exchange, CEP) each hand-rolled their own gather / scatter / evict /
snapshot program families — flint's TRC01 sweep once fixed the same
bug class (a host sync inside the traced path) in five of them. This
module is the ONE home:
every compiled state-plane program the engines dispatch is built here
(or in a sibling stateplane module) and cached in the shared
:data:`~flink_tpu.tenancy.program_cache.PROGRAM_CACHE` under a family
kind drawn from :data:`KNOWN_PROGRAM_FAMILIES`.

The registry is the flint REG04 contract: a ``PROGRAM_CACHE``
``get_or_build`` call whose kind is not in this tuple is a violation,
and a registry entry with no call site is stale. The first seven kinds
are the canonical flat families (this module + ``pane.py`` +
``rank.py``); the rest are the composite per-engine programs that
FUSE canonical pieces (exchange+scatter in one XLA program, the CEP
advance, ...) — inventoried in the README's state-plane table, each
either already built from these pieces or an explicit follow-up.

Builders key programs on WHAT they compute — reduce methods, identity
constants, dtypes, aggregate layout — never on an engine, job, or
instance identity (the multi-tenant zero-recompile contract; shapes
are handled one level down by jit + the engines' sticky-bucket
padding). The bodies are the exact programs the engines compiled
before the extraction — bit-identity of every ported path is pinned
by ``tests/test_stateplane.py``.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_tpu.ops.segment_ops import MERGE_FN, SCATTER_METHOD
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

#: Every program-family kind that may appear as the first argument of a
#: ``PROGRAM_CACHE.get_or_build`` call (flint REG04). Canonical flat
#: families first, then the composite per-engine programs.
KNOWN_PROGRAM_FAMILIES = (
    # -- canonical flat families (stateplane-owned builders) --
    "gather",           # rows out of flat accumulators (spill/snapshot read)
    "scatter-combine",  # batch fold into flat accumulators (ingest write)
    "segment-reduce",   # slot-segment merge (+finish/projection) — fires
    "evict-cohort",     # cohort put/reset (spill reload, eviction clear)
    "snapshot-lift",    # snapshot ordering fence / row lift
    "delta-harvest",    # pane-ring partial scatter + fire-row harvest
    "wide-fold",        # host partials combined into two-word int64 parts
    # -- composite per-engine programs (fused from canonical pieces) --
    "mesh-steps",
    "session-merge",
    "delta-fire",
    "exchange-scatter",
    "exchange2-stage1",
    "exchange2-stage2",
    "pod-route",
    "pod-agree",
    "replica-pub",
    "join-put",
    "join-exchange-put",
    "join-gather",
    "join-banded-probe",
    "window-join-put",
    "window-join-match",
    "regular-join-insert",
    "regular-join-probe",
    "join-exchange2-stage1",
    "join-exchange2-stage2",
    "cep-advance",
    "cep-prune",
)


def _methods(leaves) -> Tuple[str, ...]:
    return tuple(SCATTER_METHOD[l.reduce] for l in leaves)


def _idents(leaves) -> tuple:
    return tuple(l.identity for l in leaves)


def _dtypes(leaves) -> Tuple[str, ...]:
    return tuple(l.dtype.str for l in leaves)


# ------------------------------------------------------------ scatter-combine


def flat_scatter_combine(leaves):
    """Batch fold into flat accumulators; const leaves broadcast their
    compile-time constant on device, identity-masked at the reserved
    slot 0 (padded lanes target it and fires read it for missing
    slices)."""
    consts = tuple(None if l.const is None else (l.const, l.dtype.str)
                   for l in leaves)
    key = ("const", _methods(leaves), consts, _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "scatter-combine", key, lambda: _build_scatter_combine(leaves))


def _build_scatter_combine(leaves):
    methods = _methods(leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter(accs, slots, values):
        vit = iter(values)
        out = []
        for a, m, l in zip(accs, methods, leaves):
            if l.const is not None:
                # padded lanes target the reserved slot 0, which
                # must stay identity (fires read it for missing
                # slices) — mask the const there
                v = jnp.where(slots == 0,
                              jnp.asarray(l.identity, dtype=l.dtype),
                              jnp.asarray(l.const, dtype=l.dtype))
            else:
                v = next(vit)
            out.append(getattr(a.at[slots], m)(v))
        return tuple(out)

    return scatter


def flat_scatter_valued(leaves):
    """Scatter where EVERY leaf takes an explicit value array, each
    folded by its own reduce method — the merge of locally pre-
    aggregated partials (two-phase aggregation). Pad lanes must carry
    each leaf's identity at the reserved slot 0."""
    key = ("valued", _methods(leaves), _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "scatter-combine", key, lambda: _build_scatter_valued(leaves))


def _build_scatter_valued(leaves):
    methods = _methods(leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter_valued(accs, slots, values):
        return tuple(
            getattr(a.at[slots], m)(v)
            for a, m, v in zip(accs, methods, values))

    return scatter_valued


def flat_scatter_signed(leaves):
    """Scatter of sign-applied values — the retraction fold. Only valid
    for pure-add layouts, where padding with 0 at the reserved slot is
    harmless."""
    key = ("signed", _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "scatter-combine", key, lambda: _build_scatter_signed())


def _build_scatter_signed():
    @partial(jax.jit, donate_argnums=(0,))
    def scatter_signed(accs, slots, values):
        return tuple(
            a.at[slots].add(v) for a, v in zip(accs, values))

    return scatter_signed


# ------------------------------------------------------------- segment-reduce


def flat_segment_fire(agg):
    """(accs, slot_matrix [w, k]) -> result columns [w]: merge each
    window's slot segment, then ``finish``."""
    key = ("fire", agg.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "segment-reduce", key, lambda: _build_segment_fire(agg))


def _build_segment_fire(agg):
    merges = tuple(MERGE_FN[l.reduce] for l in agg.leaves)
    finish = agg.finish

    @jax.jit
    def fire(accs, slot_matrix):
        merged = tuple(
            m(a[slot_matrix], axis=1) for a, m in zip(accs, merges)
        )
        return finish(merged)

    return fire


def flat_segment_fire_projected(agg, projector):
    """The fire merge+finish fused with a FireProjector so only n rows
    cross HBM->host instead of wp; validity derives on device from the
    scalar row count (see flink_tpu.windowing.fire_projectors)."""
    key = ("fire-proj", agg.cache_key(), projector.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "segment-reduce", key,
        lambda: _build_segment_fire_projected(agg, projector))


def _build_segment_fire_projected(agg, projector):
    merges = tuple(MERGE_FN[l.reduce] for l in agg.leaves)
    finish = agg.finish
    project = projector.project

    @jax.jit
    def fire_proj(accs, slot_matrix, w):
        valid = jnp.arange(slot_matrix.shape[0]) < w
        merged = tuple(
            m(a[slot_matrix], axis=1) for a, m in zip(accs, merges)
        )
        return project(finish(merged), valid)

    return fire_proj


def flat_segment_merge(leaves):
    """(accs, slot_matrix [w, k]) -> merged leaves [w] WITHOUT finish —
    the hybrid-fire read path: device-resident slices merge on device,
    spilled slices merge on host, finish runs on host over the union."""
    key = ("merge", tuple(MERGE_FN[l.reduce].__name__ for l in leaves),
           _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "segment-reduce", key, lambda: _build_segment_merge(leaves))


def _build_segment_merge(leaves):
    merges = tuple(MERGE_FN[l.reduce] for l in leaves)

    @jax.jit
    def merge(accs, slot_matrix):
        return tuple(
            m(a[slot_matrix], axis=1) for a, m in zip(accs, merges))

    return merge


def flat_merge_pairs(leaves):
    """acc[dst] op= acc[src] for arrays of (dst, src), then reset the
    src slots — the session-merge move (padded lanes have
    src == dst == 0, a no-op on the reserved identity slot)."""
    key = ("merge-pairs", _methods(leaves), _idents(leaves),
           _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "segment-reduce", key, lambda: _build_merge_pairs(leaves))


def _build_merge_pairs(leaves):
    methods = _methods(leaves)
    idents = _idents(leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def merge(accs, dst, src):
        out = []
        for a, m, i in zip(accs, methods, idents):
            moved = a[src]
            a = getattr(a.at[dst], m)(moved)
            # src != dst for real pairs; padded lanes have src == dst == 0
            a = a.at[src].set(i)
            out.append(a)
        return tuple(out)

    return merge


# ------------------------------------------------------------------ wide-fold


_SIGN = -(1 << 31)


def _unsigned(w):
    """An int32 word flipped so that signed order is its unsigned order."""
    return jnp.bitwise_xor(w, jnp.int32(_SIGN))


def wide_fold(layout: Tuple[Tuple[str, str], ...]):
    """``(accs, slots, fresh, deltas) -> (accs, words)`` for a table that
    holds an exact 64-bit part (``windowing.aggregates.Int64Aggregate``).
    ``layout`` is each part's ``(reduce, dtype)``: a 64-bit part is two
    int32 leaves, its low word then its high word, a 32-bit one one leaf.
    ``deltas`` holds the batch's per-slot partials the same way,
    ``slots`` the distinct slots they belong to (lanes past them hold the
    capacity, which the gathers clip and the scatters drop), ``fresh``
    the slots that hold nothing yet. A 64-bit ``sum`` adds with the low
    word's carry, a 64-bit ``max`` or ``min`` compares the high words
    signed and the low words unsigned. ``words`` are the parts' new words
    at ``slots``: what the operator emits."""
    layout = tuple((str(r), np.dtype(d).str) for r, d in layout)
    for r, _ in layout:
        if r not in ("sum", "max", "min"):
            raise ValueError(f"unknown reduce {r!r}")
    return PROGRAM_CACHE.get_or_build(
        "wide-fold", layout, lambda: _build_wide_fold(layout))


def _combine64(r, alo, ahi, dlo, dhi):
    if r == "sum":
        nlo = alo + dlo
        carry = (_unsigned(nlo) < _unsigned(alo)).astype(jnp.int32)
        return nlo, ahi + dhi + carry
    above = (dhi > ahi) | ((dhi == ahi) & (_unsigned(dlo) > _unsigned(alo)))
    below = (dhi < ahi) | ((dhi == ahi) & (_unsigned(dlo) < _unsigned(alo)))
    take = above if r == "max" else below
    return jnp.where(take, dlo, alo), jnp.where(take, dhi, ahi)


def _combine32(r, a, d):
    return {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[r](a, d)


def _build_wide_fold(layout):
    @partial(jax.jit, donate_argnums=(0,))
    def group_agg_fold64(accs, slots, fresh, deltas):
        new_accs, words = [], []
        at = 0
        for r, dt in layout:
            n = 2 if np.dtype(dt).itemsize == 8 else 1
            old = [accs[at + j].at[slots].get(mode="clip") for j in range(n)]
            delta = list(deltas[at:at + n])
            new = (_combine64(r, *old, *delta) if n == 2
                   else (_combine32(r, old[0], delta[0]),))
            for j in range(n):
                w = jnp.where(fresh, delta[j], new[j])
                new_accs.append(accs[at + j].at[slots].set(w, mode="drop"))
                words.append(w)
            at += n
        return tuple(new_accs), tuple(words)

    return group_agg_fold64


# --------------------------------------------------------------------- gather


def flat_gather(leaves):
    """(accs, slots) -> per-leaf gathered values — the incremental-
    snapshot / eviction read path: only the addressed slots leave the
    device instead of the whole [capacity] arrays."""
    key = (_dtypes(leaves),)
    return PROGRAM_CACHE.get_or_build(
        "gather", key, lambda: _build_gather())


def _build_gather():
    @jax.jit
    def gather(accs, slots):
        return tuple(a[slots] for a in accs)

    return gather


# --------------------------------------------------------------- evict-cohort


def flat_put(leaves):
    """(accs, slots, values) -> ``a[slots] = v`` — the spill-reload
    write path: values gathered to host at eviction time are placed
    back verbatim (identity-masked at the reserved slot 0 pad target)."""
    idents = _idents(leaves)
    key = ("put", idents, _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "evict-cohort", key, lambda: _build_put(idents))


def _build_put(idents):
    @partial(jax.jit, donate_argnums=(0,))
    def put(accs, slots, values):
        out = []
        for a, v, i in zip(accs, values, idents):
            v = jnp.where(slots == 0, jnp.asarray(i, dtype=v.dtype),
                          v)
            out.append(a.at[slots].set(v))
        return tuple(out)

    return put


def flat_reset(leaves):
    """Reset an eviction cohort's slots to their identities."""
    idents = _idents(leaves)
    key = ("reset", idents, _dtypes(leaves))
    return PROGRAM_CACHE.get_or_build(
        "evict-cohort", key, lambda: _build_reset(idents))


def _build_reset(idents):
    @partial(jax.jit, donate_argnums=(0,))
    def reset(accs, slots):
        return tuple(
            a.at[slots].set(i) for a, i in zip(accs, idents)
        )

    return reset


# -------------------------------------------------------------- snapshot-lift


def flat_fence(dtype_str: str):
    """A tiny non-donated device read enqueued AFTER everything
    dispatched so far — its readiness proves the device caught up to
    this point (snapshot ordering, dispatch-depth bounding)."""
    return PROGRAM_CACHE.get_or_build(
        "snapshot-lift", ("fence", dtype_str),
        lambda: jax.jit(lambda a: a[:1]))


def pane_fence(dtype_str: str):
    """The [R, C] pane-plane fence: a [1, 1] slice of the live
    accumulator, enqueued behind all prior work."""
    return PROGRAM_CACHE.get_or_build(
        "snapshot-lift", ("pane-fence", dtype_str),
        lambda: jax.jit(lambda a: a[:1, :1]))
