"""The delta-harvest family: [R, C] pane-ring programs.

The fire-latency tier's incremental pre-aggregation keeps window state
as a ring of pane slices x key columns; a fire harvests ONE merged row
(the delta) instead of re-reducing the window. The six programs of
that discipline — flat 2-D scatter (const and valued variants), the
fire-row merge+finish (+optional projection), the row reset/put of the
evict/reload cohort path, and the window-partial fold — are one bundle
here, cached in the shared PROGRAM_CACHE under the ``delta-harvest``
kind, keyed on aggregate layout (+ projector identity) only.

The int8 presence plane rides as the trailing array of ``accs`` in
every program — it distinguishes "identity because empty" from
"identity because the values folded to it", which the fire validity
mask needs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flink_tpu.ops.segment_ops import MERGE_FN, SCATTER_METHOD
from flink_tpu.tenancy.program_cache import PROGRAM_CACHE


def pane_programs(agg, projector=None):
    """(scatter2d, scatter2d_valued, fire_rows, reset_row, put_row,
    fold_rows) for [R, C] pane arrays. The presence plane rides as an
    extra trailing array in ``accs``."""
    key = ("pane", agg.cache_key(),
           None if projector is None else projector.cache_key())
    return PROGRAM_CACHE.get_or_build(
        "delta-harvest", key, lambda: _build_pane_programs(agg, projector))


def _build_pane_programs(agg, projector):
    leaves = agg.leaves
    methods = tuple(SCATTER_METHOD[l.reduce] for l in leaves)
    merges = tuple(MERGE_FN[l.reduce] for l in leaves)
    idents = tuple(l.identity for l in leaves)
    finish = agg.finish
    n = len(leaves)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter2d(accs, flat, values):
        # ONE flat i32 index array crosses host->device per batch
        # (rows/cols are pre-fused on host: half the H2D bytes; flat 1-D
        # scatter also lowers better on TPU than 2-D scatter; the
        # reshape is a bitcast under jit)
        C = accs[0].shape[1]
        pad = (flat % C) == 0  # col 0 is the reserved identity column
        vit = iter(values)
        out = []
        for a, m, l in zip(accs[:n], methods, leaves):
            if l.const is not None:
                v = jnp.where(pad,
                              jnp.asarray(l.identity, dtype=l.dtype),
                              jnp.asarray(l.const, dtype=l.dtype))
            else:
                v = next(vit)
            shape = a.shape
            out.append(
                getattr(a.reshape(-1).at[flat], m)(v).reshape(shape))
        presence = accs[n].reshape(-1).at[flat].max(
            jnp.where(pad, 0, 1).astype(jnp.int8)
        ).reshape(accs[n].shape)
        return tuple(out) + (presence,)

    @jax.jit
    def fire_rows(accs, rows, used_n):
        merged = tuple(
            m(a[rows], axis=0) for a, m in zip(accs[:n], merges))
        present = accs[n][rows].max(axis=0)
        cols = finish(merged)
        valid = (jnp.arange(present.shape[0]) < used_n) & (present > 0)
        if projector is None:
            return cols, valid
        return projector.project(cols, valid)

    @partial(jax.jit, donate_argnums=(0,))
    def scatter2d_valued(accs, flat, values):
        # every leaf valued (locally pre-aggregated partials), each folded
        # by its own reduce; pad lanes carry leaf identities at flat 0
        C = accs[0].shape[1]
        pad = (flat % C) == 0
        out = [getattr(a.reshape(-1).at[flat], m)(v).reshape(a.shape)
               for a, m, v in zip(accs[:n], methods, values)]
        presence = accs[n].reshape(-1).at[flat].max(
            jnp.where(pad, 0, 1).astype(jnp.int8)).reshape(accs[n].shape)
        return tuple(out) + (presence,)

    @partial(jax.jit, donate_argnums=(0,))
    def reset_row(accs, row):
        out = [a.at[row].set(jnp.asarray(i, dtype=a.dtype))
               for a, i in zip(accs[:n], idents)]
        return tuple(out) + (accs[n].at[row].set(jnp.int8(0)),)

    @partial(jax.jit, donate_argnums=(0,))
    def put_row(accs, row, cols, values):
        out = [a.at[row, cols].set(v)
               for a, v in zip(accs[:n], values)]
        presence = accs[n].at[row, cols].set(
            jnp.where(cols == 0, 0, 1).astype(jnp.int8))
        return tuple(out) + (presence,)

    @partial(jax.jit, donate_argnums=(0,))
    def fold_rows(accs, dst, rows):
        # window-partial (re)build: dst row := merge of the given ring
        # rows (overwrite semantics — dst is freshly allocated or being
        # rebuilt from the authoritative panes). One dispatch per
        # window, amortized one per slide period.
        out = [a.at[dst].set(m(a[rows], axis=0))
               for a, m in zip(accs[:n], merges)]
        presence = accs[n].at[dst].set(accs[n][rows].max(axis=0))
        return tuple(out) + (presence,)

    return (scatter2d, scatter2d_valued, fire_rows, reset_row, put_row,
            fold_rows)
