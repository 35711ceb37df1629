"""The exchange-rank program family: rank-within-destination.

Every device exchange in the repo (the fused flat exchange+scatter, the
join ingest exchange, both stages of the two-level pod exchange) needs
the same combinator: for a staged column of destination indices ``d``,
the rank of record ``i`` within its destination — the count of PRIOR
same-destination records. Ranks flatten to per-destination bucket
offsets ``d * W + rank`` so an ``all_to_all`` block scatter preserves
stream order per destination (the property that keeps float folds
bit-identical between host bucketing and device exchange).

Two backends compute the same rank:

- ``xla``: the one-hot-cumsum idiom — ``cumsum(one_hot(d, D))`` is an
  O(n*D) matmul-shaped program standing in for a counting sort
  (ROADMAP item 3b's named worst offender).
- ``pallas``: a ``pl.pallas_call`` counting-sort kernel — one O(n)
  sequential pass, ``d`` and the ranks blocked through SMEM over a
  sequential grid with the per-destination counts carried in SMEM
  scratch. Interpreted on the ``cpu`` backend only; any other backend
  compiles it (Mosaic) or raises.

Both are A/B gated bit-identical for ALL int32 inputs (including
negative and out-of-range sentinel lanes): rank(i) = #{j < i :
0 <= d_j < D and d_j == clip(d_i, 0, D-1)}. The enclosing exchange
builders resolve the backend at build time via
:mod:`flink_tpu.stateplane.backends` and tag their PROGRAM_CACHE keys
with it, so an A/B swap is a new cache entry, never a silent retrace.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

#: records per grid step: the ``d`` block and the rank block each sit
#: in SMEM double-buffered (4 x 32 KiB), which the v5e compiler accepts
#: at every staged-column size (tests/test_chip_compile.py)
RANK_BLOCK = 8192


def xla_rank(d, num_dests: int):
    """Rank within destination via one-hot + cumsum (the XLA idiom all
    four exchange sites hand-rolled before the stateplane extraction)."""
    oh = jax.nn.one_hot(d, num_dests, dtype=jnp.int32)
    rank = jnp.cumsum(oh, axis=0) - oh
    return jnp.take_along_axis(
        rank, jnp.clip(d, 0, num_dests - 1)[:, None], axis=1)[:, 0]


def _rank_kernel(d_ref, out_ref, counts_ref, *, num_dests: int):
    """Counting sort: one sequential pass per block, counts in SMEM
    scratch carried across the (sequential) grid.

    Bit-compatible with :func:`xla_rank` for every int32 input: lanes
    with ``d`` outside ``[0, num_dests)`` READ the count at the clipped
    bucket (what take_along_axis does) but never increment (their
    one-hot row is all zero). SMEM takes scalar stores only, hence the
    scalar loop that zeroes the counts on the first block."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        def zero(b, carry):
            counts_ref[b] = 0
            return carry

        jax.lax.fori_loop(0, num_dests, zero, 0)

    def body(i, carry):
        d = d_ref[i]
        b = jnp.clip(d, 0, num_dests - 1)
        c = counts_ref[b]
        out_ref[i] = c
        counts_ref[b] = jnp.where((d >= 0) & (d < num_dests), c + 1, c)
        return carry

    jax.lax.fori_loop(0, d_ref.shape[0], body, 0)


def pallas_rank(d, num_dests: int):
    """Rank within destination as a Pallas counting-sort kernel."""
    n = d.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    block = min(RANK_BLOCK, n)
    pad = -n % block
    d = d.astype(jnp.int32)
    if pad:  # out-of-range lanes never increment a count
        d = jnp.concatenate([d, jnp.full((pad,), -1, jnp.int32)])
    rank = pl.pallas_call(
        partial(_rank_kernel, num_dests=int(num_dests)),
        out_shape=jax.ShapeDtypeStruct(d.shape, jnp.int32),
        grid=(d.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
        scratch_shapes=[pltpu.SMEM((int(num_dests),), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() == "cpu",
    )(d)
    return rank[:n] if pad else rank


_RANK_FNS = {"xla": xla_rank, "pallas": pallas_rank}


def exchange_rank_flat(d, num_dests: int, width, backend: str = "xla"):
    """Destination indices ``[C]`` -> flat bucket offsets ``[C]``.

    ``flat[i] = d[i] * width + rank(i)`` for in-range lanes whose rank
    fits the bucket; every other lane gets the out-of-range sentinel
    ``num_dests * width`` (dropped by ``.at[flat].set(mode="drop")``).
    ``width`` may be a python int or a traced scalar from a static arg.
    """
    rank_d = _RANK_FNS[backend](d, int(num_dests))
    ok = (d < num_dests) & (rank_d < width)
    return jnp.where(ok, d * width + rank_d, num_dests * width)


def build_exchange_rank(num_dests: int, backend: str = "xla"):
    """The standalone cached exchange-rank program: ``(d, width) ->
    flat``. The in-exchange sites trace :func:`exchange_rank_flat`
    inline (it fuses into their one program); this cached form is the
    unit the A/B gate, the property test and the recompile phases
    exercise directly."""
    key = (int(num_dests), str(backend))
    return PROGRAM_CACHE.get_or_build(
        "exchange-rank", key, lambda: _build_exchange_rank(
            int(num_dests), str(backend)))


def _build_exchange_rank(num_dests: int, backend: str):
    @partial(jax.jit, static_argnums=(1,))
    def rank_program(d, width):
        return exchange_rank_flat(d, num_dests, int(width), backend)

    return rank_program
