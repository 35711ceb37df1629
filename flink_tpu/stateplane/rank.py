"""Rank-within-destination: the combinator every device exchange shares.

Every device exchange in the repo (the fused flat exchange+scatter, the
join ingest exchange, both stages of the two-level pod exchange) needs
the same combinator: for a staged column of destination indices ``d``,
the rank of record ``i`` within its destination — the count of PRIOR
same-destination records. Ranks flatten to per-destination bucket
offsets ``d * W + rank`` so an ``all_to_all`` block scatter preserves
stream order per destination (the property that keeps float folds
bit-identical between host bucketing and device exchange).

The rank is the one-hot-cumsum idiom: ``cumsum(one_hot(d, D))`` is an
O(n*D) program, and D is the number of destination shards (at most a
host's or a pod's device count), so the one-hot has few columns. It is
defined for ALL int32 inputs (including negative and out-of-range
sentinel lanes): rank(i) = #{j < i : 0 <= d_j < D and d_j ==
clip(d_i, 0, D-1)}. The exchange builders trace it inline, so it fuses
into their one program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def xla_rank(d, num_dests: int):
    """Rank within destination via one-hot + cumsum (the XLA idiom all
    four exchange sites hand-rolled before the stateplane extraction)."""
    oh = jax.nn.one_hot(d, num_dests, dtype=jnp.int32)
    rank = jnp.cumsum(oh, axis=0) - oh
    return jnp.take_along_axis(
        rank, jnp.clip(d, 0, num_dests - 1)[:, None], axis=1)[:, 0]


def exchange_rank_flat(d, num_dests: int, width):
    """Destination indices ``[C]`` -> flat bucket offsets ``[C]``.

    ``flat[i] = d[i] * width + rank(i)`` for in-range lanes whose rank
    fits the bucket; every other lane gets the out-of-range sentinel
    ``num_dests * width`` (dropped by ``.at[flat].set(mode="drop")``).
    ``width`` may be a python int or a traced scalar from a static arg.
    """
    rank_d = xla_rank(d, int(num_dests))
    ok = (d < num_dests) & (rank_d < width)
    return jnp.where(ok, d * width + rank_d, num_dests * width)

