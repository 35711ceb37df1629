"""Fire-time device-side projection of window results.

The reference executes Top-N over a window's output as a separate rank
operator consuming the full fired stream (reference:
flink-table-runtime/.../operators/rank/AppendOnlyTopNFunction.java). On TPU
the expensive part of a fire is not the merge kernel but moving the [num_keys]
result rows from HBM to the host: Nexmark Q5 fires ~100k rows per HOP window
only for the next operator to keep one winner.

A ``FireProjector`` fuses that reduction INTO the fire kernel: the window's
result columns are reduced on device (``jax.lax.top_k``) and only the
projected rows are transferred. Because a fire always covers every key of the
window, the device-side reduction is exact — it is the same fusion XLA cannot
do on its own because the consumer lives in a different operator.

The projector also has a NumPy form (``project_host``) for the fire paths
that merge on host (spilled slices, cross-shard mesh merges).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax


class FireProjector:
    """Reduces the [w] rows of one fired window before host transfer.

    ``num_out`` is static (XLA shapes); ``project`` runs under jit inside
    the fire kernel; ``project_host`` is the NumPy equivalent.
    """

    #: static number of output rows per fired window
    num_out: int = 1

    def cache_key(self) -> tuple:
        raise NotImplementedError

    def project(self, cols: Dict[str, jnp.ndarray], valid: jnp.ndarray
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
        """(result cols[wp], valid[wp]) -> (row indices[n], cols[n],
        valid[n]) — jax-traced. Returns INDICES into the fired rows, not
        keys: the host resolves keys locally, so no key array ever crosses
        host->device."""
        raise NotImplementedError

    def project_host(self, keys: np.ndarray, cols: Dict[str, np.ndarray]
                     ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        raise NotImplementedError


class TopKFireProjector(FireProjector):
    """Keep the k rows with the largest (or smallest) ``order_col``.

    Exact for any consumer that keeps at most k rows ordered by that column
    (rank/Top-N, per-window arg-max). Ties beyond the k-th row are truncated
    — consumers that must surface ALL ties of the max should use a k of a
    few ties' headroom (the fused consumer filters to the true extremum).
    """

    def __init__(self, order_col: str, k: int = 16, descending: bool = True):
        self.order_col = order_col
        self.k = int(k)
        self.descending = descending
        self.num_out = self.k

    def cache_key(self) -> tuple:
        return (type(self).__module__, type(self).__qualname__,
                self.order_col, self.k, self.descending)

    def project(self, cols, valid):
        score = cols[self.order_col]
        if jnp.issubdtype(score.dtype, jnp.integer) and self.descending:
            # keep integer ordering exact in the column's own dtype (a
            # float32 cast collapses counts above 2^24). Ascending integer
            # order falls through to the float path: negating iinfo.min
            # would wrap, and x64 may be disabled (no wider int to cast to).
            floor = jnp.asarray(jnp.iinfo(score.dtype).min, score.dtype)
            score = jnp.where(valid, score, floor)
        else:
            score = score.astype(jnp.float32)
            if not self.descending:
                score = -score
            score = jnp.where(valid, score, -jnp.inf)
        k = min(self.k, int(score.shape[0]))
        _, idx = lax.top_k(score, k)
        out_valid = jnp.take(valid, idx)
        out_cols = {name: jnp.take(c, idx) for name, c in cols.items()}
        return idx, out_cols, out_valid

    def project_host(self, keys, cols):
        score = np.asarray(cols[self.order_col], dtype=np.float64)
        k = min(self.k, len(score))
        if self.descending:
            idx = np.argpartition(-score, k - 1)[:k] if k < len(score) \
                else np.arange(len(score))
            idx = idx[np.argsort(-score[idx], kind="stable")]
        else:
            idx = np.argpartition(score, k - 1)[:k] if k < len(score) \
                else np.arange(len(score))
            idx = idx[np.argsort(score[idx], kind="stable")]
        return keys[idx], {name: np.asarray(c)[idx]
                           for name, c in cols.items()}
