"""Counter-based hashing shared by the benchmark's generators.

A copy of the arithmetic of ``flink_tpu.connectors.sources._splitmix64``
(the yardstick keeps its own: a later PR may change the program's). Event
content is a pure function of the GLOBAL record index, so "the first N
events of seed s" is well defined whatever the batching.
"""

import numpy as np

_GOLDEN = 0x9E3779B97F4A7C15
_MASK = 0xFFFFFFFFFFFFFFFF


def splitmix64(idx, salt):
    """uint64 hash of each index in ``idx`` under ``salt``."""
    with np.errstate(over="ignore"):
        z = idx.astype(np.uint64) + np.uint64((salt * _GOLDEN) & _MASK)
        z = z + np.uint64(_GOLDEN)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def first_index_at(ts_ms, rate):
    """Smallest global index whose event time ``i * 1000 // rate`` is at
    least ``ts_ms``."""
    return -(-int(ts_ms) * int(rate) // 1000)
