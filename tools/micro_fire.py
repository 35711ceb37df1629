"""Micro-benchmark: fire vs fire_projected on the real backend."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from flink_tpu.platform import enable_compilation_cache

enable_compilation_cache()

import numpy as np

from flink_tpu.state.slot_table import SlotTable
from flink_tpu.windowing.aggregates import CountAggregate
from flink_tpu.windowing.fire_projectors import TopKFireProjector

N_KEYS = 100_000
K_SLICES = 5

agg = CountAggregate()
table = SlotTable(agg, capacity=1 << 20)
rng = np.random.default_rng(0)
keys = np.arange(N_KEYS, dtype=np.int64)
for s in range(K_SLICES):
    ns = np.full(N_KEYS, 1000 + s, dtype=np.int64)
    slots = table.lookup_or_insert(keys, ns)
    table.scatter(slots, agg.map_input.__self__.map_input(
        __import__("flink_tpu.core.records", fromlist=["RecordBatch"])
        .RecordBatch.from_pydict({"x": np.ones(N_KEYS)})))

proj = TopKFireProjector("count", k=16)


def timeit(label, fn, reps=10):
    fn()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    dt = (time.perf_counter() - t0) / reps * 1e3
    print(f"{label}: {dt:.2f} ms")


kz, matrix, _ = table.build_slice_matrix([1000 + s for s in range(K_SLICES)])
print(f"matrix {matrix.shape}")

timeit("build_slice_matrix", lambda: table.build_slice_matrix(
    [1000 + s for s in range(K_SLICES)]))
timeit("fire (full transfer)", lambda: table.fire(matrix))
timeit("fire_projected(top16)", lambda: table.fire_projected(
    matrix, kz, proj))

# isolate the kernel: no host padding
import jax
import jax.numpy as jnp

wp = 1 << 17
padded = np.zeros((wp, K_SLICES), dtype=np.int32)
padded[: len(kz)] = matrix
jm = jnp.asarray(padded)
fp = agg._fire_project_jit(proj)
ff = agg._fire_jit

timeit("kernel fire only", lambda: jax.block_until_ready(
    ff(table.accs, jm)))
timeit("kernel fire_proj only", lambda: jax.block_until_ready(
    fp(table.accs, jm, len(kz))))

# top_k alone
x = jnp.asarray(rng.random(wp).astype(np.float32))
topk = jax.jit(lambda v: jax.lax.top_k(v, 16))
timeit("lax.top_k(131072, 16)", lambda: jax.block_until_ready(topk(x)))
srt = jax.jit(lambda v: jnp.sort(v))
timeit("jnp.sort(131072)", lambda: jax.block_until_ready(srt(x)))
mx = jax.jit(lambda v: jnp.max(v))
timeit("jnp.max(131072)", lambda: jax.block_until_ready(mx(x)))
