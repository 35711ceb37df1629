"""The main path's device programs, compiled for a described TPU v5e.

No chip is attached here: the TPU compiler is installed, and it compiles
for a topology that is described (``/opt/skills/guides/
on-chip-measurement`` section 2.3). What it refuses here it would refuse
on the chip — a program that does not fit device memory, a
``shard_map`` that cannot be partitioned — and
refusing it here costs no chip time. A compile that passes is not a chip
run: nothing below says anything about results or speed.

Shapes are two jobs' (``Q5`` and ``KEYED_STATE`` below): NEXmark Q5
over 100,000 auctions and a keyed sum over 10,000,000 keys, with the
pad tiers read off a CPU rehearsal of both.

The topology is described inside a module-scoped fixture, in the test's
own process, and all such tests live in this one file: only one process
may hold the TPU library, and only the xdist worker that is handed this
file loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from flink_tpu.parallel.mesh import KEY_AXIS
from flink_tpu.windowing.aggregates import CountAggregate, SumAggregate

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2, with JAX's persistent compilation cache
    off around the module: a compile for a described device is written
    to the cache but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    import numpy as np

    return Mesh(np.array(topo.devices), (KEY_AXIS,))


@pytest.fixture
def off_cpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` still sees the CPU here;
    the test steers it onto the branch the chip takes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_hbm(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one chip"


# ------------------------------------- (a) the slot table's programs

#: state-table capacity and micro-batch of each job
_Q5 = dict(capacity=1 << 22, batch=1 << 17)
_KS = dict(capacity=1 << 24, batch=1 << 18)

#: aggregate, capacity, micro-batch, and the steady-state pad tier of the
#: fire's slot matrix and of a slice reset (Q5's fused top-k fire alone
#: takes the TPU compiler ~35 s, so one tier each)
ONE_CHIP = {
    "q5": (CountAggregate(), _Q5["capacity"], _Q5["batch"],
           (131_072, 5), 65_536),
    # Q5 over keys that live in one or two slices (NEXmark's own
    # auctions): ~149,000 rows at the 262,144 tier, the matrix cut to
    # its fullest row's two columns (``slice_matrix``)
    "q5_short_lived": (CountAggregate(), _Q5["capacity"], _Q5["batch"],
                       (262_144, 2), 65_536),
    "keyed_state": (SumAggregate("value"), _KS["capacity"], _KS["batch"],
                    (1 << 22, 1), 1 << 22),
}


def _accs(agg, capacity, sharding, shards=None):
    shape = (capacity,) if shards is None else (shards, capacity)
    return tuple(_spec(shape, l.dtype, sharding) for l in agg.leaves)


@pytest.mark.parametrize("job", sorted(ONE_CHIP))
def test_scatter_combine_compiles_for_v5e(one_chip, job):
    from flink_tpu.stateplane import flat_scatter_combine

    agg, capacity, batch, _, _ = ONE_CHIP[job]
    values = tuple(_spec((batch,), l.dtype, one_chip)
                   for l in agg.leaves if l.const is None)
    compiled = flat_scatter_combine(agg.leaves).lower(
        _accs(agg, capacity, one_chip),
        _spec((batch,), jnp.int32, one_chip), values).compile()
    _fits_hbm(compiled)


@pytest.mark.parametrize("job", sorted(ONE_CHIP))
def test_fire_gather_compiles_for_v5e(one_chip, job):
    from flink_tpu.stateplane import (
        flat_segment_fire,
        flat_segment_fire_projected,
    )
    from flink_tpu.windowing.fire_projectors import TopKFireProjector

    agg, capacity, _, fire_shape, _ = ONE_CHIP[job]
    accs = _accs(agg, capacity, one_chip)
    matrix = _spec(fire_shape, jnp.int32, one_chip)
    if job.startswith("q5"):  # the fused top-k of build_q5(device_top_k=16)
        lowered = flat_segment_fire_projected(
            agg, TopKFireProjector("count", k=16)).lower(
                accs, matrix, fire_shape[0])
    else:
        lowered = flat_segment_fire(agg).lower(accs, matrix)
    _fits_hbm(lowered.compile())


@pytest.mark.parametrize("job", sorted(ONE_CHIP))
def test_slice_reset_compiles_for_v5e(one_chip, job):
    from flink_tpu.stateplane import flat_reset

    agg, capacity, _, _, reset_size = ONE_CHIP[job]
    _fits_hbm(flat_reset(agg.leaves).lower(
        _accs(agg, capacity, one_chip),
        _spec((reset_size,), jnp.int32, one_chip)).compile())


# ------------------------------- (b) the four-chip exchange + scatter


def test_exchange_scatter_compiles_for_four_v5e(mesh4, off_cpu):
    """The four-chip Q5's ingest program: segment rank ->
    ``all_to_all`` over the mesh axis -> scatter into the ``[4, cap]``
    state, one shard per chip. The uncached builder: PROGRAM_CACHE keys
    on device ids, which the described chips share with CPU devices."""
    from flink_tpu.parallel.shuffle import (
        _build_exchange_scatter,
        exchange_chunk_size,
    )

    agg = CountAggregate()
    shards = mesh4.devices.size
    sharded = NamedSharding(mesh4, P(KEY_AXIS))
    staged = shards * exchange_chunk_size(_Q5["batch"], shards)
    program = _build_exchange_scatter(mesh4, agg, False)
    compiled = program.lower(
        _accs(agg, _Q5["capacity"], sharded, shards),
        _spec((staged,), jnp.int32, sharded),
        _spec((staged,), jnp.int32, sharded), (), 16_384).compile()
    assert "all-to-all" in compiled.as_text()
    _fits_hbm(compiled)
