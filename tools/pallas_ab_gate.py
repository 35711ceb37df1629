"""Pallas A/B gate (tier-1): the stateplane's first Pallas kernel —
the exchange-rank counting sort — against the XLA one-hot-cumsum it
replaces, bit-for-bit at three levels:

- KERNEL: random (num_dests, length, width) shapes with in-range,
  out-of-range (staging pads) and negative destinations — ranks and
  flattened (dest, rank) scatter positions must be EXACTLY equal.
- PROGRAM: the cached ``exchange-rank`` programs (xla vs pallas keys)
  agree, and occupy DISTINCT cache entries (cache-key honesty — a
  backend swap is a new key, never a silent retrace).
- ENGINE: a device-mode mesh session run under
  ``backend_scope("exchange-rank", "pallas")`` emits bit-identical
  fires IN ORDER vs the default backend — same ranks means same bucket
  positions means same downstream fold order.

On the ``cpu`` backend the kernel runs in Pallas interpret mode — that
IS the CI configuration; on any other backend the same code path
compiles (Mosaic) or raises. Lengths straddle ``RANK_BLOCK`` so both
the single-block and the padded multi-block grid are compared.

    JAX_PLATFORMS=cpu python tools/pallas_ab_gate.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

SHAPES = int(os.environ.get("PALLAS_AB_SHAPES", 40))
STEPS = 6
BATCH = 4000
NUM_KEYS = 15_000


def _kernel_leg(errs):
    from flink_tpu.stateplane.rank import (
        RANK_BLOCK,
        exchange_rank_flat,
        pallas_rank,
        xla_rank,
    )

    rng = np.random.default_rng(101)
    for i in range(SHAPES):
        D = int(rng.integers(1, 17))
        n = int(rng.integers(1, 600))
        if i % 8 == 7:  # multi-block grid, last block padded
            n += RANK_BLOCK
        W = int(rng.integers(1, 64))
        d = rng.integers(-2, D + 3, size=n).astype(np.int32)
        pr = np.asarray(pallas_rank(d, D))
        xr = np.asarray(xla_rank(d, D))
        if not (pr == xr).all():
            errs.append(f"kernel: rank diverges at shape {i} "
                        f"(D={D} n={n})")
            return
        pf = np.asarray(exchange_rank_flat(d, D, W, "pallas"))
        xf = np.asarray(exchange_rank_flat(d, D, W, "xla"))
        if not (pf == xf).all():
            errs.append(f"kernel: flat scatter position diverges at "
                        f"shape {i} (D={D} n={n} W={W})")
            return


def _program_leg(errs):
    from flink_tpu.stateplane.rank import build_exchange_rank

    d = np.asarray([5, 0, 2, 0, 9, 5, 5, -1, 0, 3], dtype=np.int32)
    px = build_exchange_rank(8, "xla")
    pp = build_exchange_rank(8, "pallas")
    if px is pp:
        errs.append("program: xla and pallas share one cache entry — "
                    "the backend is missing from the cache key")
    if not (np.asarray(px(d, 4)) == np.asarray(pp(d, 4))).all():
        errs.append("program: cached exchange-rank programs diverge")


def _engine_leg(mesh, errs):
    """Bit-identical fires (emission order included) for a device-mode
    session run across backends — the downstream-fold-order half."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.stateplane import backend_scope
    from flink_tpu.windowing.aggregates import SumAggregate

    def run():
        eng = MeshSessionEngine(gap=100, agg=SumAggregate("v"),
                                mesh=mesh,
                                capacity_per_shard=1 << 14,
                                shuffle_mode="device",
                                max_device_slots=1024)
        rng = np.random.default_rng(71)
        rows = []
        for s in range(STEPS):
            keys = rng.integers(0, NUM_KEYS, BATCH).astype(np.int64)
            vals = rng.integers(0, 1000, BATCH).astype(np.float32)
            ts = np.sort(rng.integers(s * 80, s * 80 + 60,
                                      BATCH)).astype(np.int64)
            eng.process_batch(RecordBatch({
                KEY_ID_FIELD: keys, "v": vals, TIMESTAMP_FIELD: ts}))
            for b in eng.on_watermark((s - 1) * 80):
                for r, t in zip(b.to_rows(),
                                np.asarray(b.timestamps).tolist()):
                    rows.append((t, tuple(sorted(r.items()))))
        return rows

    base = run()
    with backend_scope("exchange-rank", "pallas"):
        swapped = run()
    if not base:
        errs.append("engine: zero fires — vacuous A/B")
    if base != swapped:
        errs.append(f"engine: fires diverge across backends "
                    f"({len(base)} vs {len(swapped)} rows, or "
                    "order/values differ)")
    return len(base)


def main():
    import warnings

    warnings.filterwarnings("ignore")
    import jax

    from flink_tpu.parallel.mesh import make_mesh

    t0 = time.perf_counter()
    errs = []
    _kernel_leg(errs)
    _program_leg(errs)
    fires = _engine_leg(make_mesh(min(len(jax.devices()), 8)), errs)
    print(json.dumps({
        "pallas_ab_gate": "ok" if not errs else "FAIL",
        "shapes": SHAPES,
        "engine_fires": fires,
        "seconds": round(time.perf_counter() - t0, 2),
    }))
    for e in errs:
        print(f"FAIL: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
