"""Multi-tenant session cluster: N jobs multiplexed over ONE device mesh.

The production gap this closes (ROADMAP item 3): the cluster so far ran
one bench job, while the reference's dispatcher / slot-sharing /
fine-grained-resource layers exist precisely to run MANY jobs on shared
hardware. Four pillars:

- **Shared compiled-program cache** (:mod:`program_cache`): step/fire/
  evict/harvest XLA programs keyed on (kind, layout, device ids) —
  job K+1 reuses job K's executables, zero per-job steady-state
  compiles (sentinel-gated in ``tools/serving_smoke.py``).
- **Per-job state-plane quotas** (:mod:`quotas`): each job's engines get
  a bounded slice of resident [P, cap] rows with per-job spill
  directories; over-quota jobs spill their OWN cold rows — never
  another job's (no cross-job reclaim, by construction and by test).
- **Fair batch interleaving** (:mod:`fairness` + :mod:`session_cluster`):
  deficit-round-robin over per-job ready queues with per-job
  ``busyTimeMsTotal``, so one hot job cannot starve the rest.
- **Read-replica serving plane** (:mod:`serving` + :mod:`replica` +
  :mod:`hot_cache`, r17): engines publish a bounded delta into a
  double-buffered device-resident replica at fire/watermark
  boundaries (snapshot isolation, zero contention with ingest); the
  publish harvest primes a host hot-row cache so hot-key lookups
  never touch the device, and cache misses batch per sealed
  generation on sharded worker queues — one gather program + ONE
  ``jax.device_get`` per miss batch (the flint TRC01 discipline),
  (lookups/s on the chip: not measured, no benchmark cell serves
  lookups yet). The legacy
  control-queue coalescers remain for single-device engines and the
  cold-row (page tier) detour. Since r19 the hit path is NATIVE
  (:mod:`hot_cache_native` over ``native/hotcache.cpp``): a whole key
  batch probes a GIL-free seqlock-stamped table of packed composed
  results in ONE C call, results stay packed until a consumer reads
  them (``lookup_batch_packed``), publishes prime via one packed
  buffer, and sessions PRIME under their moving end instead of
  invalidating — measured 1.14M lookups/s vs the 477k same-box dict
  control.

The autoscaler composes one level up (:mod:`arbiter`): shard budgets
are arbitrated BETWEEN jobs (weighted by backlog + quota pressure),
driving each job's existing live ``reshard()``.

This ``__init__`` stays import-light (``program_cache`` is imported by
the lowest engine layers); the cluster-facing classes load lazily.
"""

from flink_tpu.tenancy.program_cache import (  # noqa: F401
    PROGRAM_CACHE,
    SharedProgramCache,
)

_LAZY = {
    "TenantQuota": "flink_tpu.tenancy.quotas",
    "QuotaLedger": "flink_tpu.tenancy.quotas",
    "DeficitRoundRobin": "flink_tpu.tenancy.fairness",
    "ServingPlane": "flink_tpu.tenancy.serving",
    "LookupCoalescer": "flink_tpu.tenancy.serving",
    "ReplicaPlane": "flink_tpu.tenancy.replica",
    "SessionReplicaAdapter": "flink_tpu.tenancy.replica",
    "WindowReplicaAdapter": "flink_tpu.tenancy.replica",
    "JoinSideReplicaAdapter": "flink_tpu.tenancy.replica",
    "HotRowCache": "flink_tpu.tenancy.hot_cache",
    "PrimeDelta": "flink_tpu.tenancy.hot_cache",
    "make_hot_row_cache": "flink_tpu.tenancy.hot_cache",
    "NativeHotRowCache": "flink_tpu.tenancy.hot_cache_native",
    "PackedLookupResult": "flink_tpu.tenancy.serving",
    "ShardArbiter": "flink_tpu.tenancy.arbiter",
    "JobDemand": "flink_tpu.tenancy.arbiter",
    "SessionCluster": "flink_tpu.tenancy.session_cluster",
    "TenantJob": "flink_tpu.tenancy.session_cluster",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
