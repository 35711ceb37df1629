"""Recompile-sentinel smoke: ZERO steady-state XLA recompiles for both
mesh engines at the bench shape (tier-1 gate).

Methodology: one warmup rep per engine compiles every step program
(scatter / merge / fire / reset / gather / put at their sticky-bucket
padded shapes), then each measured rep builds a FRESH engine over the
same mesh and replays the same stream shape (timestamps shifted so
event time advances and sessions/windows genuinely fire). Fresh engines
make the assertion strict: a step cache keyed on anything unstable
(engine identity, per-instance lambda, device object vs id) recompiles
on rep 2 and fails here. The sentinel also enforces a device->host
transfer budget — an unbatched per-leaf host read multiplies the
transfer count and trips it.

Spill is ON (max_device_slots below the live set) so the eviction /
page-reload / hybrid-fire kernels are part of the steady state too,
exactly like the mesh bench rows.

    JAX_PLATFORMS=cpu python tools/recompile_smoke.py
    RECOMPILE_SMOKE_RECORDS=... RECOMPILE_SMOKE_REPS=... to scale.

Exits non-zero on any steady-state compile, on a blown transfer
budget, or on zero fired windows (a vacuous run must not pass).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402

GAP_MS = 16_000
WINDOW_MS = 5_000
NUM_KEYS = 50_000
BATCH = 8_192
#: records per ms of event time — slow event time is what keeps the
#: concurrent live set (keys per open window / sessions inside the gap)
#: ABOVE the per-shard device budget, so the evict/reload kernels run
RECORDS_PER_MS = 4


def _batches(total, rep, rng_seed=7):
    """The rep's record stream: identical SHAPE every rep (same batch
    sizes, same key multiset), event time shifted per rep so watermarks
    advance and windows/sessions close instead of being dropped late."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    span = total // RECORDS_PER_MS  # ms of event time per rep
    # shift each rep by WHOLE windows: a non-aligned offset would slide
    # the tumbling-window phase, change how many windows close per
    # watermark, and walk the sticky fire buckets through new shapes
    stride = span + 10 * GAP_MS
    stride += -stride % WINDOW_MS
    offset = rep * stride
    rng = np.random.default_rng(rng_seed)  # same seed: same shapes
    produced = 0
    while produced < total:
        b = min(BATCH, total - produced)
        keys = rng.integers(0, NUM_KEYS, b).astype(np.int64)
        ts = offset + (produced
                       + np.arange(b, dtype=np.int64)) // RECORDS_PER_MS
        yield RecordBatch({
            KEY_ID_FIELD: keys,
            "v": np.ones(b, dtype=np.float32),
            TIMESTAMP_FIELD: ts,
        }), int(ts[-1])
        produced += b


def _drive(engine, total, rep):
    fired = 0
    last = 0
    for rb, last in _batches(total, rep):
        engine.process_batch(rb)
        fired += sum(len(b) for b in engine.on_watermark(last - GAP_MS))
    fired += sum(len(b) for b in engine.on_watermark(last + 100 * GAP_MS))
    return fired


def _make_sessions(mesh, budget):
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    return MeshSessionEngine(GAP_MS, SumAggregate("v"), mesh,
                             capacity_per_shard=budget,
                             max_device_slots=budget)


def _make_windows(mesh, budget):
    from flink_tpu.parallel.sharded_windower import MeshWindowEngine
    from flink_tpu.windowing.aggregates import SumAggregate
    from flink_tpu.windowing.assigners import TumblingEventTimeWindows

    return MeshWindowEngine(TumblingEventTimeWindows.of(WINDOW_MS),
                            SumAggregate("v"), mesh,
                            capacity_per_shard=budget,
                            max_device_slots=budget)


def check_engine(name, make, mesh, total, reps, budget):
    from flink_tpu.observe import RecompileSentinel

    # warmup: compiles the whole step-program family at the padded
    # shapes the measured reps will reuse
    warm_fired = _drive(make(mesh, budget), total, rep=0)
    ok = True
    for rep in range(1, reps + 1):
        # FRESH engine per rep: the step caches must hit across engine
        # rebuilds (restarts, rescales), not just across batches.
        # Transfer budget: each watermark advance harvests one batched
        # result read, evictions/reloads add a bounded few more.
        engine = make(mesh, budget)
        with RecompileSentinel(
                max_compiles=0,
                max_transfers=max((total // BATCH) * 8, 64),
                label=f"{name} rep {rep}") as s:
            fired = _drive(engine, total, rep)
        evicted = int(engine.spill_counters().get("rows_evicted", 0))
        print(f"  {name} rep {rep}: fired={fired} compiles={s.compiles} "
              f"transfers={s.transfers} rows_evicted={evicted}")
        if fired == 0:
            print(f"FAIL: {name}: zero windows fired — vacuous run")
            ok = False
        if evicted == 0:
            # the gate's claim is that evict/reload/hybrid-fire kernels
            # are part of the guarded steady state — a shape change that
            # stops spill from engaging would silently shrink coverage
            print(f"FAIL: {name}: spill never engaged — the "
                  "evict/reload kernels were not covered")
            ok = False
    if warm_fired == 0:
        print(f"FAIL: {name}: zero windows fired in warmup")
        ok = False
    return ok


def _drive_interleaved(engines, total, rep, serve_keys):
    """Multiplex the same stream shape across N 'jobs' (one engine
    each), the session cluster's interleave collapsed to its essence,
    with a batched queryable-state lookup per engine per batch — the
    serving path is part of the guarded steady state too."""
    import numpy as np

    fired = 0
    last = 0
    for rb, last in _batches(total, rep):
        for eng in engines:
            eng.process_batch(rb)
            fired += sum(len(b) for b in eng.on_watermark(last - GAP_MS))
            eng.query_batch(np.asarray(serve_keys, dtype=np.int64))
    for eng in engines:
        fired += sum(len(b)
                     for b in eng.on_watermark(last + 100 * GAP_MS))
    return fired


#: batch sizes for the device-shuffle tier walk: per-shard chunk tiers
#: pad_bucket_size(ceil(b / 8)) cover {256, 512, 1024} twice over, so a
#: fused exchange program keyed on anything finer than the tier (raw
#: batch length, bucket width off the tier lattice) compiles mid-rep
#: and fails the sentinel
TIER_WALK_WARM = (8192, 4096, 2048, 6000, 3000, 1900)
TIER_WALK_RUN = (8000, 3500, 2200, 7000, 2600, 1800)


def _drive_sized(engine, sizes, offset, rng_seed=11):
    """Drive ``engine`` with one batch per entry of ``sizes`` (event
    time advancing so sessions genuinely fire), then flush."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    rng = np.random.default_rng(rng_seed)
    fired = 0
    t = offset
    for b in sizes:
        keys = rng.integers(0, NUM_KEYS, b).astype(np.int64)
        ts = t + np.arange(b, dtype=np.int64) // RECORDS_PER_MS
        engine.process_batch(RecordBatch({
            KEY_ID_FIELD: keys,
            "v": np.ones(b, dtype=np.float32),
            TIMESTAMP_FIELD: ts,
        }))
        t = int(ts[-1]) + 1
        fired += sum(len(x)
                     for x in engine.on_watermark(t - GAP_MS))
    fired += sum(len(x)
                 for x in engine.on_watermark(t + 100 * GAP_MS))
    return fired


def check_device_shuffle_tiers(mesh, budget):
    """Device-shuffle phase: after one warmup engine walks every
    pad_bucket_size tier (both size lists), a FRESH engine replaying
    SHIFTED batch sizes — different lengths, same tier lattice — must
    compile NOTHING. This is exactly the recompile surface the fused
    exchange adds: its program shapes are (chunk tier, bucket-width
    tier), so a shape leak past the tiers shows up here as a
    steady-state compile."""
    from flink_tpu.observe import RecompileSentinel

    warm_eng = _make_sessions(mesh, budget)
    assert warm_eng.shuffle_mode == "device"
    warm_fired = _drive_sized(warm_eng, TIER_WALK_WARM, offset=0)
    warm_fired += _drive_sized(warm_eng, TIER_WALK_RUN,
                               offset=1 << 22)
    ok = True
    engine = _make_sessions(mesh, budget)
    with RecompileSentinel(
            max_compiles=0,
            max_transfers=max(len(TIER_WALK_RUN) * 8, 64),
            label="device-shuffle tier walk") as s:
        fired = _drive_sized(engine, TIER_WALK_RUN, offset=1 << 23)
    evicted = int(engine.spill_counters().get("rows_evicted", 0))
    print(f"  device-shuffle tiers: fired={fired} "
          f"compiles={s.compiles} transfers={s.transfers} "
          f"rows_evicted={evicted}")
    if fired == 0 or warm_fired == 0:
        print("FAIL: device-shuffle tiers: zero fires — vacuous run")
        ok = False
    return ok


def check_pallas_backend_phase(mesh, budget):
    """Stateplane backend-swap phase: the same tier walk under
    ``backend_scope("exchange-rank", "pallas")``. The pallas builders
    tag their PROGRAM_CACHE keys with the backend, so the swap pays its
    own warmup ONCE — after a warm engine walks the tier lattice in
    pallas scope, a FRESH engine replaying SHIFTED sizes (still in
    scope) must compile NOTHING. A backend hook that leaked into the
    key unstably (per-engine closure, config object identity) or that
    failed to key at all (silent retrace on every scope flip) shows up
    here as a steady-state compile."""
    from flink_tpu.observe import RecompileSentinel
    from flink_tpu.stateplane import backend_scope

    with backend_scope("exchange-rank", "pallas"):
        warm_eng = _make_sessions(mesh, budget)
        warm_fired = _drive_sized(warm_eng, TIER_WALK_WARM, offset=0)
        warm_fired += _drive_sized(warm_eng, TIER_WALK_RUN,
                                   offset=1 << 22)
        ok = True
        engine = _make_sessions(mesh, budget)
        with RecompileSentinel(
                max_compiles=0,
                max_transfers=max(len(TIER_WALK_RUN) * 8, 64),
                label="pallas-backend tier walk") as s:
            fired = _drive_sized(engine, TIER_WALK_RUN, offset=1 << 23)
    print(f"  pallas-backend tiers: fired={fired} "
          f"compiles={s.compiles} transfers={s.transfers}")
    if fired == 0 or warm_fired == 0:
        print("FAIL: pallas-backend tiers: zero fires — vacuous run")
        ok = False
    return ok


def check_two_level_exchange_tiers(mesh, budget):
    """Two-level (pod) exchange phase: a virtual (2, P/2) topology arms
    parallel/exchange2.py's stage-1/stage-2 program pair. After one
    warmup engine walks the tier lattice (both size lists), a FRESH
    engine on SHIFTED sizes must compile NOTHING — the pod programs'
    shapes are (chunk, W1, W2) tiers, and a leak past any level shows
    up here as a steady-state compile. Covers fresh-engine rebuilds:
    the PROGRAM_CACHE family must be hit, not rebuilt."""
    from flink_tpu.observe import RecompileSentinel
    from flink_tpu.parallel.mesh import HostTopology
    from flink_tpu.parallel.sharded_sessions import MeshSessionEngine
    from flink_tpu.windowing.aggregates import SumAggregate

    P = int(mesh.devices.size)
    if P % 2:
        print("  two-level tiers: skipped (odd mesh)")
        return True
    topo = HostTopology(2, P // 2)

    def make():
        return MeshSessionEngine(GAP_MS, SumAggregate("v"), mesh,
                                 capacity_per_shard=budget,
                                 max_device_slots=budget,
                                 host_topology=topo)

    warm_eng = make()
    assert warm_eng._two_level_active()
    warm_fired = _drive_sized(warm_eng, TIER_WALK_WARM, offset=0)
    warm_fired += _drive_sized(warm_eng, TIER_WALK_RUN,
                               offset=1 << 22)
    ok = True
    engine = make()
    with RecompileSentinel(
            max_compiles=0,
            max_transfers=max(len(TIER_WALK_RUN) * 8, 64),
            label="two-level exchange tier walk") as s:
        fired = _drive_sized(engine, TIER_WALK_RUN, offset=1 << 23)
    traffic = engine.exchange2_traffic()
    print(f"  two-level tiers: fired={fired} "
          f"compiles={s.compiles} transfers={s.transfers} "
          f"cross_host_rows={traffic['rows_cross_host']}")
    if fired == 0 or warm_fired == 0:
        print("FAIL: two-level tiers: zero fires — vacuous run")
        ok = False
    if traffic["rows_cross_host"] == 0:
        print("FAIL: two-level tiers: no cross-host rows — the DCN "
              "stage never carried anything")
        ok = False
    return ok


#: join-phase batch-size walks: same tier lattice, shifted lengths —
#: a probe/ingest/eviction program keyed on anything finer than the
#: (chunk, probe-bucket, band, mirror) tiers compiles mid-rep here
JOIN_WALK_WARM = (4096, 2048, 1024, 3000, 1500, 900)
JOIN_WALK_RUN = (4000, 2200, 1100, 2800, 1300, 1000)


def _drive_join_sized(engine, sizes, offset, rng_seed=17):
    """Two-sided interval-join stream: one left + one right batch per
    entry of ``sizes``, event time advancing with a lagging watermark
    so the band stays populated AND the spill tier genuinely engages
    (keys >> budget)."""
    from flink_tpu.core.records import (
        KEY_ID_FIELD,
        TIMESTAMP_FIELD,
        RecordBatch,
    )

    rng = np.random.default_rng(rng_seed)
    matches = 0
    t = offset
    for b in sizes:
        for side, name in ((0, "v"), (1, "w")):
            keys = rng.integers(0, NUM_KEYS, b).astype(np.int64)
            ts = t + np.arange(b, dtype=np.int64) // RECORDS_PER_MS
            out = engine.process_batch(RecordBatch({
                KEY_ID_FIELD: keys,
                name: np.ones(b, dtype=np.float32),
                TIMESTAMP_FIELD: ts,
            }), side)
            matches += sum(len(x) for x in out)
        t = int(ts[-1]) + 1
        engine.on_watermark(t - 3000)
    return matches


def _make_join(mesh, budget):
    from flink_tpu.joins import MeshIntervalJoinEngine

    # band as deep as the pruning horizon: probes reach well past the
    # resident (newest) rows into the paged tier, so cold service is
    # part of the guarded steady state (the vacuity check below)
    return MeshIntervalJoinEngine(
        -2500, 2500, mesh=mesh, capacity_per_shard=max(budget // 4,
                                                       256),
        max_device_slots=max(budget // 4, 256))


def check_join_phase(mesh, budget):
    """Join phase: after one warmup engine walks every tier of the
    banded-probe / ingest-exchange / eviction-gather program family
    (both batch-size lists), a FRESH interval-join engine replaying
    SHIFTED batch sizes — different lengths, same tier lattice — must
    compile NOTHING. Spill is armed and ASSERTED (rows must evict and
    cold candidates must serve from pages), so the eviction and
    cold-probe paths are part of the guarded steady state."""
    from flink_tpu.observe import RecompileSentinel

    warm = _make_join(mesh, budget)
    warm_matches = _drive_join_sized(warm, JOIN_WALK_WARM, offset=0)
    warm_matches += _drive_join_sized(warm, JOIN_WALK_RUN,
                                      offset=1 << 22)
    ok = True
    engine = _make_join(mesh, budget)
    with RecompileSentinel(
            max_compiles=0,
            max_transfers=max(len(JOIN_WALK_RUN) * 16, 64),
            label="join tier walk") as s:
        matches = _drive_join_sized(engine, JOIN_WALK_RUN,
                                    offset=1 << 23)
    sc = engine.spill_counters()
    print(f"  join tiers: matches={matches} compiles={s.compiles} "
          f"transfers={s.transfers} "
          f"rows_evicted={sc['rows_evicted']} "
          f"cold_served={sc['cold_rows_served']}")
    if matches == 0 or warm_matches == 0:
        print("FAIL: join tiers: zero matches — vacuous run")
        ok = False
    if sc["rows_evicted"] == 0 or sc["cold_rows_served"] == 0:
        print("FAIL: join tiers: spill never engaged — the eviction/"
              "cold-probe kernels were not covered")
        ok = False
    return ok


#: cep-phase batch-size walks: shifted lengths, same padded-lane tier
#: lattice — an advance/harvest/prune program keyed on raw batch
#: length (instead of the sticky padded tiers) compiles mid-walk here
CEP_WALK_WARM = (512, 256, 128, 384, 192, 96)
CEP_WALK_RUN = (448, 288, 144, 336, 224, 112)


def _drive_cep_sized(engine, sizes, offset, n_keys, rng):
    """One keyed batch + one trailing-watermark fire per entry of
    ``sizes`` — every fire drains that step's pending set, so the
    advance program runs at each shifted length."""
    from flink_tpu.core.records import RecordBatch

    matches = 0
    t = offset
    for n in sizes:
        keys = rng.integers(0, n_keys, n).astype(np.int64)
        vals = rng.integers(0, 9, n).astype(np.int64)
        ts = t + np.sort(
            rng.integers(0, 30, size=n)).astype(np.int64)
        t += 25
        engine.process_batch(RecordBatch.from_pydict(
            {"k": keys, "v": vals, "__key_id__": keys},
            timestamps=ts))
        out = engine.on_watermark(t - 5)
        matches += sum(len(b) for b in out)
    return matches, t


def check_cep_phase(mesh):
    """CEP phase: after warmup engines walk the padded-lane tier
    lattice for BOTH device program families — the within-window
    sequence (advance + within-prune) and the always-alive churn
    pattern (advance + evict/restore, spill armed, keys >> budget) —
    FRESH engines replaying SHIFTED batch sizes must compile NOTHING.
    Matches and spill churn are ASSERTED so neither leg can go
    vacuous."""
    import tempfile

    from flink_tpu.cep.mesh_engine import MeshCepEngine
    from flink_tpu.cep.pattern import (
        AfterMatchSkipStrategy,
        Pattern,
    )
    from flink_tpu.observe import RecompileSentinel

    skip = AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT
    within_pat = (Pattern.begin("a", skip=skip)
                  .where(lambda b: np.asarray(b["v"]) % 3 == 0)
                  .next("b")
                  .where(lambda b: np.asarray(b["v"]) % 3 == 1)
                  .within(50))
    churn_pat = (Pattern.begin("a", skip=skip)
                 .next("b")
                 .where(lambda b: np.asarray(b["v"]) == 7))

    def mk(pat, spill_dir=None):
        return MeshCepEngine(pat, key_field="k", mesh=mesh,
                             capacity_per_shard=256,
                             spill_dir=spill_dir)

    # warmup: both walks, both program families
    rng = np.random.default_rng(19)
    w_within = mk(within_pat)
    warm_m, t = _drive_cep_sized(w_within, CEP_WALK_WARM, 0, 64, rng)
    warm_m += _drive_cep_sized(w_within, CEP_WALK_RUN, t, 64, rng)[0]
    with tempfile.TemporaryDirectory() as td:
        w_churn = mk(churn_pat, spill_dir=td)
        _, t = _drive_cep_sized(w_churn, CEP_WALK_WARM, 0, 20_000,
                                rng)
        _drive_cep_sized(w_churn, CEP_WALK_RUN, t, 20_000, rng)

        ok = True
        within = mk(within_pat)
        churn = mk(churn_pat, spill_dir=td)
        with RecompileSentinel(
                max_compiles=0,
                max_transfers=len(CEP_WALK_RUN) * 6 * 64,
                label="cep tier walk") as s:
            m, t = _drive_cep_sized(within, CEP_WALK_RUN, 0, 64, rng)
            # two passes on the churn engine: the live key set must
            # outgrow the 8x256 slot budget so evict/restore programs
            # are part of the guarded steady state
            _, t2 = _drive_cep_sized(churn, CEP_WALK_RUN, 0, 20_000,
                                     rng)
            cm = _drive_cep_sized(churn, CEP_WALK_RUN, t2, 20_000,
                                  rng)[0]
        sc = churn.spill_counters()
    print(f"  cep tiers: matches={m} churn_matches={cm} "
          f"compiles={s.compiles} transfers={s.transfers} "
          f"rows_evicted={sc['rows_evicted']}")
    if m == 0 or warm_m == 0:
        print("FAIL: cep tiers: zero matches — vacuous run")
        ok = False
    if cm == 0:
        print("FAIL: cep tiers: churn leg emitted nothing — "
              "vacuous run")
        ok = False
    if sc["rows_evicted"] == 0:
        print("FAIL: cep tiers: spill never engaged — the "
              "evict/restore programs were not covered")
        ok = False
    return ok


def check_second_job_on_warm_cluster(mesh, total, budget):
    """The tenancy contract: after job A warms the cluster (ingest,
    fire, evict AND serving programs), a SECOND job's fresh engines on
    the same mesh — interleaved with a third, plus concurrent batched
    lookups — compile NOTHING."""
    from flink_tpu.observe import RecompileSentinel
    from flink_tpu.tenancy.program_cache import PROGRAM_CACHE

    serve_keys = list(range(0, NUM_KEYS, NUM_KEYS // 16))
    with PROGRAM_CACHE.job_scope("smoke-warm"):
        warm_fired = _drive_interleaved(
            [_make_sessions(mesh, budget)], total, rep=0,
            serve_keys=serve_keys)
    PROGRAM_CACHE.reset_stats()
    ok = True
    with PROGRAM_CACHE.job_scope("smoke-job2"):
        with RecompileSentinel(
                max_compiles=0,
                max_transfers=max((total // BATCH) * 24, 64),
                label="2 jobs on warm cluster") as s:
            fired = _drive_interleaved(
                [_make_sessions(mesh, budget),
                 _make_sessions(mesh, budget)],
                total, rep=1, serve_keys=serve_keys)
    misses = PROGRAM_CACHE.stats_for("smoke-job2")["misses"]
    print(f"  multi-tenant: fired={fired} compiles={s.compiles} "
          f"transfers={s.transfers} cache_misses={misses}")
    if fired == 0 or warm_fired == 0:
        print("FAIL: multi-tenant: zero windows fired — vacuous run")
        ok = False
    if misses:
        print(f"FAIL: multi-tenant: second job paid {misses} program-"
              "cache misses on a warm cluster")
        ok = False
    return ok


def main():
    import warnings

    warnings.filterwarnings("ignore")
    import jax

    from flink_tpu.observe.recompile_sentinel import compile_count
    from flink_tpu.parallel.mesh import make_mesh

    total = int(os.environ.get("RECOMPILE_SMOKE_RECORDS", 1 << 16))
    reps = max(int(os.environ.get("RECOMPILE_SMOKE_REPS", 2)), 1)
    P = min(len(jax.devices()), 8)
    mesh = make_mesh(P)
    # budgets well BELOW the concurrent live set per shard (thousands
    # of keys per open window x ~4 live slices, sessions alive inside
    # the 16 s gap) so the evict/reload/hybrid-fire kernels genuinely
    # run — check_engine FAILS if rows_evicted stays 0 (vacuous-coverage
    # guard). The window engine's floor is one slice's per-shard key set
    # (~2.1k here): a batch's touched namespaces are eviction-protected,
    # so a budget under that is an irreducible SlotTableFullError.
    budgets = {"mesh-sessions": 2048, "mesh-windows": 4096}
    ok = True
    for name, make in (("mesh-sessions", _make_sessions),
                       ("mesh-windows", _make_windows)):
        try:
            ok = check_engine(name, make, mesh, total, reps,
                              budgets[name]) and ok
        except Exception as e:  # SteadyStateViolation included
            print(f"FAIL: {name}: {e}")
            ok = False
    try:
        ok = check_device_shuffle_tiers(
            mesh, budgets["mesh-sessions"]) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: device-shuffle tiers: {e}")
        ok = False
    try:
        ok = check_pallas_backend_phase(
            mesh, budgets["mesh-sessions"]) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: pallas-backend tiers: {e}")
        ok = False
    try:
        ok = check_two_level_exchange_tiers(
            mesh, budgets["mesh-sessions"]) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: two-level tiers: {e}")
        ok = False
    try:
        ok = check_join_phase(mesh, budgets["mesh-sessions"]) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: join tiers: {e}")
        ok = False
    try:
        ok = check_cep_phase(mesh) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: cep tiers: {e}")
        ok = False
    try:
        ok = check_second_job_on_warm_cluster(
            mesh, total, budgets["mesh-sessions"]) and ok
    except Exception as e:  # SteadyStateViolation included
        print(f"FAIL: multi-tenant: {e}")
        ok = False
    print(f"recompile smoke: shards={P} records={total} reps={reps} "
          f"process_compiles={compile_count()} "
          f"=> {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
