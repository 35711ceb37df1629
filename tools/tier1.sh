#!/usr/bin/env bash
# Tier-1 gate: flint, the native build report, the driver's pytest
# command (the one in /root/TESTS_LAST_RUN.json: 6 xdist workers,
# --dist loadfile) and the functional smokes. No bench runs here: a
# number from a CPU run says nothing about the chip, and speed is
# measured by benchmark/run.py on the TPU (PERF.md). The CPU floors the
# smokes still carry are a debt (ROADMAP.md queue 3 item 1).
#
#   bash tools/tier1.sh                      # tests + smokes
#   SKIP_BENCH_SMOKE=1 bash tools/tier1.sh   # flint + build + tests only

set -u
cd "$(dirname "$0")/.."

# flint: TPU-tracing static analysis over the whole package (host syncs
# on the hot path, tracer-unsafe control flow, unstable jit identities,
# fault-point/metric registry drift). Pure AST — runs in ~2 s, gates
# first so a hot-path regression fails before the long test run.
# flint_report.json is the machine-readable artifact.
python -m tools.flint flink_tpu/ --fail-on-violation \
  --json flint_report.json || exit 1

# Native libraries build UP FRONT and LOUDLY (slotmap, sessions, codec,
# datagen, hotcache): a missing compiler used to surface as a silent
# pure-Python fallback mid-suite — now it is one explicit line.
native_status="$(python -c 'from flink_tpu.native import build_report; print(build_report())')"
echo "$native_status"
# when the HOTCACHE library built, the serving smoke FAILS if the plane
# silently fell back to the Python cache (its hit-rate and throughput
# gates would go vacuous)
if python -c 'import sys; from flink_tpu.native import hotcache_available; sys.exit(0 if hotcache_available() else 1)'; then
  export SERVING_REQUIRE_NATIVE_HOTCACHE=1
fi

set -o pipefail
log="${T1_LOG:-/tmp/_t1.$$.log}"   # unique per run: concurrent gates must not clobber
rm -f "$log"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" \
  | tr -cd . | wc -c)"
if [ "$rc" -ne 0 ]; then
  exit "$rc"
fi

if [ "${SKIP_BENCH_SMOKE:-0}" != "1" ]; then
  # Chaos smoke: seeded crash-restore-verify (3 injected engine crashes
  # — incl. the device data plane dying after the fused exchange
  # dispatch — + 1 torn checkpoint write over ~12k events) — FAILS on
  # any output divergence from the fault-free oracle, on a missed
  # injection, or if the torn checkpoint is restored instead of
  # skipped. ~5 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 120 \
    python tools/chaos_smoke.py || exit 1

  # Autoscale smoke: deterministic load ramp through the DS2 policy —
  # the mesh session engine must LIVE-rescale 2 -> 4 -> 2 (key-group
  # migration, no stop-redeploy) and finish bit-identical to the
  # single-device oracle. FAILS if the policy never scales, a rescale
  # takes a non-live path, or any window diverges. ~3 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 120 \
    python tools/autoscale_smoke.py || exit 1

  # Skew smoke: a skewed stream (one key ~40% of records) through the
  # LIVE SkewResponder next to a uniform control — FAILS if no key
  # group moved live, the dominant key never split (zero salted
  # records/fires: vacuous), the moves did not improve measured
  # imbalance, the output diverges from the single-device oracle by
  # one window (bit-identity — integer-valued floats keep the salted
  # fold exact), or skewed throughput drops below BENCH_SKEW_RECOVERY
  # (0.7) of the uniform control — the responder-thrash regression
  # class. ~90 s on CPU.
  BENCH_SKEW_RECOVERY=0.7 JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/skew_smoke.py || exit 1

  # Join smoke: the device-native interval + temporal join engines vs
  # the host-numpy oracle — FAILS on any bit divergence (values OR
  # order), on a steady-state XLA compile after warmup, or on a
  # vacuous run where the spill tier never engages (rows must evict
  # AND cold band candidates must serve from pages). ~2 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 120 \
    python tools/join_smoke.py || exit 1

  # CEP smoke: the device-vectorized mesh NFA engine vs the host
  # CepOperator oracle — FAILS on any bit divergence (values OR
  # emission order) across both after-match skip strategies and a
  # forced-paged-eviction leg, on a steady-state XLA compile from a
  # FRESH engine on the warm program cache, on a vacuous run (zero
  # matches, rows_evicted=0 or rows_reloaded=0), on a replica-plane
  # matched-pattern lookup diverging from the live store, or on the
  # frontend leg: the same lookups through the multi-process shm
  # serving tier (CepMatchServingAdapter) must decode bit-identical
  # with > 0 shm hits (skipped loudly without the native hotcache).
  # ~10 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 120 \
    python tools/cep_smoke.py || exit 1

  # Pallas A/B gate: the stateplane's first Pallas kernel (the
  # exchange-rank counting sort) vs the XLA one-hot-cumsum it
  # replaces — FAILS on any bit divergence at the kernel level
  # (random shapes incl. out-of-range/negative lanes), the cached-
  # program level (xla and pallas keys must also be DISTINCT cache
  # entries), or the engine level (device-mode session fires must be
  # bit-identical IN ORDER across backends). Interpret mode on CPU.
  # ~35 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/pallas_ab_gate.py || exit 1

  # Multi-process smoke: 2 REAL CPU processes (jax.distributed + gloo
  # collectives), each owning half the key-group space, exchanging
  # records over the DCN axis of the process-spanning mesh ON DEVICE
  # (the pod data plane, ROADMAP item 2). FAILS on output divergence
  # from the 1-process run (bit-identity), on any steady-state compile
  # in the measured rep, on a vacuous run (0 rows crossed a process
  # boundary), or on the chaos leg: kill 1 of 2 processes mid-stream —
  # the survivor must restore ONLY the dead host's key-group ranges
  # from its checkpoint units, replay within the per-host bound, and
  # finish bit-identical. Also emits the mesh_sessions_2proc scaling
  # numbers (gateable via MP_SMOKE_MIN_SCALING on multi-core boxes;
  # two processes on one core time-share the clock). ~2 min.
  MP_SMOKE_RECORDS=$((1 << 16)) \
    timeout -k 10 600 python tools/multiproc_smoke.py || exit 1

  # Recompile sentinel: after one warmup rep, 2 measured reps on FRESH
  # engines (both mesh engines, spill armed, disarmed chaos) must show
  # ZERO XLA backend compiles and bounded device->host transfers —
  # jax.monitoring counts real compilations, so a jit identity or
  # padded shape varying per step fails here even though every
  # correctness test still passes. Includes the multi-tenant phase: a
  # SECOND job's fresh engines interleaved on the warm cluster (plus
  # batched serving lookups) must also compile nothing, and the
  # stateplane backend-swap phase: a fresh engine under the pallas
  # exchange-rank backend on its own warm (backend-tagged) program
  # keys must compile nothing either. ~25 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/recompile_smoke.py || exit 1

  # Serving smoke: 2 concurrent ingesting jobs on one mesh + client
  # threads hammering batched queryable-state lookups through the
  # READ-REPLICA plane and the r19 NATIVE FAST PATH (GIL-free hot-row
  # probe table in native/hotcache.cpp + packed zero-copy batch
  # lookups + session priming). FAILS on any steady-state XLA compile
  # after job-1 warms the shared program cache + replica tier lattice,
  # on a per-job program-cache miss, on lookup p99 over 25 ms, on
  # throughput under 350k lookups/s (raised from 216k when the native
  # fast path landed; measured ~500-580k here at the 5 ms client
  # pause), on replica staleness p99
  # over 1 s (a starved publish loop behind big lookup numbers is a
  # different product), on a packed-vs-dict result mismatch, on a
  # silent fallback to the Python cache while the native library
  # built (SERVING_REQUIRE_NATIVE_HOTCACHE above), on a zero hot-row
  # hit rate / <2 replica generations (vacuity guards), or on a quota
  # violation. ~60 s on CPU.
  SERVING_SMOKE_RECORDS=$((1 << 17)) \
    JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/serving_smoke.py || exit 1

  # Frontend smoke: the MULTI-PROCESS serving tier — 2 frontend
  # processes attach the owner's shm hot-cache arenas and serve the
  # hit path in their own processes (seqlock probes over MAP_SHARED,
  # misses crossing to the owner's replica path). Phase 1 fuzzes the
  # cross-process seqlock: readers probe while the owner primes
  # generation after generation — FAILS on ANY torn read surfacing
  # (generation-deterministic value oracle) or a vacuous overlap.
  # Phase 2 runs real ingest + frontend lookup load — FAILS on
  # owner/frontend parity divergence, replica staleness p99 over 2 s,
  # zero frontend shm hits (hit rate must be > 0), or a dead pool.
  # ~15 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/frontend_smoke.py || exit 1

  # Lock smoke: the runtime complement of the flint LCK rules — ONE
  # LockSentinel observes every named_lock across a 2-job session
  # cluster + lookup clients (+ the 2-process frontend pool when the
  # native hotcache built), a backend_scope/set_backend churn on the
  # stateplane backend registry, and a get_or_build race on the
  # program cache's once-latch. FAILS on ANY observed lock-order
  # cycle, on a single hold over 2 s (a lock held across a compile or
  # device call — frontend.pipe's by-design IPC wait is exempt), on
  # fewer than 2 DISTINCT locks actually contended (vacuity: the load
  # must produce real cross-thread traffic on this 1-core box), or on
  # any expected lock family showing zero acquisitions (a hot class
  # reverting named_lock to the bare primitive disappears from the
  # sentinel — the unguarded-hit regression). ~30 s on CPU.
  JAX_PLATFORMS=cpu timeout -k 10 300 \
    python tools/lock_smoke.py || exit 1
fi
