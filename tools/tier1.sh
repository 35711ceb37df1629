#!/usr/bin/env bash
# Tier-1 gate: flint, the native build report and the tier-1 pytest
# command (6 xdist workers, --dist loadfile). The pytest suite is the one test suite: the
# crash-restore, steady-state compile, serving, lock-order and
# two-process pod checks are cases in it. No bench runs here and no
# timing is gated: a number from a CPU run says nothing about the chip,
# and speed is measured by benchmark/run.py on the TPU (PERF.md).
#
#   bash tools/tier1.sh

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

set -o pipefail
log="${T1_LOG:-/tmp/_t1.$$.log}"   # unique per run: concurrent gates must not clobber
rm -f "$log"
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee "$log"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$log" \
  | tr -cd . | wc -c)"

exit "$rc"

