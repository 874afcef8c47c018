#!/bin/bash
# Build the native core under the sanitizer matrix and run the
# daemon-facing pytest suite against each build (SURVEY.md §5: "ASan/TSan
# CI targets for the C++ core" — the reference has none; the rebuild's
# threaded storage daemon needs them).
#
# Usage: tools/run_sanitizers.sh [asan|tsan|ubsan|lockrank|all|both] [pytest args...]
#
#   asan      heap errors + leaks
#   tsan      data races (slot rings, chunk-store stripes, worker pools)
#   ubsan     undefined behavior, -fno-sanitize-recover (first report aborts)
#   lockrank  TSan + -DFDFS_LOCKRANK: every RankedMutex acquisition checked
#             against the per-thread held-rank stack; any lock-order
#             violation aborts with both lock sites (common/lockrank.h).
#             The native leg also runs the RankedMutex death tests.
#   all       the full matrix, in the order above
#   both      legacy alias for asan + tsan
#
# The harness picks up the instrumented binaries via FDFS_NATIVE_BUILD.
# Each instrumented tree is several hundred MB; the chip tool and the
# test driver copy the checkout as it stands, so remove native/build-*
# when the run is done.
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-all}"
shift || true
if [ "$#" -gt 0 ]; then
  PYTEST_ARGS=("$@")
else
  PYTEST_ARGS=(tests/test_storage_daemon.py tests/test_tracker_daemon.py
    tests/test_replication.py tests/test_trunk.py
    tests/test_chunked_storage.py tests/test_disk_recovery.py
    tests/test_multi_tracker.py tests/test_trace.py
    tests/test_dedup_upload.py tests/test_scrub.py
    tests/test_read_path.py tests/test_observability.py
    tests/test_report.py tests/test_slab.py tests/test_groups.py
    tests/test_cdc_kernels.py tests/test_profile.py tests/test_ec.py
    tests/test_health.py tests/test_serving_edge.py
    tests/test_admission.py tests/test_hot_replication.py)
fi

build_tree() {
  local dir="$1" sanitize="$2" lockrank="$3"
  cmake -S native -B "$dir" -G Ninja -DCMAKE_BUILD_TYPE=RelWithDebInfo \
    -DSANITIZE="$sanitize" -DFDFS_LOCKRANK="$lockrank" >/dev/null
  ninja -C "$dir"
}

run_one() {
  local flavor="$1" sanitize="$2" lockrank="${3:-OFF}"
  local dir="native/build-$flavor"
  echo "=== $flavor: configure + build (sanitize=$sanitize lockrank=$lockrank) ==="
  build_tree "$dir" "$sanitize" "$lockrank"
  echo "=== $flavor: native unit tests ==="
  # common_test's TestTraceRingThreaded/TestEventLogThreaded hammer the
  # lock-light rings from concurrent recorders + a dumping reader — the
  # TSan run is the proof the design is data-race-free, not just lucky.
  # TestRankedMutexThreaded does the same for the lock-rank checker's
  # thread_local bookkeeping, and under the lockrank flavor the
  # TestRankedMutexInversionAborts death tests prove a rank inversion
  # (including a descending-stripe RefAll violation) aborts with both
  # lock sites reported.
  "$dir/common_test"
  # storage_test's TestChunkStoreStripedConcurrency hammers the
  # digest-striped chunk store + hot-chunk read cache from concurrent
  # uploaders/deleters, cached readers, pin sessions, and a
  # quarantine/GC sweeper — under lockrank this also validates the
  # ascending-stripe RefAll protocol at runtime.
  "$dir/storage_test"
  "$dir/tracker_test"
  echo "=== $flavor: daemon suite ==="
  # halt_on_error keeps a failing daemon loud; leak detection stays on
  # for asan (daemons shut down cleanly in the harness).
  case "$sanitize" in
    thread) export TSAN_OPTIONS="halt_on_error=1" ;;
    address) export ASAN_OPTIONS="halt_on_error=1:detect_leaks=1" ;;
    undefined) export UBSAN_OPTIONS="halt_on_error=1:print_stacktrace=1" ;;
  esac
  FDFS_NATIVE_BUILD="$dir" python -m pytest "${PYTEST_ARGS[@]}" -x -q
}

case "$MODE" in
  asan) run_one asan address ;;
  tsan) run_one tsan thread ;;
  ubsan) run_one ubsan undefined ;;
  lockrank) run_one lockrank thread ON ;;
  both) run_one asan address && run_one tsan thread ;;
  all) run_one asan address && run_one tsan thread \
       && run_one ubsan undefined && run_one lockrank thread ON ;;
  *) echo "usage: $0 [asan|tsan|ubsan|lockrank|all|both] [pytest args...]" >&2
     exit 2 ;;
esac
echo "sanitizer suite: PASS ($MODE)"
