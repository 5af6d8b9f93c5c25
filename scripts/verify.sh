#!/usr/bin/env bash
# Fast verification gate: tier-1 fast subset + docs tier + segmented
# differential oracle + fixed-seed chaos tier + quick cstore benchmark
# with a perf-regression check against the committed BENCH_cstore.json
# + serving tier (tests + quick closed-loop benchmark gated against the
# committed BENCH_serving.json).
#
# Usage: scripts/verify.sh            (from the repo root)
#
# Fails when (a) any fast-subset test fails, (b) the docs/segmented/chaos
# tiers fail or hang past their per-tier timeout, (c) the benchmark
# errors, or (d) the quick-mode warm total regresses >
# REGRESSION_TOLERANCE x over the previous quick-mode BENCH_cstore.json
# (same n_fact only).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

TOL="${REGRESSION_TOLERANCE:-1.6}"
# per-tier wall-clock budgets (coreutils timeout): a wedged collective or
# an injected Hang that slipped past its in-process budget must fail the
# gate loudly, not stall it
T_FAST="${VERIFY_TIMEOUT_FAST:-600}"
T_DOCS="${VERIFY_TIMEOUT_DOCS:-300}"
T_SEG="${VERIFY_TIMEOUT_SEG:-600}"
T_CHAOS="${VERIFY_TIMEOUT_CHAOS:-900}"
T_BENCH="${VERIFY_TIMEOUT_BENCH:-600}"

echo "== tier-1 fast subset =="
timeout "$T_FAST" python -m pytest -q -x -p no:cacheprovider \
    tests/test_engine.py \
    tests/test_logical_frontend.py \
    tests/test_block_cache.py \
    tests/test_encodings.py \
    tests/test_segmentation_sma.py \
    tests/test_segmentation_props.py \
    tests/test_crash_replay_props.py \
    tests/test_locks.py \
    tests/test_faults.py \
    tests/test_serving.py \
    tests/test_kernels_seg_preagg.py \
    tests/test_kernels_bitunpack.py \
    tests/test_tpu_compile.py

echo "== compression tier: packed-exec property tests + 20-query oracle =="
# bit-packed storage round-trips and the compressed-domain execution path
# (code-domain predicates, late materialization) must stay byte-identical
# to the decoded scan -- DESIGN.md §9
timeout "$T_FAST" python -m pytest -q -x -p no:cacheprovider \
    tests/test_packed_exec.py

echo "== docs tier: README/DESIGN snippets must run green =="
timeout "$T_DOCS" python scripts/check_docs.py

echo "== segmented differential oracle (8-device CPU mesh) =="
# a separate process: jax locks the device count at backend init, so the
# 8-placeholder-device mesh needs XLA_FLAGS set before the first import
# (test_segmentation_props.py is host-only and already ran in tier-1)
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    timeout "$T_SEG" python -m pytest -q -x -p no:cacheprovider \
    tests/test_segmented_exec.py

echo "== chaos tier: seeded fault schedules on the 8-device mesh =="
# fixed seeds pin the exact fault schedule (fully deterministic given the
# seed): every corpus query must match the never-failed oracle or raise a
# typed AvailabilityError -- zero wrong answers
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    REPRO_CHAOS_SEEDS="${REPRO_CHAOS_SEEDS:-11,23}" \
    timeout "$T_CHAOS" python -m pytest -q -x -p no:cacheprovider \
    tests/test_fault_chaos.py

echo "== async serving tier: pipelined dispatch/drain on the 8-device mesh =="
# seeded 50-ticket flood, bulkhead/rate-limit/cost-model properties, and
# the crash-during-drain failover matrix (DESIGN.md §18) -- all schedules
# deterministic (VirtualClock + fixed seeds)
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    timeout "$T_CHAOS" python -m pytest -q -x -p no:cacheprovider \
    tests/test_serving_async.py

echo "== quick cstore benchmark =="
PREV=""
if [ -f BENCH_cstore.json ]; then
    PREV=$(mktemp)
    cp BENCH_cstore.json "$PREV"
fi
timeout "$T_BENCH" python -m benchmarks.run --quick cstore_queries

python - "$PREV" "$TOL" <<'EOF'
import json
import os
import shutil
import sys

prev_path, tol = sys.argv[1], float(sys.argv[2])
cur = json.load(open("BENCH_cstore.json"))
print(f"[verify] warm total {cur['total_warm_s']:.3f}s, "
      f"frontend {cur.get('total_frontend_s', 0)*1e3:.1f}ms, "
      f"speedup vs baseline {cur['total_speedup']:.2f}x")
# compression gate (DESIGN.md §9): packed device bytes must stay well
# under decoded bytes, and the budget-constrained warm total must keep
# beating the decoded-resident baseline at the same cache budget
comp = cur.get("compression") or {}
pr = comp.get("packed_ratio")
cs = comp.get("constrained_cache_speedup")
pr_max = float(os.environ.get("PACKED_RATIO_MAX", "0.7"))
cs_min = float(os.environ.get("CACHE_SPEEDUP_MIN", "1.2"))
if pr is not None:
    print(f"[verify] compression: packed/decoded {pr:.2f} "
          f"(max {pr_max:.2f}), constrained-cache speedup {cs:.2f}x "
          f"(min {cs_min:.2f}x)")
    if pr > pr_max:
        sys.exit(f"[verify] COMPRESSION REGRESSION: packed/decoded byte "
                 f"ratio {pr:.2f} exceeds {pr_max:.2f}")
    if cs is not None and cs < cs_min:
        sys.exit(f"[verify] COMPRESSION REGRESSION: constrained-cache "
                 f"speedup {cs:.2f}x below {cs_min:.2f}x")
if not prev_path:
    print("[verify] no previous BENCH_cstore.json; quick baseline kept")
    sys.exit(0)
# verify.sh is a GATE, not a record-writer: restore the tracked bench
# file (the full benchmarks.run is the explicit way to update it); the
# quick numbers stay in results/bench/results.json
prev = json.load(open(prev_path))
shutil.copy(prev_path, "BENCH_cstore.json")
if not (prev.get("quick") and cur.get("quick")
        and prev.get("n_fact") == cur.get("n_fact")):
    print("[verify] previous bench not comparable (size/mode); skipping "
          "regression check")
    sys.exit(0)
ratio = cur["total_warm_s"] / max(prev["total_warm_s"], 1e-9)
print(f"[verify] warm total vs previous: {ratio:.2f}x "
      f"(tolerance {tol:.2f}x)")
if ratio > tol:
    sys.exit(f"[verify] PERF REGRESSION: warm total {ratio:.2f}x slower "
             f"than previous run (> {tol:.2f}x)")
# segmented-vs-single-node gate: the device-resident slab path must not
# slide back toward host round-trips (ratio is mesh-size-normalized --
# both runs are 1-shard quick mode here)
sp = cur.get("segmented", {}).get("speedup_vs_single_node")
pp = prev.get("segmented", {}).get("speedup_vs_single_node")
if sp is not None:
    print(f"[verify] segmented speedup vs single-node: {sp:.2f}x"
          + (f" (previous {pp:.2f}x)" if pp is not None else ""))
    if pp is not None and sp < pp / tol:
        sys.exit(f"[verify] PERF REGRESSION: segmented ratio {sp:.2f}x "
                 f"fell below previous {pp:.2f}x / {tol:.2f}")
EOF

echo "== quick serving benchmark =="
PREV_SRV=""
if [ -f BENCH_serving.json ]; then
    PREV_SRV=$(mktemp)
    cp BENCH_serving.json "$PREV_SRV"
fi
timeout "$T_BENCH" python -m benchmarks.run --quick serving

python - "$PREV_SRV" "$TOL" <<'EOF'
import json
import shutil
import sys

prev_path, tol = sys.argv[1], float(sys.argv[2])
cur = json.load(open("BENCH_serving.json"))
# the serving tier's hard requirements: tail latency reported, the
# shared-scan path actually coalescing (a hit rate of 0 means every
# query ran solo -- the subsystem's point is gone), and the pipelined
# core actually parking flights (async_units of 0 means every unit ran
# synchronously -- DESIGN.md §18's point is gone)
assert cur.get("p99_ms"), "serving bench missing p99 latency"
assert cur.get("shared_scan_hit_rate", 0) > 0, \
    "serving bench: shared-scan hit rate is 0"
assert cur.get("async_units", 0) > 0, \
    "serving bench: nothing dispatched asynchronously"
print(f"[verify] serving p50 {cur['p50_ms']:.1f}ms "
      f"p99 {cur['p99_ms']:.1f}ms, {cur['throughput_qps']} qps, "
      f"shared-scan hit rate {cur['shared_scan_hit_rate']:.0%}, "
      f"speedup vs serial {cur['speedup_vs_serial']:.2f}x")
# interactive isolation gate: probe p99 under a bulkheaded batch flood
# must stay within FLOOD_RATIO_MAX x its unloaded p99
import os
fr = cur.get("interactive_p99_flood_ratio")
fr_max = float(os.environ.get("FLOOD_RATIO_MAX", "1.5"))
if fr is not None:
    print(f"[verify] interactive p99 flood ratio {fr:.2f}x "
          f"(max {fr_max:.2f}x)")
    if fr > fr_max:
        sys.exit(f"[verify] ISOLATION REGRESSION: interactive p99 under "
                 f"batch flood is {fr:.2f}x unloaded (> {fr_max:.2f}x)")
if not prev_path:
    print("[verify] no previous BENCH_serving.json; quick baseline kept")
    sys.exit(0)
prev = json.load(open(prev_path))
shutil.copy(prev_path, "BENCH_serving.json")
if not (prev.get("quick") and cur.get("quick")
        and prev.get("n_fact") == cur.get("n_fact")):
    print("[verify] previous serving bench not comparable (size/mode); "
          "skipping regression check")
    sys.exit(0)
ratio = prev["throughput_qps"] / max(cur["throughput_qps"], 1e-9)
print(f"[verify] serving throughput vs previous: {ratio:.2f}x slower "
      f"(tolerance {tol:.2f}x)")
if ratio > tol:
    sys.exit(f"[verify] PERF REGRESSION: serving throughput {ratio:.2f}x "
             f"below previous run (> {tol:.2f}x)")
EOF
echo "== verify OK =="
