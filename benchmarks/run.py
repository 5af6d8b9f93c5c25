"""Benchmark driver: one module per paper table/claim.

  compression       -> Table 4 (1M ints + meter data)
  cstore_queries    -> Table 3 (7-query workload, 2 execution models)
  encoded_exec      -> §6.1 operate-on-encoded-data ablation
  tuple_mover_bench -> §4 ingest/merge behaviour
  distribution      -> §3.6/§6.2 join locality decisions + Send/Recv
  serving           -> §7 concurrent serving: closed-loop latency/qps
  roofline          -> §Roofline reader over results/dryrun/

Writes results/bench/results.json and prints a summary per benchmark.
After a cstore_queries run, also writes repo-root BENCH_cstore.json (the
headline perf numbers: cold/warm totals, speedups, disk ratio) so the
perf trajectory is tracked PR-over-PR; a serving run likewise writes
BENCH_serving.json (p50/p95/p99, throughput, shared-scan hit rate).

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [name ...]
  --quick: CI-smoke sizes (small N_FACT) via REPRO_BENCH_QUICK=1
"""
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "results" / "bench"


def main() -> None:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (compression, cstore_queries, distribution,
                            encoded_exec, roofline, serving,
                            tuple_mover_bench)
    mods = {
        "compression": compression,
        "cstore_queries": cstore_queries,
        "encoded_exec": encoded_exec,
        "tuple_mover_bench": tuple_mover_bench,
        "distribution": distribution,
        "serving": serving,
        "roofline": roofline,
    }
    args = sys.argv[1:]
    if "--quick" in args:
        os.environ["REPRO_BENCH_QUICK"] = "1"
        args = [a for a in args if a != "--quick"]
    names = args or list(mods)
    unknown = [n for n in names if n not in mods]
    if unknown:
        sys.exit(f"[run] unknown benchmark(s) {unknown}; "
                 f"available: {', '.join(mods)}")
    OUT.mkdir(parents=True, exist_ok=True)
    results = {}
    prev = OUT / "results.json"
    if prev.exists():  # merge: partial runs must not clobber other tables
        results.update(json.loads(prev.read_text()))

    def report(key, value):
        results[key] = value

    for name in names:
        print(f"===== {name} =====", flush=True)
        t0 = time.time()
        mods[name].run(report)
        print(f"===== {name} done in {time.time()-t0:.1f}s =====",
              flush=True)
    (OUT / "results.json").write_text(json.dumps(results, indent=1,
                                                 default=str))
    print(f"[run] wrote {OUT/'results.json'}")
    t3 = results.get("cstore_queries/table3")
    if t3 is not None and "cstore_queries" in names:
        bench = {k: t3.get(k) for k in (
            "n_fact", "quick", "total_vertica_s", "total_baseline_s",
            "total_speedup", "total_cold_s", "total_warm_s",
            "warm_speedup_vs_cold", "total_frontend_s", "disk_ratio",
            "segmented", "failover", "compression")}
        bench["frontend_ms_per_query"] = {
            name: row.get("frontend_ms")
            for name, row in t3.get("queries", {}).items()}
        (ROOT / "BENCH_cstore.json").write_text(
            json.dumps(bench, indent=1) + "\n")
        print(f"[run] wrote {ROOT/'BENCH_cstore.json'}")
    srv = results.get("serving/closed_loop")
    if srv is not None and "serving" in names:
        (ROOT / "BENCH_serving.json").write_text(
            json.dumps(srv, indent=1) + "\n")
        print(f"[run] wrote {ROOT/'BENCH_serving.json'}")


if __name__ == '__main__':
    main()
