#!/usr/bin/env python3
"""The control of a cell's check: the reference in bfloat16 in the
program's place, which the check has to find not correct.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3

For each seed it generates the cell's data at the cell's own size,
answers every (template, literal set) of the cell's traffic twice -- with
the exact reference and with the same reference carried in bfloat16 on
the device -- and compares the second against the first exactly as a run
compares the program's answers.  It prints the numbers and their limits
per seed, and one JSON line: the readings and whether any seed came out
correct (it must not).  The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path.cwd()
HERE = pathlib.Path(__file__).resolve().parent


def readings(root: pathlib.Path, workload_name: str, seed: int) -> dict:
    from chipbench import compare, datagen, harness, reference, workload
    r = harness.resolve(root, harness.load_bench(root), workload_name)
    config, traffic = r["config"], r["traffic"]
    tables = datagen.generate(config, seed)
    lits = workload.literal_sets(traffic)
    parts = []
    for t, k in workload.all_pairs(traffic):
        spec = traffic["templates"][t]
        want = reference.answer(spec, lits[t][k], tables)
        low = reference.answer(spec, lits[t][k], tables, dtype="bfloat16")
        got = {a: v for a, v in low["aggs"].items()}
        if low["keys"] is not None:
            got[spec["group_by"][0]] = low["keys"]
        parts.append(compare.readings(spec, got, want))
    values = compare.combine(parts)
    correct, checks = compare.verdict(values, config["limits"])
    return {"seed": seed, "correct": correct, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE.parent)]
    import jax
    dev = jax.devices()[0]
    out = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(ROOT, args.workload, seed)
        print(f"[control] {args.workload} seed {seed}: correct "
              f"{r['correct']} in {time.perf_counter() - t0:.1f} s; "
              + "; ".join(f"{n} {c['value']!r} (limit {c['limit']!r})"
                          for n, c in r["checks"].items()),
              file=sys.stderr, flush=True)
        out.append(r)
    print(json.dumps({"workload": args.workload, "device": dev.device_kind,
                      "any_correct": any(r["correct"] for r in out),
                      "runs": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
