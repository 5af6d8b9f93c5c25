#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU this process is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout (``BENCHMARK.json`` and ``src/`` beside
``chipbench/``).  The run refuses to start without a TPU, or with fewer
chips than the cell asks for, or on a device ``peaks.json`` does not
know; then it exits non-zero and prints no result.  Otherwise it prints
set-up and check lines on standard error and, as the last line of
standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
JAX's persistent compilation cache lives in ``.jax_cache/`` inside the
checkout, whatever the environment says, so runs of a cell share what
they compiled and two checkouts share nothing.  Only the first run of a
cell in a checkout writes to it (it leaves the marker
``.jax_cache/chipbench.filled.<cell>``); later runs read it and store
nothing.  Part of the program's compiled shapes follow the seed's data,
so a new seed compiles those anew whatever the cache holds; were they
stored, a repeated seed would skip that work, and set-up would read the
cache's history instead of the program.  So every run after the first
sets up from the same cache: what all seeds share, found; what its own
seed's data shapes, compiled.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path.cwd()
HERE = pathlib.Path(__file__).resolve().parent
TRACE_DIR = ROOT / ".chipbench_trace"
CACHE_DIR = ROOT / ".jax_cache"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    # JAX's persistent cache at a fixed path inside the checkout; set
    # before JAX is imported, and taken by the program's
    # enable_compile_cache()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if not (ROOT / "BENCHMARK.json").is_file() or \
            not (ROOT / "src" / "repro").is_dir():
        sys.exit("[chipbench] run from the root of a checkout: "
                 "BENCHMARK.json and src/repro are needed")
    sys.path[:0] = [str(ROOT / "src"), str(HERE.parent)]
    from chipbench import harness

    try:
        bench = harness.load_bench(ROOT)
        cell = harness.resolve(ROOT, bench, args.workload)["cell"]
        import jax
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise harness.BenchError(
                f"no TPU: JAX finds platform {devices[0].platform!r}")
        if len(devices) < int(cell["chips"]):
            raise harness.BenchError(f"{cell['chips']} chips asked for, "
                                     f"{len(devices)} found")
        with open(HERE / "peaks.json") as f:
            table = json.load(f)["devices"]
        kind = devices[0].device_kind
        if kind not in table:
            raise harness.BenchError(f"device kind {kind!r} is not in "
                                     f"peaks.json")
        from repro.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        filled = CACHE_DIR / f"chipbench.filled.{args.workload}"
        read_only = filled.exists()
        if read_only:
            # read the cache, write nothing: no entry takes longer than
            # this to compile
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              math.inf)
        harness.log(f"device {devices[0].platform} {kind} x{len(devices)}; "
                    f"compile cache {cache} "
                    f"({'read only' if read_only else 'written'} "
                    f"in this run)")
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               devices=devices, trace_dir=str(TRACE_DIR))
        CACHE_DIR.mkdir(exist_ok=True)
        filled.touch()
    except harness.BenchError as e:
        sys.exit(f"[chipbench] {e}")
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    harness.log(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
