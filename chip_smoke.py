#!/usr/bin/env python3
"""Chip smoke test: the database's main path on a TPU, end to end.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # segmented queries on a 4-chip mesh

One chip (the default) loads the C-Store harness schema
(benchmarks/cstore_queries.py: lineitem + orders, RLE on the sorted ship
date) at 48M lineitem rows -- TPC-H SF8's lineitem row count -- with
orders at the harness's 40:1 ratio, through ``VerticaDB.insert/commit``.
It then runs the seven harness queries with ``.collect()``, cold and
warm, in ``decoded`` and ``compressed`` execution and through the
segmented executor on a one-chip mesh; five of them through two
``db.serve()`` sessions; and three after a trickle-load commit into the
WOS.  Every answer is checked against a plain numpy reference over
``db.read_table``: counts and integer sums exactly, float sums and
averages to a relative tolerance of ``FLOAT_RTOL``.  The Pallas kernels
the path dispatches are checked once more on small inputs, and the
lowered programs are searched for the three main-path kernels
(``bitunpack``, ``rle_grouped_agg``, ``seg_preagg``) compiled as TPU
custom calls.

``--chips 4`` runs only the segmented phase, at 4M rows: Q2, Q4 and the
Q5 join (local exchange) plus one broadcast and one resegment join on a
4-device mesh, each compared with the same query on one device and with
the host reference.

The script refuses to run without a TPU, exits non-zero on any mismatch,
and prints one JSON object as its last line:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import sys
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
N_FACT = 48_000_000          # TPC-H SF8 lineitem rows
N_FACT_4CHIP = 4_000_000     # the mesh phase checks exchanges, not scale
DIM_RATIO = 40               # orders rows = lineitem rows / 40 (harness)
N_TRICKLE = 100_000          # rows of the trickle-load commit
FLOAT_RTOL = 1e-4            # f32 sums over up to ~1e5 rows per group
CACHE_BUDGET = 8 << 30       # device block cache: holds the decoded set
KERNELS = ("bitunpack", "rle_grouped_agg", "seg_preagg")
QUERY_NAMES = ("Q1", "Q2", "Q3", "Q4", "Q5", "Q6", "Q7")
# on the mesh: pre-aggregation (Q2), the RLE path (Q4), and one join per
# exchange -- local (Q5), broadcast (QB), resegment (QR)
MESH_NAMES = ("Q2", "Q4", "Q5", "QB", "QR")


class SmokeError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    """The device list, or exit non-zero: this script never runs on the
    CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"[smoke] no TPU visible (jax platform "
                 f"{devs[0].platform!r}); refusing to run")
    if len(devs) < n_chips:
        sys.exit(f"[smoke] {n_chips} chips requested, {len(devs)} visible")
    return devs


# ---------------------------------------------------------------- load --

def load(n_fact: int, seed: int):
    from benchmarks.cstore_queries import build_db
    from repro.core.encodings import device_bytes

    t0 = time.perf_counter()
    db = build_db(n_fact, n_fact // DIM_RATIO, seed=seed,
                  cache_budget_bytes=CACHE_BUDGET)
    load_s = time.perf_counter() - t0
    packed = decoded = 0
    for node in db.nodes:
        for c in node.stores["lineitem_super"].containers:
            for ec in c.columns.values():
                inner = ec.inner if ec.inner is not None else ec
                packed += device_bytes(inner.arrays)
                decoded += inner.n_blocks * inner.block_rows * 4
    log(f"load: {n_fact} lineitem + {n_fact // DIM_RATIO} orders rows in "
        f"{load_s:.3f} s; lineitem packed {packed} B, decoded {decoded} B")
    return db, load_s


# ----------------------------------------------------------- reference --

def _lookup(keys: np.ndarray, dim: dict, dim_key: str):
    """Inner-join probe: (matched mask, index of each probe row's dim row)."""
    order = np.argsort(dim[dim_key], kind="stable")
    sk = dim[dim_key][order]
    pos = np.clip(np.searchsorted(sk, keys), 0, len(sk) - 1)
    return sk[pos] == keys, order[pos]


def _group(keys, mask, values=None, kind="count"):
    """{key: aggregate} over the rows in ``mask`` (non-negative keys).
    Integer sums are exact: float64 holds them below 2**53."""
    k = keys[mask]
    cnt = np.bincount(k)
    present = np.flatnonzero(cnt)
    if kind == "count":
        return {int(g): int(cnt[g]) for g in present}
    v = values[mask]
    s = np.bincount(k, weights=v, minlength=len(cnt))
    if kind == "avg":
        return {int(g): float(s[g]) / int(cnt[g]) for g in present}
    if np.issubdtype(v.dtype, np.integer):
        return {int(g): int(s[g]) for g in present}
    return {int(g): float(s[g]) for g in present}


def reference(db, names=QUERY_NAMES):
    """Plain numpy answers to the named queries over db.read_table:
    name -> (group column or None, agg name, {key: value} or scalar)."""
    li = db.read_table("lineitem")
    sd, sk = li["l_shipdate"], li["l_suppkey"]
    want = {
        "Q1": lambda: (None, "c", int((sd == 180).sum())),
        "Q2": lambda: ("l_suppkey", "c", _group(sk, sd == 180)),
        "Q3": lambda: ("l_suppkey", "s", _group(
            sk, (sd > 60) & (sd < 120), li["l_qty"], "sum")),
        "Q4": lambda: ("l_shipdate", "c", _group(sd, np.ones_like(sd, bool))),
        "Q6": lambda: ("l_suppkey", "a", _group(
            sk, sd > 300, li["l_extprice"], "avg")),
    }
    if {"Q5", "Q7"} & set(names):
        od = db.read_table("orders")
        hit, o = _lookup(li["l_orderkey"], od, "o_orderkey")
        cust, odate = od["o_custkey"][o], od["o_orderdate"][o]
        want["Q5"] = lambda: ("o_custkey", "s", _group(
            cust, hit & (odate < 60), li["l_extprice"], "sum"))
        want["Q7"] = lambda: ("o_custkey", "c", _group(cust, hit & (sk < 10)))
    if "QB" in names:
        su = db.read_table("supplier")
        shit, s = _lookup(sk, su, "s_suppkey")
        want["QB"] = lambda: ("s_nation", "s", _group(
            su["s_nation"][s], shit, li["l_qty"], "sum"))
    if "QR" in names:
        sw = db.read_table("supplier_wide")
        whit, w = _lookup(sk, sw, "sw_suppkey")
        want["QR"] = lambda: ("sw_region", "c", _group(
            sw["sw_region"][w], whit & (sd < 100)))
    return {name: want[name]() for name in names}, len(sd)


def check(name: str, out, want) -> None:
    group, agg, expect = want
    got = np.asarray(out[agg])
    if group is None:
        if got.shape != (1,) or int(got[0]) != expect:
            raise SmokeError(f"{name}: {agg}={got} expected {expect}")
        return
    keys = np.asarray(out[group]).astype(np.int64)
    if sorted(keys.tolist()) != sorted(expect):
        raise SmokeError(f"{name}: {len(keys)} groups, expected "
                         f"{len(expect)}")
    want_v = np.asarray([expect[int(k)] for k in keys])
    if np.issubdtype(got.dtype, np.integer):
        bad = np.flatnonzero(got.astype(np.int64) != want_v)
        if bad.size:
            i = bad[0]
            raise SmokeError(f"{name}: {bad.size} groups differ, e.g. "
                             f"{group}={keys[i]}: {got[i]} vs {want_v[i]}")
    else:
        err = np.abs(got - want_v) / np.maximum(np.abs(want_v), 1e-12)
        if not np.all(err <= FLOAT_RTOL):
            i = int(np.argmax(err))
            raise SmokeError(f"{name}: relative error {err[i]:.3g} > "
                             f"{FLOAT_RTOL} at {group}={keys[i]}: "
                             f"{got[i]} vs {want_v[i]}")


def same(name: str, a, b, want) -> None:
    """Two engine answers to one query agree, group by group (exact for
    integers)."""
    group, agg, _ = want
    if group is None:
        va, vb = np.asarray(a[agg]), np.asarray(b[agg])
    else:
        oa = np.argsort(np.asarray(a[group]))
        ob = np.argsort(np.asarray(b[group]))
        if not np.array_equal(np.asarray(a[group])[oa],
                              np.asarray(b[group])[ob]):
            raise SmokeError(f"{name}: mesh and single-device groups "
                             f"differ")
        va, vb = np.asarray(a[agg])[oa], np.asarray(b[agg])[ob]
    if np.issubdtype(va.dtype, np.integer):
        ok = np.array_equal(va, vb)
    else:
        ok = np.allclose(va, vb, rtol=FLOAT_RTOL, atol=0)
    if not ok:
        raise SmokeError(f"{name}: mesh and single-device answers differ")


# -------------------------------------------------------------- phases --

def run_queries(label: str, builders, ref, names=QUERY_NAMES):
    """Each query cold then warm; both answers checked.  Returns
    {name: (cold_s, warm_s, stats)} and the warm answers."""
    times, answers = {}, {}
    for name in names:
        qb = builders[name]
        t0 = time.perf_counter()
        cold = qb.collect()
        t1 = time.perf_counter()
        warm = qb.collect()
        t2 = time.perf_counter()
        check(f"{label}/{name}", cold, ref[name])
        check(f"{label}/{name}", warm, ref[name])
        st = qb.stats
        times[name] = (t1 - t0, t2 - t1, st)
        answers[name] = warm
        log(f"{label} {name}: cold {t1 - t0:.6f} s warm {t2 - t1:.6f} s "
            f"groupby={st.groupby_algorithm} fused={st.fused} "
            f"compressed={st.compressed_scan} segmented={st.segmented} "
            f"exchange={st.exchange or '-'} ok")
    cold = sum(t[0] for t in times.values())
    warm = sum(t[1] for t in times.values())
    log(f"{label}: {len(names)} queries match the reference; "
        f"cold total {cold:.6f} s, warm total {warm:.6f} s")
    return times, answers


def _fresh_caches(db) -> None:
    from repro.engine import PLAN_CACHE
    PLAN_CACHE.clear()
    db.block_cache.clear()


def trickle_load(db, n_rows: int, seed: int) -> int:
    """One small commit that lands in the WOS (not direct to ROS)."""
    from repro.data.synth import star_schema
    n_dim = len(db.read_table("orders")["o_orderkey"])
    fact, _ = star_schema(n_rows, n_dim, seed + 1)
    t = db.begin()
    db.insert(t, "lineitem", fact)
    db.commit(t)
    wos = sum(n.stores["lineitem_super"].wos.n_rows for n in db.nodes)
    if wos < n_rows:
        raise SmokeError(f"trickle load: {wos} WOS rows, expected "
                         f">= {n_rows}")
    return wos


def serve_phase(db, builders, ref) -> None:
    svc = db.serve(max_concurrent=2, queue_depth=16)
    tickets = []
    with svc.session("interactive") as s_int, \
            svc.session("batch") as s_bat:
        for name in ("Q2", "Q3", "Q4"):
            tickets.append((name, s_int.submit(builders[name])))
        for name in ("Q6", "Q1"):
            tickets.append((name, s_bat.submit(builders[name])))
        t0 = time.perf_counter()
        svc.drain()
        drain_s = time.perf_counter() - t0
    for name, t in tickets:
        if not t.done or t.error is not None:
            raise SmokeError(f"serve/{name}: not answered ({t.error})")
        check(f"serve/{name}", t.result(), ref[name])
    log(f"serve: {len(tickets)} queries over 2 sessions drained in "
        f"{drain_s:.6f} s, all match the reference "
        f"(shared-scan hit rate {svc.stats.shared_hit_rate():.3f})")


def _close(name: str, got, want, tol: float) -> None:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape == want.shape:
        with np.errstate(invalid="ignore"):     # equal infinities
            err = np.where(got == want, 0.0,
                           np.abs(got - want) - tol * np.abs(want))
    if got.shape != want.shape or not np.all(err <= tol):
        worst = float(np.max(np.abs(got - want))) \
            if got.shape == want.shape else float("nan")
        raise SmokeError(f"kernel {name} differs from its oracle: shapes "
                         f"{got.shape} / {want.shape}, max abs error "
                         f"{worst:.6g} (tolerance {tol})")


def kernel_selfcheck(seed: int) -> None:
    """Every kernel ops.py dispatches, on small inputs, vs its oracle."""
    import jax.numpy as jnp
    from repro.core.encodings import pack_words
    from repro.kernels import ops, ref
    from repro.kernels.seg_preagg import seg_preagg_pallas

    rng = np.random.default_rng(seed)
    for width in (1, 5, 13, 32):
        syms = rng.integers(0, 1 << width, (8, 4096), dtype=np.uint64)
        words = jnp.asarray(pack_words(syms.astype(np.int64), width))
        base = rng.integers(-1000, 1000, 8)
        got = ops.bitunpack(words, width, 4096, jnp.asarray(base, jnp.int32))
        want = (syms.astype(np.uint32).astype(np.int32).astype(np.int64)
                + base[:, None]).astype(np.int32)
        _close(f"bitunpack width {width}", got, want, 0)
    rv = jnp.asarray(rng.integers(0, 370, (16, 128)), jnp.int32)
    rl = jnp.asarray(rng.integers(0, 4097, (16, 128)), jnp.int32)
    # small integer values: every float sum is exact, in any order
    val = jnp.asarray(rng.integers(-8, 9, (16, 128)), jnp.float32)
    got = ops.rle_grouped_agg(rv, rl, val, domain=365)
    want = ref.rle_grouped_agg_ref(rv, rl, val, 365, -3.0e38, 3.0e38)
    for name in ("count", "sum", "min", "max"):
        _close(f"rle_grouped_agg {name}", got[name], want[name], 0)
    keys = jnp.asarray(rng.integers(-5, 1030, 20000), jnp.int32)
    valid = jnp.asarray(rng.random(20000) < 0.8)
    values = {"q": jnp.asarray(rng.integers(-50, 50, 20000), jnp.int32),
              "p": jnp.asarray(rng.integers(-8, 9, 20000), jnp.float32)}
    aggs = (("n", "*", "count"), ("s", "q", "sum"), ("mn", "q", "min"),
            ("mx", "p", "max"), ("fs", "p", "sum"))
    got = seg_preagg_pallas(keys, valid, values, 1024, aggs,
                            interpret=not ops.on_tpu())
    want = ref.seg_preagg_ref(keys, valid, values, 1024, aggs)
    for name in ("group_count", "n", "s", "mn", "mx", "fs"):
        _close(f"seg_preagg {name}", got[name], want[name], 0)
    # the kernels with no caller on the query path
    rvf = jnp.asarray(rng.integers(0, 100, (10, 200)), jnp.float32)
    rlf = jnp.asarray(rng.integers(0, 20, (10, 200)), jnp.float32)
    _close("rle_filter_agg", ops.rle_filter_agg(rvf, rlf, lo=25.0, hi=75.0),
           ref.rle_filter_agg_ref(rvf, rlf, 25.0, 75.0), 0)
    k = jnp.asarray(rng.integers(0, 1000, (3, 1024)), jnp.int32)
    v = jnp.asarray(rng.integers(-8, 9, (3, 1024)), jnp.float32)
    _close("onehot_groupby", ops.onehot_groupby(k, v, domain=1000),
           ref.onehot_groupby_ref(k, v, 1000), 0)
    build = jnp.asarray(rng.choice(2000, 1000, replace=False), jnp.int32)
    _close("semijoin_probe", ops.semijoin_probe(k, build),
           ref.semijoin_probe_ref(k, jnp.pad(build, (0, 24),
                                             constant_values=-1)), 0)
    first = jnp.asarray(rng.integers(0, 1000, (5, 1)), jnp.float32)
    deltas = jnp.asarray(rng.integers(-5, 6, (5, 4096)), jnp.float32)
    _close("delta_decode", ops.delta_decode(first, deltas),
           ref.delta_decode_ref(first, deltas), 0)
    # bf16 inputs, at the kernel test's bf16 tolerance
    q = jnp.asarray(rng.normal(size=(2, 256, 128)), jnp.bfloat16)
    _close("flash_attention", ops.flash_attention(q, q, q),
           ops.flash_attention(q, q, q, force_ref=True), 2e-2)
    log("kernels: bitunpack, rle_grouped_agg, seg_preagg, rle_filter_agg, "
        "onehot_groupby, semijoin_probe, delta_decode, flash_attention "
        "match their oracles")


def kernel_programs(dump_dir: str):
    """Lowered programs (from jax_dump_ir_to) that hold each Pallas
    kernel as a compiled TPU custom call."""
    found = {}
    pat = re.compile(r'kernel_name = "([A-Za-z_]+)"')
    for path in glob.glob(os.path.join(dump_dir, "*.mlir")):
        text = open(path).read()
        if "tpu_custom_call" not in text:
            continue
        for name in set(pat.findall(text)):
            found[name] = found.get(name, 0) + 1
    return found


# ---------------------------------------------------------------- runs --

def run_one_chip(n_fact: int, seed: int) -> None:
    from benchmarks.cstore_queries import make_builders
    from repro.distributed.mesh import make_query_mesh

    db, _ = load(n_fact, seed)
    t0 = time.perf_counter()
    ref, n_rows = reference(db)
    log(f"reference: {n_rows} rows in {time.perf_counter() - t0:.3f} s")
    builders = make_builders(db)
    for mode in ("decoded", "compressed"):
        _fresh_caches(db)
        db.exec_mode = mode
        times, _ = run_queries(mode, builders, ref)
        if mode == "compressed" and not any(
                t[2].compressed_scan for t in times.values()):
            raise SmokeError("compressed mode ran no code-domain scan")
    db.exec_mode = "auto"
    _fresh_caches(db)
    db.attach_mesh(make_query_mesh(1))
    times, _ = run_queries("segmented", builders, ref)
    db.detach_mesh()
    if not all(times[n][2].segmented for n in ("Q2", "Q3", "Q6")):
        raise SmokeError("segmented mode did not segment Q2/Q3/Q6")
    serve_phase(db, builders, ref)
    wos = trickle_load(db, N_TRICKLE, seed)
    seen = ("Q1", "Q3", "Q6")
    ref, n_rows = reference(db, seen)
    log(f"trickle load: {N_TRICKLE} rows committed, {wos} rows in the WOS, "
        f"reference over {n_rows} rows")
    run_queries("trickle", builders, ref, seen)
    kernel_selfcheck(seed)


def run_four_chips(n_fact: int, seed: int) -> None:
    from benchmarks.cstore_queries import make_builders
    from repro.core import ColumnDef, TableSchema
    from repro.distributed.mesh import make_query_mesh
    from repro.engine import col

    db, _ = load(n_fact, seed)
    rng = np.random.default_rng(seed + 2)
    # two dimensions that are NOT co-located with lineitem (segmented by
    # l_orderkey): a small supplier table the planner broadcasts, and a
    # supplier table larger than a quarter of the fact table (suppliers
    # beyond the 100 that lineitem references), for which it resegments
    # the probe side on l_suppkey
    n_wide = n_fact // 3
    db.create_table(TableSchema("supplier", (
        ColumnDef("s_suppkey"), ColumnDef("s_nation"))),
        sort_order=("s_suppkey",), segment_by=("s_suppkey",))
    db.create_table(TableSchema("supplier_wide", (
        ColumnDef("sw_suppkey"), ColumnDef("sw_region"))),
        sort_order=("sw_suppkey",), segment_by=("sw_region",))
    t = db.begin(direct_to_ros=True)
    db.insert(t, "supplier", {
        "s_suppkey": np.arange(100, dtype=np.int64),
        "s_nation": rng.integers(0, 25, 100)})
    db.insert(t, "supplier_wide", {
        "sw_suppkey": np.arange(n_wide, dtype=np.int64),
        "sw_region": rng.integers(0, 5, n_wide)})
    db.commit(t)
    names = MESH_NAMES
    ref, _ = reference(db, names)
    builders = make_builders(db)
    li = db.query("lineitem")
    builders["QB"] = (li.join("supplier", on=("l_suppkey", "s_suppkey"),
                              cols=("s_nation",))
                      .group_by("s_nation").agg(s=("l_qty", "sum")))
    builders["QR"] = (li.where(col("l_shipdate") < 100)
                      .join("supplier_wide", on=("l_suppkey", "sw_suppkey"),
                            cols=("sw_region",))
                      .group_by("sw_region").agg(c=("*", "count")))
    _, single = run_queries("single-device", builders, ref, names)
    db.attach_mesh(make_query_mesh(4))
    times, mesh = run_queries("mesh4", builders, ref, names)
    db.detach_mesh()
    for name in names:
        same(name, mesh[name], single[name], ref[name])
    want = {"Q5": "local", "QB": "broadcast", "QR": "resegment"}
    for name, exch in want.items():
        st = times[name][2]
        if not st.segmented or st.n_shards != 4 or exch not in st.exchange:
            raise SmokeError(f"mesh4/{name}: segmented={st.segmented} "
                             f"shards={st.n_shards} exchange="
                             f"{st.exchange!r}, expected {exch}")
    log("mesh4: all queries match the single-device run and the "
        "reference; exchanges local, broadcast and resegment all ran")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("[smoke] run from a checkout of the repository "
                 "(src/repro not found next to this script)")
    devs = require_tpu(args.chips)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.compile_cache import enable_compile_cache

    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {enable_compile_cache()}")
    with tempfile.TemporaryDirectory(prefix="smoke_ir_") as dump_dir:
        jax.config.update("jax_dump_ir_to", dump_dir)
        t0 = time.perf_counter()
        try:
            if args.chips == 4:
                run_four_chips(N_FACT_4CHIP, args.seed)
            else:
                run_one_chip(N_FACT, args.seed)
        except SmokeError as e:
            sys.exit(f"[smoke] FAIL: {e}")
        jax.config.update("jax_dump_ir_to", "")
        found = kernel_programs(dump_dir)
    log(f"compiled kernels (lowered programs holding each as a "
        f"tpu_custom_call): {json.dumps(found, sort_keys=True)}")
    need = ("seg_preagg",) if args.chips == 4 else KERNELS
    missing = [k for k in need if not found.get(k)]
    if missing:
        sys.exit(f"[smoke] FAIL: kernels never compiled for the chip: "
                 f"{missing}")
    stats = devs[0].memory_stats() or {}
    log(f"device memory: peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}, "
        f"bytes_limit {stats.get('bytes_limit', 'not reported')}; "
        f"run {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
