"""One run of one cell: set-up, measured window, check, result line.

The cell, its deployment, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` at ``root``: ``configs/<config>.json`` via the
configuration's ``file``, ``traffic/<mix>.json`` beside it, and
``metrics/<metric>.py`` for every metric the cell reports.  Nothing here
names a cell, a deployment or a metric.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, List, Optional

import numpy as np

from . import compare, datagen, program, reference, tracing, workload


class BenchError(Exception):
    """The run cannot start or cannot finish; no result is printed."""


def load_bench(root: pathlib.Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(root: pathlib.Path, bench: dict, workload_name: str) -> dict:
    """The cell with its config, traffic and metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in cells:
        raise BenchError(f"no workload {workload_name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[workload_name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    traffic_path = root / pathlib.Path(cfg_entry["file"]).parent.parent \
        / "traffic" / f"{cell['traffic']}.json"
    with open(traffic_path) as f:
        traffic = json.load(f)

    def applies(m):
        return "workloads" not in m or workload_name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if applies(m) and
             ("workloads" in m or m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer,
            "metrics_dir": root / pathlib.Path(cfg_entry["file"]).parent
            .parent / "metrics"}


def _reader(metrics_dir: pathlib.Path, name: str) -> Callable:
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a metric reader (``read(ctx) -> float | None``) may look at."""

    # the window's queries: template, lit (literal set), latency_s,
    # stats (``program.stats_of``), end, failed
    queries: List[dict]
    window_s: float
    setup_s: float
    trace: Optional[tracing.Reduced]        # --trace 1 only


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, counted
    through ``jax.monitoring`` while ``active``."""

    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.cache_hits = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if self.active and event == self.event:
            self.count += 1

    def _on_event(self, event, **kw):
        if self.active and event == self.HIT:
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def _warm_up(queries: dict, counter: CompileCounter) -> None:
    """Every (template, literal set) once: a second pass compiled nothing
    on the chip, in any cell."""
    counter.active = True
    for q in queries.values():
        q.collect()
    counter.active = False
    log(f"warm-up: {len(queries)} queries, {counter.count} programs "
        f"compiled or loaded ({counter.cache_hits} from the persistent "
        f"cache)")


def _window(queries: dict, order, seconds: float, span) -> tuple:
    """The closed loop: send the next query while the window is open.
    Returns (records, {(template, k): [answer or None, ...]}, window_s);
    the window runs from the first send to the last answer, the query in
    flight at the close included."""
    records, answers = [], {}
    w_start = time.perf_counter()
    deadline = w_start + seconds
    with span(tracing.WINDOW):
        while time.perf_counter() < deadline:
            t, k = next(order)
            q = queries[(t, k)]
            a = time.perf_counter()
            try:
                with span(tracing.QUERY + t):
                    out = {c: np.asarray(v) for c, v in q.collect().items()}
            except Exception as e:  # counted as unanswered, not fatal
                log(f"query {t}[{k}] failed: {type(e).__name__}: {e}")
                out = None
            b = time.perf_counter()
            with span(tracing.CLIENT):
                records.append({"template": t, "lit": k, "latency_s": b - a,
                                "stats": program.stats_of(q), "end": b,
                                "failed": out is None})
                answers.setdefault((t, k), []).append(out)
    window_s = records[-1]["end"] - w_start if records else 0.0
    return records, answers, window_s


def _check(answers: dict, specs: dict, lits: dict, tables: dict,
           limits: dict, off_mesh: Optional[int]) -> tuple:
    """Every answer against the reference, and on a mesh the queries
    that left it: (correct, checks)."""
    t_ref = time.perf_counter()
    parts = []
    for (t, k), outs in answers.items():
        want = reference.answer(specs[t], lits[t][k], tables)
        parts.extend(compare.readings(specs[t], o, want) for o in outs)
    log(f"reference: {len(answers)} distinct queries in "
        f"{time.perf_counter() - t_ref:.3f} s")
    values = compare.combine(parts)
    if off_mesh is not None:
        values["off_mesh"] = off_mesh
    return compare.verdict(values, limits)


def _check_mesh(config: dict, cell: dict, devices: list) -> Optional[int]:
    """The chips a deployment puts its nodes on (None: it states none),
    refused unless the cell asks for as many, the process sees them and
    the deployment gives ``off_mesh`` a limit."""
    chips = config["cluster"].get("chips")
    if chips is None:
        return None
    chips = int(chips)
    if chips != int(cell["chips"]):
        raise BenchError(f"the configuration puts its nodes on {chips} "
                         f"chips, the cell asks for {cell['chips']}")
    if chips > len(devices):
        raise BenchError(f"the configuration puts its nodes on {chips} "
                         f"chips, the process sees {len(devices)}")
    if "off_mesh" not in config["limits"]:
        raise BenchError("a configuration with cluster.chips needs "
                         "limits.off_mesh")
    return chips


def run_cell(root: pathlib.Path, workload_name: str, seed: int,
             seconds: float, trace: bool, *, t_start: float,
             devices: list, trace_dir: Optional[str] = None) -> dict:
    """Run the cell once; returns the result line's object."""
    import jax

    r = resolve(root, load_bench(root), workload_name)
    config, traffic = r["config"], r["traffic"]
    specs = traffic["templates"]
    chips = _check_mesh(config, r["cell"], devices)
    counter = CompileCounter()

    t0 = time.perf_counter()
    tables = datagen.generate(config, seed)
    t_gen = time.perf_counter()
    db = program.load(config, tables)
    t_load = time.perf_counter()
    rows = ", ".join(f"{t} {len(next(iter(c.values())))} rows"
                     for t, c in tables.items())
    log(f"data: {rows}; generated in {t_gen - t0:.3f} s, loaded in "
        f"{t_load - t_gen:.3f} s; block-cache budget "
        f"{db.block_cache.budget_bytes} B; "
        f"{'no mesh' if chips is None else f'mesh of {chips} chips'}")
    lits = workload.literal_sets(traffic)
    queries = {(t, k): program.build_query(db, specs[t], lits[t][k])
               for t, k in workload.all_pairs(traffic)}
    _warm_up(queries, counter)
    setup_s = time.time() - t_start
    log(f"set-up {setup_s:.3f} s (warm-up "
        f"{time.perf_counter() - t_load:.3f} s)")

    span = jax.profiler.TraceAnnotation if trace else \
        (lambda name: contextlib.nullcontext())
    if trace:
        tracing.start(trace_dir)
    counter.count, counter.active = 0, True
    try:
        records, answers, window_s = _window(
            queries, workload.order(traffic, seed), seconds, span)
    finally:
        counter.active = False
        if trace:
            tracing.stop()
    log(f"window: {len(records)} queries in {window_s:.3f} s; "
        f"{counter.count} programs compiled or loaded inside it")
    for t in specs:
        mine = [q for q in records if q["template"] == t]
        lat = sorted(q["latency_s"] * 1e3 for q in mine)
        if lat:
            log(f"  {t}: {len(lat)} queries, median "
                f"{lat[len(lat) // 2]:.3f} ms, max {lat[-1]:.3f} ms")
        if lat and chips is not None:
            st = [q["stats"] or {} for q in mine]
            log(f"  {t} on the mesh: {compare.off_mesh(mine)} off it; "
                f"shards {sorted({s.get('n_shards') for s in st})}, "
                f"exchange {sorted({s.get('exchange') for s in st})}, "
                f"slab {sorted({s.get('seg_slab') for s in st})}, "
                f"overflow {sum(s.get('reseg_overflow', 0) for s in st)}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del queries, db
    gc.collect()

    reduced = None
    if trace:
        t_tr = time.perf_counter()
        reduced = tracing.reduce(tracing.read_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_tr:.3f} s: busy "
            f"{reduced.busy_s:.6f} s of {reduced.window_s:.6f} s")

    correct, checks = _check(
        answers, specs, lits, tables, config["limits"],
        None if chips is None else compare.off_mesh(records))

    ctx = Context(queries=records, window_s=window_s, setup_s=setup_s,
                  trace=reduced)
    metrics = {}
    for m in r["per_layer"] if trace else r["end_to_end"]:
        v = _reader(r["metrics_dir"], m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": sum(q["failed"] for q in records),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.device_ops,
                            "idle_gaps": reduced.idle_gaps}
    out["checks"] = checks
    return out
