"""The trace read down to the program's layers, and the per-layer metrics
that read the program's spans and load counters."""
import importlib.util
import json
import pathlib

import jax
import pytest

from chipbench import harness, spantrace, tracing
from chipbench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1e6


def _nested_trace():
    """One 100 ms window: Q3 0..60 holds exec.query 2..58, which holds
    exec.scan 5..25 and exec.result_sync 30..50; the client 60..70; Q1
    70..100 with no program span."""
    return {"spans": [["window", 0, 100 * MS],
                      ["query.Q3", 0, 60 * MS],
                      ["exec.query", 2 * MS, 56 * MS],
                      ["exec.scan", 5 * MS, 20 * MS],
                      ["exec.result_sync", 30 * MS, 20 * MS],
                      ["client", 60 * MS, 10 * MS],
                      ["query.Q1", 70 * MS, 30 * MS]],
            "device": {"TPU:0": [
                ["jit_fused/fusion.1", 10 * MS, 10 * MS, "groupby"],
                ["jit_fused/while.2", 35 * MS, 10 * MS, "join_probe"],
                ["jit_fused/fusion.3", 40 * MS, 10 * MS, "join_probe"],
                ["jit_gather/copy", 80 * MS, 5 * MS, ""]]},
            "programs": {"TPU:0": [10 * MS, 35 * MS, 80 * MS, 120 * MS]}}


def test_idle_goes_to_the_innermost_span():
    r = spantrace.reduce(_nested_trace())
    gaps = dict(r.idle_all)
    assert r.busy_s == pytest.approx(0.010 + 0.015 + 0.005)
    # 0..2 and 58..60 in Q3 itself; 2..5 and 25..30 and 50..58 in
    # exec.query; 5..10 and 20..25 in exec.scan; 30..35 in result_sync
    assert gaps["query.Q3"] == pytest.approx(0.004)
    assert gaps["exec.query"] == pytest.approx(0.003 + 0.005 + 0.008)
    assert gaps["exec.scan"] == pytest.approx(0.010)
    assert gaps["exec.result_sync"] == pytest.approx(0.005)
    assert gaps["client"] == pytest.approx(0.010)
    assert gaps["query.Q1"] == pytest.approx(0.025)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)


def test_self_time_leaves_out_nested_spans():
    r = spantrace.reduce(_nested_trace())
    self_s = r.span_self_s
    assert self_s["exec.query"] == pytest.approx(0.056 - 0.020 - 0.020)
    assert self_s["exec.scan"] == pytest.approx(0.020)
    assert self_s["query.Q3"] == pytest.approx(0.004)
    assert sum(self_s.values()) == pytest.approx(r.window_s)


def test_device_time_by_scope_and_programs():
    trace = _nested_trace()
    trace["device"]["TPU:1"] = [["jit_fused/fusion.1", 0, 40 * MS,
                                 "groupby"]]
    trace["programs"]["TPU:1"] = [0.0]
    r = spantrace.reduce(trace)
    # join_probe: the while and the fusion inside it overlap: 35..50
    assert r.scope_busy_s["join_probe"] == pytest.approx(0.015 / 2)
    assert r.scope_busy_s["groupby"] == pytest.approx((0.010 + 0.040) / 2)
    assert r.scope_busy_s[""] == pytest.approx(0.005 / 2)
    # 120 ms starts after the window closes
    assert r.programs == pytest.approx((3 + 1) / 2)


_DUMP = """HloModule {module}, is_scheduled=true, entry_computation_layout={{}}

%fused_computation (param_0: s32[8]) -> s32[8] {{
  %param_0 = s32[8]{{0}} parameter(0)
  ROOT %add.1 = s32[8]{{0}} add(s32[8]{{0}} %param_0, s32[8]{{0}} %param_0), \
metadata={{op_name="jit(fused)/jit(join_lookup)/join_probe/add"}}
}}

ENTRY %main.3 (a: s32[8]) -> (s32[8]) {{
  %a = s32[8]{{0}} parameter(0)
  %fusion.2 = s32[8]{{0:T(1024)}} fusion(s32[8]{{0}} %a), kind=kLoop, \
calls=%fused_computation, metadata={{op_name="{fusion}"}}
  %while.12 = (s32[], s32[8]{{0}}) while((s32[], s32[8]{{0}}) %t), \
metadata={{op_name="jit(fused)/jit(join_lookup)/join_probe/jit(searchsorted)/while"}}
  ROOT %tuple = (s32[8]{{0}}) tuple(s32[8]{{0}} %fusion.2)
}}
"""


def test_op_scopes_from_the_optimized_hlo_dump(tmp_path):
    """Each op of a dumped program maps to the innermost registered scope
    of its ``op_name``; programs of one name that disagree give ``?``."""
    for i, (module, fusion) in enumerate([
            ("jit_fused", "jit(fused)/jit(groupby_dense)/groupby/scatter"),
            ("jit_fused", "jit(fused)/predicate/and"),
            ("jit_cd_mask", "jit(cd_mask)/cd_decode/jit(_where)/select_n")]):
        (tmp_path / f"module_{i:04d}.{module}.tpu_after_optimizations.txt"
         ).write_text(_DUMP.format(module=module, fusion=fusion))
    (tmp_path / "module_0000.jit_fused.before_optimizations.txt"
     ).write_text(_DUMP.format(module="jit_fused", fusion="x/groupby"))
    scopes = spantrace.op_scopes(str(tmp_path))
    assert scopes["jit_fused/while.12 tuple"] == "join_probe"
    assert scopes["jit_fused/fusion.2 s32[8]"] == "?"
    assert scopes["jit_cd_mask/fusion.2 s32[8]"] == "cd_decode"
    assert scopes["jit_cd_mask/add.1 s32[8]"] == "join_probe"
    assert scopes["jit_cd_mask/a s32[8]"] == ""
    assert spantrace.op_scopes(str(tmp_path / "none")) == {}


def test_recorded_chip_trace_reduces_as_before():
    """No program span in the recorded trace: busy time, idle gaps and
    device ops equal the harness's own reduction, number for number."""
    trace = json.loads((DATA / "scan_agg_trace.json").read_text())
    old = tracing.reduce(trace)
    new = spantrace.reduce({"spans": trace["spans"],
                            "device": {c: [e + [""] for e in ev]
                                       for c, ev in trace["device"].items()}})
    assert new.busy_s == old.busy_s
    assert new.window_s == old.window_s
    assert new.idle_gaps == old.idle_gaps
    assert new.device_ops == old.device_ops
    assert new.programs == 0


def test_innermost_keeps_untouched_spans_as_they_are():
    spans = [["query.Q1", 0.1, 0.7], ["client", 0.8, 0.3],
             ["query.Q2", 1.1, 2.0]]
    assert spantrace.innermost(spans) == spans


def test_profiled_tiny_query_nests_program_spans(tmp_path):
    """A profiler run of one query on the CPU: the program's spans sit
    inside the benchmark's query span on the host plane."""
    from chipbench import datagen, program
    root = tiny.make_root(tmp_path, rows=20_000)
    cfg = json.loads((root / "chipbench" / "configs" /
                      "cstore7_sf2.json").read_text())
    db = program.load(cfg, datagen.generate(cfg, 5))
    spec = {"table": "lineitem",
            "where": [["l_shipdate", ">", 100]],
            "group_by": ["l_suppkey"], "agg": {"s": ["l_qty", "sum"]}}
    q = program.build_query(db, spec, {})
    q.collect()
    tracing.start(str(tmp_path / "trace"))
    try:
        with jax.profiler.TraceAnnotation("query.Q3"):
            q.collect()
    finally:
        tracing.stop()
    spans = spantrace.read_trace(str(tmp_path / "trace"))["spans"]
    (_, q0, qd), = [s for s in spans if s[0] == "query.Q3"]
    inside = {name for name, s, d in spans
              if name.startswith("exec.") and q0 <= s and s + d <= q0 + qd}
    assert {"exec.query", "exec.plan", "exec.scan", "exec.dispatch",
            "exec.result_sync", "exec.finalize"} <= inside
    assert spantrace.spans_per_query(spans)["Q3"] == len(
        [s for s in spans if s[0].startswith("exec.")])


def _reader(name):
    path = tiny.CHIPBENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(n_queries=4):
    # four queries of 10 s each, back to back from t = 100 s
    queries = [{"template": "Q1", "lit": 0, "latency_s": 10.0,
                "end": 110.0 + 10 * i, "stats": None, "failed": False}
               for i in range(n_queries)]
    return harness.Context(queries=queries, window_s=40.0, setup_s=1.0,
                           trace=None)


@pytest.mark.parametrize("metric,span", [
    ("scan_assembly_ms_per_query", "exec.scan"),
    ("survivor_sync_ms_per_query", "exec.cd_sync"),
    ("result_wait_ms_per_query", "exec.result_sync")])
def test_span_readers(metric, span, monkeypatch):
    from repro import obs
    read = _reader(metric)
    monkeypatch.setattr(obs, "recorded", lambda: [])
    assert read(_ctx()) is None
    monkeypatch.setattr(obs, "recorded", lambda: [
        ("exec.query", 100.5, 101.5, 0.2),
        (span, 101.0, 101.2, 0.2),
        (span, 121.0, 121.6, 0.6),
        (span, 95.0, 95.5, 0.5)])          # before the window
    assert read(_ctx()) == pytest.approx(1e3 * 0.8 / 4)
    assert read(_ctx(0)) is None


def test_load_reader(monkeypatch):
    from repro.core import database
    read = _reader("load_s_per_mrow")
    monkeypatch.setattr(database, "PROCESS_LOAD", database.LoadStats())
    assert read(_ctx()) is None
    monkeypatch.setattr(database, "PROCESS_LOAD",
                        database.LoadStats(rows=2_000_000, load_s=11.0))
    assert read(_ctx()) == pytest.approx(5.5)


# the packed cell's cache rule leaves room for the decoded working set
# below ~100k rows, and the code-domain scan runs only where it has none
@pytest.mark.parametrize("cell,templates,rows,metrics", [
    ("cstore7_sf2.join", ("Q7",), tiny.ROWS,
     {"scan_assembly_ms_per_query", "result_wait_ms_per_query",
      "load_s_per_mrow"}),
    ("cstore7_sf2_packedcache.scan_agg", ("Q3",), 200_000,
     {"survivor_sync_ms_per_query", "result_wait_ms_per_query",
      "load_s_per_mrow"})])
def test_traced_run_reports_the_new_metrics(tmp_path, monkeypatch, cell,
                                            templates, rows, metrics):
    """A traced run of each listed cell reports its new metrics (the
    device side is the recorded chip trace: a CPU run has no TPU plane)."""
    recorded = json.loads((DATA / "scan_agg_trace.json").read_text())
    monkeypatch.setattr(tracing, "read_trace", lambda d: recorded)
    root = tiny.make_root(tmp_path, rows=rows, templates=templates)
    out = harness.run_cell(root, cell, 2**31 + 11, 0.5, True, t_start=0.0,
                           devices=jax.devices(),
                           trace_dir=str(tmp_path / "trace"))
    assert out["correct"] is True, out["checks"]
    assert metrics <= set(out["metrics"])
    for name in metrics:
        assert out["metrics"][name]["value"] > 0, name
