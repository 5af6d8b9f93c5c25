"""Discovery by name: a new configuration, traffic mix and per-layer
metric become a runnable cell as new files plus BENCHMARK.json entries,
with no edit to a file that exists."""
import json

from chipbench.tests import tiny


def test_new_files_make_a_runnable_cell(tmp_path):
    root = tiny.make_root(tmp_path)
    cb = root / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}

    cfg = json.loads((cb / "configs" / "cstore7_sf2.json").read_text())
    cfg["name"] = "test_only_small"
    cfg["tables"]["lineitem"]["rows"] = 30_000
    (cb / "configs" / "test_only_small.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "test_only_mix.json").write_text(json.dumps({
        "client": {"kind": "closed", "clients": 1},
        "literal_sets": 2,
        "templates": {"QX": {
            "table": "lineitem", "params": {"q": [10, 40]},
            "where": [["l_qty", "<", "q"], ["l_shipdate", ">=", 100]],
            "group_by": ["l_suppkey"],
            "agg": {"n": ["*", "count"], "m": ["l_extprice", "avg"]}}}}))
    (cb / "metrics" / "test_only_queries.py").write_text(
        "def read(ctx):\n    return float(len(ctx.queries))\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "test_only_small", "source": "test",
                             "file": "chipbench/configs/test_only_small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "test_only_small.mix",
                               "config": "test_only_small",
                               "traffic": "test_only_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "test_only_queries", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "queries_per_s",
                               "workloads": ["test_only_small.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    from chipbench import harness
    r = harness.resolve(root, harness.load_bench(root), "test_only_small.mix")
    assert [m["name"] for m in r["per_layer"]] == ["test_only_queries"]
    assert {m["name"] for m in r["end_to_end"]} == {"queries_per_s",
                                                    "setup_s"}
    out = tiny.run(root, "test_only_small.mix")
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"queries_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_traced_run_reports_the_per_layer_metrics(tmp_path, monkeypatch):
    """--trace 1 reports the cell's per-layer metrics, the device's busy
    and window seconds and the breakdown; the trace is the recorded chip
    trace (a CPU run has no TPU plane to read)."""
    from chipbench import harness, tracing
    recorded = json.loads((tiny.CHIPBENCH / "tests" / "data" /
                           "scan_agg_trace.json").read_text())
    monkeypatch.setattr(tracing, "read_trace", lambda d: recorded)
    root = tiny.make_root(tmp_path, templates=("Q2", "Q6"))
    out = harness.run_cell(root, "cstore7_sf2.scan_agg", 2**31 + 3, 0.5,
                           True, t_start=0.0, devices=tiny.jax.devices(),
                           trace_dir=str(tmp_path / "trace"))
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"device_idle_share",
                                   "frontend_ms_per_query",
                                   "blocks_pruned_share"}
    assert 0 < out["metrics"]["device_idle_share"]["value"] < 100
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] == 0.4
    assert len(out["breakdown"]["device_ops"]) == 10
    assert list(out)[-1] == "checks"
