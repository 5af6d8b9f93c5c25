"""The trace reduction: busy union, idle share, gap labelling."""
import json
import pathlib

import pytest

from chipbench import tracing

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_busy_union_and_gap_labels_by_hand():
    ms = 1e6
    trace = {"spans": [["window", 0, 100 * ms],
                       ["query.Q1", 0, 40 * ms],
                       ["client", 40 * ms, 10 * ms],
                       ["query.Q5", 50 * ms, 50 * ms]],
             "device": {"TPU:0": [
                 ["fusion.1", 5 * ms, 10 * ms],       # 5..15
                 ["bitunpack", 10 * ms, 10 * ms],     # 10..20 overlaps
                 ["fusion.1", 60 * ms, 20 * ms],      # 60..80
                 ["fusion.2", 95 * ms, 20 * ms]]}}    # clipped at 100
    r = tracing.reduce(trace)
    assert r.window_s == pytest.approx(0.1)
    assert r.busy_s == pytest.approx(0.015 + 0.020 + 0.005)
    gaps = dict(r.idle_gaps)
    # 0..5 and 20..40 inside Q1; 40..50 client; 50..60, 80..95 inside Q5
    assert gaps["query.Q1"] == pytest.approx(0.025)
    assert gaps["query.Q5"] == pytest.approx(0.025)
    assert gaps["client"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s)
    ops = dict(r.device_ops)
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert ops["bitunpack"] == pytest.approx(0.010)
    assert ops["fusion.2"] == pytest.approx(0.005)


def test_busy_is_averaged_over_chips():
    ms = 1e6
    trace = {"spans": [["window", 0, 10 * ms]],
             "device": {"TPU:0": [["a", 0, 10 * ms]],
                        "TPU:1": [["a", 0, 5 * ms]]}}
    r = tracing.reduce(trace)
    assert r.busy_s == pytest.approx(0.0075)
    assert dict(r.idle_gaps)["client"] == pytest.approx(0.0025)


def test_trace_without_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce({"spans": [], "device": {"TPU:0": []}})
    with pytest.raises(ValueError):
        tracing.reduce({"spans": [["window", 0, 1]], "device": {}})


def _raster(trace, step_ns=1000.0):
    """Busy time and per-label idle time by sampling the window every
    ``step_ns``: a second, independent reading of the same trace."""
    import numpy as np
    w = [s for s in trace["spans"] if s[0] == "window"][0]
    t = np.arange(w[1], w[1] + w[2], step_ns) + step_ns / 2
    busy = np.zeros(t.size, bool)
    for _, s, d in trace["device"]["TPU:0"]:
        busy[(t >= s) & (t < s + d)] = True
    label = np.full(t.size, "client", dtype=object)
    for name, s, d in trace["spans"]:
        if name != "window":
            label[(t >= s) & (t < s + d)] = name
    idle = {}
    for name in set(label[~busy]):
        idle[name] = float(np.count_nonzero(~busy & (label == name))) \
            * step_ns * 1e-9
    return float(busy.sum()) * step_ns * 1e-9, idle


def test_recorded_chip_trace():
    """0.4 s of a traced scan_agg window on one TPU v5 lite."""
    trace = json.loads((DATA / "scan_agg_trace.json").read_text())
    r = tracing.reduce(trace)
    busy, idle = _raster(trace)
    assert r.window_s == pytest.approx(0.4)
    # 1 us sampling of ~1.7k op intervals: within a few us per interval
    assert r.busy_s == pytest.approx(busy, abs=2e-4)
    got = dict(r.idle_gaps)
    assert set(got) == set(idle)
    for name, v in idle.items():
        assert got[name] == pytest.approx(v, abs=2e-4), name
    assert 0 < r.busy_s < r.window_s
    assert sum(got.values()) == pytest.approx(r.window_s - r.busy_s)
    # the group-by scatter of the fused program is the busiest op here
    assert r.device_ops[0][0].startswith("jit_fused/")
    assert sum(v for _, v in r.device_ops) <= r.busy_s + 1e-9
