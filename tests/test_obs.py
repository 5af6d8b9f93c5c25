"""Host spans, device scopes and load counters (repro/obs.py,
core/database.LoadStats)."""
import pathlib
import re
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import (ColumnDef, Encoding, SQLType, TableSchema,
                        VerticaDB)
from repro.core import database
from repro.core.projection import super_projection

REPO = pathlib.Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


def _used(pattern):
    names = set()
    for f in SRC.rglob("*.py"):
        names |= set(re.findall(pattern, f.read_text()))
    return names


def test_every_span_and_scope_is_registered_used_and_documented():
    spans = _used(r'obs\.span\(\s*"([^"]+)"')
    scopes = _used(r'named_scope\(\s*"([^"]+)"')
    assert spans == set(obs.SPANS)
    assert scopes == set(obs.SCOPES)
    perf = (REPO / "PERF.md").read_text()
    for name in list(obs.SPANS) + list(obs.SCOPES):
        assert f"`{name}`" in perf, name
    layers = set(re.findall(r"^\| ([a-z][a-z -]+) \|", perf, re.M))
    assert set(obs.SPANS.values()) | set(obs.SCOPES.values()) <= layers


def test_span_costs_nothing_but_a_check_without_a_trace():
    before = len(obs.recorded())
    with obs.span("exec.scan") as s:
        pass
    assert s is None
    assert len(obs.recorded()) == before


def test_spans_are_recorded_with_self_time_while_a_trace_runs(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        t0 = time.perf_counter()
        with obs.span("exec.query", qid=7):
            with obs.span("exec.scan"):
                time.sleep(0.02)
            with obs.span("exec.result_sync"):
                time.sleep(0.01)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    got = [r for r in obs.recorded() if t0 <= r[1] and r[2] <= t1]
    assert [r[0] for r in got] == ["exec.scan", "exec.result_sync",
                                   "exec.query"]
    scan, sync, query = got
    assert scan[3] == pytest.approx(scan[2] - scan[1])
    assert query[3] == pytest.approx(
        (query[2] - query[1]) - (scan[2] - scan[1]) - (sync[2] - sync[1]))
    assert 0 <= query[3] < 0.01


def _db():
    schema = TableSchema("t", (ColumnDef("k", SQLType.INT),
                               ColumnDef("d", SQLType.INT),
                               ColumnDef("v", SQLType.FLOAT)))
    db = VerticaDB(n_nodes=3, k_safety=0, block_rows=256)
    db.catalog.add_table(schema)
    db.create_projection(super_projection(
        schema, ("d",), ("k",), encodings={"d": Encoding.RLE}))
    return db


def _rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 1000, n), "d": rng.integers(0, 50, n),
            "v": np.round(rng.normal(100, 10, n), 2)}


def test_load_stats_count_rows_and_phases_fit_the_wall_time():
    db = _db()
    process_rows = database.PROCESS_LOAD.rows
    t0 = time.perf_counter()
    txn = db.begin(direct_to_ros=True)
    db.insert(txn, "t", _rows(5000))
    db.insert(txn, "t", _rows(3000, seed=1))
    db.commit(txn)
    wall = time.perf_counter() - t0
    st = db.load_stats
    assert st.rows == 8000
    assert database.PROCESS_LOAD.rows - process_rows >= 8000
    assert set(st.encode_s) == {"AUTO", "RLE"}
    phases = st.segment_s + st.sort_s + st.sma_s + sum(st.encode_s.values())
    assert 0 < phases <= st.load_s <= wall
    # a rolled-back insert commits no rows; a WOS load leaves the encode
    # to the tuple mover
    txn = db.begin()
    db.insert(txn, "t", _rows(100))
    db.rollback(txn)
    txn = db.begin()
    db.insert(txn, "t", _rows(200))
    encoded = dict(st.encode_s)
    db.commit(txn)
    assert st.rows == 8200
    assert st.encode_s == encoded
    db.run_tuple_mover(force_moveout=True)
    assert st.encode_s == encoded


def test_each_query_has_its_own_id():
    db = _db()
    txn = db.begin(direct_to_ros=True)
    db.insert(txn, "t", _rows(2000))
    db.commit(txn)
    q = db.query("t").group_by("d").agg(n=("*", "count"))
    q.collect()
    first = q.stats.query_id
    q.collect()
    assert q.stats.query_id > first > 0
