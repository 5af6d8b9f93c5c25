"""Crash-replay property test (paper §4.4/§5): ANY interleaving of
trickle commits, deletes, moveouts and mergeouts, with a node failure +
rejoin + incremental recovery spliced in at arbitrary points, must yield
byte-identical query results to the same commit sequence applied to a
cluster that never failed.

Two clusters receive the identical DML stream; the "crashy" one
additionally runs a fail_node -> (more commits) -> rejoin_node -> (more
commits) -> recover_node cycle at positions chosen by the strategy.
Comparisons are exact: raw snapshot reads compare as sorted tuple sets
(identical values, container layout may legally differ), and aggregate
queries restrict to integer columns so no float summation order can
differ.
"""
import numpy as np
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.core import ColumnDef, SQLType, TableSchema, VerticaDB
from repro.core.recovery import recover_node, rejoin_node
from repro.engine import col

N_KEYS = 24


def _mk_db():
    db = VerticaDB(n_nodes=4, k_safety=1, block_rows=32)
    db.create_table(TableSchema("events", (
        ColumnDef("eid"), ColumnDef("key"), ColumnDef("bucket"),
        ColumnDef("val"))),
        sort_order=("bucket",), segment_by=("eid",))
    return db


def _commit_batch(db, seed, base):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 40))
    t = db.begin()
    db.insert(t, "events", {
        "eid": base + np.arange(n, dtype=np.int64),
        "key": rng.integers(0, N_KEYS, n),
        "bucket": rng.integers(0, 50, n),
        "val": rng.integers(-100, 100, n)})
    db.commit(t)
    return n


def _apply(db, op, base):
    """Apply one DML/maintenance op; returns rows inserted (0 if none)."""
    kind = op[0]
    if kind == "commit":
        return _commit_batch(db, op[1], base)
    if kind == "delete":
        t = db.begin()
        k = op[1] % N_KEYS
        db.delete(t, "events", lambda r: r["key"] == k)
        db.commit(t)
    elif kind == "moveout":
        db.run_tuple_mover(force_moveout=True)
    elif kind == "mover":
        db.run_tuple_mover()            # moveout-if-saturated + mergeouts
    return 0


def _tuples(rows):
    cols = sorted(rows)
    return sorted(zip(*[np.asarray(rows[c]).tolist() for c in cols]))


def _agg(db):
    q = (db.query("events").where(col("bucket") < 40)
         .group_by("key")
         .agg(n=("*", "count"), s=("val", "sum")))
    out = q.collect()
    order = np.argsort(np.asarray(out["key"]))
    return [(int(out["key"][i]), int(out["n"][i]), int(out["s"][i]))
            for i in order]


_OP = st.tuples(st.sampled_from(["commit", "commit", "delete", "moveout",
                                 "mover"]),
                st.integers(0, 2 ** 20))


@settings(max_examples=12)
@given(st.lists(_OP, min_size=3, max_size=10),
       st.integers(0, 3),              # node to crash
       st.integers(0, 2 ** 10),        # where in the stream it fails
       st.integers(0, 2 ** 10),        # ... rejoins
       st.integers(0, 2 ** 10))        # ... recovers
def test_crash_replay_equals_never_failed(ops, node, p_fail, p_rejoin,
                                          p_recover):
    ref = _mk_db()
    crashy = _mk_db()
    # seed both with one identical committed + moved-out batch
    base = 0
    for db in (ref, crashy):
        _commit_batch(db, 7, base)
        db.run_tuple_mover(force_moveout=True)
    base += 10 ** 6

    n_ops = len(ops)
    fail_at = p_fail % n_ops
    rejoin_at = fail_at + 1 + (p_rejoin % max(n_ops - fail_at, 1))
    recover_at = rejoin_at + (p_recover % max(n_ops - rejoin_at + 1, 1))

    for i, op in enumerate(ops):
        if i == fail_at:
            crashy.fail_node(node)
        if i == rejoin_at:
            rejoin_node(crashy, node)
        if i == recover_at:
            recover_node(crashy, node)
        _apply(ref, op, base)
        _apply(crashy, op, base)
        base += 10 ** 6
    if not crashy.nodes[node].serving():
        recover_node(crashy, node)

    # byte-identical visible state and (integer) aggregates
    assert _tuples(crashy.read_table("events")) == \
        _tuples(ref.read_table("events"))
    assert _agg(crashy) == _agg(ref)
    # the recovered node serves its own segment again: take its buddy
    # host down and the data must still all be there
    buddy_host = (node + 1) % 4
    ref.fail_node(buddy_host)
    crashy.fail_node(buddy_host)
    assert _tuples(crashy.read_table("events")) == \
        _tuples(ref.read_table("events"))
