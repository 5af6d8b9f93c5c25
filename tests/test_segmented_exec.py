"""Differential oracle harness for segmented multi-device execution.

Every query in a seeded generated corpus runs twice -- single-node
(mesh detached) and segmented over a jax device mesh (engine/segmented.py)
-- and the results must match row-for-row: same groups, same counts, same
aggregates (floats to tolerance; partial sums merge in a different order).

The mesh spans every device the process sees: 1 under plain tier-1
pytest (the degenerate but still fully exercised 1-shard path), 8 under
``scripts/verify.sh``'s segmented tier, which re-runs this file with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

The star schema is built so the planner's three exchange strategies all
occur across the corpus:

  customer  segmented by c_custkey = fact's segmentation -> co-located
  supplier  replicated                                   -> co-located
  parts     large, segmented by p_partkey != fact seg    -> resegment
  promo     small, segmented by pr_day   != fact seg     -> broadcast
"""
import jax
import numpy as np
import pytest

from repro.core import ColumnDef, SQLType, TableSchema, VerticaDB
from repro.core.recovery import recover_node
from repro.engine import col, execute
from repro.engine.exchange import resegment
from repro.planner import plan_query

N_FACT = 4000
N_CUST, N_SUPP, N_PART, N_PROMO = 300, 40, 2000, 30


def make_db(k_safety=1, n_nodes=4, seed=7):
    rng = np.random.default_rng(seed)
    db = VerticaDB(n_nodes=n_nodes, k_safety=k_safety, block_rows=64)
    db.create_table(TableSchema("sales", (
        ColumnDef("sale_id"), ColumnDef("custkey"), ColumnDef("suppkey"),
        ColumnDef("partkey"), ColumnDef("day"), ColumnDef("qty"),
        ColumnDef("delta"), ColumnDef("price", SQLType.FLOAT))),
        sort_order=("day",), segment_by=("custkey",))
    db.create_table(TableSchema("customer", (
        ColumnDef("c_custkey"), ColumnDef("c_nation"))),
        sort_order=("c_custkey",), segment_by=("c_custkey",))
    db.create_table(TableSchema("supplier", (
        ColumnDef("s_suppkey"), ColumnDef("s_region"))),
        sort_order=("s_suppkey",), segment_by=())        # replicated
    db.create_table(TableSchema("parts", (
        ColumnDef("p_partkey"), ColumnDef("p_cat"))),
        sort_order=("p_partkey",), segment_by=("p_partkey",))
    db.create_table(TableSchema("promo", (
        ColumnDef("pr_day"), ColumnDef("pr_kind"))),
        sort_order=("pr_day",), segment_by=("pr_day",))
    t = db.begin()
    db.insert(t, "sales", {
        "sale_id": np.arange(N_FACT, dtype=np.int64),
        "custkey": rng.integers(0, N_CUST, N_FACT),
        "suppkey": rng.integers(0, N_SUPP, N_FACT),
        "partkey": rng.integers(0, N_PART, N_FACT),
        "day": rng.integers(0, 365, N_FACT),
        "qty": rng.integers(1, 50, N_FACT),
        "delta": rng.integers(-40, 40, N_FACT),      # negative group keys
        "price": np.round(rng.normal(100, 10, N_FACT), 2)})
    db.insert(t, "customer", {
        "c_custkey": np.arange(N_CUST, dtype=np.int64),
        "c_nation": rng.integers(0, 12, N_CUST)})
    db.insert(t, "supplier", {
        "s_suppkey": np.arange(N_SUPP, dtype=np.int64),
        "s_region": rng.integers(0, 5, N_SUPP)})
    db.insert(t, "parts", {
        "p_partkey": np.arange(N_PART, dtype=np.int64),
        "p_cat": rng.integers(0, 9, N_PART)})
    db.insert(t, "promo", {
        "pr_day": np.arange(N_PROMO, dtype=np.int64) * 12,
        "pr_kind": rng.integers(0, 4, N_PROMO)})
    db.commit(t)
    db.run_tuple_mover(force_moveout=True)
    return db


@pytest.fixture(scope="module")
def star_db():
    return make_db()


# -- join templates: (dim, on, carried col, forced exchange strategy) --
JOINS = {
    "customer": (("custkey", "c_custkey"), "c_nation", "local"),
    "supplier": (("suppkey", "s_suppkey"), "s_region", "local"),
    "parts": (("partkey", "p_partkey"), "p_cat", "resegment"),
    "promo": (("day", "pr_day"), "pr_kind", "broadcast"),
}


def gen_query(db, rng):
    """One random corpus member: filters, 0-3 joins, 1-3 group keys,
    aggregates, sometimes HAVING / ORDER BY / LIMIT."""
    qb = db.query("sales")
    if rng.random() < 0.7:
        lo = int(rng.integers(0, 280))
        hi = lo + int(rng.integers(30, 200))
        qb = qb.where((col("day") >= lo) & (col("day") < hi))
    if rng.random() < 0.3:
        qb = qb.where(col("qty") > int(rng.integers(1, 25)))
    dims = [d for d in JOINS if rng.random() < 0.45][:3]
    pool = ["suppkey", "delta", "day"]
    for d in dims:
        on, carried, _ = JOINS[d]
        where = None
        if d == "customer" and rng.random() < 0.5:
            where = col("c_nation") < int(rng.integers(4, 12))
        qb = qb.join(d, on=on, cols=(carried,), where=where)
        pool.append(carried)
    k = int(rng.integers(1, min(3, len(pool)) + 1))
    keys = [pool[i] for i in rng.choice(len(pool), size=k, replace=False)]
    qb = qb.group_by(*keys)
    qb = qb.agg(n=("*", "count"))
    for name, spec in (("s", ("qty", "sum")), ("mn", ("price", "min")),
                       ("mx", ("price", "max")), ("a", ("price", "avg"))):
        if rng.random() < 0.4:
            qb = qb.agg(**{name: spec})
    if rng.random() < 0.25:
        qb = qb.having(col("n") > int(rng.integers(1, 4)))
    if rng.random() < 0.4:
        # deterministic total order: count desc, then every group key
        qb = qb.order_by("-n", *keys).limit(int(rng.integers(5, 25)))
    return qb


def canon(out, ordered):
    """Sorted row-set view (already-ordered outputs keep their order)."""
    cols = sorted(out)
    if not cols or len(next(iter(out.values()))) == 0:
        return {c: np.asarray(out[c]) for c in cols}
    if ordered:
        return {c: np.asarray(out[c]) for c in cols}
    order = np.lexsort([np.asarray(out[c]) for c in cols])
    return {c: np.asarray(out[c])[order] for c in cols}


def assert_match(ref, seg, ordered, label):
    a, b = canon(ref, ordered), canon(seg, ordered)
    assert set(a) == set(b), (label, sorted(a), sorted(b))
    for c in a:
        av, bv = a[c], b[c]
        assert av.shape == bv.shape, (label, c, av.shape, bv.shape)
        if av.dtype.kind in "iub" and bv.dtype.kind in "iub":
            assert (av == bv).all(), (label, c, av[:8], bv[:8])
        else:
            assert np.allclose(np.asarray(av, np.float64),
                               np.asarray(bv, np.float64),
                               rtol=1e-3, atol=1e-2), \
                (label, c, av[:8], bv[:8])


def run_both(db, qb):
    db.detach_mesh()
    ref, _ = execute(db, qb.to_ir())
    db.attach_mesh()
    out, stats = execute(db, qb.to_ir())
    db.detach_mesh()
    return ref, out, stats


# ---------------------------------------------------------------------------
# the corpus
# ---------------------------------------------------------------------------

def test_differential_corpus(star_db):
    """~20 seeded queries: segmented == single-node, exactly, and all
    three exchange strategies occur across the corpus."""
    db = star_db
    rng = np.random.default_rng(2024)
    exchanges_seen = set()
    for i in range(20):
        qb = gen_query(db, rng)
        ir = qb.to_ir()
        ref, out, stats = run_both(db, qb)
        assert stats.segmented, (i, ir.signature())
        assert stats.n_shards == jax.device_count()
        exchanges_seen.update(e for e in stats.exchange.split(";") if e)
        assert stats.reseg_overflow == 0
        assert_match(ref, out, ordered=bool(ir.order_by), label=f"q{i}")
    assert {"local", "broadcast", "resegment"} <= exchanges_seen, \
        exchanges_seen


def test_exchange_strategy_per_join(star_db):
    """The planner's per-join exchange choice matches the physical design
    each dimension was built for."""
    db = star_db
    for dim, (on, carried, expected) in JOINS.items():
        qb = (db.query("sales").join(dim, on=on, cols=(carried,))
              .group_by(carried).agg(n=("*", "count")))
        plan = plan_query(db, qb.to_ir())
        assert plan.join_exchanges == (expected,), (dim, plan.join_strategy)
        ref, out, stats = run_both(db, qb)
        assert stats.exchange == expected
        assert_match(ref, out, ordered=False, label=dim)


def test_scalar_and_snowflake(star_db):
    db = star_db
    # scalar aggregate, no group keys
    qb = db.query("sales").where(col("day") > 200).agg(
        n=("*", "count"), s=("qty", "sum"), a=("price", "avg"))
    ref, out, stats = run_both(db, qb)
    assert stats.segmented
    assert_match(ref, out, ordered=False, label="scalar")
    # snowflake: the second join's key only exists after the first join,
    # so the planner must demote it to broadcast
    db.create_table(TableSchema("nation", (
        ColumnDef("n_nation"), ColumnDef("n_cont"))),
        sort_order=("n_nation",), segment_by=("n_nation",))
    t = db.begin()
    db.insert(t, "nation", {"n_nation": np.arange(12, dtype=np.int64),
                            "n_cont": np.arange(12, dtype=np.int64) % 3})
    db.commit(t)
    db.run_tuple_mover(force_moveout=True)
    qb = (db.query("sales")
          .join("customer", on=("custkey", "c_custkey"), cols=("c_nation",))
          .join("nation", on=("c_nation", "n_nation"), cols=("n_cont",))
          .group_by("n_cont").agg(n=("*", "count")))
    plan = plan_query(db, qb.to_ir())
    assert plan.join_exchanges[1] == "broadcast", plan.join_strategy
    ref, out, stats = run_both(db, qb)
    assert stats.segmented
    assert_match(ref, out, ordered=False, label="snowflake")


def test_repeat_resegment_key_becomes_local(star_db):
    """Two joins probing the SAME fact key, both of which would resegment:
    after the first exchange the probe side is already placed by that key,
    so the second join must run local (one exchange, not two -- and not a
    crash on the consumed destination column)."""
    db = star_db
    rng = np.random.default_rng(5)
    db.create_table(TableSchema("partsx", (
        ColumnDef("px_partkey"), ColumnDef("px_weight"))),
        sort_order=("px_partkey",), segment_by=("px_weight",))
    t = db.begin()
    db.insert(t, "partsx", {
        "px_partkey": np.arange(N_PART, dtype=np.int64),
        "px_weight": rng.integers(0, 7, N_PART)})
    db.commit(t)
    db.run_tuple_mover(force_moveout=True)
    qb = (db.query("sales")
          .join("parts", on=("partkey", "p_partkey"), cols=("p_cat",))
          .join("partsx", on=("partkey", "px_partkey"),
                cols=("px_weight",))
          .group_by("p_cat", "px_weight").agg(n=("*", "count")))
    plan = plan_query(db, qb.to_ir())
    assert plan.join_exchanges == ("resegment", "local"), \
        plan.join_strategy
    ref, out, stats = run_both(db, qb)
    assert stats.segmented
    assert_match(ref, out, ordered=False, label="repeat-reseg-key")


def test_plan_cache_hit_keyed_by_mesh(star_db):
    db = star_db
    qb = (db.query("sales").where(col("qty") > 10)
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")))
    _, _, s1 = run_both(db, qb)
    _, out2, s2 = run_both(db, qb)
    assert s1.segmented and s2.segmented
    assert s2.plan_cache == "hit"
    # the second run must also still be correct (cached program + slab)
    db.detach_mesh()
    ref, _ = execute(db, qb.to_ir())
    assert_match(ref, out2, ordered=False, label="warm")


def test_failover_to_buddy_shards():
    """fail_node(): scans transparently route to buddy-projection shards
    (k_safety=1) and the segmented result is unchanged."""
    db = make_db(k_safety=1, seed=11)
    queries = [
        db.query("sales").where(col("day") < 180)
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")),
        db.query("sales")
          .join("customer", on=("custkey", "c_custkey"), cols=("c_nation",))
          .group_by("c_nation").agg(n=("*", "count")),
        db.query("sales")
          .join("parts", on=("partkey", "p_partkey"), cols=("p_cat",))
          .group_by("p_cat").agg(n=("*", "count"), mx=("price", "max")),
    ]
    refs = [execute(db, qb.to_ir())[0] for qb in queries]
    db.fail_node(1)
    for qb, ref in zip(queries, refs):
        plan = plan_query(db, qb.to_ir())
        assert any(owner.endswith("_b1") for _, owner in plan.sources), \
            "expected a buddy store in the failover routing"
        db.attach_mesh()
        out, stats = execute(db, qb.to_ir())
        db.detach_mesh()
        assert stats.segmented
        assert_match(ref, out, ordered=False, label="failover")


def test_plan_cache_distinguishes_build_placement():
    """Two databases with identically-named tables but different dim
    segmentation (segmented-by-key vs replicated) produce the same
    logical signature and exchange plan ('local'), yet need different
    shard_map in_specs -- the plan cache must not hand one the other's
    executable."""
    def mk(replicated):
        rng = np.random.default_rng(3)
        db = VerticaDB(n_nodes=4, k_safety=0, block_rows=64)
        db.create_table(TableSchema("f", (
            ColumnDef("k"), ColumnDef("v"))),
            sort_order=("k",), segment_by=("k",))
        db.create_table(TableSchema("d", (
            ColumnDef("dk"), ColumnDef("attr"))),
            sort_order=("dk",),
            segment_by=() if replicated else ("dk",))
        t = db.begin()
        db.insert(t, "f", {"k": rng.integers(0, 50, 1000),
                           "v": rng.integers(0, 100, 1000)})
        db.insert(t, "d", {"dk": np.arange(50, dtype=np.int64),
                           "attr": np.arange(50, dtype=np.int64) % 5})
        db.commit(t)
        db.run_tuple_mover(force_moveout=True)
        return db
    for replicated in (False, True):
        db = mk(replicated)
        qb = (db.query("f").join("d", on=("k", "dk"), cols=("attr",))
              .group_by("attr").agg(n=("*", "count"), s=("v", "sum")))
        ref, out, stats = run_both(db, qb)
        assert stats.segmented
        assert stats.exchange == "local"
        assert_match(ref, out, ordered=False,
                     label=f"placement-{replicated}")


def test_fallback_outside_segmented_subset(star_db):
    """Plain selects fall back to the single-node pipeline untouched."""
    db = star_db
    qb = db.query("sales").where(col("day") == 17).select("sale_id", "qty")
    db.detach_mesh()
    ref, _ = execute(db, qb.to_ir())
    db.attach_mesh()
    out, stats = execute(db, qb.to_ir())
    db.detach_mesh()
    assert not stats.segmented
    assert_match(ref, out, ordered=False, label="select")


# ---------------------------------------------------------------------------
# distributed trickle load: writes interleaved between segmented queries
# ---------------------------------------------------------------------------

def _trickle(db, rng, n=60, base=100_000):
    """One small committed batch into the fact table (lands in per-shard
    WOS slabs, ring-tagged at commit)."""
    t = db.begin()
    db.insert(t, "sales", {
        "sale_id": base + np.arange(n, dtype=np.int64),
        "custkey": rng.integers(0, N_CUST, n),
        "suppkey": rng.integers(0, N_SUPP, n),
        "partkey": rng.integers(0, N_PART, n),
        "day": rng.integers(0, 365, n),
        "qty": rng.integers(1, 50, n),
        "delta": rng.integers(-40, 40, n),
        "price": np.round(rng.normal(100, 10, n), 2)})
    return db.commit(t)


def test_trickle_load_interleaved_oracle():
    """The 20-query differential corpus with trickle-load commits BETWEEN
    queries: segmented results must keep matching single-node, and after
    the first query per container-state the cached ROS slab must stay
    warm (only the small WOS delta re-slabs) even though every commit
    advances the cluster epoch."""
    db = make_db(seed=31)
    rng = np.random.default_rng(77)
    base = 100_000
    ros_state_seen = False
    for i in range(20):
        if i % 2 == 1:           # trickle between queries
            _trickle(db, rng, base=base)
            base += 1000
        if i == 13:              # a moveout mid-stream: containers change
            db.run_tuple_mover(force_moveout=True)
        qb = gen_query(db, rng)
        ir = qb.to_ir()
        ref, out, stats = run_both(db, qb)
        assert stats.segmented, (i, ir.signature())
        assert_match(ref, out, ordered=bool(ir.order_by), label=f"t{i}")
        if i >= 1 and "+wos" in stats.seg_slab:
            ros_state_seen = True
    assert ros_state_seen, "no query observed a WOS delta slab"


def test_trickle_commit_keeps_ros_slab_warm():
    """A commit that lands purely in the WOS must NOT invalidate the
    cached ROS slab: the epoch advances but ROS visibility (its epoch
    ceiling) is unchanged, so the warm query re-slabs only the delta."""
    db = make_db(seed=32)
    rng = np.random.default_rng(5)
    qb = (db.query("sales").where(col("qty") > 5)
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")))
    _, _, s1 = run_both(db, qb)
    assert s1.seg_slab == "miss"
    _trickle(db, rng)                       # epoch advances, WOS only
    ref, out, s2 = run_both(db, qb)
    assert s2.seg_slab == "hit+wos", s2.seg_slab
    assert_match(ref, out, ordered=False, label="warm-ros+wos")
    # moveout drains the WOS into new containers: the old slab is evicted
    # precisely (key carries the container set) and the next run misses
    db.run_tuple_mover(force_moveout=True)
    ref, out, s3 = run_both(db, qb)
    assert s3.seg_slab == "miss", s3.seg_slab
    assert_match(ref, out, ordered=False, label="post-moveout")


def test_fail_load_rejoin_recover_cycle():
    """The full distributed-ingest availability story: fail a node, keep
    trickle-loading (buddy serves its segments), REJOIN it (it receives
    new commits but serves no reads), keep loading, then incremental
    recovery replays ONLY the epochs missed while down -- adopting
    segment-aligned buddy containers wholesale -- and the differential
    oracle holds at every stage."""
    db = make_db(k_safety=1, seed=41)
    rng = np.random.default_rng(13)
    queries = [
        db.query("sales").where(col("day") < 250)
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")),
        db.query("sales")
          .join("customer", on=("custkey", "c_custkey"),
                cols=("c_nation",))
          .group_by("c_nation").agg(n=("*", "count")),
        db.query("sales")
          .join("parts", on=("partkey", "p_partkey"), cols=("p_cat",))
          .group_by("p_cat").agg(n=("*", "count"), s=("qty", "sum")),
    ]

    def check(label):
        for qi, qb in enumerate(queries):
            ref, out, stats = run_both(db, qb)
            assert stats.segmented
            assert_match(ref, out, ordered=False, label=f"{label}-{qi}")

    db.fail_node(1)
    _trickle(db, rng, base=200_000)        # loads route around the corpse
    check("down")
    # move the while-down loads into ROS on the buddy (moveout only: a
    # mergeout would fold them into pre-failure containers): recovery can
    # then adopt whole segment-aligned containers instead of replaying rows
    db.run_tuple_mover(force_moveout=True, do_mergeout=False)
    e_join = db.rejoin_node(1)
    assert db.nodes[1].up and db.nodes[1].recovering
    # rejoined-but-recovering: reads still route to the buddy...
    plan = plan_query(db, queries[0].to_ir())
    assert any(owner.endswith("_b1") for _, owner in plan.sources)
    # ...but NEW commits land on node 1 live (its WOS fills again)
    _trickle(db, rng, base=300_000)
    assert db.nodes[1].stores["sales_super"].wos.n_rows > 0
    check("recovering")
    replayed = recover_node(db, 1)
    assert not db.nodes[1].recovering
    rec = db.nodes[1].last_recovery
    assert rec["replay_hi"] == e_join      # only missed epochs replayed
    assert rec["adopted_containers"] > 0   # wholesale container copies
    assert replayed.get("sales_super", 0) > 0
    check("recovered")
    # node 1 must now serve its own segment: fail its buddy host and the
    # oracle still holds (would raise AvailabilityError pre-recovery)
    db.fail_node(2)
    check("buddy-down")


# ---------------------------------------------------------------------------
# exchange overflow is reported, never silent (satellite: resegment fix)
# ---------------------------------------------------------------------------

def test_resegment_overflow_is_reported():
    from repro.distributed.mesh import make_query_mesh
    mesh = make_query_mesh()
    n_shards = mesh.shape["data"]
    n = 64 * n_shards
    keys = np.arange(n, dtype=np.int32)
    dest = np.zeros(n, np.int32)            # everything wants shard 0
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("data"))
    cols = {"k": _jax.device_put(keys, sharding)}
    dest_dev = _jax.device_put(dest, sharding)
    capacity = (n // 2 // n_shards) * n_shards   # half the needed slots
    out, valid, overflow = resegment(mesh, "data", cols, dest_dev,
                                     capacity)
    # capacity//n_shards slots per (source, dest) bucket: every source
    # holds n/n_shards rows for shard 0 and can ship only per of them
    per = capacity // n_shards
    per_source = n // n_shards
    dropped = (per_source - per) * n_shards
    ov = np.asarray(overflow)
    assert ov.shape == (n_shards,)
    # all overflow is on shard 0, and it is REPORTED, not silent
    assert int(ov[0]) == dropped
    assert int(ov.sum()) == dropped
    kept = np.asarray(out["k"])[np.asarray(valid)]
    assert kept.size == n - dropped
    # ample capacity -> zero overflow, every tuple arrives exactly once
    out2, valid2, overflow2 = resegment(mesh, "data", cols, dest_dev,
                                        n * n_shards)
    assert int(np.asarray(overflow2).sum()) == 0
    kept2 = np.asarray(out2["k"])[np.asarray(valid2)]
    assert sorted(kept2.tolist()) == keys.tolist()


# ---------------------------------------------------------------------------
# empty-snapshot scans: the slab build must degrade, never raise
# ---------------------------------------------------------------------------

def test_segmented_all_rows_deleted():
    """Deleting every fact row empties the snapshot: the slab build has
    zero visible rows (``v.min()`` on an empty partition used to raise)
    and the query must still answer -- falling back to the single-node
    shape of an empty aggregate, identically on both paths."""
    db = make_db(seed=51)
    t = db.begin()
    db.delete(t, "sales", lambda r: r["sale_id"] >= 0)
    db.commit(t)
    qb = (db.query("sales").where(col("qty") > 0)
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")))
    ref, out, _stats = run_both(db, qb)
    assert len(ref["n"]) == 0
    assert_match(ref, out, ordered=False, label="all-deleted")


def test_segmented_wos_only_snapshot():
    """A projection whose every row still sits in the WOS (no moveout
    yet) has no ROS slab at all: the segmented path must run off the
    trickle buffers alone and match single-node."""
    db = make_db(seed=52)
    rng = np.random.default_rng(9)
    t = db.begin()
    db.delete(t, "sales", lambda r: r["sale_id"] >= 0)
    db.commit(t)
    _trickle(db, rng, n=120)                # WOS-only visible rows
    qb = (db.query("sales").group_by("suppkey")
          .agg(n=("*", "count"), s=("qty", "sum"), a=("price", "avg")))
    ref, out, stats = run_both(db, qb)
    assert len(ref["n"]) > 0
    assert stats.segmented, stats.seg_slab
    assert "+wos" in stats.seg_slab, stats.seg_slab
    assert_match(ref, out, ordered=False, label="wos-only")


def test_segmented_pruned_to_empty():
    """A predicate outside every block's SMA range prunes ALL slab
    blocks: the all-pads program must yield exactly the empty result the
    predicate implies, not raise or mis-shape."""
    db = make_db(seed=53)
    qb = (db.query("sales").where(col("day") >= 100_000)
          .group_by("suppkey").agg(n=("*", "count")))
    ref, out, stats = run_both(db, qb)
    assert len(ref["n"]) == 0
    assert stats.segmented
    assert stats.blocks_total > 0
    assert stats.blocks_pruned == stats.blocks_total
    assert_match(ref, out, ordered=False, label="pruned-empty")


def test_segmented_pruning_differential(star_db):
    """Selective range predicates drive the slab-block pruner; results
    must stay exact and the pruned-block telemetry must move."""
    db = star_db
    qb = (db.query("sales").where((col("day") >= 40) & (col("day") < 80))
          .group_by("suppkey").agg(n=("*", "count"), s=("qty", "sum")))
    ref, out, stats = run_both(db, qb)
    assert stats.segmented
    assert stats.blocks_total > 0
    assert stats.blocks_pruned < stats.blocks_total
    assert_match(ref, out, ordered=False, label="pruned-range")


@pytest.mark.parametrize("forced", [False, True],
                         ids=["xla-preagg", "interpreted-kernel"])
def test_stage_checks_varying_axes_unless_kernel_interpreted(
        star_db, monkeypatch, forced):
    """The fused stage shard_map keeps jax's varying-axes check on; only
    around the interpret-mode seg_preagg (REPRO_SEG_PREAGG=pallas off
    TPU), whose loop discharge drops varying axes, is it turned off."""
    from repro.engine import segmented as seg
    from repro.engine.executor import PLAN_CACHE

    built = []

    def recording_shard_map(*args, **kwargs):
        if "check_vma" in kwargs:
            built.append(kwargs["check_vma"])
        return jax.shard_map(*args, **kwargs)

    if forced:
        monkeypatch.setenv("REPRO_SEG_PREAGG", "pallas")
    monkeypatch.setattr(seg, "shard_map", recording_shard_map)
    PLAN_CACHE.clear()
    db = star_db
    qb = (db.query("sales").where(col("qty") > 7)
          .join("parts", on=("partkey", "p_partkey"), cols=("p_cat",))
          .group_by("p_cat").agg(n=("*", "count"), s=("qty", "sum")))
    ref, out, stats = run_both(db, qb)
    PLAN_CACHE.clear()
    assert stats.segmented
    assert built and all(v is not forced for v in built), built
    assert_match(ref, out, ordered=False, label=f"vma-forced={forced}")


# ---------------------------------------------------------------------------
# shard-index-column cache: bounded, oldest-first eviction
# ---------------------------------------------------------------------------

def test_shard_index_cache_retention(star_db):
    from repro.engine import segmented as seg

    db = star_db
    db.attach_mesh()
    try:
        mesh, axis = db.mesh, db.mesh_axis
        n_shards = int(mesh.shape[axis])
        seg._SHARD_IDX_CACHE.clear()
        first = seg._shard_index_col(mesh, axis, n_shards, 8)
        # warm re-request returns the SAME device array (no rebuild)
        assert seg._shard_index_col(mesh, axis, n_shards, 8) is first
        for w in range(2, 2 + seg._SHARD_IDX_CAP + 10):
            seg._shard_index_col(mesh, axis, n_shards, 8 * w)
        # bounded: never grows past the cap
        assert len(seg._SHARD_IDX_CACHE) <= seg._SHARD_IDX_CAP
        # oldest-first: the width-8 entry fell out, the newest survive
        # (a wholesale clear() would have left exactly one entry)
        sig = seg._mesh_sig(mesh, axis)
        assert (sig, 8) not in seg._SHARD_IDX_CACHE
        last_w = 8 * (2 + seg._SHARD_IDX_CAP + 9)
        newest = seg._SHARD_IDX_CACHE[(sig, last_w)]
        assert seg._shard_index_col(mesh, axis, n_shards, last_w) is newest
        assert len(seg._SHARD_IDX_CACHE) > 1
    finally:
        db.detach_mesh()
