"""The join probe: a dense position index against the sorted binary search.

``operators.dense_index`` admits a build side whose integer keys are
unique and spread over at most ``max(4 n, 2**20)`` values; ``hash_join``
then probes with one gather.  Both probes must give the same rows, the
same carried values and the same masks; every other build side keeps the
search path.  End to end, the C-Store join queries (Q5 with a dimension
filter, Q7 with a fact filter) take the dense probe through the fused
executor and the general pipeline, and the segmented executor keeps the
search.
"""
import jax
import numpy as np
import pytest

from repro.core import ColumnDef, Encoding, SQLType, TableSchema, VerticaDB
from repro.core.projection import super_projection
from repro.engine import col
from repro.engine import operators as ops
from repro.engine.executor import KIND_BUILD

I32 = np.iinfo(np.int32)


def _shuffled(keys, seed=0):
    return np.random.default_rng(seed).permutation(keys)


def _q5_build():
    # orders keys filtered by a ~10% date predicate: R / n about 10
    rng = np.random.default_rng(5)
    keys = np.arange(3000)
    return keys[rng.random(3000) < 0.1]


# (build keys, probe keys) per case; keys are cast to each dtype
PROBE_CASES = {
    "arange": (_shuffled(np.arange(1000)),
               np.random.default_rng(1).integers(-5, 1005, 4000)),
    "q5_filtered": (_q5_build(),
                    np.random.default_rng(2).integers(0, 3000, 4000)),
    "negative_lo": (_shuffled(np.arange(-500, 500)),
                    np.random.default_rng(3).integers(-600, 600, 4000)),
    "out_of_range": (np.arange(100, 400),
                     np.array([99, 100, 101, 399, 400, 401, -1, 0, 250,
                               I32.min, I32.max, I32.min + 100])),
}


def _join_both(keys, probe_keys, how):
    n = len(keys)
    build = {"k": jax.numpy.asarray(keys),
             "attr": jax.numpy.asarray(np.arange(n) * 7 % 13),
             "w": jax.numpy.asarray(np.linspace(0.5, 2.0, n,
                                                dtype=np.float32))}
    probe = {"fk": jax.numpy.asarray(probe_keys),
             "x": jax.numpy.asarray(np.arange(len(probe_keys)))}
    valid = jax.numpy.asarray(np.arange(len(probe_keys)) % 5 != 0)
    dense = ops.dense_index(keys)
    got = ops.hash_join(build, "k", probe, "fk", valid, how=how, dense=dense)
    want = ops.hash_join(build, "k", probe, "fk", valid, how=how)
    return dense, got, want


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_dense_probe_matches_search(case, dtype, how):
    keys, probe_keys = PROBE_CASES[case]
    keys = keys.astype(dtype)
    probe_keys = probe_keys.astype(dtype)
    dense, (got, gvalid), (want, wvalid) = _join_both(keys, probe_keys, how)
    assert dense is not None
    gvalid, wvalid = np.asarray(gvalid), np.asarray(wvalid)
    np.testing.assert_array_equal(gvalid, wvalid)
    assert set(got) == set(want)
    # under "inner" an unmatched row's carried value is masked out; under
    # "left" it is the -1 sentinel on both paths
    rows = wvalid if how == "inner" else np.ones_like(wvalid)
    for c in want:
        np.testing.assert_array_equal(np.asarray(got[c])[rows],
                                      np.asarray(want[c])[rows], err_msg=c)
    # the reference: a dict lookup of every probe key
    pos = {int(k): i for i, k in enumerate(keys)}
    hit = np.array([int(k) in pos for k in probe_keys])
    if how == "left":
        np.testing.assert_array_equal(np.asarray(got["_matched"]), hit)
    else:
        np.testing.assert_array_equal(gvalid,
                                      hit & (np.arange(len(hit)) % 5 != 0))


def test_dense_probe_has_no_search_loop():
    keys, probe_keys = PROBE_CASES["arange"]
    dense = ops.dense_index(keys)
    build = {"k": jax.numpy.asarray(keys), "attr": jax.numpy.asarray(keys)}
    probe = {"fk": jax.numpy.asarray(probe_keys)}
    valid = jax.numpy.ones(len(probe_keys), bool)

    def hlo(dense):
        return jax.jit(lambda d: ops.hash_join(
            build, "k", probe, "fk", valid, dense=d)).lower(dense).as_text()
    assert "while" not in hlo(dense)
    assert "while" in hlo(None)


@pytest.mark.parametrize("keys, admitted", [
    (_shuffled(np.arange(-3, 7)), True),
    (np.array([0, (1 << 20) - 1]), True),          # R = 2**20, the floor
    (np.array([0, 1 << 20]), False),               # R = 2**20 + 1
    (np.arange(0, 4 << 20, 4), True),              # R = 4 n - 3
    (np.arange(0, 5 << 20, 5), False),             # R = 5 n - 4
    (np.array([3, 7, 3]), False),                  # duplicate keys
    (np.array([1.0, 2.0, 3.0]), False),            # float keys
    (np.zeros(0, np.int64), False),                # empty build
    (np.arange(4, dtype=np.int64) + (1 << 40), False),  # outside int32
])
def test_dense_index_rule(keys, admitted):
    d = ops.dense_index(keys)
    assert (d is not None) == admitted
    if d is not None:
        pos = np.asarray(d.pos)
        assert pos.dtype == np.int32 and int(d.lo) == int(keys.min())
        assert len(pos) == int(keys.max()) - int(keys.min()) + 1
        np.testing.assert_array_equal(pos[keys - keys.min()],
                                      np.arange(len(keys)))
        assert np.count_nonzero(pos >= 0) == len(keys)


# build keys that take the search path, with the probe keys and the
# build's carried values; duplicate keys carry equal values (as the
# segmented executor's pad copies do), so the answer is defined
FALLBACK_CASES = {
    "wide_range": (np.arange(10) * (1 << 21), np.arange(10) * 3,
                   np.array([0, 1 << 21, 5, 9 * (1 << 21), -1])),
    "duplicates": (np.array([4, 9, 4, 2]), np.array([40, 90, 40, 20]),
                   np.array([4, 2, 3, 9, 9, -1])),
    "float_keys": (np.array([1.5, 2.5, 4.0], np.float32),
                   np.array([15, 25, 40]),
                   np.array([2.5, 3.0, 4.0, 1.5], np.float32)),
    "empty": (np.zeros(0, np.int64), np.zeros(0, np.int64),
              np.array([0, 1, 2])),
}


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("case", sorted(FALLBACK_CASES))
def test_fallback_keeps_search(case, how):
    keys, attr, probe_keys = FALLBACK_CASES[case]
    dense = ops.dense_index(keys)
    assert dense is None
    build = {"k": jax.numpy.asarray(keys), "attr": jax.numpy.asarray(attr)}
    probe = {"fk": jax.numpy.asarray(probe_keys)}
    valid = jax.numpy.ones(len(probe_keys), bool)
    out, v = ops.hash_join(build, "k", probe, "fk", valid, how=how,
                           dense=dense)
    lookup = {float(k): int(a) for k, a in zip(keys, attr)}
    hit = np.array([float(k) in lookup for k in probe_keys])
    want = np.array([lookup.get(float(k), -1) for k in probe_keys])
    got = np.asarray(out["attr"])
    if how == "inner":
        np.testing.assert_array_equal(np.asarray(v), hit)
        np.testing.assert_array_equal(got[hit], want[hit])
    else:
        np.testing.assert_array_equal(np.asarray(v), np.ones_like(hit))
        np.testing.assert_array_equal(np.asarray(out["_matched"]), hit)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# End to end: a small database of the C-Store 7-query harness's shape
# ---------------------------------------------------------------------------

N_LINE, N_ORD = 8000, 200


def _tables(seed=11):
    rng = np.random.default_rng(seed)
    line = {"l_orderkey": rng.integers(0, N_ORD, N_LINE),
            "l_suppkey": rng.integers(0, 100, N_LINE),
            "l_shipdate": np.sort(rng.integers(0, 365, N_LINE)),
            "l_qty": rng.integers(1, 50, N_LINE),
            "l_extprice": np.round(rng.normal(1000, 200, N_LINE), 2)}
    orders = {"o_orderkey": np.arange(N_ORD, dtype=np.int64),
              "o_custkey": rng.integers(0, N_ORD // 10, N_ORD),
              "o_orderdate": rng.integers(0, 365, N_ORD)}
    return line, orders


def _cstore_db(line, orders, *, lineitem_in_wos=False):
    db = VerticaDB(n_nodes=4, k_safety=0, block_rows=256)
    for name, cols, sort, seg, enc in (
            ("lineitem", line, ("l_shipdate", "l_suppkey"), ("l_orderkey",),
             {"l_shipdate": Encoding.RLE}),
            ("orders", orders, ("o_orderkey",), (), {})):
        schema = TableSchema(name, tuple(
            ColumnDef(c, SQLType.FLOAT if c == "l_extprice" else SQLType.INT)
            for c in cols))
        db.catalog.add_table(schema)
        db.create_projection(super_projection(schema, sort, seg,
                                              encodings=enc))
    t = db.begin(direct_to_ros=True)
    db.insert(t, "orders", orders)
    if not lineitem_in_wos:
        db.insert(t, "lineitem", line)
    db.commit(t)
    if lineitem_in_wos:
        # rows left in the WOS: the fused executor declines and the
        # general pipeline runs
        t = db.begin()
        db.insert(t, "lineitem", line)
        db.commit(t)
    return db


def _q5(db, d=36):
    return (db.query("lineitem")
            .join("orders", on=("l_orderkey", "o_orderkey"),
                  cols=("o_custkey",), where=col("o_orderdate") < d)
            .group_by("o_custkey").agg(s=("l_extprice", "sum")))


def _q7(db, s=10):
    return (db.query("lineitem").where(col("l_suppkey") < s)
            .join("orders", on=("l_orderkey", "o_orderkey"),
                  cols=("o_custkey",))
            .group_by("o_custkey").agg(c=("*", "count")))


def _reference(name, line, orders):
    """numpy over the generated arrays (orders keys are their rows)."""
    ok = line["l_orderkey"]
    if name == "q5":
        m = orders["o_orderdate"][ok] < 36
        val, agg = line["l_extprice"], "s"
    else:
        m = line["l_suppkey"] < 10
        val, agg = np.ones(N_LINE, np.int64), "c"
    cust = orders["o_custkey"][ok][m]
    keys = np.unique(cust)
    sums = np.array([val[m][cust == k].sum() for k in keys])
    return keys, agg, sums


def _check(name, out, line, orders):
    keys, agg, sums = _reference(name, line, orders)
    order = np.argsort(out["o_custkey"])
    np.testing.assert_array_equal(np.asarray(out["o_custkey"])[order], keys)
    got = np.asarray(out[agg])[order]
    if agg == "c":
        np.testing.assert_array_equal(got, sums)
    else:
        np.testing.assert_allclose(got, sums, rtol=3e-4)


QUERIES = {"q5": _q5, "q7": _q7}


@pytest.fixture(scope="module")
def cstore():
    line, orders = _tables()
    return {"line": line, "orders": orders,
            "fused": _cstore_db(line, orders),
            "general": _cstore_db(line, orders, lineitem_in_wos=True)}


@pytest.mark.parametrize("path", ["fused", "general"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_cstore_join_takes_dense_probe(cstore, name, path):
    qb = QUERIES[name](cstore[path])
    out = qb.collect()
    _check(name, out, cstore["line"], cstore["orders"])
    assert qb.stats.fused == (path == "fused")
    assert qb.stats.join_probe == "dense"


def _build_entries(db):
    return {k: nb for k, (v, nb) in db.block_cache._entries.items()
            if k[2] == KIND_BUILD and not k[1].startswith("seg|")}


def test_repeat_query_reuses_cached_index(cstore, monkeypatch):
    db = cstore["fused"]
    db.block_cache.invalidate_container("dim:orders")
    made = []
    real = ops.dense_index
    monkeypatch.setattr(ops, "dense_index",
                        lambda keys: made.append(len(keys)) or real(keys))
    for _ in range(2):
        qb = _q7(db)
        qb.collect()
        assert qb.stats.join_probe == "dense"
    assert made == [N_ORD]          # built with the side, once
    (key, nbytes), = _build_entries(db).items()
    build, dense = db.block_cache.get(*key)
    assert dense is not None
    # the entry's counted bytes hold the index beside the build columns
    cols = sum(int(a.size) * a.dtype.itemsize for a in build.values())
    assert nbytes == cols + dense.pos.size * 4 + dense.lo.dtype.itemsize
    assert dense.pos.size == N_ORD


def test_segmented_query_keeps_search(cstore):
    db = cstore["fused"]
    db.attach_mesh()
    try:
        qb = _q7(db)
        out = qb.collect()
    finally:
        db.detach_mesh()
    assert qb.stats.segmented
    assert qb.stats.join_probe == "search"
    _check("q7", out, cstore["line"], cstore["orders"])
