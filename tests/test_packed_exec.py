"""Differential + property tests for real bit-packed storage and
compressed-domain execution.

Two guarantees are under test (DESIGN.md §9):

1.  **Packing is lossless** -- property tests (hypothesis) round-trip
    every width 1..32, negative values,
    empty inputs and FLOAT_SCALED through the actual packed word streams.

2.  **Code-domain execution is byte-identical** -- a 20-query seeded
    corpus runs twice, ``db.exec_mode = "decoded"`` (legacy decode-then-
    filter) vs ``"compressed"`` (code-domain predicates, code-space GROUP
    BY, late materialization; engine/compressed.py), and every output
    column must match exactly -- assert_array_equal, not allclose.
"""
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.core import (ColumnDef, Encoding, SQLType, TableSchema,
                        VerticaDB)
from repro.core.encodings import (MAX_PACK_BITS, encode, pack_words,
                                  symbol_width, unpack_words)
from repro.core.projection import super_projection
from repro.engine import col, execute

# ---------------------------------------------------------------------------
# property tests: packing round-trips
# ---------------------------------------------------------------------------


@settings(max_examples=25)
@given(st.integers(1, MAX_PACK_BITS),
       st.integers(1, 200), st.integers(0, 2 ** 31))
def test_pack_words_round_trip(width, n, seed):
    rng = np.random.default_rng(seed)
    syms = rng.integers(0, 1 << width, (2, n), dtype=np.uint64) \
        .astype(np.int64)
    words = pack_words(syms, width)
    assert words.dtype == np.uint32
    assert words.shape == (2, ((n + 31) // 32) * width)
    np.testing.assert_array_equal(unpack_words(words, width, n), syms)


@settings(max_examples=25)
@given(st.lists(st.integers(-2 ** 40, 2 ** 40), min_size=0, max_size=300),
       st.sampled_from([e for e in Encoding
                        if e not in (Encoding.AUTO, Encoding.FLOAT_SCALED)]))
def test_int_encodings_round_trip_any_values(xs, enc):
    """Every integer encoding round-trips bit-exactly through its real
    packed buffers -- negatives, wide deltas (>32-bit fallback), empty."""
    values = np.asarray(xs, dtype=np.int64)
    c = encode(values, SQLType.INT, enc, block_rows=64)
    np.testing.assert_array_equal(c.decode(), values)
    assert c.packed_bytes >= 0


@settings(max_examples=15)
@given(st.lists(st.integers(-10 ** 6, 10 ** 6), min_size=1, max_size=200))
def test_packed_device_decode_matches_host(xs):
    """decode_jnp (device bit-unpack kernel path) == decode (host numpy)
    for every encoding that packs, byte-identical."""
    from repro.core.encodings import decode_jnp
    values = np.asarray(xs, dtype=np.int64)
    for enc in (Encoding.DELTA_VALUE, Encoding.BLOCK_DICT,
                Encoding.DELTA_RANGE, Encoding.COMMON_DELTA):
        c = encode(values, SQLType.INT, enc, block_rows=64)
        dev = np.asarray(decode_jnp(c)).reshape(-1)[: values.size]
        host = c.decode().astype(np.int32)      # device lanes are int32
        np.testing.assert_array_equal(dev.astype(np.int64), host)


@settings(max_examples=15)
@given(st.lists(st.integers(-10 ** 4, 10 ** 4), min_size=1, max_size=150),
       st.integers(0, 2))
def test_float_scaled_round_trip(xs, k):
    values = np.asarray(xs, dtype=np.float64) / (10.0 ** k)
    c = encode(values, SQLType.FLOAT, Encoding.FLOAT_SCALED, block_rows=64)
    np.testing.assert_array_equal(c.decode(), values)


def test_empty_column_every_encoding():
    for enc in Encoding:
        if enc == Encoding.FLOAT_SCALED:
            continue
        c = encode(np.zeros(0, np.int64), SQLType.INT, enc, block_rows=64)
        assert c.decode().size == 0


def test_symbol_width_edges():
    assert symbol_width(0) == 1
    assert symbol_width(1) == 1
    assert symbol_width(2) == 2
    assert symbol_width((1 << 32) - 1) == 32


# ---------------------------------------------------------------------------
# differential corpus: compressed vs decoded execution, byte-identical
# ---------------------------------------------------------------------------

N_ROWS = 3000
N_DIM = 120


def _build_db():
    rng = np.random.default_rng(11)
    db = VerticaDB(n_nodes=4, k_safety=0, block_rows=64)
    schema = TableSchema("sales", (
        ColumnDef("sale_id"), ColumnDef("cid"), ColumnDef("day"),
        ColumnDef("qty"), ColumnDef("price", SQLType.FLOAT)))
    db.catalog.add_table(schema)
    # force BLOCK_DICT on the low-cardinality filter/group column so the
    # code-range predicate rewrite actually engages
    db.create_projection(super_projection(
        schema, ("day",), ("sale_id",),
        encodings={"cid": Encoding.BLOCK_DICT}))
    db.create_table(TableSchema("customer", (
        ColumnDef("c_cid"), ColumnDef("c_nation"))),
        sort_order=("c_cid",), segment_by=())
    t = db.begin()
    db.insert(t, "sales", {
        "sale_id": np.arange(N_ROWS, dtype=np.int64),
        "cid": rng.integers(0, N_DIM, N_ROWS),
        "day": rng.integers(0, 365, N_ROWS),
        "qty": rng.integers(1, 50, N_ROWS),
        "price": np.round(rng.normal(100, 10, N_ROWS), 2)})
    db.insert(t, "customer", {
        "c_cid": np.arange(N_DIM, dtype=np.int64),
        "c_nation": rng.integers(0, 8, N_DIM)})
    db.commit(t)
    db.run_tuple_mover(force_moveout=True)
    return db


@pytest.fixture(scope="module")
def packed_db():
    return _build_db()


def _corpus(db, rng):
    """One seeded corpus query: int-interval filters (dict + non-dict
    columns), 1-2 group keys, mixed aggregates, sometimes a join."""
    qb = db.query("sales")
    r = rng.random()
    if r < 0.4:                       # dict-column interval (code range)
        lo = int(rng.integers(0, 80))
        qb = qb.where((col("cid") >= lo)
                      & (col("cid") <= lo + int(rng.integers(5, 60))))
    elif r < 0.7:                     # mixed dict + sorted column
        qb = qb.where((col("cid") < int(rng.integers(20, 100)))
                      & (col("day") >= int(rng.integers(0, 200))))
    elif r < 0.9:                     # equality on the dict column
        qb = qb.where(col("cid") == int(rng.integers(0, N_DIM)))
    # else: no predicate -> ineligible, must still match via decoded path
    if rng.random() < 0.3:
        qb = qb.join("customer", on=("cid", "c_cid"), cols=("c_nation",))
        keys = ["c_nation"]
    else:
        keys = ["cid"] if rng.random() < 0.6 else ["day"]
        if rng.random() < 0.3:
            keys.append("qty")
    qb = qb.group_by(*keys).agg(n=("*", "count"))
    for name, spec in (("s", ("qty", "sum")), ("mn", ("price", "min")),
                       ("mx", ("price", "max")), ("a", ("price", "avg"))):
        if rng.random() < 0.4:
            qb = qb.agg(**{name: spec})
    return qb


def _run_mode(db, q, mode):
    # exec_mode "decoded"/"compressed" force their scan path regardless of
    # cache residency, and the compressed plan signature carries a "cdom"
    # suffix -- the two modes can share warm caches without collisions
    db.exec_mode = mode
    out, stats = execute(db, q)
    return out, stats


def test_differential_corpus_byte_identical(packed_db):
    db = packed_db
    rng = np.random.default_rng(5)
    n_compressed = 0
    db.block_cache.clear()
    try:
        for i in range(20):
            q = _corpus(db, rng).to_ir()
            ref, _ = _run_mode(db, q, "decoded")
            out, st = _run_mode(db, q, "compressed")
            n_compressed += bool(st.compressed_scan)
            assert set(ref) == set(out), (i, sorted(ref), sorted(out))
            for c in ref:
                np.testing.assert_array_equal(
                    np.asarray(ref[c]), np.asarray(out[c]),
                    err_msg=f"query {i} column {c}")
    finally:
        db.exec_mode = "auto"
    # the corpus must actually exercise the code-domain path
    assert n_compressed >= 8, n_compressed


def test_compressed_with_deleted_tail_blocks(packed_db):
    """All-deleted tail blocks: survivors must respect delete vectors and
    the padded tail, byte-identically."""
    db = _build_db()
    t = db.begin()
    db.delete(t, "sales", lambda r: r["day"] >= 300)   # kills tail blocks
    db.commit(t)
    q = (db.query("sales")
         .where((col("cid") >= 10) & (col("cid") <= 90))
         .group_by("cid").agg(n=("*", "count"), s=("qty", "sum"))
         .to_ir())
    ref, _ = _run_mode(db, q, "decoded")
    out, st = _run_mode(db, q, "compressed")
    assert st.compressed_scan
    for c in ref:
        np.testing.assert_array_equal(np.asarray(ref[c]),
                                      np.asarray(out[c]), err_msg=c)


def test_zero_survivors(packed_db):
    db = packed_db
    q = (db.query("sales").where(col("cid") == N_DIM + 5)
         .group_by("cid").agg(n=("*", "count")).to_ir())
    ref, _ = _run_mode(db, q, "decoded")
    out, st = _run_mode(db, q, "compressed")
    db.exec_mode = "auto"
    assert set(ref) == set(out)
    for c in ref:
        np.testing.assert_array_equal(np.asarray(ref[c]),
                                      np.asarray(out[c]), err_msg=c)


def test_auto_mode_prefers_warm_decoded(packed_db):
    """auto: a budget too small for the decoded working set takes the
    compressed scan; a comfortable budget keeps the legacy path (same
    plan signature cold and warm, so repeats stay plan-cache hits)."""
    db = packed_db
    db.exec_mode = "auto"
    q = (db.query("sales")
         .where((col("cid") >= 5) & (col("cid") <= 50))
         .group_by("cid").agg(n=("*", "count")).to_ir())
    db.block_cache.clear()
    old_budget = db.block_cache.budget_bytes
    try:
        # constrained: decoded residency unattainable -> code domain
        db.block_cache.budget_bytes = 1 << 14
        _, st_cold = execute(db, q)
        assert st_cold.compressed_scan
        # comfortable budget: legacy decode-and-cache, cold AND warm
        db.block_cache.budget_bytes = old_budget
        db.block_cache.clear()
        _, st_cold2 = execute(db, q)
        assert not st_cold2.compressed_scan
        _, st_warm = execute(db, q)
        assert not st_warm.compressed_scan
    finally:
        db.block_cache.budget_bytes = old_budget
        db.exec_mode = "auto"


def test_plan_signature_includes_symbol_width(packed_db):
    """Dictionary growth changes the packed symbol width, which must be
    part of the compressed plan identity (width_signature)."""
    c = encode(np.arange(10, dtype=np.int64), SQLType.INT,
               Encoding.BLOCK_DICT, block_rows=64)
    c2 = encode(np.arange(40, dtype=np.int64) % 33, SQLType.INT,
                Encoding.BLOCK_DICT, block_rows=64)
    assert c.width_signature() != c2.width_signature()
    assert c.widths["codes_packed"] == symbol_width(9)
