"""Shared fixtures. NOTE: no XLA_FLAGS here -- tests see 1 CPU device;
only launch/dryrun.py requests 512 placeholder devices (assignment rule)."""
import numpy as np
import pytest
from hypothesis import settings

# a property test's first example traces and compiles its programs, which
# takes seconds; the per-example deadline would count that as the test's
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")

from repro.core import ColumnDef, SQLType, TableSchema, VerticaDB


@pytest.fixture
def sales_db():
    rng = np.random.default_rng(7)
    db = VerticaDB(n_nodes=4, k_safety=1, block_rows=64)
    db.create_table(
        TableSchema("sales", (
            ColumnDef("sale_id"), ColumnDef("cid"), ColumnDef("date"),
            ColumnDef("price", SQLType.FLOAT))),
        sort_order=("date",), segment_by=("sale_id",),
        partition_by=("date", "div_1000"))
    n = 2000
    data = {
        "sale_id": np.arange(n, dtype=np.int64),
        "cid": rng.integers(0, 20, n),
        "date": rng.integers(0, 3000, n),
        "price": np.round(rng.normal(100, 10, n), 2),
    }
    t = db.begin()
    db.insert(t, "sales", data)
    db.commit(t)
    db.run_tuple_mover(force_moveout=True)
    return db, data


def visible_rows(db, table="sales", as_of=None):
    return db.read_table(table, as_of=as_of)


def sorted_tuples(rows):
    cols = sorted(rows)
    arr = np.stack([np.asarray(rows[c], np.float64) for c in cols])
    order = np.lexsort(arr)
    return arr[:, order]
