"""The system under test, driven through its public API.

Everything the benchmark asks of the program goes through here: build a
database for a deployment, bulk-load the generated tables through
``begin/insert/commit``, and turn a query spec into a fluent-builder query.
"""
from __future__ import annotations

import operator
from typing import Dict, Optional

import numpy as np

from . import workload

_OPS = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


def _schema(name: str, spec: dict):
    from repro.core import ColumnDef, SQLType, TableSchema
    return TableSchema(name, tuple(
        ColumnDef(c["name"], getattr(SQLType, c.get("type", "INT")))
        for c in spec["columns"]))


def load(config: dict, tables: Dict[str, Dict[str, np.ndarray]]):
    """A ``VerticaDB`` for the deployment, holding ``tables``.  A
    deployment that states ``cluster.chips`` gets a query mesh of that
    many devices, one segmentation shard each, attached before any query
    is built: its queries then run through the segmented executor."""
    from repro.core import Encoding, VerticaDB
    from repro.core.projection import super_projection

    cl = config["cluster"]
    cache = config["cache"]
    db = VerticaDB(n_nodes=int(cl["nodes"]), k_safety=int(cl["k_safety"]),
                   block_rows=int(cl["block_rows"]),
                   cache_budget_bytes=int(cache.get("budget_bytes",
                                                    1 << 40)))
    for name, spec in config["tables"].items():
        schema = _schema(name, spec)
        proj = spec["projection"]
        db.catalog.add_table(schema)
        db.create_projection(super_projection(
            schema, tuple(proj["sort_order"]), tuple(proj["segment_by"]),
            encodings={c: getattr(Encoding, e)
                       for c, e in proj.get("encodings", {}).items()}))
    t = db.begin(direct_to_ros=True)
    for name, data in tables.items():
        db.insert(t, name, data)
    db.commit(t)
    if "chips" in cl:
        from repro.distributed.mesh import make_query_mesh
        db.attach_mesh(make_query_mesh(int(cl["chips"])))
    if "packed_fit" in cache:
        db.block_cache.budget_bytes = packed_fit_budget(
            db, cache["packed_fit"]["table"], cache["packed_fit"]["columns"])
    return db


def _containers(db, table: str):
    for node in db.nodes:
        store = node.stores.get(f"{table}_super")
        if store is not None:
            yield from store.containers


def packed_fit_budget(db, table: str, columns) -> int:
    """A block-cache budget that holds the packed working set of
    ``columns`` but not the decoded one: max(0.55 (packed + decoded),
    2 packed + 1 MiB), the rule of the program's compression benchmark."""
    from repro.core.encodings import device_bytes
    packed = decoded = 0
    for c in _containers(db, table):
        for name in columns:
            ec = c.columns[name]
            inner = ec.inner if ec.inner is not None else ec
            packed += device_bytes(inner.arrays)
            decoded += inner.n_blocks * inner.block_rows * 4
    return max(int(0.55 * (packed + decoded)), 2 * packed + (1 << 20))


def _conj(terms, lits):
    from repro.engine import col
    pred = None
    for c, op, term in terms or ():
        e = _OPS[op](col(c), workload.literal(term, lits))
        pred = e if pred is None else pred & e
    return pred


def build_query(db, spec: dict, lits: Dict[str, int]):
    """The fluent-builder query for a spec at one literal set."""
    q = db.query(spec["table"])
    pred = _conj(spec.get("where"), lits)
    if pred is not None:
        q = q.where(pred)
    for j in spec.get("joins", []):
        q = q.join(j["table"], on=tuple(j["on"]), cols=tuple(j["cols"]),
                   where=_conj(j.get("where"), lits))
    if spec.get("group_by"):
        q = q.group_by(*spec["group_by"])
    return q.agg(**{name: tuple(a) for name, a in spec["agg"].items()})


def stats_of(builder) -> Optional[dict]:
    """The ``ExecStats`` counters the per-layer metrics and the check
    read: the scan's, and the segmented executor's (``segmented`` is
    true only where it answered; ``exchange``, ``join_probe`` one entry
    per join)."""
    st = builder.stats
    if st is None:
        return None
    return {k: getattr(st, k) for k in (
        "frontend_s", "blocks_pruned", "blocks_total", "block_cache_hits",
        "block_cache_misses", "segmented", "n_shards", "exchange",
        "join_probe", "reseg_overflow", "seg_slab")}
