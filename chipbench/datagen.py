"""Seeded table generator driven by a deployment's column specs.

The benchmark's own copy of the C-Store harness generator
(``star_schema`` of the program's data module): the same draws, in the
same order, from one ``numpy`` generator seeded with ``--seed``, so a
later change to the program cannot move the data the benchmark measures.
A configuration lists its tables and, for each column, how it is drawn;
tables and columns are drawn in the order the file lists them.

Column kinds (``gen``):

- ``uniform``: integers in ``[lo, hi)``;
- ``sorted_uniform``: the same, sorted (a sort key as a bulk load delivers
  it);
- ``arange``: ``0 .. rows-1`` (a dense primary key; draws nothing);
- ``normal``: ``round(normal(mean, std), decimals)`` as float64.

A bound may name another table's row count (``"rows:orders"``) or a
fraction of it (``{"rows": "orders", "div": 10, "min": 10}``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def _bound(v, rows: Dict[str, int]) -> int:
    if isinstance(v, int):
        return v
    if isinstance(v, str) and v.startswith("rows:"):
        return rows[v[5:]]
    if isinstance(v, dict):
        return max(int(v.get("min", 0)), rows[v["rows"]] // int(v["div"]))
    raise ValueError(f"bad bound {v!r}")


def _column(rng: np.random.Generator, gen: dict, n: int,
            rows: Dict[str, int]) -> np.ndarray:
    kind = gen["kind"]
    if kind == "arange":
        return np.arange(n, dtype=np.int64)
    if kind in ("uniform", "sorted_uniform"):
        v = rng.integers(_bound(gen["lo"], rows), _bound(gen["hi"], rows), n)
        if kind == "sorted_uniform":
            v = np.sort(v)
        return v.astype(np.int64)
    if kind == "normal":
        return np.round(rng.normal(gen["mean"], gen["std"], n),
                        int(gen["decimals"]))
    raise ValueError(f"unknown column kind {kind!r}")


def generate(config: dict, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """{table: {column: array}} for a configuration, from ``seed``."""
    rng = np.random.default_rng(seed)
    tables = config["tables"]
    rows = {t: int(spec["rows"]) for t, spec in tables.items()}
    return {t: {c["name"]: _column(rng, c["gen"], rows[t], rows)
                for c in spec["columns"]}
            for t, spec in tables.items()}
