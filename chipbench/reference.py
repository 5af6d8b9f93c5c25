"""Plain reference: the same queries over the generated arrays.

Independent of the program: it imports nothing of it and reads only the
arrays ``datagen`` made from the seed, never the program's storage or
decode.  Semantics: the ``where`` terms AND together; an inner join keeps
the fact rows whose key matches a dimension row that passes the join's
``where``; a group exists when at least one row reaches it; ``count``
counts rows, ``sum`` adds, ``avg`` is sum over count.  Integer results are
exact (int64); float results are float64.

``answer(..., dtype="bfloat16")`` is the control: the same masks and
joins, but every value, count and sum carried in bfloat16 on the device,
the precision below the float32 that the deployment's aggregates state.
"""
from __future__ import annotations

import operator
from typing import Dict, Optional

import numpy as np

from . import workload

_OPS = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


def _mask(table: Dict[str, np.ndarray], terms, lits) -> Optional[np.ndarray]:
    m = None
    for c, op, term in terms or ():
        t = _OPS[op](table[c], workload.literal(term, lits))
        m = t if m is None else m & t
    return m


def _rows(spec: dict, lits, tables) -> tuple:
    """(mask over fact rows, {column: per-fact-row values})."""
    fact = tables[spec["table"]]
    n = len(next(iter(fact.values())))
    mask = _mask(fact, spec.get("where"), lits)
    if mask is None:
        mask = np.ones(n, bool)
    cols = dict(fact)
    for j in spec.get("joins", []):
        dim = tables[j["table"]]
        fk, dk = j["on"]
        order = np.argsort(dim[dk], kind="stable")
        sk = dim[dk][order]
        if np.any(sk[1:] == sk[:-1]):
            raise ValueError(f"join key {dk} is not unique")
        pos = np.clip(np.searchsorted(sk, fact[fk]), 0, len(sk) - 1)
        row = order[pos]
        hit = sk[pos] == fact[fk]
        dmask = _mask(dim, j.get("where"), lits)
        if dmask is not None:
            hit &= dmask[row]
        mask = mask & hit
        for c in j.get("cols", []):
            cols[c] = dim[c][row]
    return mask, cols


def _groups(k: np.ndarray) -> tuple:
    """(sorted distinct keys, index of each row's key among them)."""
    if k.size and k.min() >= 0 and k.max() < (1 << 24):
        present = np.bincount(k) > 0
        keys = np.flatnonzero(present).astype(np.int64)
        lut = np.cumsum(present) - 1
        return keys, lut[k]
    return np.unique(k, return_inverse=True)


def answer(spec: dict, lits: Dict[str, int], tables, *,
           dtype: str = "exact") -> dict:
    """{"keys": sorted group keys or None, "aggs": {out: values}}."""
    mask, cols = _rows(spec, lits, tables)
    group = spec.get("group_by") or []
    if len(group) > 1:
        raise ValueError("the reference groups by one column")
    if group:
        keys, inv = _groups(cols[group[0]][mask].astype(np.int64))
    else:
        keys, inv = None, np.zeros(int(mask.sum()), np.int64)
    ng = 1 if keys is None else len(keys)
    if dtype == "bfloat16":
        return {"keys": keys,
                "aggs": _aggs_bf16(spec, cols, mask, inv, ng)}
    cnt = np.bincount(inv, minlength=ng).astype(np.int64)
    aggs = {}
    for out, (c, kind) in spec["agg"].items():
        if kind == "count":
            aggs[out] = cnt
            continue
        v = cols[c][mask]
        s = np.bincount(inv, weights=v.astype(np.float64), minlength=ng)
        if kind == "sum":
            aggs[out] = (s.astype(np.int64)
                         if np.issubdtype(v.dtype, np.integer) else s)
        elif kind == "avg":
            aggs[out] = s / cnt
        else:
            raise ValueError(f"the reference has no aggregate {kind!r}")
    return {"keys": keys, "aggs": aggs}


def _aggs_bf16(spec, cols, mask, inv, ng) -> dict:
    """Counts, sums and averages carried in bfloat16 on the device."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    seg = jnp.asarray(inv, jnp.int32)
    cnt = jax.ops.segment_sum(jnp.ones(seg.shape, bf), seg, ng)
    aggs = {}
    for out, (c, kind) in spec["agg"].items():
        if kind == "count":
            aggs[out] = np.asarray(cnt.astype(jnp.float32)).astype(np.int64)
            continue
        v = jnp.asarray(cols[c][mask].astype(np.float32)).astype(bf)
        s = jax.ops.segment_sum(v, seg, ng)
        if kind == "avg":
            s = s / cnt
        r = np.asarray(s.astype(jnp.float32)).astype(np.float64)
        if kind == "sum" and np.issubdtype(cols[c].dtype, np.integer):
            r = np.round(r).astype(np.int64)
        aggs[out] = r
    return aggs
