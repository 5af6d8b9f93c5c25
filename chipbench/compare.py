"""The comparison that decides ``correct``.

Every answer the window produced is held against the reference answer to
the same (template, literal set).  Four numbers come out of a run, each
with its limit from the deployment's ``limits``:

- ``unanswered``: queries sent whose answer never came (an exception, or
  a result without the columns asked for);
- ``groups_differ``: answers whose set of groups is not the reference's;
- ``int_mismatch``: integer results (counts, integer sums) that differ
  from the reference's, over all answers: exact, so its limit is 0;
- ``float_rel_err``: the largest relative error of a float result (sum
  or average of a FLOAT column) over all answers.

A deployment that puts its nodes on a mesh of chips (``cluster.chips``)
adds a fifth, after these four:

- ``off_mesh``: queries of the window that the segmented executor did
  not answer (a fallback to one device, or no answer at all).  Its nodes
  answering is the deployment's guarantee: an answer from one chip is a
  different result, not a faster one.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import numpy as np

NAMES = ("unanswered", "groups_differ", "int_mismatch", "float_rel_err")


def readings(spec: dict, got: Optional[Dict[str, np.ndarray]],
             ref: dict) -> Dict[str, float]:
    """The four numbers for one answer (``got`` None: never answered)."""
    r = dict.fromkeys(NAMES, 0)
    r["float_rel_err"] = 0.0
    if got is None or any(a not in got for a in spec["agg"]):
        r["unanswered"] = 1
        return r
    group = spec.get("group_by") or []
    if group:
        if group[0] not in got:
            r["unanswered"] = 1
            return r
        keys = np.asarray(got[group[0]]).astype(np.int64).reshape(-1)
        order = np.argsort(keys, kind="stable")
        keys = keys[order]
        if not np.array_equal(keys, ref["keys"]):
            r["groups_differ"] = 1
            return r
    else:
        order = slice(None)
    for out, want in ref["aggs"].items():
        v = np.asarray(got[out]).reshape(-1)[order]
        if v.shape != want.shape:
            r["groups_differ"] = 1
            continue
        if np.issubdtype(want.dtype, np.integer):
            exact = np.issubdtype(v.dtype, np.integer) or \
                np.all(np.isfinite(v) & (v == np.round(v)))
            bad = np.ones(v.shape, bool) if not exact else \
                v.astype(np.int64) != want
            r["int_mismatch"] += int(np.count_nonzero(bad))
        else:
            v = v.astype(np.float64)
            with np.errstate(invalid="ignore", divide="ignore"):
                err = np.abs(v - want) / np.maximum(np.abs(want), 1e-300)
            err = np.where(np.isfinite(err), err, np.inf)
            if err.size:
                r["float_rel_err"] = max(r["float_rel_err"],
                                         float(err.max()))
    return r


def combine(parts: Iterable[Dict[str, float]]) -> Dict[str, float]:
    total = dict.fromkeys(NAMES, 0)
    total["float_rel_err"] = 0.0
    for p in parts:
        for k in ("unanswered", "groups_differ", "int_mismatch"):
            total[k] += p[k]
        total["float_rel_err"] = max(total["float_rel_err"],
                                     p["float_rel_err"])
    return total


def off_mesh(records: List[dict]) -> int:
    """Window queries (``harness._window`` records) that the segmented
    executor did not answer."""
    return sum(1 for q in records
               if q["failed"] or not (q["stats"] or {}).get("segmented"))


def verdict(values: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """(correct, {name: {"value": v, "limit": l}}), one check per number
    in ``values``, in its order."""
    checks = {n: {"value": v, "limit": limits[n]} for n, v in values.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
