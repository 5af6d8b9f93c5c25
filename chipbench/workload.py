"""Traffic mixes: query templates, their literal sets, and the order.

A mix is a JSON file under ``traffic/``; this one generator reads every
mix.  Its keys:

- ``client``: ``{"kind": "closed", "clients": 1}``, the only kind so far:
  one client sends its next query when the last one has answered.
- ``literal_sets``: K, the literal sets each template gets.
- ``templates``: name -> query spec (see ``spec_columns`` for the shape).
  ``params`` maps a parameter to an integer range ``[lo, hi)``; literal
  set k takes the middle of the k-th of K equal strata of that range:
  the K quantiles of a uniform draw over the range, not a fresh draw.
  The literals are the same for every seed, so every seed sends queries
  of the same selectivities: the seed changes the data and the order,
  not the work.  (The program compiles one program per literal, so
  every literal set is warmed in set-up and K stays small.)

The order is a sequence of mini-rounds, each holding every template once
in a seeded order; template t in mini-round r uses literal set
``perm_t[r % K]``, with ``perm_t`` a seeded permutation drawn anew every K
mini-rounds.  Every prefix therefore holds the templates in equal shares
(to within one mini-round), whatever the seed, and every block of K
mini-rounds sends each (template, literal set) exactly once.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

# the order's stream of the seed's generator (datagen uses the bare seed)
_ORDER_STREAM = 2


def literal_sets(traffic: dict) -> Dict[str, List[Dict[str, int]]]:
    """template -> K dicts of parameter values."""
    k = int(traffic["literal_sets"])
    out = {}
    for name, spec in traffic["templates"].items():
        out[name] = [{p: lo + (2 * i + 1) * (hi - lo) // (2 * k)
                      for p, (lo, hi) in spec.get("params", {}).items()}
                     for i in range(k)]
    return out


def order(traffic: dict, seed: int) -> Iterator[Tuple[str, int]]:
    """Endless (template, literal set index) sequence for the window."""
    names = list(traffic["templates"])
    k = int(traffic["literal_sets"])
    rng = np.random.default_rng([seed, _ORDER_STREAM])
    while True:
        perms = {t: rng.permutation(k) for t in names}
        for r in range(k):
            for i in rng.permutation(len(names)):
                t = names[i]
                yield t, int(perms[t][r])


def all_pairs(traffic: dict) -> List[Tuple[str, int]]:
    k = int(traffic["literal_sets"])
    return [(t, i) for t in traffic["templates"] for i in range(k)]


def literal(term, lits: Dict[str, int]) -> int:
    """A term's right-hand side: an int, a parameter name, or
    ``[parameter, offset]``."""
    if isinstance(term, int):
        return term
    if isinstance(term, str):
        return int(lits[term])
    p, off = term
    return int(lits[p]) + int(off)


def spec_columns(spec: dict) -> Dict[str, set]:
    """Columns a query spec reads, per table.

    A spec: ``{"table": t, "where": [[col, op, term], ...],
    "joins": [{"table": d, "on": [fact_key, dim_key], "cols": [...],
    "where": [...]}], "group_by": [col], "agg": {out: [col | "*", kind]}}``.
    ``where`` terms AND together; ops are ``== != < <= > >=``."""
    cols = {spec["table"]: set()}
    fact = cols[spec["table"]]
    fact |= {w[0] for w in spec.get("where", [])}
    for j in spec.get("joins", []):
        fact.add(j["on"][0])
        d = cols.setdefault(j["table"], set())
        d |= {j["on"][1], *j.get("cols", [])}
        d |= {w[0] for w in j.get("where", [])}
    joined = {c for j in spec.get("joins", []) for c in j.get("cols", [])}
    for c in spec.get("group_by", []):
        if c not in joined:
            fact.add(c)
    for c, _ in spec["agg"].values():
        if c != "*" and c not in joined:
            fact.add(c)
    return cols
