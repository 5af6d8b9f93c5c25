"""The independent reference against the engine, every template of every
traffic mix, at a tiny size on the CPU; and the generator's copy."""
import json

import numpy as np
import pytest

from chipbench import compare, datagen, program, reference, workload
from chipbench.tests import tiny


def _config(name):
    c = json.loads((tiny.CHIPBENCH / "configs" / f"{name}.json").read_text())
    c["tables"]["lineitem"]["rows"] = tiny.ROWS
    c["tables"]["orders"]["rows"] = tiny.ROWS // 40
    c["cluster"]["block_rows"] = tiny.BLOCK_ROWS
    return c


def _mixes():
    return sorted(p.stem for p in (tiny.CHIPBENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("config", ["cstore7_sf2", "cstore7_sf2_packedcache"])
def test_reference_matches_engine_on_every_template(config):
    cfg = _config(config)
    seed = 2**31 + 11
    tables = datagen.generate(cfg, seed)
    db = program.load(cfg, tables)
    seen = 0
    for mix in _mixes():
        traffic = json.loads((tiny.CHIPBENCH / "traffic" / f"{mix}.json")
                             .read_text())
        lits = workload.literal_sets(traffic)
        for t, k in workload.all_pairs(traffic):
            spec = traffic["templates"][t]
            got = program.build_query(db, spec, lits[t][k]).collect()
            want = reference.answer(spec, lits[t][k], tables)
            r = compare.readings(spec, got, want)
            assert r["unanswered"] == r["groups_differ"] == 0, (mix, t, k)
            assert r["int_mismatch"] == 0, (mix, t, k)
            assert r["float_rel_err"] < cfg["limits"]["float_rel_err"], \
                (mix, t, k, r)
            seen += 1
    assert seen >= 20


def test_generator_is_the_harness_generator():
    """The benchmark's copy draws what the program's star_schema draws."""
    from repro.data.synth import star_schema
    cfg = _config("cstore7_sf2")
    tables = datagen.generate(cfg, 2**32 + 5)
    fact, dim = star_schema(tiny.ROWS, tiny.ROWS // 40, 2**32 + 5)
    for name, want in [("lineitem", fact), ("orders", dim)]:
        assert set(tables[name]) == set(want)
        for c in want:
            np.testing.assert_array_equal(tables[name][c], want[c])


def test_reference_semantics_by_hand():
    tables = {"f": {"k": np.array([0, 1, 1, 2, 5]),
                    "g": np.array([3, 3, 4, 4, 4]),
                    "x": np.array([1.5, 2.5, 3.0, 4.0, 5.0])},
              "d": {"dk": np.array([2, 0, 1]), "dg": np.array([7, 8, 9]),
                    "dd": np.array([0, 5, 5])}}
    spec = {"table": "f", "where": [["g", ">=", 3]],
            "joins": [{"table": "d", "on": ["k", "dk"], "cols": ["dg"],
                       "where": [["dd", ">", "p"]]}],
            "group_by": ["dg"],
            "agg": {"n": ["*", "count"], "s": ["x", "sum"],
                    "a": ["x", "avg"]}}
    a = reference.answer(spec, {"p": 1}, tables)
    # rows k=0 (dd 5, dg 8), k=1 twice (dd 5, dg 9); k=2 fails dd, k=5
    # has no dimension row
    np.testing.assert_array_equal(a["keys"], [8, 9])
    np.testing.assert_array_equal(a["aggs"]["n"], [1, 2])
    np.testing.assert_allclose(a["aggs"]["s"], [1.5, 5.5])
    np.testing.assert_allclose(a["aggs"]["a"], [1.5, 2.75])


def test_literal_sets_are_fixed_and_order_balanced():
    traffic = json.loads((tiny.CHIPBENCH / "traffic" / "scan_agg.json")
                         .read_text())
    lits = workload.literal_sets(traffic)
    # the middle of each quarter of [240, 330)
    assert [s["d"] for s in lits["Q6"]] == [251, 273, 296, 318]
    assert lits["Q4"] == [{}] * 4
    orders = set()
    for seed in (1, 2**31 + 3, 2**40):
        seq = workload.order(traffic, seed)
        first = [next(seq) for _ in range(5 * 4)]
        orders.add(tuple(first))
        # one block of K mini-rounds sends every pair exactly once
        assert sorted(first) == sorted(workload.all_pairs(traffic))
        for r in range(4):
            assert sorted(t for t, _ in first[5 * r:5 * r + 5]) == \
                sorted(traffic["templates"])
    assert len(orders) == 3
