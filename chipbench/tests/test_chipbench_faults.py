"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false.  The control (the
reference in bfloat16 in the program's place) has to fail too."""

import numpy as np
import pytest

from chipbench.tests import tiny

# a count, an integer sum and a float average: every kind of number
TEMPLATES = ("Q2", "Q3", "Q6")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults"),
                          templates=TEMPLATES)


@pytest.mark.parametrize("cell", ["cstore7_sf2.scan_agg",
                                  "cstore7_sf2_packedcache.scan_agg"])
def test_sound_run_is_correct(root, cell):
    out = tiny.run(root, cell)
    assert out["correct"] is True, out["checks"]


def _alter_answer(monkeypatch):
    """One aggregate value of every answer altered where it is
    produced."""
    from repro.engine import pipeline
    real = pipeline._finalize

    def finalize(q, out):
        out = dict(real(q, out))
        name = q.aggs[0][0]
        v = np.array(out[name], copy=True)
        v[0] += 1
        out[name] = v
        return out
    monkeypatch.setattr(pipeline, "_finalize", finalize)


def _drop_half_the_blocks(monkeypatch):
    """Half of the scanned blocks left out: SMA pruning also skips every
    second block it would keep."""
    from repro.core import sma
    real = sma.ColumnSMA.prune_blocks

    def prune(self, lo, hi):
        keep = np.array(real(self, lo, hi), copy=True)
        keep[1::2] = False
        return keep
    monkeypatch.setattr(sma.ColumnSMA, "prune_blocks", prune)


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half_the_blocks])
@pytest.mark.parametrize("cell", ["cstore7_sf2.scan_agg",
                                  "cstore7_sf2_packedcache.scan_agg"])
def test_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch)
    out = tiny.run(root, cell)
    assert out["correct"] is False, out["checks"]


def test_join_answer_altered_is_not_correct(tmp_path, monkeypatch):
    root = tiny.make_root(tmp_path, templates=("Q5", "Q7"))
    _alter_answer(monkeypatch)
    out = tiny.run(root, "cstore7_sf2.join")
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("cell", ["cstore7_sf2.scan_agg", "cstore7_sf2.join",
                                  "cstore7_sf2_packedcache.scan_agg"])
def test_bfloat16_control_is_not_correct(tmp_path, cell):
    from chipbench import control
    root = tiny.make_root(tmp_path, rows=200_000)
    for seed in (5, 2**31 + 9, 2**33):
        r = control.readings(root, cell, seed)
        assert r["correct"] is False, r["checks"]
        assert r["checks"]["float_rel_err"]["value"] > \
            3 * r["checks"]["float_rel_err"]["limit"]
