"""A deployment that states ``cluster.chips`` runs on a query mesh of that
many devices, and its check counts the window's queries that left the
mesh (``off_mesh``); one that states none runs as before, with the four
checks of old."""
import json
import os
import subprocess
import sys

import jax
import pytest

from chipbench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("mesh"),
                          templates=("Q2", "Q6"))
    tiny.add_mesh_cell(root, jax.device_count())
    return root


def _records(monkeypatch):
    """The window's query records of the next run."""
    from chipbench import harness
    seen, real = [], harness._window

    def window(*a, **kw):
        out = real(*a, **kw)
        seen.extend(out[0])
        return out
    monkeypatch.setattr(harness, "_window", window)
    return seen


def test_mesh_config_answers_on_the_mesh(root, monkeypatch):
    records = _records(monkeypatch)
    out = tiny.run(root, "test_only_mesh.seg")
    assert out["correct"] is True, out["checks"]
    assert list(out["checks"]) == ["unanswered", "groups_differ",
                                   "int_mismatch", "float_rel_err",
                                   "off_mesh"]
    assert out["checks"]["off_mesh"] == {"value": 0, "limit": 0}
    assert len(records) == out["attempted"] > 0
    assert {q["template"] for q in records} == {"Q2", "Q3", "Q4", "Q6",
                                                "Q5"}
    assert all(q["stats"]["segmented"] and q["stats"]["n_shards"] == 1
               for q in records)


def test_fallback_to_one_device_is_not_correct(root, monkeypatch):
    from repro.engine import segmented
    monkeypatch.setattr(segmented, "execute_segmented",
                        lambda *a, **kw: None)
    out = tiny.run(root, "test_only_mesh.seg")
    assert out["checks"]["off_mesh"]["value"] == out["attempted"] > 0
    assert out["checks"]["unanswered"]["value"] == 0
    assert out["correct"] is False


def _more_chips_than_devices(cfg, cell):
    cfg["cluster"]["chips"] = cell["chips"] = jax.device_count() + 1


@pytest.mark.parametrize("edit, refusal", [
    (_more_chips_than_devices, "the process sees"),
    (lambda cfg, cell: cell.update(chips=4), "the cell asks for 4"),
    (lambda cfg, cell: cfg["limits"].pop("off_mesh"), "limits.off_mesh"),
], ids=["devices", "cell", "limit"])
def test_mesh_refused_before_data_generation(tmp_path, monkeypatch, edit,
                                             refusal):
    from chipbench import datagen, harness
    root = tiny.make_root(tmp_path, templates=("Q2",))
    name = tiny.add_mesh_cell(root, jax.device_count())
    cfg_path = root / "chipbench" / "configs" / "test_only_mesh.json"
    cfg = json.loads(cfg_path.read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    edit(cfg, bench["workloads"][-1])
    cfg_path.write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    def generate(*a, **kw):
        raise AssertionError("data generated")
    monkeypatch.setattr(datagen, "generate", generate)
    with pytest.raises(harness.BenchError, match=refusal):
        tiny.run(root, name, seconds=0.1)


def test_configurations_without_chips_attach_no_mesh(tmp_path,
                                                     monkeypatch):
    from chipbench import compare, program
    for f in (tiny.CHIPBENCH / "configs").glob("*.json"):
        assert "chips" not in json.loads(f.read_text())["cluster"], f.name
    root = tiny.make_root(tmp_path, templates=("Q2", "Q5"))
    meshes, real_load = [], program.load

    def load(config, tables):
        db = real_load(config, tables)
        meshes.append(db.mesh)
        return db
    monkeypatch.setattr(program, "load", load)
    for cell in ("cstore7_sf2.scan_agg", "cstore7_sf2.join"):
        records = _records(monkeypatch)
        out = tiny.run(root, cell, seconds=0.2)
        assert out["correct"] is True, out["checks"]
        assert list(out["checks"]) == list(compare.NAMES)
        assert records and not any(q["stats"]["segmented"]
                                   for q in records)
    assert meshes == [None, None]


FOUR_DEVICES = """
import json, pathlib, sys
from chipbench import harness
from chipbench.tests import tiny
root = tiny.make_root(pathlib.Path(sys.argv[1]), templates=("Q2", "Q6"))
name = tiny.add_mesh_cell(root, 4, literal_sets=1)
seen, real = [], harness._window
def window(*a, **kw):
    out = real(*a, **kw)
    seen.extend(out[0])
    return out
harness._window = window
out = tiny.run(root, name, seconds=0.3)
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "count": out["device"]["count"],
                  "shards": sorted({q["stats"]["n_shards"] for q in seen}),
                  "segmented": all(q["stats"]["segmented"] for q in seen)}))
"""


def test_four_device_mesh_end_to_end(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.REPO / "src"),
                                           str(tiny.REPO)]))
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["count"] == 4
    assert out["shards"] == [4] and out["segmented"]
    assert out["correct"] is True, out["checks"]
