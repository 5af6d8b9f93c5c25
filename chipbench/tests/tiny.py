"""A benchmark root at a size a CPU test can hold, built from the real
files under ``chipbench/`` with only row counts (and the traffic)
changed."""
from __future__ import annotations

import json
import pathlib
import shutil
import time

import jax

CHIPBENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = CHIPBENCH.parent

# small enough to load and compile in seconds on the CPU; several blocks
# per container so pruning and partial scans happen
ROWS = 60_000
BLOCK_ROWS = 1024


def make_root(tmp: pathlib.Path, *, rows: int = ROWS,
              templates=None) -> pathlib.Path:
    """A copy of BENCHMARK.json and chipbench/ under ``tmp`` with tiny
    tables; ``templates`` keeps only those templates in every traffic mix
    (and 2 literal sets), to keep the CPU compile short."""
    root = tmp / "bench"
    shutil.copytree(CHIPBENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root)
    for f in (root / "chipbench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["tables"]["lineitem"]["rows"] = rows
        c["tables"]["orders"]["rows"] = max(rows // 40, 100)
        c["cluster"]["block_rows"] = BLOCK_ROWS
        f.write_text(json.dumps(c))
    if templates is not None:
        for f in (root / "chipbench" / "traffic").glob("*.json"):
            t = json.loads(f.read_text())
            t["templates"] = {k: v for k, v in t["templates"].items()
                              if k in templates}
            t["literal_sets"] = 2
            f.write_text(json.dumps(t))
    return root


def add_mesh_cell(root: pathlib.Path, chips: int, *,
                  literal_sets: int = 2) -> str:
    """A deployment that puts ``cstore7_sf2``'s nodes on ``chips`` chips
    (``limits.off_mesh`` 0), under the planned segmented mix (Q2, Q3, Q4,
    Q6 of ``scan_agg`` and Q5 of ``join``), added as new files plus
    BENCHMARK.json entries; returns the cell's name."""
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "cstore7_sf2.json").read_text())
    cfg["name"] = "test_only_mesh"
    cfg["cluster"]["chips"] = chips
    cfg["limits"]["off_mesh"] = 0
    (cb / "configs" / "test_only_mesh.json").write_text(json.dumps(cfg))
    scan = json.loads((CHIPBENCH / "traffic" / "scan_agg.json").read_text())
    join = json.loads((CHIPBENCH / "traffic" / "join.json").read_text())
    templates = {t: scan["templates"][t] for t in ("Q2", "Q3", "Q4", "Q6")}
    templates["Q5"] = join["templates"]["Q5"]
    (cb / "traffic" / "test_only_seg.json").write_text(json.dumps({
        "client": {"kind": "closed", "clients": 1},
        "literal_sets": literal_sets, "templates": templates}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "test_only_mesh", "source": "test",
                             "file": "chipbench/configs/test_only_mesh.json",
                             "reduced": [], "why": "test"})
    name = "test_only_mesh.seg"
    bench["workloads"].append({
        "name": name, "config": "test_only_mesh", "traffic": "test_only_seg",
        "chips": chips, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def run(root: pathlib.Path, cell: str, *, seed: int = 2**31 + 7,
        seconds: float = 0.5) -> dict:
    """``run_cell`` on the CPU, past the harness's look for a TPU."""
    from chipbench import harness
    return harness.run_cell(root, cell, seed, seconds, False,
                            t_start=time.time(), devices=jax.devices())
