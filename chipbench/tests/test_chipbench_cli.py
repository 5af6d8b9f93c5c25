"""The command refuses to run where it must: no TPU, or a directory that
holds only the benchmark's own files.  It exits non-zero and prints no
result either way."""
import os
import shutil
import subprocess
import sys

from chipbench.tests import tiny

ARGS = ["chipbench/run.py", "--workload", "cstore7_sf2.scan_agg",
        "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(tiny.CHIPBENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    import json
    peaks = json.loads((tiny.CHIPBENCH / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks["devices"]
    assert "cpu" not in peaks["devices"]
