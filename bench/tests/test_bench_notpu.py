"""Without a TPU, or without the program beside it, a run exits non-zero and
prints no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "deepfm-h24.zipf",
                        "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
                       cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    return p.returncode, [l for l in p.stdout.splitlines() if l.strip().startswith("{")]


def test_no_tpu_no_result():
    rc, results = _run(ROOT)
    assert rc != 0 and results == []


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, results = _run(tmp_path)
    assert rc != 0 and results == []
