"""The four-chip check on four virtual CPU devices: a sound run of the
row-sharded cell passes, and the same run with the exchange between chips
left out (each chip's all_to_all hands its own buckets back) fails. Each run
is a child process, since the device count is fixed when JAX starts."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import registry

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys, time
root, fault = sys.argv[1], sys.argv[2]
sys.path[:0] = [root, root + "/src_link"]
from pathlib import Path
if fault == "exchange":
    import repro.core.packed_embedding as pe
    pe._a2a = lambda x, axes: x
from bench import harness
res = harness.run(Path(root), "tiny-deepfm-x4.zipf", 3000000013, 0.1, False,
                  t_start=time.perf_counter(), require_chip=False)
print("RESULT " + json.dumps(res))
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("root4")
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (r / "src_link").symlink_to(ROOT / "src")
    bm = registry.load_benchmark(ROOT)
    bm["configs"] = [{"name": "tiny-deepfm-x4", "source": "test",
                      "file": "bench/tests/data/tiny-deepfm-x4.json",
                      "reduced": [], "why": "test"}]
    bm["workloads"] = [{"name": "tiny-deepfm-x4.zipf", "config": "tiny-deepfm-x4",
                        "traffic": "zipf", "chips": 4, "why": "test"}]
    for m in bm["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    return r


def _child(root, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", CHILD, str(root), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_four_chip_sound_run_is_correct(root):
    res = _child(root, "none")
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4


def test_fault_exchange_left_out_fails(root):
    res = _child(root, "exchange")
    assert res["correct"] is False
