"""The harness finds a cell's parts by name, and ``BENCHMARK.json`` names
only parts that exist."""
import json
import re
import shutil
import sys
import time
import types
from pathlib import Path

import pytest

from bench import correct as cmp
from bench import registry

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_parts_that_exist():
    bm = registry.load_benchmark(ROOT)
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    cells = {w["name"] for w in bm["workloads"]}
    for c in bm["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "configs" / f"{cfg['reference']}.py").is_file()
        assert cfg["limits"] and set(cfg["limits"]) <= set(cmp.NUMBERS)
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(registry.end_to_end(bm, w["name"])) >= 2
        assert registry.per_layer(bm, w["name"])
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", [])) <= cells


def _copy_bench(dst: Path):
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "bench" / "tests" / "data" / "tiny-deepfm.json",
                dst / "bench" / "configs" / "added-cfg.json")


def test_added_config_traffic_and_metric_are_found_as_files(tmp_path):
    """A new configuration, traffic mix and per-layer metric, added as files
    with new ``BENCHMARK.json`` entries and no edit to an existing file,
    are found and drive a whole run."""
    _copy_bench(tmp_path)
    cfg = json.loads((tmp_path / "bench/configs/added-cfg.json").read_text())
    cfg["name"] = "added-cfg"
    (tmp_path / "bench/configs/added-cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/added-mix.json").write_text(
        json.dumps({"ids": "uniform", "id_pool_seed": 3, "labels": "random",
                    "dense": "normal"}))
    (tmp_path / "bench/metrics/added_metric.py").write_text(
        "def read(ctx):\n    return float(ctx.n_steps)\n")
    bm = registry.load_benchmark(ROOT)
    bm["configs"].append({"name": "added-cfg", "source": "test",
                          "file": "bench/configs/added-cfg.json",
                          "reduced": cfg["reduced"], "why": "test"})
    bm["workloads"].append({"name": "added-cfg.added-mix", "config": "added-cfg",
                            "traffic": "added-mix", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "added_metric", "unit": "steps", "better": "higher",
                            "source": "host_clock", "layer": "test",
                            "moves": "train_samples_per_s",
                            "workloads": ["added-cfg.added-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    bm2 = registry.load_benchmark(tmp_path)
    cell = "added-cfg.added-mix"
    assert registry.config(tmp_path, bm2, "added-cfg")["name"] == "added-cfg"
    assert registry.traffic(tmp_path, "added-mix")["ids"] == "uniform"
    assert "added_metric" in {m["name"] for m in registry.per_layer(bm2, cell)}
    read = registry.metric_reader(tmp_path, "added_metric")
    assert read(types.SimpleNamespace(n_steps=4)) == 4.0

    sys.path.insert(0, str(tmp_path))
    try:
        from bench import harness
        res = harness.run(tmp_path, cell, 5, 0.1, False, t_start=time.perf_counter(),
                          require_chip=False)
    finally:
        sys.path.remove(str(tmp_path))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_samples_per_s", "peak_hbm_gib", "setup_s"}


def test_unknown_device_kind_has_no_peaks():
    from bench import peaks
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
