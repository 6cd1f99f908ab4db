"""Find a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name, so a later change adds a cell or
a metric by adding files and entries and edits none:

  configuration  the ``file`` its ``configs`` entry names (JSON of sizes),
                 with the plain reference it names beside it in
                 ``bench/configs/<reference>.py``
  traffic mix    ``bench/traffic/<traffic>.json``, read by ``generator.py``
  metric         ``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns
                 the value or None when the run has nothing to read
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def workload(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"({', '.join(w['name'] for w in bm['workloads'])})")


def config(root: Path, bm: Dict, name: str) -> Dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def traffic(root: Path, name: str) -> Dict:
    from bench.generator import check_mix
    return check_mix(json.loads(
        (Path(root) / "bench" / "traffic" / f"{name}.json").read_text()))


def _module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(root: Path, cfg: Dict) -> ModuleType:
    return _module(Path(root) / "bench" / "configs" / f"{cfg['reference']}.py")


def end_to_end(bm: Dict, cell: str) -> List[Dict]:
    return [m for m in bm["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bm: Dict, cell: str) -> List[Dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a ``workloads`` key whose end-to-end metric it reports."""
    e2e = {m["name"] for m in end_to_end(bm, cell)}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def metric_reader(root: Path, name: str):
    return _module(Path(root) / "bench" / "metrics" / f"{name}.py").read
