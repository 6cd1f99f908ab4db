"""The correctness check at a size a test run holds, on the CPU: a sound run
passes, and the control and each fault a training cell can have on one chip
make ``correct`` false. The runs skip the look for a chip and drive the rest
of the harness, with the timed path broken underneath where a fault asks."""
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import correct as cmp
from bench import faults, generator, harness, registry

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
CELLS = {"tiny-deepfm": "tiny-deepfm.zipf", "tiny-dcn": "tiny-dcn.zipf",
         "tiny-multihot": "tiny-multihot.zipf"}


def _checkout(r: Path, cells) -> Path:
    """``r`` made a checkout whose ``BENCHMARK.json`` holds ``cells``,
    configuration name -> cell, each configuration the file of its name
    under ``bench/tests/data``."""
    shutil.copytree(ROOT / "bench", r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = registry.load_benchmark(ROOT)
    bm["configs"], bm["workloads"] = [], []
    for cfg_name, cell in cells.items():
        bm["configs"].append({"name": cfg_name, "source": "test",
                              "file": f"bench/tests/data/{cfg_name}.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": cell, "config": cfg_name, "traffic": "zipf",
                                "chips": 1, "why": "test"})
    for m in bm["per_layer"]:
        m.pop("workloads", None)
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    return r


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout with the tiny cells in its ``BENCHMARK.json``, at the
    limits of the full-size configurations they stand for."""
    return _checkout(tmp_path_factory.mktemp("root"), CELLS)


def _run(root, cell, **kw):
    return harness.run(root, cell, 3_000_000_007, 0.1, False,
                       t_start=time.perf_counter(), require_chip=False, **kw)


@pytest.mark.parametrize("cell", sorted(CELLS.values()))
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cfg_name", sorted(CELLS))
def test_control_fails(root, cfg_name):
    """The reference computed in bfloat16, put in the program's place, at the
    cells' batch of 16384 (the control's error grows with the duplicate rows
    a step sums, so a small batch would understate it)."""
    bm = registry.load_benchmark(root)
    cfg = dict(registry.config(root, bm, cfg_name), global_batch=16384)
    ref = registry.reference(root, cfg)
    mix = registry.traffic(root, "zipf")
    fields = ref.fields(cfg)
    seed = 3_000_000_011
    batches = [generator.batch_at(mix, fields, cfg.get("n_dense", 0), cfg["global_batch"],
                                  seed, i) for i in range(int(cfg["flush_iters"]) + 1)]
    want = ref.readings(cfg, seed, batches)
    control = ref.readings(cfg, seed, batches, dtype=jnp.bfloat16)
    rows = cmp.checks(cmp.compare(control, want), cfg["limits"])
    assert not cmp.passed(rows), rows


def _frozen(step):
    """A step that returns its state unchanged."""
    def f(state, batch):
        _, m = step(jax.tree.map(jnp.copy, state), batch)
        return state, m
    return f


def test_fault_state_unchanged_fails(root):
    res = _run(root, "tiny-deepfm.zipf", step_wrapper=_frozen)
    assert res["correct"] is False
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", ["tiny-deepfm.zipf", "tiny-multihot.zipf"])
def test_fault_half_batch_fails(root, cell):
    """Half of the batch left out, the mean taken over the rest."""
    res = _run(root, cell, batch_fault=faults.half_batch)
    assert res["correct"] is False


def test_fault_no_write_back_fails(root):
    """Tier flushes that leave the master as it was: only the table changes
    read from the master after the set-up's second flush can see it."""
    bm = registry.load_benchmark(root)
    fi = int(registry.config(root, bm, "tiny-deepfm")["flush_iters"])
    res = _run(root, "tiny-deepfm.zipf", step_wrapper=faults.no_write_back(fi))
    assert res["correct"] is False
    checks = res["checks"]
    assert checks["writeback_gap"]["value"] > checks["writeback_gap"]["limit"]
    for n in ("loss_gap", "grad_gap", "change_gap"):
        assert checks[n]["value"] <= checks[n]["limit"], n


def test_low_rank_cross_config_stops_at_the_missing_program_leaf(tmp_path):
    """A configuration with bags, a bottom MLP and a low-rank cross is a new
    file and a ``BENCHMARK.json`` entry, with no edit to a file of the
    harness: the reference takes it as it is, and the run stops where the
    reference's cross leaves are set into the program's state, which has no
    low-rank cross, naming the leaf the program lacks."""
    cfg = json.loads((DATA / "tiny-multihot.json").read_text())
    cfg["name"] = "tiny-lowrank"
    cfg["interactions"] = [{"kind": "cross", "n_layers": 3, "low_rank": 8}]
    r = _checkout(tmp_path, {"tiny-lowrank": "tiny-lowrank.zipf"})
    (r / "bench/tests/data/tiny-lowrank.json").write_text(json.dumps(cfg))
    ref = registry.reference(r, cfg)
    assert ((("i0_cross", "l0", "w_down"), (144, 8)) in
            [(path, shape) for path, shape, _ in ref.dense_spec(cfg)])
    with pytest.raises(ValueError, match="no dense leaf i0_cross/l0/w_down"):
        _run(r, "tiny-lowrank.zipf")
