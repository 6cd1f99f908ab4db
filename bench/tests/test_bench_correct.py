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


def _lowrank_cfg():
    """``tiny-multihot`` with its cross at rank 8: bags, a bottom MLP and a
    low-rank cross, as MLPerf's DLRM-DCNv2 has them."""
    cfg = json.loads((DATA / "tiny-multihot.json").read_text())
    cfg["name"] = "tiny-lowrank"
    cfg["interactions"] = [{"kind": "cross", "n_layers": 3, "low_rank": 8}]
    return cfg


def test_low_rank_cross_config_needs_no_harness_edit(tmp_path):
    """A configuration with bags, a bottom MLP and a low-rank cross is a new
    file and a ``BENCHMARK.json`` entry, with no edit to a file of the
    harness: the registry finds it, the generator draws its bags, the
    reference names its cross leaves as a program's tree would and follows
    its steps, and the dense FLOPs count the cross at its rank."""
    from bench import flops
    from bench.model import wdl_config
    cfg = _lowrank_cfg()
    r = _checkout(tmp_path, {"tiny-lowrank": "tiny-lowrank.zipf"})
    (r / "bench/tests/data/tiny-lowrank.json").write_text(json.dumps(cfg))
    bm = registry.load_benchmark(r)
    assert registry.config(r, bm, "tiny-lowrank") == cfg
    ref = registry.reference(r, cfg)
    spec = {path: shape for path, shape, _ in ref.dense_spec(cfg)}
    for l in range(3):
        assert spec[("i0_cross", f"l{l}", "w_down")] == (144, 8)
        assert spec[("i0_cross", f"l{l}", "w_up")] == (8, 144)
        assert spec[("i0_cross", f"l{l}", "b")] == (144,)
        assert ("i0_cross", f"l{l}", "w") not in spec
    assert [dict(i.kwargs) for i in wdl_config(cfg, ref).interactions] == \
        [{"n_layers": 3, "low_rank": 8}]
    mix = registry.traffic(r, "zipf")
    seed = 3_000_000_017
    batches = [generator.batch_at(mix, ref.fields(cfg), cfg["n_dense"], cfg["global_batch"],
                                  seed, i) for i in range(int(cfg["flush_iters"]) + 1)]
    assert batches[0]["fields"]["cat_2"]["ids"].shape == (cfg["global_batch"], 8)
    got = ref.readings(cfg, seed, batches)
    assert len(got["loss"]) == harness.COMPARED_STEPS
    assert all(0 < x < 10 for x in got["loss"])
    for l in range(3):
        for leaf in ("w_down", "w_up"):
            assert got["grad1"][f"dense/i0_cross/l{l}/{leaf}"] > 0
            assert got["change"][f"dense/i0_cross/l{l}/{leaf}"] > 0
    assert set(got["tables"]) == {f"emb/{f.name}" for f in ref.fields(cfg)}
    full = dict(cfg, interactions=[{"kind": "cross", "n_layers": 3}])
    b, dd = cfg["global_batch"], 144
    assert flops.dense_step_flops(full, b) - flops.dense_step_flops(cfg, b) == \
        3 * 6 * b * dd * dd - 3 * 12 * b * dd * 8


def test_set_names_a_missing_dense_leaf():
    """Where the program's dense tree lacks a leaf the reference sets, the
    run stops naming it; a leaf of another shape is named with both."""
    from bench.model import _set
    tree = {"i0_cross": {"l0": {"w": jnp.zeros((144, 144)), "b": jnp.zeros((144,))}}}
    with pytest.raises(ValueError, match="no dense leaf i0_cross/l0/w_down"):
        _set(tree, ("i0_cross", "l0", "w_down"), jnp.ones((144, 8)))
    with pytest.raises(ValueError, match="no dense leaf i1_cross/l0/b"):
        _set(tree, ("i1_cross", "l0", "b"), jnp.ones((144,)))
    with pytest.raises(ValueError, match=r"i0_cross/l0/b: program shape \(144,\)"):
        _set(tree, ("i0_cross", "l0", "b"), jnp.ones((145,)))
    _set(tree, ("i0_cross", "l0", "b"), jnp.ones((144,)))
    assert float(tree["i0_cross"]["l0"]["b"].sum()) == 144.0
