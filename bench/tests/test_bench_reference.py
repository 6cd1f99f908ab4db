"""The plain reference (``bench/configs/wdl_reference.py``) on the CPU: it
reads as it did when each step differentiated over every row of the run,
its memory follows a step's distinct rows and not the run's, and the
precision it is asked for reaches its matmuls."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import generator, registry

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
PINNED = json.loads((DATA / "reference_readings.json").read_text())


def _ref():
    return registry.reference(ROOT, {"reference": "wdl_reference"})


@pytest.mark.parametrize("cfg_name", sorted(PINNED["readings"]))
def test_reference_reads_as_pinned(cfg_name):
    """Every reading of the first five zipf steps, to 1e-6 of its pinned
    value (the order of a few sums changed, no equation)."""
    cfg = json.loads((DATA / f"{cfg_name}.json").read_text())
    ref, seed = _ref(), PINNED["seed"]
    mix = registry.traffic(ROOT, PINNED["traffic"])
    batches = [generator.batch_at(mix, ref.fields(cfg), cfg.get("n_dense", 0),
                                  cfg["global_batch"], seed, i)
               for i in range(int(cfg["flush_iters"]) + 1)]
    got = ref.readings(cfg, seed, batches)
    want = PINNED["readings"][cfg_name]
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-6, atol=0)
    for part in ("grad1", "change", "tables"):
        assert set(got[part]) == set(want[part]), part
        for leaf, v in want[part].items():
            assert got[part][leaf] == pytest.approx(v, rel=1e-6, abs=0), (part, leaf)


D = 32
CFG = {"reference": "wdl_reference", "categorical_vocab": [1 << 16] * 4, "embed_dim": D,
       "n_dense": 0, "interactions": [{"kind": "fm"}], "mlp_dims": [16],
       "lr_emb": 0.05, "lr_dense": 0.001}


def _batches(steps, batch, per_step, spread):
    """Each step touches ``per_step`` rows of each field; with ``spread``
    every step its own rows, else every step the same ones. Ids under the
    vocabulary hash to distinct rows (a bijection there)."""
    out = []
    for s in range(steps):
        base = s * per_step if spread else 0
        out.append({"fields": {f.name: {"ids": (base + (np.arange(batch) * 7 + j) % per_step)
                                        .astype(np.int32)[:, None]}
                               for j, f in enumerate(_ref().fields(CFG))},
                    "labels": (np.arange(batch) % 2).astype(np.float32)})
    return out


def test_reference_memory_follows_a_steps_rows():
    """Two runs alike but for how many rows the steps touch in all: every
    step touches 4 x 512 rows, the same ones in one run, its own in the
    other. The compiled reference's temporaries grow with the run's rows by
    no more than two ``[rows, d]`` tables and two accumulators (the table,
    and at most one copy of its initial rows), not by a gradient, its
    square and an updated table for every row the run touched."""
    ref = _ref()
    temp, rows = [], []
    for spread in (False, True):
        args = ref.inputs(CFG, 5, _batches(8, 1024, 512, spread))
        assert args[4].shape == (8, 4 * 512)          # each step's distinct rows
        rows.append(args[1].shape[0])
        ma = ref._program(CFG, jnp.float32, 3).lower(*args).compile().memory_analysis()
        temp.append(ma.temp_size_in_bytes)
    assert rows == [4 * 512, 8 * 4 * 512]
    per_row = (temp[1] - temp[0]) / (rows[1] - rows[0])
    assert per_row <= (2 * D + 2) * 4, per_row


@pytest.mark.parametrize("precision,want", [("highest", "HIGHEST"), ("default", "DEFAULT")])
def test_reference_precision_reaches_its_dots(precision, want):
    """The precision ``readings`` is asked for (``highest`` for the
    reference, ``default`` for the ``default_precision`` control) is the
    operand precision of every matmul in its program."""
    cfg = json.loads((DATA / "tiny-multihot.json").read_text())
    ref = _ref()
    mix = registry.traffic(ROOT, "zipf")
    batches = [generator.batch_at(mix, ref.fields(cfg), cfg["n_dense"], 256, 7, i)
               for i in range(4)]
    with jax.default_matmul_precision(precision):
        text = ref._program(cfg, jnp.float32, 3).lower(
            *ref.inputs(cfg, 7, batches)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all(f"precision = [{want}, {want}]" in line for line in dots)
