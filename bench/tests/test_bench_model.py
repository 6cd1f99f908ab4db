"""The benchmark's configurations, reference and weights against the
program's own definitions, on the CPU."""
import dataclasses
import zlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry, weights

ROOT = Path(__file__).resolve().parents[2]
CAP = 1 << 24


@pytest.mark.parametrize("name,arch", [("deepfm-h24", "deepfm")])
def test_config_is_the_program_config_with_fields_capped(name, arch):
    from bench.model import wdl_config
    from repro.configs import get_config
    from repro.configs.criteo import CRITEO_VOCABS
    bm = registry.load_benchmark(ROOT)
    cfg = registry.config(ROOT, bm, name)
    got = wdl_config(cfg, registry.reference(ROOT, cfg))
    want = get_config(arch)
    want = dataclasses.replace(want, fields=tuple(
        dataclasses.replace(f, vocab=min(f.vocab, CAP)) for f in want.fields))
    assert got.fields == want.fields
    assert got.n_dense == want.n_dense and got.mlp_dims == want.mlp_dims
    assert [(i.kind, i.kwargs) for i in got.interactions] == \
        [(i.kind, i.kwargs) for i in want.interactions]
    assert cfg["source_categorical_vocab"] == list(CRITEO_VOCABS)


def test_reference_hashing_matches_the_programs_row_map():
    from repro.core.hashing import scramble
    ref = registry.reference(ROOT, {"reference": "wdl_reference"})
    rng = np.random.default_rng(0)
    for name, vocab in [("cat_0", CAP), ("cat_10", 2953546), ("cat_11", 403346),
                        ("cat_5", 3), ("numb_3", 1024)]:
        ids = rng.integers(0, vocab, size=4096).astype(np.int32)
        salt = zlib.crc32(name.encode()) % 10007
        want = np.asarray(scramble(jnp.asarray(ids), vocab, salt=salt))
        np.testing.assert_array_equal(ref.hashed_rows(name, vocab, ids), want)


def test_weights_are_addressable_by_row():
    full = weights.table_rows(2**33 + 5, 7, jnp.arange(1000, dtype=jnp.uint32), 10, 0.3)
    rows = np.array([0, 17, 999, 500])
    part = weights.table_rows(2**33 + 5, 7, rows, 10, 0.3)
    np.testing.assert_array_equal(np.asarray(full)[rows], np.asarray(part))
    other = weights.table_rows(2**33 + 6, 7, rows, 10, 0.3)
    assert not np.array_equal(np.asarray(other), np.asarray(part))
    assert abs(float(full.std()) - 0.3) < 0.01 and abs(float(full.mean())) < 0.01


def test_every_seed_does_the_same_work():
    """Two seeds train on the same ids in the same order; one seed repeats
    itself; labels differ."""
    from bench import generator
    mix = registry.traffic(ROOT, "zipf")
    fields = [("cat_0", 1 << 24), ("cat_5", 3)]
    s1, s2 = 3_000_000_001, 3_000_000_002
    a = generator.batch_at(mix, fields, 13, 4096, s1, 5)
    again = generator.batch_at(mix, fields, 13, 4096, s1, 5)
    b = generator.batch_at(mix, fields, 13, 4096, s2, 5)
    other = generator.batch_at(mix, fields, 13, 4096, s1, 6)
    for name, _ in fields:
        np.testing.assert_array_equal(a["fields"][name]["ids"], again["fields"][name]["ids"])
        np.testing.assert_array_equal(a["fields"][name]["ids"], b["fields"][name]["ids"])
    assert not np.array_equal(a["fields"]["cat_0"]["ids"], other["fields"]["cat_0"]["ids"])
    np.testing.assert_array_equal(a["labels"], again["labels"])
    assert not np.array_equal(a["labels"], b["labels"])
