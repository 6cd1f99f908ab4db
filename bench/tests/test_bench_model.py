"""The benchmark's configurations, reference and weights against the
program's own definitions, on the CPU."""
import dataclasses
import hashlib
import json
import zlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry, weights

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
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
    ref = registry.reference(ROOT, {"reference": "wdl_reference"})
    mix = registry.traffic(ROOT, "zipf")
    fields = [ref.Field("cat_0", 1 << 24, 10, 1), ref.Field("cat_5", 3, 10, 1),
              ref.Field("cat_9", 4096, 10, 8)]
    s1, s2 = 3_000_000_001, 3_000_000_002
    a = generator.batch_at(mix, fields, 13, 4096, s1, 5)
    again = generator.batch_at(mix, fields, 13, 4096, s1, 5)
    b = generator.batch_at(mix, fields, 13, 4096, s2, 5)
    other = generator.batch_at(mix, fields, 13, 4096, s1, 6)
    assert a["fields"]["cat_9"]["ids"].shape == (4096, 8)
    for f in fields:
        np.testing.assert_array_equal(a["fields"][f.name]["ids"], again["fields"][f.name]["ids"])
        np.testing.assert_array_equal(a["fields"][f.name]["ids"], b["fields"][f.name]["ids"])
    assert not np.array_equal(a["fields"]["cat_0"]["ids"], other["fields"]["cat_0"]["ids"])
    np.testing.assert_array_equal(a["labels"], again["labels"])
    assert not np.array_equal(a["labels"], b["labels"])


def test_bag_is_its_first_id_and_a_fixed_uniform_tail():
    """torchrec's ``Multihot`` (uniform): a bag's first id comes from the
    mix as a one-hot field's does, and its other ids are fixed for that
    first id and uniform over the field's rows."""
    from bench import generator
    ref = registry.reference(ROOT, {"reference": "wdl_reference"})
    mix = registry.traffic(ROOT, "zipf")
    vocab = 40_000_000
    one = [ref.Field("cat_0", 4096, 10, 1), ref.Field("cat_1", vocab, 10, 1)]
    bags = [ref.Field("cat_0", 4096, 10, 3), ref.Field("cat_1", vocab, 10, 100)]
    a = generator.batch_at(mix, one, 0, 4096, 3_000_000_041, 2)
    b = generator.batch_at(mix, bags, 0, 4096, 3_000_000_041, 2)
    for f in bags:
        np.testing.assert_array_equal(b["fields"][f.name]["ids"][:, :1],
                                      a["fields"][f.name]["ids"])
    small = b["fields"]["cat_0"]["ids"]              # zipf: first ids repeat
    first, at = np.unique(small[:, 0], return_index=True)
    assert first.size < 4096
    np.testing.assert_array_equal(small, small[at][np.searchsorted(first, small[:, 0])])
    big = b["fields"]["cat_1"]["ids"].astype(np.int64)
    assert big.shape == (4096, 100)
    # the tails of distinct first ids: uniform over 40M rows (mean vocab/2
    # to 0.5%, almost no repeats), so zipf's low ids do not pile up
    tail = big[np.unique(big[:, 0], return_index=True)[1], 1:]
    assert tail.shape[0] > 1000 and 0 <= tail.min() and tail.max() < vocab
    assert abs(tail.mean() / vocab - 0.5) < 0.005
    assert np.unique(tail).size > 0.99 * tail.size
    other = generator.batch_at(dict(mix, id_pool_seed=mix["id_pool_seed"] + 1), bags, 0,
                               4096, 3_000_000_041, 2)
    assert not np.array_equal(other["fields"]["cat_1"]["ids"], big)


# sha256 of the ids of every field, field after field (int32, C order), and
# of the labels at seed 3,000,000,031, as the generator drew them before
# fields could carry bags: a field of one id a sample draws as it did
PINNED = {
    ("zipf", 0): ("abeff01313d5fec721511fda1f74397c47e525b6a8d55a588ecb5a3941051592",
                  "482981f961c6ce6d2154b8aeb128c692e2341b9a3847ca66165dc39a70e9ac94"),
    ("zipf", 57): ("f3ba286052d6d25c542a562a25418cbd83d62b710e1f91fb687f02a0df3bfd7f",
                   "bedc9d0d5c9a815e287c0e16aab827bae01f554ad47fed02d10d127ec99ae833"),
    ("uniform", 0): ("11ca0cb6e71dc69e1d7ff3d0e6499411fc3a956af98712395bec6ca9c5a6edbe",
                     "482981f961c6ce6d2154b8aeb128c692e2341b9a3847ca66165dc39a70e9ac94"),
    ("uniform", 57): ("4063801d47300439f99076ebc5d5e885e51a0a2a18518a06075078ea3f368c3b",
                      "bedc9d0d5c9a815e287c0e16aab827bae01f554ad47fed02d10d127ec99ae833"),
}


@pytest.mark.parametrize("mix_name,i", sorted(PINNED))
def test_deepfm_batches_are_pinned(mix_name, i):
    """``deepfm-h24``'s batches 0 and 57 in both mixes, as the harness feeds
    them (``SUT.fields``: the reference's fields, one id a sample)."""
    from bench import generator
    bm = registry.load_benchmark(ROOT)
    cfg = registry.config(ROOT, bm, "deepfm-h24")
    fields = registry.reference(ROOT, cfg).fields(cfg)
    b = generator.batch_at(registry.traffic(ROOT, mix_name), fields, 0, 16384,
                           3_000_000_031, i)
    h = hashlib.sha256()
    for f in fields:
        assert b["fields"][f.name]["ids"].shape == (16384, 1)
        h.update(np.ascontiguousarray(b["fields"][f.name]["ids"], np.int32).tobytes())
    assert (h.hexdigest(), hashlib.sha256(b["labels"].tobytes()).hexdigest()) \
        == PINNED[(mix_name, i)]


def test_reference_low_rank_cross_is_the_full_rank_cross_of_the_product():
    """``low_rank``: each layer's ``(h @ w_down) @ w_up`` is ``h @ w`` with
    ``w = w_down @ w_up``, at ``highest``; the leaves are named and sized as
    the program's tree would name a low-rank cross."""
    import jax
    ref = registry.reference(ROOT, {"reference": "wdl_reference"})
    full = json.loads((DATA / "tiny-multihot.json").read_text())
    low = dict(full, interactions=[dict(full["interactions"][0], low_rank=8)])
    dd = 8 * 16 + 16
    spec = {path: (shape, std) for path, shape, std in ref.dense_spec(low)}
    assert spec[("i0_cross", "l0", "w_down")] == ((dd, 8), pytest.approx(dd ** -0.5))
    assert spec[("i0_cross", "l0", "w_up")] == ((8, dd), pytest.approx(8 ** -0.5))
    assert spec[("i0_cross", "l0", "b")] == ((dd,), 0.0)
    assert ("bottom", "l1", "w") in spec and ("i0_cross", "l0", "w") not in spec
    seed = 3_000_000_041
    p_low = ref.init_dense(low, seed)
    for l in range(3):
        p_low["i0_cross"][f"l{l}"]["b"] = weights.leaf(seed, 7 + l, (dd,), 0.1)
    p_full = dict(p_low, i0_cross={
        k: {"w": jnp.matmul(q["w_down"], q["w_up"], precision="highest"), "b": q["b"]}
        for k, q in p_low["i0_cross"].items()})
    x = weights.leaf(seed, 3, (64, 8, 16), 0.5)
    dense_x = weights.leaf(seed, 4, (64, 13), 1.0)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(ref.logits(low, p_low, x, dense_x))
        b = np.asarray(ref.logits(full, p_full, x, dense_x))
    assert np.max(np.abs(a - b)) <= 1e-6 * np.max(np.abs(b))


@pytest.mark.parametrize("cfg_name", ["tiny-deepfm", "tiny-multihot"])
def test_reference_pool_sums_each_bag(cfg_name):
    """``pool`` sums each field's bag; with one id a field it returns the
    rows bit for bit."""
    ref = registry.reference(ROOT, {"reference": "wdl_reference"})
    cfg = json.loads((DATA / f"{cfg_name}.json").read_text())
    hot = [f.hot for f in ref.fields(cfg)]
    rows = np.asarray(weights.leaf(3_000_000_051, 5, (16, sum(hot), 4), 1.0))
    got = np.asarray(ref.pool(cfg, jnp.asarray(rows)))
    ends = np.cumsum(hot)
    want = np.stack([rows[:, e - k:e].sum(axis=1) for k, e in zip(hot, ends)], axis=1)
    if max(hot) == 1:
        np.testing.assert_array_equal(got, rows)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _lowered_step(cfg):
    """The text of the benchmark's step for ``cfg`` lowered on its shapes,
    and that of the program's jitted step it wraps, lowered outside any
    precision context."""
    import jax

    from bench import generator
    from bench import model as sutmod
    from repro.dist.sharding import batch_specs, to_named
    raw = []
    sut = sutmod.build(cfg, registry.reference(ROOT, cfg),
                       step_wrapper=lambda s: raw.append(s) or s)
    state = jax.eval_shape(sutmod.state_fn(sut), weights.seed_words(3_000_000_061))
    b = generator.batch_at(registry.traffic(ROOT, "zipf"), sut.fields, cfg.get("n_dense", 0),
                           sut.global_batch, 3_000_000_061, 0)
    b = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
                     b, to_named(sut.mesh, batch_specs(b, sut.axes)))
    return sut.step.lower(state, b).as_text(), raw[0].lower(state, b).as_text()


def test_matmul_precision_traces_the_step_at_it():
    """``matmul_precision: "highest"`` puts HIGHEST on every dot of the
    step and changes nothing else; without the key the step is the
    program's own, traced at the default precision."""
    cfg = json.loads((DATA / "tiny-deepfm.json").read_text())
    plain, plain_raw = _lowered_step(cfg)
    high, high_raw = _lowered_step(dict(cfg, matmul_precision="highest"))
    assert plain == plain_raw == high_raw and "HIGHEST" not in plain
    dots = [line for line in high.splitlines() if "dot_general" in line]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in line for line in dots)
    assert high.replace("HIGHEST", "DEFAULT") == plain
    with pytest.raises(ValueError, match="matmul_precision 'bf16'"):
        _lowered_step(dict(cfg, matmul_precision="bf16"))
