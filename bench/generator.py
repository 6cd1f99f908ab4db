"""The one traffic generator: a training stream read from a mix's data file.

A copy of the program's ``repro/data/synthetic.py`` sampler (bounded zipf ids
by the inverse-CDF power approximation, random labels, standard-normal dense
features), kept here so that no change to the program changes the traffic.
Every seed does the same work: the ids of the batch at position ``i`` are
drawn from ``SeedSequence((id_pool_seed, i))``, the mix's own number, and
its labels and dense features from ``SeedSequence((seed, i))``. The same
seed gives the same batches in any run, and the reference can draw them
again.

A field of ``k`` ids a sample (a bag, which the model sum-pools) is made as
MLPerf DLRM-DCNv2's torchrec reference makes it (``torchrec_dlrm/
multi_hot.py``, ``Multihot`` with ``--multi_hot_distribution_type
uniform``): its first id comes from the mix like a one-hot field's, and the
other ``k - 1`` are fixed for that first id, uniform over the field's rows.
torchrec keeps them in a ``[vocab, k - 1]`` table drawn once; here they are
a hash of (``id_pool_seed``, field, first id, position), so no table is
built and every seed sees the same bags.

Mix parameters (``bench/traffic/<mix>.json``):
  ``ids``      ``"zipf"`` (bounded power sampler) or ``"uniform"``
  ``zipf_a``   skew of the power sampler (id ~ vocab * u^(1/(a-1)))
  ``id_pool_seed``  the number the ids are drawn from
  ``labels``   ``"random"``: Bernoulli(1/2) click labels
  ``dense``    ``"normal"``: standard-normal numeric features
"""
from __future__ import annotations

import itertools
from typing import Dict, Iterator, Sequence

import numpy as np

KNOWN = {"ids": ("zipf", "uniform"), "labels": ("random",),
         "dense": ("normal",)}


def check_mix(mix: Dict) -> Dict:
    if not isinstance(mix.get("id_pool_seed"), int):
        raise ValueError("a traffic mix names the id_pool_seed its ids are drawn from")
    for k, allowed in KNOWN.items():
        if mix.get(k, allowed[0]) not in allowed:
            raise ValueError(f"traffic {k}={mix[k]!r}: the generator knows "
                             f"{allowed}")
    return mix


def zipf_ids(rng: np.random.Generator, vocab: int, size, a: float) -> np.ndarray:
    """Bounded zipf sampler via inverse-CDF power approximation."""
    u = rng.random(size)
    expo = 1.0 / max(a - 1.0, 0.05)
    ids = np.floor(vocab * np.power(u, expo)).astype(np.int64)
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a bijection of uint64 whose bits all depend
    on every input bit."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def bag_tail(id_pool_seed: int, field: int, vocab: int, first: np.ndarray,
             hot: int) -> np.ndarray:
    """``[batch, hot - 1]`` ids that follow each ``first`` id (``[batch,
    1]``) in a bag of field number ``field``: a fixed function of the three
    and the position, uniform over ``[0, vocab)``."""
    with np.errstate(over="ignore"):
        key = _mix64(np.uint64(id_pool_seed) * _GOLDEN + np.uint64(field))
        h = _mix64(key ^ (first.astype(np.uint64) * _GOLDEN))
        pos = np.arange(1, hot, dtype=np.uint64)[None, :]
        h = _mix64(h + pos * _GOLDEN)
    return (h % np.uint64(vocab)).astype(np.int32)


def make_batch(mix: Dict, fields: Sequence, n_dense: int, batch: int,
               ids_rng: np.random.Generator, rng: np.random.Generator) -> Dict:
    """``fields``: the reference's ``Field``s in order (``name``, ``vocab``
    and ``hot``, the ids a sample, are read), each id of weight 1. Every
    field draws its ``[batch, 1]`` first ids from ``ids_rng`` in turn, so
    the draws do not depend on the bags; the rest of a bag is
    ``bag_tail``."""
    out_fields = {}
    for k, f in enumerate(fields):
        if mix.get("ids", "zipf") == "zipf":
            ids = zipf_ids(ids_rng, f.vocab, (batch, 1), float(mix["zipf_a"]))
        else:
            ids = ids_rng.integers(0, f.vocab, size=(batch, 1)).astype(np.int32)
        if f.hot > 1:
            ids = np.concatenate(
                [ids, bag_tail(int(mix["id_pool_seed"]), k, f.vocab, ids, f.hot)], axis=1)
        out_fields[f.name] = {"ids": ids, "weights": np.ones((batch, f.hot), np.float32)}
    out = {"fields": out_fields,
           "labels": rng.integers(0, 2, size=(batch,)).astype(np.float32)}
    if n_dense > 0:
        out["dense"] = rng.normal(size=(batch, n_dense)).astype(np.float32)
    return out


def batch_at(mix: Dict, fields: Sequence, n_dense: int, batch: int, seed: int,
             i: int) -> Dict:
    ids_rng = np.random.default_rng(np.random.SeedSequence((int(mix["id_pool_seed"]), int(i))))
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), int(i))))
    return make_batch(mix, fields, n_dense, batch, ids_rng, rng)


def stream(mix: Dict, fields: Sequence, n_dense: int, batch: int,
           seed: int) -> Iterator[Dict]:
    for i in itertools.count():
        yield batch_at(mix, fields, n_dense, batch, seed, i)
