"""The one traffic generator: a training stream read from a mix's data file.

A copy of the program's ``repro/data/synthetic.py`` sampler (bounded zipf ids
by the inverse-CDF power approximation, random labels, standard-normal dense
features), kept here so that no change to the program changes the traffic.
Every seed does the same work: the ids of the batch at position ``i`` are
drawn from ``SeedSequence((id_pool_seed, i))``, the mix's own number, and
its labels and dense features from ``SeedSequence((seed, i))``. The same
seed gives the same batches in any run, and the reference can draw them
again.

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


def make_batch(mix: Dict, fields: Sequence, n_dense: int, batch: int,
               ids_rng: np.random.Generator, rng: np.random.Generator) -> Dict:
    """``fields``: ``(name, vocab)`` pairs, one id per sample each."""
    out_fields = {}
    for name, vocab in fields:
        if mix.get("ids", "zipf") == "zipf":
            ids = zipf_ids(ids_rng, vocab, (batch, 1), float(mix["zipf_a"]))
        else:
            ids = ids_rng.integers(0, vocab, size=(batch, 1)).astype(np.int32)
        out_fields[name] = {"ids": ids, "weights": np.ones((batch, 1), np.float32)}
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
