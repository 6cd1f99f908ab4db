"""D-Packing of the input batch (paper Fig. 7a -> 7b).

Turns the per-field batch dict {field: ids [B, L], weights [B, L]} into one
packed (ids, weights, seg) triple per PackedGroup — the single packed ID
tensor the paper feeds to each packed operation. Scrambling + table offsets
map raw per-table IDs into the packed global row space.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.hashing import scramble
from repro.core.packing import PackedGroup, PicassoPlan


class PackedBatch(NamedTuple):
    ids: jnp.ndarray      # [B * ids_per_sample]
    weights: jnp.ndarray  # [B * ids_per_sample]
    seg: jnp.ndarray      # [B * ids_per_sample] bag index in [0, B*n_bags)
    n_bags: int           # per sample


class FieldView(NamedTuple):
    gid: int
    bag_offset: int
    n_bags: int
    dim: int


def field_index(plan: PicassoPlan) -> Dict[str, FieldView]:
    out = {}
    for g in plan.groups:
        for s in g.slots:
            out[s.field.name] = FieldView(g.gid, s.bag_offset, s.n_bags, g.dim)
    return out


def pack_group(group: PackedGroup, batch: Dict[str, Dict[str, jnp.ndarray]]) -> PackedBatch:
    """Build the packed ID tensor for one group (jit-traceable)."""
    ids_l: List[jnp.ndarray] = []
    w_l: List[jnp.ndarray] = []
    seg_l: List[np.ndarray] = []
    b = next(iter(batch.values()))["ids"].shape[0]
    n_bags = group.n_bags
    for s in group.slots:
        f = s.field
        raw = batch[f.name]["ids"]            # [B, L]
        w = batch[f.name]["weights"]          # [B, L]
        table = next(t for t in group.tables if t.name == s.table)
        # crc32, not hash(): str hashes are salted per process, which made
        # the id -> row map (and the compiled step) differ from run to run
        salt = zlib.crc32(s.table.encode()) % 10007
        packed = scramble(raw, table.vocab, salt=salt) + group.table_offsets[s.table]
        ids_l.append(packed.astype(jnp.int32))
        if f.pooling == "mean":
            denom = jnp.clip(w.sum(axis=1, keepdims=True), 1e-9, None)
            w = w / denom
        w_l.append(w)
        # bag index per position (static per config)
        if f.pooling == "none":
            bag = s.bag_offset + np.arange(f.max_len, dtype=np.int32)
        else:
            bag = np.full((f.max_len,), s.bag_offset, dtype=np.int32)
        seg_l.append(bag)
    ids = jnp.concatenate(ids_l, axis=1).reshape(-1)
    weights = jnp.concatenate(w_l, axis=1).reshape(-1).astype(jnp.float32)
    per_sample = np.concatenate(seg_l)                       # [ids_per_sample]
    seg = (np.arange(b, dtype=np.int32)[:, None] * n_bags + per_sample[None, :]).reshape(-1)
    return PackedBatch(ids=ids, weights=weights, seg=jnp.asarray(seg), n_bags=n_bags)


def pack_all(plan: PicassoPlan, batch: Dict[str, Dict[str, jnp.ndarray]]) -> Dict[int, PackedBatch]:
    return {g.gid: pack_group(g, batch) for g in plan.groups}
