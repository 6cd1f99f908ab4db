"""Faults planted under the timed path, to show that ``correct`` catches
them. Neither the benchmark's runs nor the program use them; the readings
script and the tests do."""
from __future__ import annotations

from typing import Dict

import numpy as np


def half_batch(batch: Dict) -> Dict:
    """Half of the batch left out, the mean taken over the rest: the first
    half written over the second, so the step's mean over the batch is the
    mean over its first half."""
    def dup(x):
        h = x.shape[0] // 2
        return np.concatenate([x[:h], x[:h]], axis=0)
    out = {"fields": {n: {k: dup(v) for k, v in f.items()}
                      for n, f in batch["fields"].items()},
           "labels": dup(batch["labels"])}
    if "dense" in batch:
        out["dense"] = dup(batch["dense"])
    return out


def no_write_back(flush_iters: int):
    """A step wrapper whose tier flushes leave the master as it was: after a
    step that flushes, the master's rows and accumulators at the keys the
    tier held before the step are put back to their values from before it,
    which are the values they had when the tier loaded them, so the updates
    the tier took since then are lost."""
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def f(state, batch):
            if (int(state["step"]) + 1) % flush_iters:
                return step(state, batch)
            kept = {}
            for gid, st in state["emb"].items():
                rows = st.w.shape[0]
                at = jnp.where(st.cache.keys < rows, st.cache.keys, rows)
                kept[gid] = (at, st.w[jnp.minimum(at, rows - 1)],
                             st.acc[jnp.minimum(at, rows - 1)])
            jax.block_until_ready(kept)
            state, m = step(state, batch)
            emb = dict(state["emb"])
            for gid, (at, w, acc) in kept.items():
                st = emb[gid]
                emb[gid] = st._replace(
                    w=jax.device_put(st.w.at[at].set(w, mode="drop"), st.w.sharding),
                    acc=jax.device_put(st.acc.at[at].set(acc, mode="drop"),
                                       st.acc.sharding))
            return {**state, "emb": emb}, m
        return f
    return wrap
